"""Plain reference for the ``k-exaone-236b-a23b`` configuration: the
language model of K-EXAONE-236B-A23B (``config.json`` of
https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B), forward, loss and
gradients, in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no sort, no grouped matmul, no buffer, no remat: a scan over
the held experts with a boolean mask.  It imports nothing of the program
and takes the program's parameter tree (any dtype; cast here to float32, a
layer at a time), so seeded weights serve both.

Layer ``index`` (0-based) of the stack, residual stream ``x`` [B, S, d];
``sizes["layer_types"][index]`` says ``sliding_attention`` (L) or
``full_attention`` (G), ``sizes["mlp_layer_types"][index]`` ``dense`` or
``sparse``::

    a  = rms(x, g1)
    q  = heads(Wq a) [S, H, hd];  k = heads(Wk a), v = heads(Wv a) [S, Hkv, hd]
    q  = rms(q, gq) over each head's hd;  k = rms(k, gk) likewise
    L: q, k = rope(q), rope(k), rotate-half over the whole head;
       allowed(i, j) = i - W < j <= i
    G: no positions at all;  allowed(i, j) = j <= i
    query head h reads key/value head h // (H / Hkv)
    h  = x + Wo concat_h softmax(q_h k^T / sqrt(hd)) v
    m  = rms(h, g2)
    dense:   y = h + Wd (silu(Wg m) * (Wu m))
    sparse:  s = sigmoid(Wr m) [E], float32
             T = the k largest of s + b        b selects and does not weigh
             g_e = scale * s_e / sum_{j in T} s_j   for e in T
             y = h + Shared(m) + sum_{e in T, e held} g_e Expert_e(m)
    logits = Wlm rms(x_L, gf)

**The share** (``sizes["held"] = (first, count)``): the parameter tree
holds ``count`` of a layer's ``E`` experts, experts ``first .. first +
count - 1``; the router keeps its ``E`` outputs and its ``k``, the gates
are normalised over all ``k`` chosen, and what the absent experts would
have added is left out.  ``held = None``: every expert is in the tree.
The vocabulary held is whatever the embedding and the head span.

Departures from the published description, each because ``config.json``
has no key for it (the configuration file lists them under ``assumed``):
the norms come BEFORE the blocks; the query/key norm is per head with one
scale of ``head_dim``; the global layers are not rotated; the selection
bias ``b`` [E] is a parameter no gradient reaches; the multi-token
prediction block is not built.  The losses beside the cross-entropy are
this repository's (load balance ``E * sum_e mean(s_e / sum s) *
top-1-load_e`` and ``mean(logsumexp(router logits)^2)``, per mixture
layer, mean over them), and weigh 0 in this configuration.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the keys
(a [64, 256, 16384] float32 score block is 1.1 GB, the whole 69 GB), the
caller runs a layer at a time, and the head and the cross-entropy take a
block of positions at a time (:func:`head`).

``operand_dtype`` rounds every matmul's operands (weights and
activations) to that dtype and back to float32: the same mathematics at
a lower precision, for showing that a tolerance tells the stated
precision from the one below it.  The router stays in float32, as the
program's does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    n_heads=64, n_kv_heads=8, head_dim=128, experts_per_token=8,
    norm_eps=1e-5, rope_theta=1e6, sliding_window=128,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    routed_scaling_factor=2.5, norm_topk_prob=True, held=(0, 8),
    aux_loss_weight=0.0, router_z_weight=0.0,
)
ATTENTION_BLOCK = 256  # queries a block


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda a: a
    return lambda a: a.astype(operand_dtype).astype(jnp.float32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [B, S, H, hd]; position of a token = its index in the sequence."""
    s, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [S, hd]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v, window, r):
    """q [B, S, H, hd], k and v [B, S, Hkv, hd] -> [B, S, H, hd]: causal,
    ``window`` keys with the query's own where it is not None; a block of
    queries at a time against all the keys."""
    b, s, h, hd = q.shape
    group = h // k.shape[2]
    k, v = r(k), r(v)
    block = min(ATTENTION_BLOCK, s)
    j = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        qb = r(qb).reshape(b, block, h // group, group, hd)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        i = start + jnp.arange(block)
        allowed = j[None, :] <= i[:, None]
        if window is not None:
            allowed &= j[None, :] > i[:, None] - window
        scores = jnp.where(allowed, scores, -jnp.inf)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", r(jax.nn.softmax(scores, axis=-1)), v
        )
        return out.reshape(b, block, h, hd)

    blocks = jax.lax.map(one_block, jnp.arange(0, s, block))  # [n, B, blk, H, hd]
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, hd)


def gated(p, u, r):
    """``Wd (silu(Wg u) * (Wu u))``: the dense layer, the shared expert."""
    hidden = jax.nn.silu(r(u) @ r(p["w_gate"])) * (r(u) @ r(p["w_up"]))
    return r(hidden) @ r(p["w_down"])


def router(moe, u, sizes):
    """``u`` [n, d] -> ``(logits, scores, chosen [n, E] bool, gates [n, E])``
    in float32: gates are 0 off the chosen."""
    logits = u @ moe["gate"]
    s = jax.nn.sigmoid(logits)
    sel = s + moe["router_bias"]
    num_experts = logits.shape[1]
    # the k largest: an expert is chosen when fewer than k beat it (ties
    # toward the lower index, as lax.top_k breaks them)
    beats = (sel[:, None, :] > sel[:, :, None]) | (
        (sel[:, None, :] == sel[:, :, None])
        & (jnp.arange(num_experts)[None, None, :]
           < jnp.arange(num_experts)[None, :, None])
    )
    chosen = beats.sum(axis=-1) < sizes["experts_per_token"]
    g = jnp.where(chosen, s, 0.0)
    if sizes["norm_topk_prob"]:
        g = g / g.sum(axis=-1, keepdims=True)
    return logits, s, chosen, g * sizes["routed_scaling_factor"]


def routed_part(moe, u, sizes, r=lambda a: a):
    """What the experts in the tree add for ``u`` [n, d]: the held ones'
    gate-weighted outputs, gates over all E (:func:`router`)."""
    _, _, _, g = router(moe, u, sizes)
    first, count = sizes["held"] or (0, g.shape[1])

    def one_expert(y, e):
        w_gate, w_up, w_down, g_e = e
        hidden = jax.nn.silu(r(u) @ r(w_gate)) * (r(u) @ r(w_up))
        return y + g_e[:, None] * (r(hidden) @ r(w_down)), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (moe["w_gate"], moe["w_up"], moe["w_down"],
         g[:, first:first + count].T),
    )
    return y


def attention_part(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """The stream after block ``index``'s attention."""
    with jax.default_matmul_precision("highest"):
        lp = _f32({k: v for k, v in lp.items()
                   if k not in ("ffn", "moe", "shared")})
        r = _rounder(operand_dtype)
        b, s, d = x.shape
        heads, kv_heads, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        eps = sizes["norm_eps"]
        local = sizes["layer_types"][index] == "sliding_attention"

        a = rms(x, lp["ln1"]["scale"], eps)
        q = (r(a) @ r(lp["wq"])).reshape(b, s, heads, hd)
        k = (r(a) @ r(lp["wk"])).reshape(b, s, kv_heads, hd)
        v = (r(a) @ r(lp["wv"])).reshape(b, s, kv_heads, hd)
        q = rms(q, lp["q_norm"]["scale"], eps)
        k = rms(k, lp["k_norm"]["scale"], eps)
        if local:
            q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
        attn = attention(q, k, v, sizes["sliding_window"] if local else None, r)
        return x + r(attn.reshape(b, s, heads * hd)) @ r(lp["wo"])


def ffn_part(lp, h, sizes=SIZES, index=0, operand_dtype=None):
    """Block ``index``'s feed-forward part on the stream ``h`` its
    attention left: ``(x_out, aux_loss, router_z_loss)``, both losses 0
    for a dense layer."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = h.shape
        m = rms(h, jnp.asarray(lp["ln2"]["scale"], jnp.float32),
                sizes["norm_eps"]).reshape(b * s, d)
        if sizes["mlp_layer_types"][index] == "dense":
            return h + gated(_f32(lp["ffn"]), m, r).reshape(b, s, d), 0.0, 0.0
        moe = _f32(lp["moe"])
        y = gated(_f32(lp["shared"]), m, r) + routed_part(moe, m, sizes, r)

        logits, scores, _, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        first_choice = jnp.argmax(scores + moe["router_bias"], axis=-1)
        p = scores / scores.sum(axis=-1, keepdims=True)
        aux = num_experts * jnp.sum(
            p.mean(axis=0) * jax.nn.one_hot(first_choice, num_experts).mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return h + y.reshape(b, s, d), aux, z


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """Block ``index`` of the stack: ``(x_out, aux_loss, router_z_loss)``.
    ``lp`` is a layer of the program's parameter tree."""
    return ffn_part(
        lp, attention_part(lp, x, sizes, index, operand_dtype), sizes, index,
        operand_dtype,
    )


def router_scores(lp, h, sizes=SIZES):
    """[B * S, E]: ``s + b``, what the choice of experts is made on, on the
    stream ``h`` [B, S, d] the layer's attention left."""
    with jax.default_matmul_precision("highest"):
        moe = _f32(lp["moe"])
        m = rms(h, jnp.asarray(lp["ln2"]["scale"], jnp.float32),
                sizes["norm_eps"])
        return jax.nn.sigmoid(
            m.reshape(-1, h.shape[-1]) @ moe["gate"]) + moe["router_bias"]


def router_margin(lp, h, sizes=SIZES):
    """[B * S]: by how much a token's k-th largest ``s + b`` exceeds its
    (k+1)-th, where one of those two experts is HELD: how firmly this
    share's part of the token's result is decided.  Infinite where
    neither is held: whichever of the two is chosen, the experts here
    compute the same for the token (their gates' normaliser moves by the
    difference of two near-equal scores)."""
    scores = router_scores(lp, h, sizes)
    k = sizes["experts_per_token"]
    order = jnp.argsort(scores, axis=-1)
    pair = order[:, -k - 1:-k + 1 or None]  # the (k+1)-th and the k-th
    ranked = jnp.take_along_axis(scores, pair, axis=-1)
    first, count = sizes["held"] or (0, scores.shape[1])
    held = ((pair >= first) & (pair < first + count)).any(axis=-1)
    return jnp.where(held, ranked[:, 1] - ranked[:, 0], jnp.inf)


def embed(params, token_ids):
    return jnp.asarray(params["embed"], jnp.float32)[token_ids]


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm and the untied head on ``x`` [.., n, d], all the
    positions or a block of them: logits [.., n, V]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        final = rms(
            x, jnp.asarray(params["ln_f"]["scale"], jnp.float32),
            sizes["norm_eps"],
        )
        return r(final) @ r(jnp.asarray(params["lm_head"], jnp.float32))


def ce_sum_of_logits(logits, targets):
    """Sum over the positions given of the next-token cross-entropy."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def total_loss(ce_mean, aux_sum, z_sum, n_sparse, sizes=SIZES):
    return (ce_mean + sizes["aux_loss_weight"] * aux_sum / n_sparse
            + sizes["router_z_weight"] * z_sum / n_sparse)


def sparse_layers(params, sizes=SIZES) -> int:
    return sizes["mlp_layer_types"][: len(params["layers"])].count("sparse")


def forward(params, token_ids, sizes=SIZES, operand_dtype=None):
    """``(logits [B, S, V], sum of aux losses, sum of router z-losses)``:
    everything at once, for sizes at which whole logits fit."""
    x = embed(params, token_ids)
    aux_sum = z_sum = 0.0
    for index, lp in enumerate(params["layers"]):
        x, aux, z = layer(lp, x, sizes, index, operand_dtype)
        aux_sum, z_sum = aux_sum + aux, z_sum + z
    return head(params, x, sizes, operand_dtype), aux_sum, z_sum


def loss(params, token_ids, targets, sizes=SIZES, operand_dtype=None):
    """The training loss: mean next-token cross-entropy plus the weighted
    load-balance and router z losses."""
    logits, aux_sum, z_sum = forward(params, token_ids, sizes, operand_dtype)
    return total_loss(
        ce_sum_of_logits(logits, targets) / targets.size, aux_sum, z_sum,
        sparse_layers(params, sizes), sizes,
    )


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
