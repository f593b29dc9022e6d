"""Plain reference for the ``glm-4.7-flash`` configuration: the language
model of GLM-4.7-Flash (``config.json`` of
https://huggingface.co/zai-org/GLM-4.7-Flash, ``glm4_moe_lite``), forward,
both losses and gradients, in straightforward ``jax.numpy`` at float32
under ``jax.default_matmul_precision("highest")``.

No kernel, no sort, no grouped matmul, no buffer, no remat: a scan over
the held experts with a boolean mask.  It imports nothing of the program
and takes the program's parameter tree (any dtype; cast here to float32, a
layer at a time), so seeded weights serve both.  Written from the
equations below, which are the issue's, not from the program.

Layer ``index`` (0-based), residual stream ``x`` [B, S, d]; it is dense
where ``index < sizes["first_k_dense_replace"]`` and a mixture after::

    a     = rms(x, g1)
    c_q   = rms(Wqa a, gq) [S, 768];   q = heads(Wqb c_q) [S, H, 192 + 64]
    [c | k_r] = Wkva a  [S, 512 + 64];  c_kv = rms(c, gkv)
    per head [k_nope 192 | v 256] = Wkvb c_kv
    q_rope = rope(q[.., 192:]),  k_r = rope(k_r): rotate-half over the 64,
        theta 1e6, positions 0..S-1; ONE k_r a token, shared by the heads
    q = [q_nope | q_rope],  k = [k_nope | k_r]
    h  = x + Wo concat_h softmax_{j <= i}(q_h k_h^T / sqrt(256)) v_h
    m  = rms(h, g2)
    dense:   y = h + Wd (silu(Wg m) * (Wu m))
    mixture: s = sigmoid(Wr m) [E], float32
             T = the k largest of s + b        b selects and does not weigh
             g_e = scale * s_e / sum_{j in T} s_j   for e in T
             y = h + Shared(m) + sum_{e in T, e held} g_e Expert_e(m)
    hf     = rms(x_L, gf);   logits = Wlm hf

and after the stack the block that predicts the next-but-one token, with
``t_{i+1}`` position i's next id (a training row's targets)::

    z      = We_h [rms(Emb[t_{i+1}], ge) ; rms(hf, gh)]     [4096 -> 2048]
    z'     = Layer_L(z)     a mixture layer with parameters of its own
    logits' = Wlm rms(z', go)                    the SAME head and table
    loss   = ce + w ce',   ce' = mean_{i <= S-2} CE(logits'_i, t_{i+2})

**The share** (``sizes["held"] = (first, count)``): the parameter tree
holds ``count`` of a layer's ``E`` experts, experts ``first .. first +
count - 1``; the router keeps its ``E`` outputs and its ``k``, the gates
are normalised over all ``k`` chosen, and what the absent experts would
have added is left out.  ``held = None``: every expert is in the tree.
The vocabulary held is whatever the embedding and the head span.

Assumptions, each because ``config.json`` has no key for it (the
configuration file lists them under ``assumed``): the weight ``w`` (0.3);
the embedding's half comes first in the concatenation; the block reads
the final NORMALIZED stream; rope pairs rotate-half; both latents are
normalized (eps 1e-5) and ``k_r`` is not; the selection bias ``b`` [E] is
a parameter no gradient reaches; no biases anywhere.  The losses beside
the cross-entropies are this repository's (load balance ``E * sum_e
mean(s_e / sum s) * top-1-load_e`` and ``mean(logsumexp(router
logits)^2)``, per mixture layer, mean over them, the block's among them),
and weigh 0 in this configuration.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the keys
(a [20, 256, 16384] float32 score block is 0.34 GB, the whole 21.5 GB),
the caller runs a layer at a time, and the head and the cross-entropy take
a block of positions at a time (:func:`head`).

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
    n_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, experts_per_token=4,
    norm_eps=1e-5, rope_theta=1e6, first_k_dense_replace=1,
    routed_scaling_factor=1.8, norm_topk_prob=True, held=(0, 32),
    aux_loss_weight=0.0, router_z_weight=0.0, mtp_loss_weight=0.3,
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
    """x [B, S, H, r]; position of a token = its index in the sequence;
    dimension j pairs with j + r/2."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [S, r]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v, r):
    """q, k [B, S, H, dk], v [B, S, H, dv] -> [B, S, H, dv]: causal; a
    block of queries at a time against all the keys."""
    b, s, h, dk = q.shape
    k, v = r(k), r(v)
    block = min(ATTENTION_BLOCK, s)
    j = jnp.arange(s)

    def one_block(start):
        qb = r(jax.lax.dynamic_slice_in_dim(q, start, block, axis=1))
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k) / jnp.sqrt(jnp.float32(dk))
        i = start + jnp.arange(block)
        scores = jnp.where(j[None, :] <= i[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd", r(jax.nn.softmax(scores, axis=-1)), v)

    blocks = jax.lax.map(one_block, jnp.arange(0, s, block))  # [n, B, blk, H, dv]
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, v.shape[-1])


def queries_keys_values(lp, a, sizes, r=lambda a: a, rotate_keys=True):
    """The expanded q, k [B, S, H, nope + rope] and v [B, S, H, dv] of the
    normalized input ``a``; ``lp`` float32.  ``rotate_keys`` False leaves
    the keys' shared part out of the rotation (what a test breaks)."""
    b, s, _ = a.shape
    heads, eps = sizes["n_heads"], sizes["norm_eps"]
    rank, nope = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"]
    rot, dv = sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    c_q = rms(r(a) @ r(lp["wq_a"]), lp["q_a_norm"]["scale"], eps)
    q = (r(c_q) @ r(lp["wq_b"])).reshape(b, s, heads, nope + rot)
    down = r(a) @ r(lp["wkv_a"])  # [c | k_r]
    c_kv = rms(down[..., :rank], lp["kv_a_norm"]["scale"], eps)
    k_r = down[..., rank:].reshape(b, s, 1, rot)
    up = (r(c_kv) @ r(lp["wkv_b"])).reshape(b, s, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    q_rope = rope(q[..., nope:], sizes["rope_theta"])
    if rotate_keys:
        k_r = rope(k_r, sizes["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, s, heads, rot))], axis=-1)
    return q, k, v


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
    # toward the lower index)
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


def is_dense(sizes, index) -> bool:
    return index < sizes["first_k_dense_replace"]


def attention_part(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """The stream after block ``index``'s attention (every layer's is the
    same: ``index`` is not read)."""
    with jax.default_matmul_precision("highest"):
        lp = _f32({k: v for k, v in lp.items()
                   if k not in ("ffn", "moe", "shared")})
        r = _rounder(operand_dtype)
        b, s, _ = x.shape
        a = rms(x, lp["ln1"]["scale"], sizes["norm_eps"])
        q, k, v = queries_keys_values(lp, a, sizes, r)
        attn = attention(q, k, v, r)
        return x + r(attn.reshape(b, s, -1)) @ r(lp["wo"])


def ffn_part(lp, h, sizes=SIZES, index=0, operand_dtype=None):
    """Block ``index``'s feed-forward part on the stream ``h`` its
    attention left: ``(x_out, aux_loss, router_z_loss)``, both losses 0
    for a dense layer."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = h.shape
        m = rms(h, jnp.asarray(lp["ln2"]["scale"], jnp.float32),
                sizes["norm_eps"]).reshape(b * s, d)
        if is_dense(sizes, index):
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


def final_norm(params, x, sizes=SIZES):
    """``rms(x_L, gf)``: what the head and the prediction block read."""
    return rms(x, jnp.asarray(params["ln_f"]["scale"], jnp.float32),
               sizes["norm_eps"])


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm (``params["ln_f"]``: the stack's, or the prediction
    block's own given under that name) and the untied head on ``x`` [.., n,
    d], all the positions or a block of them: logits [.., n, V]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        return r(final_norm(params, x, sizes)) @ r(
            jnp.asarray(params["lm_head"], jnp.float32))


def mtp_input(mp, table, hf, next_ids, sizes=SIZES, operand_dtype=None,
              embedding_first=True):
    """``We_h [rms(Emb[next]) ; rms(hf)]``: what the prediction block's
    layer reads, from the stack's final NORMALIZED stream ``hf``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        mp = _f32({k: v for k, v in mp.items() if k != "layer"})
        e = rms(jnp.asarray(table, jnp.float32)[next_ids],
                mp["e_norm"]["scale"], sizes["norm_eps"])
        h = rms(hf, mp["h_norm"]["scale"], sizes["norm_eps"])
        halves = [e, h] if embedding_first else [h, e]
        return r(jnp.concatenate(halves, axis=-1)) @ r(mp["w_eh"])


def mtp_head_params(params) -> dict:
    """The prediction block's norm and the model's own head, in the form
    :func:`head` takes."""
    return {"ln_f": params["mtp"]["out_norm"], "lm_head": params["lm_head"]}


def ce_sum_of_logits(logits, targets):
    """Sum over the positions given of the cross-entropy; a position whose
    target is negative has none and adds nothing."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(targets >= 0, picked, 0.0))


def after_next(targets):
    """Position i's next-but-one id: the row's targets shifted by one, -1
    (no target) at the last position."""
    return jnp.concatenate(
        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)


def total_loss(ce_mean, aux_sum, z_sum, n_sparse, sizes=SIZES, ce_mtp_mean=0.0):
    return (ce_mean + sizes["mtp_loss_weight"] * ce_mtp_mean
            + sizes["aux_loss_weight"] * aux_sum / n_sparse
            + sizes["router_z_weight"] * z_sum / n_sparse)


def sparse_layers(params, sizes=SIZES) -> int:
    """Mixture layers whose routers a training step runs: the stack's and
    the prediction block's."""
    stack = sum(not is_dense(sizes, i) for i in range(len(params["layers"])))
    return stack + ("mtp" in params)


def forward(params, token_ids, next_ids=None, sizes=SIZES, operand_dtype=None):
    """``(logits [B, S, V], logits' or None, sum of aux losses, sum of
    router z-losses)``: everything at once, for sizes at which whole
    logits fit.  ``next_ids`` [B, S]: each position's next id; None = the
    stack alone."""
    x = embed(params, token_ids)
    aux_sum = z_sum = 0.0
    for index, lp in enumerate(params["layers"]):
        x, aux, z = layer(lp, x, sizes, index, operand_dtype)
        aux_sum, z_sum = aux_sum + aux, z_sum + z
    logits = head(params, x, sizes, operand_dtype)
    if next_ids is None:
        return logits, None, aux_sum, z_sum
    with jax.default_matmul_precision("highest"):
        hf = final_norm(params, x, sizes)
    zed = mtp_input(params["mtp"], params["embed"], hf, next_ids, sizes,
                    operand_dtype)
    zed, aux, z = layer(params["mtp"]["layer"], zed, sizes,
                        len(params["layers"]), operand_dtype)
    return (logits, head(mtp_head_params(params), zed, sizes, operand_dtype),
            aux_sum + aux, z_sum + z)


def losses(params, token_ids, targets, sizes=SIZES, operand_dtype=None):
    """``(loss, ce, ce')``: both mean cross-entropies (``ce'`` of position
    i against ``t_{i+2}`` over the B (S - 1) positions that have one) and
    their weighted sum with the load-balance and router z losses."""
    logits, logits_mtp, aux_sum, z_sum = forward(
        params, token_ids, targets, sizes, operand_dtype)
    b, s = targets.shape
    ce = ce_sum_of_logits(logits, targets) / (b * s)
    ce_mtp = ce_sum_of_logits(logits_mtp, after_next(targets)) / (b * (s - 1))
    return total_loss(ce, aux_sum, z_sum, sparse_layers(params, sizes), sizes,
                      ce_mtp), ce, ce_mtp


def loss(params, token_ids, targets, sizes=SIZES, operand_dtype=None):
    return losses(params, token_ids, targets, sizes, operand_dtype)[0]


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
