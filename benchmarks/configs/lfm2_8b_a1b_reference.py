"""Plain reference for the ``lfm2-8b-a1b`` configuration: the language model
of LFM2-8B-A1B (``config.json`` of
https://huggingface.co/LiquidAI/LFM2-8B-A1B, ``lfm2_moe``), forward, loss
and gradients, in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no sort, no grouped matmul: the convolution is three shifted
products, the mixture a scan over the experts with a boolean mask.  The only
remat is what lets a backward pass fit the chip at 16,384 tokens: a block
of attention scores and an expert's hidden rows are made again
(``jax.checkpoint``: the same operations in the same order).
It imports nothing of the program and takes the program's parameter tree
(any dtype; cast here to float32, a layer at a time), so seeded weights
serve both.

Layer ``index`` (0-based) of the layers RUN, residual stream ``x`` [B, S,
d]; ``sizes["layer_types"][index]`` says ``conv`` or ``full_attention``,
``sizes["mlp_layer_types"][index]`` ``dense`` or ``sparse``, with ``N(x; w)
= x / sqrt(mean(x^2) + eps) * w``::

    a  = N(x; w_op)
    conv:            [B | C | u] = a W_in        (three thirds, in that order)
                     v = B * u
                     c[t] = sum_{j=0..K-1} w[:, j] v[t - (K - 1) + j],  v[t<0] = 0
                     h = x + (C * c) W_out       no activation, no state
    full_attention:  q = heads(a Wq) [S, H, hd];  k, v = heads(a Wk), heads(a Wv)
                     q = N(q; gq) over each head's hd;  k = N(k; gk) likewise
                     q, k = rope(q), rope(k), rotate-half over the whole head
                     query head h reads key/value head h // (H / Hkv)
                     h = x + Wo concat_h softmax(q_h k^T / sqrt(hd)) v, causal
    m  = N(h; w_ffn)
    dense:   y = h + W2 (silu(W1 m) * (W3 m))
    sparse:  s = sigmoid(m Wr) [E], float32
             T = the k largest of s + b        b selects and does not weigh
             g_e = scale * s_e / sum_{j in T} s_j   for e in T
             y = h + sum_{e in T} g_e Expert_e(m)
    logits = N(x_L; w_f) embed^T               the head is the embedding table

Departures from the published description, each because ``config.json``
has no key for it (the configuration file lists them under ``assumed``):
the head is tied; the renormalising sum is written without the 1e-6 the
published code is believed to add; the selection bias ``b`` [E] is a
parameter no gradient reaches.  The losses beside the cross-entropy are
this repository's (load balance ``E * sum_e mean(s_e / sum s) *
top-1-load_e`` and ``mean(logsumexp(router logits)^2)``, per mixture
layer, mean over them), and weigh 0 in this configuration.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the keys
(a [32, 256, 16384] float32 score block is 0.54 GB, the whole 34 GB), the
caller runs a layer at a time (under ``jax.checkpoint`` a part where it
differentiates one), and the head and the cross-entropy take a block of
positions at a time (:func:`head`).

``operand_dtype`` rounds every matmul's operands (weights and activations)
AND the convolution's (``B``, ``C``, ``u``, the filter and ``v = B * u``
itself) to that dtype and back to float32: the same mathematics at a lower
precision, for showing that a tolerance tells the stated precision from the
one below it.  The router stays in float32, as the program's does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    n_heads=32, n_kv_heads=8, head_dim=64, experts_per_token=4,
    norm_eps=1e-5, rope_theta=1e6,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    routed_scaling_factor=1.0, norm_topk_prob=True,
    aux_loss_weight=0.0, router_z_weight=0.0,
)
ATTENTION_BLOCK = 256  # queries a block


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda a: a
    return lambda a: a.astype(operand_dtype).astype(jnp.float32)


def kind(sizes, index: int) -> tuple:
    """What layer ``index`` of the layers run is: ``(mixer, feed-forward)``."""
    return sizes["layer_types"][index], sizes["mlp_layer_types"][index]


def norm(x, p, eps):
    scale = jnp.asarray(p["scale"], jnp.float32)
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


def attention(q, k, v, r):
    """q [B, S, H, hd], k and v [B, S, Hkv, hd] -> [B, S, H, hd]: causal; a
    block of queries at a time against all the keys."""
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
        allowed = j[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(allowed, scores, -jnp.inf)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", r(jax.nn.softmax(scores, axis=-1)), v)
        return out.reshape(b, block, h, hd)

    # a backward pass makes a block's scores again: it keeps none
    blocks = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, hd)


def short_conv(bcu, w, r=lambda a: a):
    """``C * conv(B * u)`` of ``bcu`` [B, S, 3 Ch] under ``w`` [Ch, K]: K
    shifted products, zeros before position 0 of every row."""
    s, taps = bcu.shape[1], w.shape[1]
    b, c, u = jnp.split(r(bcu), 3, axis=-1)
    v = jnp.pad(r(b * u), ((0, 0), (taps - 1, 0), (0, 0)))
    w = r(w)
    return c * sum(w[:, j] * v[:, j:j + s] for j in range(taps))


def gated(p, u, r):
    """``Wd (silu(Wg u) * (Wu u))``: the dense layer's block."""
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
    """What the experts add for ``u`` [n, d]: their gate-weighted outputs,
    every expert of the layer."""
    _, _, _, g = router(moe, u, sizes)

    @jax.checkpoint  # a backward pass makes an expert's hidden rows again
    def expert(w_gate, w_up, w_down, g_e):
        hidden = jax.nn.silu(r(u) @ r(w_gate)) * (r(u) @ r(w_up))
        return g_e[:, None] * (r(hidden) @ r(w_down))

    def one_expert(y, e):
        return y + expert(*e), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (moe["w_gate"], moe["w_up"], moe["w_down"], g.T))
    return y


def conv_parts(lp, x, sizes=SIZES, operand_dtype=None):
    """A conv layer's mixer on the stream ``x`` by its three parts: ``([B | C
    | u], C * conv(B * u), what the mixer adds to the stream)``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        p = _f32(lp["conv"])
        bcu = r(norm(x, lp["ln1"], sizes["norm_eps"])) @ r(p["w_in"])
        y = short_conv(bcu, p["conv_w"], r)
        return bcu, y, r(y) @ r(p["w_out"])


def mixer_part(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """What layer ``index``'s token mixer adds to the stream ``x``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = x.shape
        eps = sizes["norm_eps"]
        if sizes["layer_types"][index] == "conv":
            return conv_parts(lp, x, sizes, operand_dtype)[2]
        a = norm(x, lp["ln1"], eps)
        lp = _f32({k: lp[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")})
        heads, kv_heads, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        q = (r(a) @ r(lp["wq"])).reshape(b, s, heads, hd)
        k = (r(a) @ r(lp["wk"])).reshape(b, s, kv_heads, hd)
        v = (r(a) @ r(lp["wv"])).reshape(b, s, kv_heads, hd)
        q, k = norm(q, lp["q_norm"], eps), norm(k, lp["k_norm"], eps)
        q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
        return r(attention(q, k, v, r).reshape(b, s, heads * hd)) @ r(lp["wo"])


def ffn_part(lp, h, sizes=SIZES, index=0, operand_dtype=None):
    """Layer ``index``'s feed-forward part on the stream ``h`` its mixer
    left: ``(x_out, aux_loss, router_z_loss)``, both losses 0 for a dense
    layer."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = h.shape
        m = norm(h, lp["ln2"], sizes["norm_eps"]).reshape(b * s, d)
        if sizes["mlp_layer_types"][index] == "dense":
            return h + gated(_f32(lp["ffn"]), m, r).reshape(b, s, d), 0.0, 0.0
        moe = _f32(lp["moe"])
        y = routed_part(moe, m, sizes, r)
        logits, scores, _, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        first_choice = jnp.argmax(scores + moe["router_bias"], axis=-1)
        p = scores / scores.sum(axis=-1, keepdims=True)
        aux = num_experts * jnp.sum(
            p.mean(axis=0) * jax.nn.one_hot(first_choice, num_experts).mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return h + y.reshape(b, s, d), aux, z


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """Layer ``index`` of the layers run: ``(x_out, aux_loss,
    router_z_loss)``.  ``lp`` is a layer of the program's parameter tree."""
    h = x + mixer_part(lp, x, sizes, index, operand_dtype)
    return ffn_part(lp, h, sizes, index, operand_dtype)


def router_logits(lp, h, sizes=SIZES):
    """[B * S, E] float32, on the stream ``h`` [B, S, d] the mixer left."""
    with jax.default_matmul_precision("highest"):
        m = norm(h, lp["ln2"], sizes["norm_eps"])
        return m.reshape(-1, h.shape[-1]) @ jnp.asarray(
            lp["moe"]["gate"], jnp.float32)


def router_scores(lp, h, sizes=SIZES):
    """[B * S, E]: ``s + b``, what the choice of experts is made on."""
    return jax.nn.sigmoid(router_logits(lp, h, sizes)) + jnp.asarray(
        lp["moe"]["router_bias"], jnp.float32)


def router_margin(lp, h, sizes=SIZES):
    """[B * S]: by how much a token's k-th largest ``s + b`` exceeds its
    (k+1)-th: how firmly the token's experts are decided."""
    ranked = jnp.sort(router_scores(lp, h, sizes), axis=-1)
    k = sizes["experts_per_token"]
    return ranked[:, -k] - ranked[:, -k - 1]


def embed(params, token_ids):
    return jnp.asarray(params["embed"], jnp.float32)[token_ids]


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm and the tied head on ``x`` [.., n, d], all the positions
    or a block of them: logits [.., n, V] (``params`` holds ``ln_f`` and
    ``embed``)."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        final = norm(x, params["ln_f"], sizes["norm_eps"])
        return r(final) @ r(jnp.asarray(params["embed"], jnp.float32)).T


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
