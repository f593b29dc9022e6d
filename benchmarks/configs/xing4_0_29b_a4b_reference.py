"""Plain reference for the ``xing4.0-29b-a4b`` configuration: the language
model of Xing4.0-29B-A4B (``config.json`` of
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B, ``xing4_0``), forward,
both losses and gradients, in straightforward ``jax.numpy`` at float32
under ``jax.default_matmul_precision("highest")``.

No kernel, no sort, no grouped matmul, no buffer, no remat: a scan over
the held experts with a boolean mask.  It imports nothing of the program
and takes the program's parameter tree (any dtype; cast here to float32, a
layer at a time), so seeded weights serve both.  Written from the
equations below, which are the issue's, not from the program.

**The residual path** (manifold-constrained hyper-connections,
arXiv:2512.24880 over arXiv:2409.19606).  The stream is ``X`` [B, S, n, C],
``n = hc_mult = 4`` streams a token; ``X_0`` is the embedding copied to the
``n``.  Every PART (a layer's attention, a layer's feed-forward part; the
prediction block's two) has ``phi`` [n C, 2 n + n^2], ``b`` [2 n + n^2],
``alpha`` [3] of its own::

    u       = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)    one norm over the
              n C numbers, no learned scale
    m       = u phi                                        [2 n + n^2]
    H_pre   = sigmoid(alpha[0] m[:n] + b[:n])              [n]
    H_post  = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])        [n]
    M_0     = exp(clip(alpha[2] mat(m[2n:]) + mat(b[2n:]), clamp))   [n, n],
              row i the stream WRITTEN
    M_t     = T_r(T_c(M_{t-1})),  t = 1 .. hc_sinkhorn_iters
              T_c: each column over (its sum + hc_eps); T_r: rows alike
    H_res   = M_iters
    h       = sum_j H_pre[j] X[j]                          [C]
    y       = Part(h)       the model's own pre-norm part: Attn(rms(h, g1))
              or F(rms(h, g2)), the part's output alone (no ``h +``)
    X'[i]   = sum_j H_res[i, j] X[j]  +  H_post[i] y

After the last layer ``x_L = sum_i X[i]``, then the final norm and the
head.  Layer ``index`` (0-based) is dense where ``index <
sizes["first_k_dense_replace"]`` and a mixture after.

**Attention** (latent, expanded; ``a = rms(h, g1)``)::

    c_q   = rms(Wqa a, gq) [S, 768];   q = heads(Wqb c_q) [S, H, 128 + 64]
    [c | k_r] = Wkva a  [S, 512 + 64];  c_kv = rms(c, gkv)
    per head [k_nope 128 | v 128] = Wkvb c_kv
    q_rope = rope(q[.., 128:]),  k_r = rope(k_r): rotate-half over the 64,
        ONE k_r a token, shared by the heads; YaRN's frequencies (below)
    Attn  = Wo concat_h softmax_{j <= i}(scale q_h k_h^T) v_h,
        scale = (0.1 mscale_all_dim ln factor + 1)^2 / sqrt(192)

**YaRN** as the DeepSeek-V3 family writes it: pair ``i`` of the 32 turns at
``f_i = theta^(-2i/64)``; with ``pair(t) = 64 ln(original / (2 pi t)) / (2
ln theta)``, ``low = floor(pair(beta_fast))`` (10), ``high =
ceil(pair(beta_slow))`` (23), ``ramp_i = clip((i - low) / (high - low), 0,
1)``: ``inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i``; cosines and
sines times ``(0.1 mscale ln factor + 1) / (0.1 mscale_all_dim ln factor +
1)`` (1 here).

**Feed-forward part** (``m = rms(h, g2)``)::

    dense:   F = Wd (silu(Wg m) * (Wu m))
    mixture: s = sigmoid(Wr m) [E], float32
             T = the k largest of s + b        b selects and does not weigh
             g_e = scale * s_e / sum_{j in T} s_j   for e in T
             F = Shared(m) + sum_{e in T, e held} g_e Expert_e(m)

and after the stack the block that predicts the next-but-one token, with
``t_{i+1}`` position i's next id (a training row's targets)::

    hf     = rms(x_L, gf)
    z      = We_h [rms(Emb[t_{i+1}], ge) ; rms(hf, gh)]     [7168 -> 3584]
    Z_0    = z copied to n streams;  Z' = Layer_L(Z_0), a mixture layer with
             parameters (hyper-connections too) of its own;  z' = sum_i Z'[i]
    logits' = Wlm rms(z', go)                    the SAME head and table
    loss   = ce + w ce',   ce' = mean_{i <= S-2} CE(logits'_i, t_{i+2})

**The share** (``sizes["held"] = (first, count)``): the parameter tree
holds ``count`` of a layer's ``E`` experts; the router keeps its ``E``
outputs and its ``k``, the gates are normalised over all ``k`` chosen, and
what the absent experts would have added is left out.  ``held = None``:
every expert is in the tree.  The vocabulary held is whatever the
embedding and the head span.

It is written in blocks so that it fits one chip at 16,384 tokens, its
backward pass too: the attention takes ``ATTENTION_BLOCK`` queries at a
time against all the keys and an expert's hidden rows are one expert's at
a time (both under ``jax.checkpoint``: a backward pass makes them again),
the caller runs a layer (or a part) at a time, and the head and the
cross-entropy take a block of positions at a time (:func:`head`).

``operand_dtype`` rounds every matmul's operands (weights and
activations) to that dtype and back to float32: the same mathematics at a
lower precision, for showing that a tolerance tells the stated precision
from the one below it.  The router and the hyper-connections (their
coefficients, the read and the write) stay in float32, as the program's do.

``variant`` names a WRONG program for the tests (``hc_coefficients``):
``one_iteration``, ``identity_res``, ``static`` (alpha = 0), ``post_1``
(H_post without its 2), ``unnormalised`` (m = vec(X) phi).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SIZES = dict(
    n_heads=32, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, experts_per_token=4,
    norm_eps=1e-6, rope_theta=1e4,
    rope_scaling=dict(factor=64, original_max_position_embeddings=4096,
                      beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    first_k_dense_replace=1,
    routed_scaling_factor=2.0, norm_topk_prob=True, held=(0, 32),
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=(-30.0, 30.0),
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


# ---- the residual path ------------------------------------------------------


def hc_coefficients(hp, x, sizes=SIZES, variant=None):
    """``(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n])`` of the
    streams ``x`` [B, S, n, C] under the part's ``hp`` (``phi``, ``b``,
    ``alpha``), float32."""
    with jax.default_matmul_precision("highest"):
        hp = _f32(hp)
        b, s, n, c = x.shape
        vec = x.reshape(b, s, n * c)
        u = vec if variant == "unnormalised" else vec / jnp.sqrt(
            jnp.mean(vec * vec, axis=-1, keepdims=True) + sizes["norm_eps"])
        m = u @ hp["phi"]
        alpha = jnp.zeros(3) if variant == "static" else hp["alpha"]
        bias = hp["b"]
        pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
        post = jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + bias[n:2 * n])
        if variant != "post_1":
            post = 2.0 * post
        logits = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
        res = jnp.exp(jnp.clip(logits, *sizes["hc_clamp"]))
        iters = 1 if variant == "one_iteration" else sizes["hc_sinkhorn_iters"]

        def one_round(res, _):  # T_c, then T_r
            res = res / (res.sum(axis=-2, keepdims=True) + sizes["hc_eps"])
            res = res / (res.sum(axis=-1, keepdims=True) + sizes["hc_eps"])
            return res, None

        # a loop, not ``iters`` copies of the round: a program compiles in
        # half the time, and the comparison's are compiled in every run
        res, _ = jax.lax.scan(one_round, res, None, length=iters)
        if variant == "identity_res":
            res = jnp.broadcast_to(jnp.eye(n), res.shape)
        return pre, post, res


def hc_part(hp, x, part, sizes=SIZES, variant=None):
    """The streams after a part: ``part(h)`` is the part's output [B, S, C]
    for what it reads, ``h``.  Returns ``(X', h, y)``."""
    pre, post, res = hc_coefficients(hp, x, sizes, variant)
    with jax.default_matmul_precision("highest"):
        h = jnp.einsum("bsj,bsjc->bsc", pre, x)
        y = part(h)
        return (jnp.einsum("bsij,bsjc->bsic", res, x)
                + post[..., None] * y[:, :, None, :]), h, y


def copy_in(x, sizes=SIZES):
    """[B, S, C] copied to the ``hc_mult`` streams."""
    return jnp.broadcast_to(
        x[:, :, None, :], (*x.shape[:2], sizes["hc_mult"], x.shape[-1]))


def sum_out(x):
    return x.sum(axis=2)


# ---- attention --------------------------------------------------------------


def yarn_inv_freq(dim, theta, scaling):
    """The ``dim / 2`` frequencies under YaRN, and what multiplies the
    cosines and sines."""
    pairs = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    plain = 1.0 / theta ** pairs
    if scaling is None:
        return plain, 1.0
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * (1.0 - ramp) + plain / factor * ramp,
            _mscale(factor, scaling["mscale"])
            / _mscale(factor, scaling["mscale_all_dim"]))


def _mscale(factor, weight):
    return 1.0 if factor <= 1 else 0.1 * weight * math.log(factor) + 1.0


def softmax_scale(sizes) -> float:
    """``1 / sqrt(192)`` times YaRN's ``mscale`` squared."""
    scale = 1.0 / math.sqrt(sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"])
    scaling = sizes.get("rope_scaling")
    if scaling is not None:
        scale *= _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rope(x, theta, scaling=None):
    """x [B, S, H, r]; position of a token = its index in the sequence;
    dimension j pairs with j + r/2."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq, amplitude = yarn_inv_freq(r, theta, scaling)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [S, r]
    cos = amplitude * jnp.cos(angles)[None, :, None, :]
    sin = amplitude * jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v, r, scale):
    """q, k [B, S, H, dk], v [B, S, H, dv] -> [B, S, H, dv]: causal; a
    block of queries at a time against all the keys."""
    b, s, h, _ = q.shape
    k, v = r(k), r(v)
    block = min(ATTENTION_BLOCK, s)
    j = jnp.arange(s)

    def one_block(start):
        qb = r(jax.lax.dynamic_slice_in_dim(q, start, block, axis=1))
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k) * scale
        i = start + jnp.arange(block)
        scores = jnp.where(j[None, :] <= i[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd", r(jax.nn.softmax(scores, axis=-1)), v)

    # a backward pass makes a block's scores again (jax.checkpoint: the same
    # operations in the same order)
    blocks = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, v.shape[-1])


def queries_keys_values(lp, a, sizes, r=lambda a: a):
    """The expanded q, k [B, S, H, nope + rope] and v [B, S, H, dv] of the
    normalized input ``a``; ``lp`` float32."""
    b, s, _ = a.shape
    heads, eps = sizes["n_heads"], sizes["norm_eps"]
    rank, nope = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"]
    rot, dv = sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    scaling = sizes.get("rope_scaling")
    c_q = rms(r(a) @ r(lp["wq_a"]), lp["q_a_norm"]["scale"], eps)
    q = (r(c_q) @ r(lp["wq_b"])).reshape(b, s, heads, nope + rot)
    down = r(a) @ r(lp["wkv_a"])  # [c | k_r]
    c_kv = rms(down[..., :rank], lp["kv_a_norm"]["scale"], eps)
    k_r = down[..., rank:].reshape(b, s, 1, rot)
    up = (r(c_kv) @ r(lp["wkv_b"])).reshape(b, s, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    q_rope = rope(q[..., nope:], sizes["rope_theta"], scaling)
    k_r = rope(k_r, sizes["rope_theta"], scaling)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, s, heads, rot))], axis=-1)
    return q, k, v


def attention_output(lp, h, sizes=SIZES, operand_dtype=None):
    """``Attn(rms(h, g1))``: what the attention part gives for what it
    reads, ``h`` [B, S, C]."""
    with jax.default_matmul_precision("highest"):
        lp = _f32({k: v for k, v in lp.items()
                   if k not in ("ffn", "moe", "shared", "hc_attn", "hc_ffn")})
        r = _rounder(operand_dtype)
        b, s, _ = h.shape
        a = rms(h, lp["ln1"]["scale"], sizes["norm_eps"])
        q, k, v = queries_keys_values(lp, a, sizes, r)
        attn = attention(q, k, v, r, softmax_scale(sizes))
        return r(attn.reshape(b, s, -1)) @ r(lp["wo"])


# ---- the feed-forward part --------------------------------------------------


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


def routed_part(moe, u, sizes, r=lambda a: a, held=None):
    """What the experts in the tree add for ``u`` [n, d]: the held ones'
    gate-weighted outputs, gates over all E (:func:`router`).  ``held``
    overrides ``sizes["held"]`` (the share test's two shares)."""
    _, _, _, g = router(moe, u, sizes)
    first, count = held or sizes["held"] or (0, g.shape[1])

    @jax.checkpoint  # a backward pass makes an expert's hidden rows again
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


def ffn_output(lp, h, sizes=SIZES, index=0, operand_dtype=None):
    """``F(rms(h, g2))`` for what the feed-forward part reads, ``h`` [B, S,
    C]: ``(y, aux_loss, router_z_loss)``, both losses 0 for a dense layer."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = h.shape
        m = rms(h, jnp.asarray(lp["ln2"]["scale"], jnp.float32),
                sizes["norm_eps"]).reshape(b * s, d)
        if is_dense(sizes, index):
            return gated(_f32(lp["ffn"]), m, r).reshape(b, s, d), 0.0, 0.0
        moe = _f32(lp["moe"])
        y = gated(_f32(lp["shared"]), m, r) + routed_part(moe, m, sizes, r)

        logits, scores, _, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        first_choice = jnp.argmax(scores + moe["router_bias"], axis=-1)
        p = scores / scores.sum(axis=-1, keepdims=True)
        aux = num_experts * jnp.sum(
            p.mean(axis=0) * jax.nn.one_hot(first_choice, num_experts).mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return y.reshape(b, s, d), aux, z


# ---- a layer ----------------------------------------------------------------


def attention_part(lp, x, sizes=SIZES, index=0, operand_dtype=None,
                   variant=None):
    """The streams [B, S, n, C] after block ``index``'s attention."""
    return hc_part(
        lp["hc_attn"], x,
        lambda h: attention_output(lp, h, sizes, operand_dtype),
        sizes, variant)[0]


def ffn_part(lp, x, sizes=SIZES, index=0, operand_dtype=None, variant=None):
    """Block ``index``'s feed-forward part on the streams ``x`` its
    attention left: ``(X', aux_loss, router_z_loss)``."""
    losses = []

    def part(h):
        y, aux, z = ffn_output(lp, h, sizes, index, operand_dtype)
        losses.append((aux, z))
        return y

    out = hc_part(lp["hc_ffn"], x, part, sizes, variant)[0]
    return out, *losses[0]


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None, variant=None):
    """Block ``index`` of the stack: ``(X', aux_loss, router_z_loss)``.
    ``lp`` is a layer of the program's parameter tree."""
    return ffn_part(
        lp, attention_part(lp, x, sizes, index, operand_dtype, variant),
        sizes, index, operand_dtype, variant)


def ffn_reads(lp, x, sizes=SIZES):
    """What the feed-forward part reads of the streams ``x`` its attention
    left: ``h`` [B, S, C]."""
    pre, _, _ = hc_coefficients(lp["hc_ffn"], x, sizes)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bsj,bsjc->bsc", pre, x)


def router_scores(lp, x, sizes=SIZES):
    """[B * S, E]: ``s + b``, what the choice of experts is made on, on the
    streams ``x`` [B, S, n, C] the layer's attention left."""
    h = ffn_reads(lp, x, sizes)
    with jax.default_matmul_precision("highest"):
        moe = _f32(lp["moe"])
        m = rms(h, jnp.asarray(lp["ln2"]["scale"], jnp.float32),
                sizes["norm_eps"])
        return jax.nn.sigmoid(
            m.reshape(-1, h.shape[-1]) @ moe["gate"]) + moe["router_bias"]


def router_margin(lp, x, sizes=SIZES):
    """[B * S]: by how much a token's k-th largest ``s + b`` exceeds its
    (k+1)-th, where one of those two experts is HELD; infinite where
    neither is (``glm_4_7_flash_reference.router_margin``)."""
    scores = router_scores(lp, x, sizes)
    k = sizes["experts_per_token"]
    order = jnp.argsort(scores, axis=-1)
    pair = order[:, -k - 1:-k + 1 or None]  # the (k+1)-th and the k-th
    ranked = jnp.take_along_axis(scores, pair, axis=-1)
    first, count = sizes["held"] or (0, scores.shape[1])
    held = ((pair >= first) & (pair < first + count)).any(axis=-1)
    return jnp.where(held, ranked[:, 1] - ranked[:, 0], jnp.inf)


# ---- the ends ---------------------------------------------------------------


def embed(params, token_ids):
    return jnp.asarray(params["embed"], jnp.float32)[token_ids]


def final_norm(params, x, sizes=SIZES):
    """``rms(x_L, gf)`` of the SUMMED stream ``x`` [B, S, C]: what the head
    and the prediction block read."""
    return rms(x, jnp.asarray(params["ln_f"]["scale"], jnp.float32),
               sizes["norm_eps"])


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm (``params["ln_f"]``: the stack's, or the prediction
    block's own given under that name) and the untied head on the summed
    stream ``x`` [.., n, d], all the positions or a block of them."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        return r(final_norm(params, x, sizes)) @ r(
            jnp.asarray(params["lm_head"], jnp.float32))


def mtp_input(mp, table, hf, next_ids, sizes=SIZES, operand_dtype=None):
    """``We_h [rms(Emb[next]) ; rms(hf)]`` [B, S, C]: what is copied to the
    prediction block's streams, from the stack's final NORMALIZED stream."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        mp = _f32({k: v for k, v in mp.items() if k != "layer"})
        e = rms(jnp.asarray(table, jnp.float32)[next_ids],
                mp["e_norm"]["scale"], sizes["norm_eps"])
        h = rms(hf, mp["h_norm"]["scale"], sizes["norm_eps"])
        return r(jnp.concatenate([e, h], axis=-1)) @ r(mp["w_eh"])


def mtp_head_params(params) -> dict:
    return {"ln_f": params["mtp"]["out_norm"], "lm_head": params["lm_head"]}


def ce_sum_of_logits(logits, targets):
    """Sum over the positions given of the cross-entropy; a position whose
    target is negative has none and adds nothing."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    # the target's entry by a mask, not a lookup: the same number, and its
    # transpose is a product where a lookup's is a scatter-add, which the
    # chip's compiler takes seconds over
    at_target = targets[..., None] == jnp.arange(logits.shape[-1])
    return -jnp.sum(jnp.where(at_target, logp, 0.0))


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
    stack = sum(not is_dense(sizes, i) for i in range(len(params["layers"])))
    return stack + ("mtp" in params)


def forward(params, token_ids, next_ids=None, sizes=SIZES, operand_dtype=None,
            variant=None, streams=None):
    """``(logits [B, S, V], logits' or None, sum of aux losses, sum of
    router z-losses)``: everything at once, for sizes at which whole
    logits fit.  ``streams``: a list that receives the streams after the
    copy-in, after every layer and after the block's layer."""
    x = copy_in(embed(params, token_ids), sizes)
    keep = (lambda a: None) if streams is None else streams.append
    keep(x)
    aux_sum = z_sum = 0.0
    for index, lp in enumerate(params["layers"]):
        x, aux, z = layer(lp, x, sizes, index, operand_dtype, variant)
        keep(x)
        aux_sum, z_sum = aux_sum + aux, z_sum + z
    x = sum_out(x)
    logits = head(params, x, sizes, operand_dtype)
    if next_ids is None:
        return logits, None, aux_sum, z_sum
    with jax.default_matmul_precision("highest"):
        hf = final_norm(params, x, sizes)
    zed = copy_in(mtp_input(params["mtp"], params["embed"], hf, next_ids, sizes,
                            operand_dtype), sizes)
    zed, aux, z = layer(params["mtp"]["layer"], zed, sizes,
                        len(params["layers"]), operand_dtype, variant)
    keep(zed)
    return (logits,
            head(mtp_head_params(params), sum_out(zed), sizes, operand_dtype),
            aux_sum + aux, z_sum + z)


def losses(params, token_ids, targets, sizes=SIZES, operand_dtype=None,
           variant=None):
    """``(loss, ce, ce')``."""
    logits, logits_mtp, aux_sum, z_sum = forward(
        params, token_ids, targets, sizes, operand_dtype, variant)
    b, s = targets.shape
    ce = ce_sum_of_logits(logits, targets) / (b * s)
    ce_mtp = ce_sum_of_logits(logits_mtp, after_next(targets)) / (b * (s - 1))
    return total_loss(ce, aux_sum, z_sum, sparse_layers(params, sizes), sizes,
                      ce_mtp), ce, ce_mtp


def loss(params, token_ids, targets, sizes=SIZES, operand_dtype=None):
    return losses(params, token_ids, targets, sizes, operand_dtype)[0]


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
