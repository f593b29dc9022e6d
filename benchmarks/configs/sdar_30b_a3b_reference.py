"""Plain reference for the ``sdar-30b-a3b`` configuration: the language model
that ``config.json`` of https://huggingface.co/JetLM/SDAR-30B-A3B-Chat
describes (``model_type`` ``sdar_moe``: Qwen3-MoE's block) TRAINED BY BLOCK
DIFFUSION (BD3-LMs' vectorised training, arXiv:2503.09573, which SDAR adopts
to adapt an autoregressive checkpoint): the noising, the doubled row, the
mask, the forward pass, the weighted loss and the gradients, in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no computable mask, no sort, no grouped matmul, no buffer, no
chunked loss: the mask is an explicit boolean array made of the three
published terms, the attention full scores a block of queries at a time,
the mixture a scan over the held experts under the gates.  It imports
nothing of the program and takes the program's parameter tree (any dtype;
cast here to float32, a layer at a time), so seeded weights serve both.

``N(x; w) = x / sqrt(mean(x^2) + eps) * w`` in float32 (the tree's
``scale``).  Layer on the stream ``x`` [B, 2 S, d]::

    h = x + Attn(N(x; w1));  y = h + MoE(N(h; w2));  logits = Wlm N(x_L; wf)

    Attn  (H heads over Hkv key/value heads of hd; no biases)
       q = a Wq, k = a Wk, v = a Wv;  q <- N(q; wq), k <- N(k; wk) over each
       head's hd, one scale shared by the heads;  rotate-half rotary over the
       whole head, pairs (j, j + hd/2), theta, POSITION i mod S;  query head
       h reads key/value head h // (H / Hkv);  scores / sqrt(hd), float32
       softmax under the mask M;  Attn = concat_h(softmax(q_h k^T) v) Wo
    MoE(m) = sum_{e in T, e held} p_e / sum_T p * E_e(m)
       p = softmax(m W_r) over all E, float32;  T the k largest (ties to the
       lower index);  E_e(m) = Wd (silu(Wg m) * (Wu m));  no shared expert

**The row and the mask** (block length ``L'``).  The stack's input is ``[x_t
| x_0]``: the noised copy, then the clean copy, of the same ``S`` ids (the
published order).  With ``blk(i) = (i mod S) // L'`` and ``noised(i) = i <
S``::

    M[i, j] =  (blk(i) == blk(j) and noised(i) == noised(j))        block-diagonal
            or (blk(i) >  blk(j) and noised(i) and not noised(j))   offset block-causal
            or (blk(i) >= blk(j) and not noised(i) and not noised(j))  block-causal

A clean position never sees a noised one; a noised block sees the clean
blocks before it and itself, both directions inside the block.

**The noising** (the draws are INPUTS: ``u`` [B, S] one a token, ``t`` [B, S
/ L'] one a block, both uniform on [0, 1)): ``p = floor + (1 - floor) t`` a
block; a token becomes the mask id where ``u < p``.

**The loss**: logits at the noised half only, in place (position ``i``
predicts ``x_0[i]``, no shift)::

    L = (1 / (B S)) sum_{i masked} CE(logits_i, x_0[i]) / p_blk(i)
        + aux_weight * mean over layers of the load-balancing term

Departures from a published form, each by the family's convention where the
config is silent (the configuration file's ``assumed``): ``L'`` = 4, the
linear schedule with one ``t`` a block, the floor 1e-3 and the weight ``1 /
p`` (BD3-LMs' and LLaDA's form), in-place prediction, the mask id the
slice's last id.  The load-balancing term is this repository's form (``E *
sum_e mean(p_e) * top-1-load_e`` over all ``2 S`` positions, a layer each,
mean over them; the z term ``mean(logsumexp(router logits)^2)`` at weight
0).

**The share** (``sizes["held"] = (first, count)``): the tree holds ``count``
of a layer's ``E`` experts; the router keeps its ``E`` outputs and its
``k``, the gates are normalised over all ``k`` chosen, and what the absent
experts would have added is left out.  The vocabulary held is whatever the
embedding and the head span; the layers run are the tree's.

It is written in blocks so that it fits one chip at 16,384 positions: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the keys,
the caller runs a layer at a time, and the head and the cross-entropy take
a block of positions at a time (:func:`head`).  A gradient fits too
(``jax.vjp`` of a layer at a time): the attention forgets a block's scores
and the mixture an expert's hidden rows, and a backward pass makes them
again (``jax.checkpoint``: the same operations in the same order).

``operand_dtype`` rounds every matmul's operands (weights and activations)
to that dtype and back to float32: the same mathematics at a lower
precision, for showing that a tolerance tells the stated precision from the
one below it.  The router, the norms and the softmax stay float32, as the
program's do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    n_heads=32, n_kv_heads=4, head_dim=128, rope_theta=1e6, norm_eps=1e-6,
    experts_per_token=8, norm_topk_prob=True, held=(0, 32),
    aux_loss_weight=1e-3, router_z_weight=0.0,
    block_length=4, p_floor=1e-3, mask_token_id=37983,
)
ATTENTION_BLOCK = 256  # queries a block


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda a: a
    return lambda a: a.astype(operand_dtype).astype(jnp.float32)


def norm(x, p, eps):
    """``N(x; w)``."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * jnp.asarray(p["scale"], jnp.float32)


# ---- the row, the mask, the noising ---------------------------------------


def mask(s: int, block_length: int):
    """``M`` [2 s, 2 s] bool, from the three published terms."""
    i = jnp.arange(2 * s)
    blk, noised = (i % s) // block_length, i < s
    bq, bk, nq, nk = blk[:, None], blk[None, :], noised[:, None], noised[None, :]
    block_diagonal = (bq == bk) & (nq == nk)
    offset_block_causal = (bq > bk) & nq & ~nk
    block_causal = (bq >= bk) & ~nq & ~nk
    return block_diagonal | offset_block_causal | block_causal


def masking_probability(t, sizes=SIZES):
    """``p`` a TOKEN [B, S] from a block's ``t`` [B, S / L']."""
    floor = sizes["p_floor"]
    return jnp.repeat(floor + (1.0 - floor) * t, sizes["block_length"], axis=1)


def noised_row(token_ids, u, t, sizes=SIZES):
    """``([x_t | x_0] [B, 2 S], weights [B, S])``: the weights are ``1 / p``
    at a masked position and 0 elsewhere."""
    p = masking_probability(t, sizes)
    masked = u < p
    x_t = jnp.where(masked, sizes["mask_token_id"], token_ids)
    return (jnp.concatenate([x_t, token_ids], axis=1),
            jnp.where(masked, 1.0 / p, 0.0))


# ---- attention ------------------------------------------------------------


def rope(x, theta):
    """x [B, 2 S, H, hd]: whole heads rotated, pairs ``(j, j + hd / 2)``,
    position ``i mod S``."""
    n, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    positions = (jnp.arange(n) % (n // 2)).astype(jnp.float32)
    angles = positions[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, allowed, r):
    """q [B, N, H, hd], k and v [B, N, Hkv, hd], ``allowed`` [N, N] bool ->
    [B, N, H, hd]: a block of queries at a time against all the keys."""
    b, n, h, hd = q.shape
    group = h // k.shape[2]
    k, v = r(k), r(v)
    block = min(ATTENTION_BLOCK, n)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        qb = r(qb).reshape(b, block, h // group, group, hd)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        rows = jax.lax.dynamic_slice_in_dim(allowed, start, block, axis=0)
        scores = jnp.where(rows, scores, -jnp.inf)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", r(jax.nn.softmax(scores, axis=-1)), v)
        return out.reshape(b, block, h, hd)

    # a backward pass makes a block's scores again: it keeps none
    blocks = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, n, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, n, h, hd)


def attention_part(lp, x, sizes=SIZES, operand_dtype=None):
    """``x + Attn(N(x))`` on the doubled stream ``x`` [B, 2 S, d]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        w = _f32({name: lp[name] for name in ("wq", "wk", "wv", "wo")})
        b, n, _ = x.shape
        heads, kv_heads, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        eps = sizes["norm_eps"]
        a = norm(x, lp["ln1"], eps)
        q = (r(a) @ r(w["wq"])).reshape(b, n, heads, hd)
        k = (r(a) @ r(w["wk"])).reshape(b, n, kv_heads, hd)
        v = (r(a) @ r(w["wv"])).reshape(b, n, kv_heads, hd)
        q = rope(norm(q, lp["q_norm"], eps), sizes["rope_theta"])
        k = rope(norm(k, lp["k_norm"], eps), sizes["rope_theta"])
        out = attention(q, k, v, mask(n // 2, sizes["block_length"]), r)
        return x + r(out.reshape(b, n, heads * hd)) @ r(w["wo"])


# ---- the mixture ----------------------------------------------------------


def router(moe, u, sizes):
    """``u`` [n, d] -> ``(logits, p, rank [n, E], gates [n, E])`` in
    float32: ``p`` the softmax over all E, ``rank`` an expert's place among
    a token's (0 the largest; ties to the lower index), gates 0 off the k
    chosen."""
    logits = u @ moe["gate"]
    p = jax.nn.softmax(logits, axis=-1)
    rank = jnp.argsort(jnp.argsort(-p, axis=-1, stable=True), axis=-1)
    g = jnp.where(rank < sizes["experts_per_token"], p, 0.0)
    if sizes["norm_topk_prob"]:
        g = g / g.sum(axis=-1, keepdims=True)
    return logits, p, rank, g


def routed_part(moe, u, sizes, r=lambda a: a):
    """What the experts in the tree add for ``u`` [n, d]: the held ones'
    gate-weighted outputs, gates over all E (:func:`router`)."""
    g = router(moe, u, sizes)[3]
    first, count = sizes["held"] or (0, g.shape[1])

    @jax.checkpoint  # a backward pass makes an expert's hidden rows again
    def added(e):
        w_gate, w_up, w_down, g_e = e
        hidden = jax.nn.silu(r(u) @ r(w_gate)) * (r(u) @ r(w_up))
        return g_e[:, None] * (r(hidden) @ r(w_down))

    def one_expert(y, e):
        return y + added(e), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (moe["w_gate"], moe["w_up"], moe["w_down"],
         g[:, first:first + count].T),
    )
    return y


def ffn_part(lp, h, sizes=SIZES, operand_dtype=None):
    """A layer's mixture on the stream ``h`` its attention left: ``(y,
    aux_loss, router_z_loss)``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, n, d = h.shape
        m = norm(h, lp["ln2"], sizes["norm_eps"]).reshape(b * n, d)
        moe = _f32(lp["moe"])
        y = routed_part(moe, m, sizes, r)
        logits, p, rank, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        aux = num_experts * jnp.sum(
            p.mean(axis=0) * (rank == 0).astype(jnp.float32).mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return h + y.reshape(b, n, d), aux, z


def router_logits(lp, h, sizes=SIZES):
    """[B * N, E]: what the choice of experts is made on, on the stream
    ``h`` [B, N, d] the layer's attention left."""
    with jax.default_matmul_precision("highest"):
        m = norm(h, lp["ln2"], sizes["norm_eps"])
        return m.reshape(-1, h.shape[-1]) @ jnp.asarray(
            lp["moe"]["gate"], jnp.float32)


def router_margin(lp, h, sizes=SIZES):
    """[B * N]: by how much a token's k-th largest router logit exceeds its
    (k+1)-th, where one of those two experts is HELD: how firmly this
    share's part of the token's result is decided.  Infinite where neither
    is held: whichever of the two is chosen, the experts here compute the
    same for the token (their gates' normaliser moves by the difference of
    two near-equal probabilities)."""
    logits = router_logits(lp, h, sizes)
    k = sizes["experts_per_token"]
    order = jnp.argsort(logits, axis=-1)
    pair = order[:, -k - 1:-k + 1 or None]  # the (k+1)-th and the k-th
    ranked = jnp.take_along_axis(logits, pair, axis=-1)
    first, count = sizes["held"] or (0, logits.shape[1])
    held = ((pair >= first) & (pair < first + count)).any(axis=-1)
    return jnp.where(held, ranked[:, 1] - ranked[:, 0], jnp.inf)


# ---- the layer and the stack ----------------------------------------------


def layer(lp, x, sizes=SIZES, operand_dtype=None):
    """A layer of the stack on the doubled stream ``x`` [B, 2 S, d]: ``(y,
    aux_loss, router_z_loss)``; ``lp`` is a layer of the program's tree."""
    return ffn_part(lp, attention_part(lp, x, sizes, operand_dtype), sizes,
                    operand_dtype)


def embed(params, row):
    return jnp.asarray(params["embed"], jnp.float32)[row]


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm and the untied head on ``x`` [.., n, d], the noised half's
    positions or a block of them: logits [.., n, V]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        final = norm(x, params["ln_f"], sizes["norm_eps"])
        return r(final) @ r(jnp.asarray(params["lm_head"], jnp.float32))


def ce_sum_of_logits(logits, targets, weights):
    """Sum over the positions given of ``weight * CE(logits_i, target_i)``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(
        weights * jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])


def total_loss(ce_mean, aux_sum, z_sum, n_layers, sizes=SIZES):
    return (ce_mean + sizes["aux_loss_weight"] * aux_sum / n_layers
            + sizes["router_z_weight"] * z_sum / n_layers)


def forward(params, token_ids, u, t, sizes=SIZES, operand_dtype=None):
    """``(logits of the noised half [B, S, V], weights [B, S], sum of aux
    losses, sum of router z-losses)``: everything at once, for sizes at
    which whole logits fit."""
    row, weights = noised_row(token_ids, u, t, sizes)
    x = embed(params, row)
    aux_sum = z_sum = 0.0
    for lp in params["layers"]:
        x, aux, z = layer(lp, x, sizes, operand_dtype)
        aux_sum, z_sum = aux_sum + aux, z_sum + z
    s = token_ids.shape[1]
    return head(params, x[:, :s], sizes, operand_dtype), weights, aux_sum, z_sum


def loss(params, token_ids, u, t, sizes=SIZES, operand_dtype=None):
    """The training loss: the masked positions' cross-entropies over their
    blocks' masking probabilities, over ``B S``, plus the weighted
    load-balance and router z losses (every layer routes)."""
    logits, weights, aux_sum, z_sum = forward(
        params, token_ids, u, t, sizes, operand_dtype)
    return total_loss(
        ce_sum_of_logits(logits, token_ids, weights) / token_ids.size,
        aux_sum, z_sum, len(params["layers"]), sizes)


def loss_and_grads(params, token_ids, u, t, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, u, t, sizes)
