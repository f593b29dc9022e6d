"""Plain reference for the ``qwen3-next-80b-a3b`` configuration: the language
model that ``config.json`` of
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct describes
(``model_type`` ``qwen3_next``), forward, loss and gradients, in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no chunks, no triangular solve, no sort, no grouped matmul, no
buffer: the delta rule is a ``lax.scan`` over the POSITIONS that
carries the state ``S_t``, the attention full scores a block of queries at
a time, the mixture a scan over the held experts under a boolean mask.  It
imports nothing of the program and takes the program's parameter tree (any
dtype; cast here to float32, a layer at a time), so seeded weights serve
both.

Every norm but one is ``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(the tree's ``offset``).  Layer ``index`` (0-based) on the stream ``x``
[B, S, d]; ``sizes["layer_types"][index]`` says which mixer::

    h = x + Mixer(N(x; w1));  y = h + MoE(N(h; w2));  logits = Wlm N(x_L; wf)

    linear_attention  (the gated delta rule; Hk key heads of dk, Hv value
                       heads of dv, K taps; S_t in R^{dk x dv} a VALUE
                       head, S_0 = 0; value head j reads key head
                       j // (Hv / Hk))
       [q~ | k~ | v~ | z | b | a] = a W_in   widths Hk dk | Hk dk | Hv dv | Hv dv | Hv | Hv
       q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                 conv(u)[t] = sum_{j<K} w[:, j] u[t - (K-1) + j], zeros
                 before position 0, a channel at a time, no bias
       q_t <- q_t / sqrt(sum q_t^2 + 1e-6) / sqrt(dk)     a key head
       k_t <- k_t / sqrt(sum k_t^2 + 1e-6)
       beta_t = sigmoid(b_t)                   no factor 2
       g_t = -exp(A_log) softplus(a_t + dt_bias);  alpha_t = exp(g_t)
       S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
       o_t = S_t^T q_t
       y_t = rms(o_t) * gn * silu(z_t)         the norm FIRST (a head's dv,
                                               one PLAIN scale shared by
                                               the heads), then the gate
       Mixer = concat_j(y) W_out
    full_attention    (H heads over Hkv key/value heads of hd)
       [q | g] = a Wq   a head's 2 hd columns its query's hd then its gate's
       k = a Wk, v = a Wv;  q <- N(q; wq), k <- N(k; wk) over each head's hd
       the FIRST R of a head's hd rotated, pairs (j, j + R/2), theta; the
       rest untouched;  allowed(i, j) = j <= i;  scores / sqrt(hd)
       Mixer = (concat_h softmax(q_h k^T) v * sigmoid(g)) Wo

    MoE(m) = sigmoid(m w_g) * Shared(m) + sum_{e in T, e held} p_e / sum_T p * E_e(m)
       p = softmax(m W_r) over all E, float32;  T the k largest (ties to the
       lower index);  E_e(m) = Wd (silu(Wg m) * (Wu m));  Shared likewise

**The share** (``sizes["held"] = (first, count)``): the tree holds ``count``
of a layer's ``E`` experts; the router keeps its ``E`` outputs and its
``k``, the gates are normalised over all ``k`` chosen, and what the absent
experts would have added is left out.  The vocabulary held is whatever the
embedding and the head span; the layers run are the tree's.

The losses beside the cross-entropy are this repository's form (load
balance ``E * sum_e mean(p_e) * top-1-load_e`` and ``mean(logsumexp(router
logits)^2)``, a mixture layer each, mean over them) at the weights in
``sizes``.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the
keys, the caller runs a layer at a time, and the head and the
cross-entropy take a block of positions at a time (:func:`head`).  A
GRADIENT fits too (``jax.vjp`` of a layer at a time, as the caller runs
them): the recurrence forgets its states inside runs of ``RECURRENCE_RUN``
positions, the rule's projections and convolutions what lies between the
stream and the heads, the attention a block's scores and the mixture an
expert's hidden rows, and a backward pass makes them again (``jax.checkpoint``: the
same operations in the same order, so the values are the forward's).

``operand_dtype`` rounds every matmul's operands (weights and activations;
in the recurrence the state where a key or a query reads it, the keys, the
queries and what is written) to that dtype and back to float32: the same
mathematics at a lower precision, for showing that a tolerance tells the
stated precision from the one below it.  Every decay, every write strength,
the router and both gates' sigmoids stay float32, as the program's do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    layer_types=("linear_attention",) * 3 + ("full_attention",),
    n_heads=16, n_kv_heads=2, head_dim=256, rotary_dim=64, rope_theta=1e7,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128, conv_kernel=4,
    norm_eps=1e-6, experts_per_token=10, norm_topk_prob=True, held=(0, 64),
    aux_loss_weight=1e-3, router_z_weight=0.0,
)
ATTENTION_BLOCK = 256  # queries a block
RECURRENCE_RUN = 128  # positions whose states a gradient keeps as one
UNIT_EPS = 1e-6


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda a: a
    return lambda a: a.astype(operand_dtype).astype(jnp.float32)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def norm(x, p, eps):
    """``N(x; w)``: the multiplier is ``1 + w``."""
    return rms(x, eps) * (1.0 + jnp.asarray(p["offset"], jnp.float32))


def kind(sizes, index: int) -> str:
    """``linear_attention`` or ``full_attention``: the pattern repeats."""
    types = sizes["layer_types"]
    return types[index % len(types)]


# ---- the gated delta rule --------------------------------------------------


def causal_conv(u, w):
    """``conv(u)[t] = sum_j w[:, j] u[t - (K-1) + j]``: position t reads
    itself and the K-1 before it, zeros before the sequence; no bias."""
    b, s, channels = u.shape
    taps = w.shape[1]
    before = jnp.concatenate(
        [jnp.zeros((b, taps - 1, channels), jnp.float32), u], axis=1)
    return sum(w[:, j] * before[:, j:j + s] for j in range(taps))


def delta_inputs(p, a, sizes, r=lambda x: x):
    """``(q, k [B,S,Hv,dk], v, z [B,S,Hv,dv], beta, g [B,S,Hv])`` from the
    normalized stream ``a`` [B, S, d]: the in-projection, the convolutions
    with their SiLU, unit lengths, each key head given to the value heads
    that read it, write strengths, decays."""
    b, s, _ = a.shape
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    proj = r(a) @ r(p["w_in"])
    widths = (hk * dk, hk * dk, hv * dv, hv * dv, hv, hv)
    edges = [sum(widths[:i]) for i in range(len(widths) + 1)]
    q, k, v, z, write, step = (
        proj[..., lo:hi] for lo, hi in zip(edges, edges[1:]))
    filters = p["conv_w"]
    q = jax.nn.silu(causal_conv(q, filters[:edges[1]]))
    k = jax.nn.silu(causal_conv(k, filters[edges[1]:edges[2]]))
    v = jax.nn.silu(causal_conv(v, filters[edges[2]:]))

    def unit(x):
        x = x.reshape(b, s, hk, dk)
        x = x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + UNIT_EPS)
        return jnp.repeat(x, hv // hk, axis=2)  # value head j: key head j // ratio

    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(step + p["dt_bias"])
    return (unit(q) / jnp.sqrt(jnp.float32(dk)), unit(k),
            v.reshape(b, s, hv, dv), z.reshape(b, s, hv, dv),
            jax.nn.sigmoid(write), g)


def delta_recurrence(q, k, v, g, beta, r=lambda x: x):
    """The rule AS WRITTEN, a position at a time: ``(o [B,S,H,dv], S_{S-1}
    [B,H,dk,dv])``."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def one_position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # [B,H,dk] x2, [B,H,dv], [B,H] x2
        decayed = jnp.exp(g_t)[..., None, None] * state
        answered = jnp.sum(r(decayed) * r(k_t)[..., :, None], axis=-2)
        written = r(beta_t[..., None] * (v_t - answered))
        state = decayed + r(k_t)[..., :, None] * written[..., None, :]
        return state, jnp.sum(r(state) * r(q_t)[..., :, None], axis=-2)

    # the same scan in runs of RECURRENCE_RUN positions, each run's states
    # forgotten and made again in a backward pass (a gradient then keeps a
    # state a run, not a state a position: [S, B, H, dk, dv] is 34 GB at
    # 16,384 positions of 32 heads of 128 x 128)
    s = q.shape[1]
    run = RECURRENCE_RUN if s % RECURRENCE_RUN == 0 else s
    final, o = jax.lax.scan(
        jax.checkpoint(lambda state, at: jax.lax.scan(one_position, state, at)),
        jnp.zeros((b, h, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0).reshape(s // run, run, *t.shape[:1],
                                            *t.shape[2:])
              for t in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o.reshape(s, *o.shape[2:]), 0, 1), final


def delta_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A linear layer's mixer on the stream ``x`` [B, S, d]: ``(Mixer(N(x))
    [B, S, d], the state after the last position [B, Hv, dk, dv])``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        p = _f32(lp["delta"])
        b, s, _ = x.shape
        a = norm(x, lp["ln1"], sizes["norm_eps"])
        # a backward pass makes the projections and convolutions again, after
        # it is through the recurrence
        q, k, v, z, beta, g = jax.checkpoint(
            lambda p, a: delta_inputs(p, a, sizes, r))(p, a)
        o, final = delta_recurrence(q, k, v, g, beta, r)
        # the norm FIRST, a plain scale, then the gate
        y = rms(o, sizes["norm_eps"]) * p["gate_norm"]["scale"] * jax.nn.silu(z)
        return r(y.reshape(b, s, -1)) @ r(p["w_out"]), final


# ---- full attention -----------------------------------------------------------


def rope_first(x, theta, rotary_dim):
    """x [B, S, H, hd]: the first ``rotary_dim`` columns of a head rotated
    (pairs ``(j, j + rotary_dim / 2)``), position = index in the sequence;
    the rest untouched."""
    s = x.shape[1]
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    half = rotary_dim // 2
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


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
        i = start + jnp.arange(block)
        scores = jnp.where(j[None, :] <= i[:, None], scores, -jnp.inf)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", r(jax.nn.softmax(scores, axis=-1)), v)
        return out.reshape(b, block, h, hd)

    # a backward pass makes a block's scores again: it keeps none
    blocks = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, hd)


def attention_mixer(lp, x, sizes=SIZES, operand_dtype=None):
    """A full layer's mixer on the stream ``x``: ``Mixer(N(x))``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        w = _f32({name: lp[name] for name in ("wq", "wk", "wv", "wo")})
        b, s, _ = x.shape
        heads, kv_heads, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        eps = sizes["norm_eps"]
        a = norm(x, lp["ln1"], eps)
        qg = (r(a) @ r(w["wq"])).reshape(b, s, heads, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (r(a) @ r(w["wk"])).reshape(b, s, kv_heads, hd)
        v = (r(a) @ r(w["wv"])).reshape(b, s, kv_heads, hd)
        q, k = norm(q, lp["q_norm"], eps), norm(k, lp["k_norm"], eps)
        q = rope_first(q, sizes["rope_theta"], sizes["rotary_dim"])
        k = rope_first(k, sizes["rope_theta"], sizes["rotary_dim"])
        out = attention(q, k, v, r) * jax.nn.sigmoid(gate)
        return r(out.reshape(b, s, heads * hd)) @ r(w["wo"])


# ---- the mixture -------------------------------------------------------------


def gated(p, u, r):
    """``Wd (silu(Wg u) * (Wu u))``: an expert, the shared expert."""
    hidden = jax.nn.silu(r(u) @ r(p["w_gate"])) * (r(u) @ r(p["w_up"]))
    return r(hidden) @ r(p["w_down"])


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


def shared_gate(lp, m):
    """``sigmoid(m w_g)`` [n, 1]: one number a token."""
    return jax.nn.sigmoid(m @ jnp.asarray(lp["shared_gate"], jnp.float32))


def ffn_part(lp, h, sizes=SIZES, operand_dtype=None):
    """A layer's mixture on the stream ``h`` its mixer left: ``(y, aux_loss,
    router_z_loss)``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = h.shape
        m = norm(h, lp["ln2"], sizes["norm_eps"]).reshape(b * s, d)
        moe = _f32(lp["moe"])
        y = shared_gate(lp, m) * gated(_f32(lp["shared"]), m, r) + routed_part(
            moe, m, sizes, r)
        logits, p, rank, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        aux = num_experts * jnp.sum(
            p.mean(axis=0) * (rank == 0).astype(jnp.float32).mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return h + y.reshape(b, s, d), aux, z


def router_logits(lp, h, sizes=SIZES):
    """[B * S, E]: what the choice of experts is made on, on the stream
    ``h`` [B, S, d] the layer's mixer left."""
    with jax.default_matmul_precision("highest"):
        m = norm(h, lp["ln2"], sizes["norm_eps"])
        return m.reshape(-1, h.shape[-1]) @ jnp.asarray(
            lp["moe"]["gate"], jnp.float32)


def router_margin(lp, h, sizes=SIZES):
    """[B * S]: by how much a token's k-th largest router logit exceeds its
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


# ---- the layer and the stack ---------------------------------------------


def mixer_part(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """``(Mixer(N(x)), the delta state after the last position or None)``."""
    if kind(sizes, index) == "linear_attention":
        return delta_part(lp, x, sizes, operand_dtype)
    return attention_mixer(lp, x, sizes, operand_dtype), None


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """Layer ``index`` of the stack on the stream ``x`` [B, S, d]: ``(y,
    aux_loss, router_z_loss)``; ``lp`` is a layer of the program's tree."""
    out, _ = mixer_part(lp, x, sizes, index, operand_dtype)
    return ffn_part(lp, x + out, sizes, operand_dtype)


def embed(params, token_ids):
    return jnp.asarray(params["embed"], jnp.float32)[token_ids]


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm and the untied head on ``x`` [.., n, d], all the
    positions or a block of them: logits [.., n, V]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        final = norm(x, params["ln_f"], sizes["norm_eps"])
        return r(final) @ r(jnp.asarray(params["lm_head"], jnp.float32))


def ce_sum_of_logits(logits, targets):
    """Sum over the positions given of the next-token cross-entropy."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def total_loss(ce_mean, aux_sum, z_sum, n_layers, sizes=SIZES):
    return (ce_mean + sizes["aux_loss_weight"] * aux_sum / n_layers
            + sizes["router_z_weight"] * z_sum / n_layers)


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
    load-balance and router z losses (every layer routes)."""
    logits, aux_sum, z_sum = forward(params, token_ids, sizes, operand_dtype)
    return total_loss(
        ce_sum_of_logits(logits, targets) / targets.size, aux_sum, z_sum,
        len(params["layers"]), sizes)


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
