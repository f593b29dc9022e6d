"""Plain reference for the ``ling-3.0-flash-vl`` configuration: the language
model that ``config.json`` of
https://huggingface.co/inclusionAI/Ling-3.0-flash-VL describes, forward,
loss and gradients, in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no chunks, no triangular solve, no sort, no grouped matmul, no
buffer: the delta rule is a ``lax.scan`` over the POSITIONS that carries the
state ``S_t``, the attention full scores a block of queries at a time, the
router a loop-free restatement of "the two best of a group, the four best
groups, the eight best inside them", the mixture a scan over the held
experts under a mask.  It imports nothing of the program and takes the
program's parameter tree (any dtype; cast here to float32, a layer at a
time), so seeded weights serve both.  Written from the equations below.

``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.  Layer ``index`` on the stream
``x`` [B, S, d]; ``sizes["layer_types"][index]`` says which mixer, and the
layer's tree which feed-forward part (``ffn``: dense; ``moe``: the mixture)::

    h = x + Mixer(N(x; w1));  y = h + F(N(h; w2));  logits = Wlm N(x_L; wf)

    kda   (Kimi Delta Attention, arXiv:2510.26692; H heads, keys dk, values dv)
       [q~ | k~ | v~] = a W_in            three [d, H dk] blocks side by side
       q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                 conv(u)[t] = sum_{j<K} w[:, j] u[t - (K-1) + j], zeros
                 before position 0, a channel at a time, no bias
       q_t <- q_t / sqrt(sum q_t^2 + 1e-6) / sqrt(dk);  k_t <- k_t / sqrt(sum k_t^2 + 1e-6)
       beta_t = sigmoid(a W_beta)                          [H]
       f_t = a W_decay                                      [H, dk]
       g_t = lower_bound * sigmoid(exp(A_log_h) (f_t + dt_bias))   in (lower_bound, 0)
       alpha_t = exp(g_t)                                   a number a KEY CHANNEL
       S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
       o_t = S_t^T q_t
       y_t = N(o_t; gn) * sigmoid(a W_gate)_h     the norm over a head's dv,
                                                  ONE gate a head a token
       Mixer = concat_h(y) W_out
    latent (H heads; no query latent)
       q = heads(a W_q)  [S, H, nope + rope]
       [c | k_r] = a W_kva;  c_kv = N(c; gkv);  per head [k_nope | v] = c_kv W_kvb
       the LAST rope of a head's query and k_r rotated (rotate-half pairs,
       theta), ONE k_r a token shared by the heads;  allowed(i, j) = j <= i;
       scores / sqrt(nope + rope), float32 softmax
       Mixer = (concat_h softmax(q_h k_h^T) v_h * sigmoid(a W_gate)_h) W_o

    dense:   F(m) = Wd (silu(Wg m) * (Wu m))
    mixture: s = sigmoid(m W_r) [E];  sel = s + b        b selects, never weighs
             the E experts are n_group groups of E / n_group CONSECUTIVE
             experts; a group's score the sum of its two largest sel; the
             topk_group best groups are kept (ties to the lower group);
             T = the k largest sel among the kept groups' experts (ties to
             the lower index);  g_e = scale * s_e / sum_{j in T} s_j
             F(m) = Shared(m) + sum_{e in T, e held} g_e Expert_e(m)

**The share** (``sizes["held"] = (first, count)``): the tree holds ``count``
of a layer's ``E`` experts; the router keeps its ``E`` outputs, its groups
and its ``k``, the gates are normalised over all ``k`` chosen, and what the
absent experts would have added is left out.  ``held`` None: every expert
is in the tree.  The vocabulary held is whatever the embedding and the head
span; the layers run are the tree's.

The losses beside the cross-entropy are this repository's form (load
balance ``E * sum_e mean(p_e) * top-1-load_e`` with ``p = s / sum s``, and
``mean(logsumexp(router logits)^2)``, a mixture layer each, mean over them)
at the weights in ``sizes`` (both 0 in the configuration).

Written in blocks so that it fits one chip at 16,384 tokens, as
``qwen3_next_80b_a3b_reference`` is: the attention a block of queries at a
time, the recurrence forgetting its states inside runs of
``RECURRENCE_RUN`` positions (``jax.checkpoint``), the head a block of
positions at a time (the caller's loop).  ``operand_dtype`` rounds every
matmul's operands to that dtype and back: the same mathematics at a lower
precision.  Every decay, write strength, the router and the gates' sigmoids
stay float32, as the program's do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    # published layers 1..7: layer i latent where (i + 1) % 6 == 0
    layer_types=("kda",) * 4 + ("latent",) + ("kda",) * 2,
    n_heads=32, kda_key_dim=128, kda_value_dim=128, conv_kernel=4,
    kda_lower_bound=-5.0, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=6e6, norm_eps=1e-6,
    experts_per_token=8, n_group=8, topk_group=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, held=(0, 64),
    aux_loss_weight=0.0, router_z_weight=0.0,
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
    return rms(x, eps) * jnp.asarray(p["scale"], jnp.float32)


def kind(sizes, index: int) -> str:
    """``kda`` or ``latent``: the layer's mixer."""
    return sizes["layer_types"][index % len(sizes["layer_types"])]


# ---- Kimi Delta Attention ----------------------------------------------------


def causal_conv(u, w):
    """``conv(u)[t] = sum_j w[:, j] u[t - (K-1) + j]``, zeros before the
    sequence; no bias."""
    b, s, channels = u.shape
    taps = w.shape[1]
    before = jnp.concatenate(
        [jnp.zeros((b, taps - 1, channels), jnp.float32), u], axis=1)
    return sum(w[:, j] * before[:, j:j + s] for j in range(taps))


def head_gate(p, a, r=lambda x: x):
    """``sigmoid(a W_gate)`` [B, S, H, 1]: one number a head a token."""
    return jax.nn.sigmoid(r(a) @ r(p["w_gate"]))[..., None]


def kda_decay(p, a, sizes, r=lambda x: x):
    """``g`` [B, S, H, dk] in ``(lower_bound, 0)``: the bounded gate."""
    b, s, _ = a.shape
    h, dk = sizes["n_heads"], sizes["kda_key_dim"]
    f = (r(a) @ r(p["w_decay"])).reshape(b, s, h, dk)
    return sizes["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * (f + p["dt_bias"].reshape(h, dk)))


def kda_inputs(p, a, sizes, r=lambda x: x):
    """``(q, k [B,S,H,dk], v [B,S,H,dv], beta [B,S,H], g [B,S,H,dk])``
    from the normalized stream ``a`` [B, S, d]."""
    b, s, _ = a.shape
    h, dk, dv = sizes["n_heads"], sizes["kda_key_dim"], sizes["kda_value_dim"]
    proj = r(a) @ r(p["w_in"])
    edges = (0, h * dk, 2 * h * dk, 2 * h * dk + h * dv)
    q, k, v = (jax.nn.silu(causal_conv(proj[..., lo:hi], p["conv_w"][lo:hi]))
               for lo, hi in zip(edges, edges[1:]))

    def unit(x):
        x = x.reshape(b, s, h, dk)
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + UNIT_EPS)

    return (unit(q) / jnp.sqrt(jnp.float32(dk)), unit(k),
            v.reshape(b, s, h, dv), jax.nn.sigmoid(r(a) @ r(p["w_beta"])),
            kda_decay(p, a, sizes, r))


def kda_recurrence(q, k, v, g, beta, r=lambda x: x):
    """The rule AS WRITTEN, a position at a time: ``(o [B,S,H,dv], S_{S-1}
    [B,H,dk,dv])``.  ``g`` [B,S,H,dk]: the decay scales the state's ROWS."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def one_position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # [B,H,dk] x2, [B,H,dv], [B,H,dk], [B,H]
        decayed = jnp.exp(g_t)[..., :, None] * state
        answered = jnp.sum(r(decayed) * r(k_t)[..., :, None], axis=-2)
        written = r(beta_t[..., None] * (v_t - answered))
        state = decayed + r(k_t)[..., :, None] * written[..., None, :]
        return state, jnp.sum(r(state) * r(q_t)[..., :, None], axis=-2)

    run = RECURRENCE_RUN if s % RECURRENCE_RUN == 0 else s
    final, o = jax.lax.scan(
        jax.checkpoint(lambda state, at: jax.lax.scan(one_position, state, at)),
        jnp.zeros((b, h, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0).reshape(s // run, run, *t.shape[:1],
                                            *t.shape[2:])
              for t in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o.reshape(s, *o.shape[2:]), 0, 1), final


def kda_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A KDA layer's mixer on the stream ``x`` [B, S, d]: ``(Mixer(N(x)),
    the state after the last position [B, H, dk, dv])``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        p = _f32(lp["delta"])
        b, s, _ = x.shape
        a = norm(x, lp["ln1"], sizes["norm_eps"])
        q, k, v, beta, g = jax.checkpoint(
            lambda p, a: kda_inputs(p, a, sizes, r))(p, a)
        o, final = kda_recurrence(q, k, v, g, beta, r)
        y = rms(o, sizes["norm_eps"]) * p["gate_norm"]["scale"] * head_gate(p, a, r)
        return r(y.reshape(b, s, -1)) @ r(p["w_out"]), final


# ---- latent attention ----------------------------------------------------------


def rope(x, theta):
    """x [B, S, H, R]: rotate-half pairs ``(j, j + R/2)``, position = index
    in the sequence."""
    s, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, r):
    """q, k [B, S, H, hd], v [B, S, H, hd_v] -> [B, S, H, hd_v]: causal, a
    block of queries at a time against all the keys."""
    b, s, h, hd = q.shape
    k, v = r(k), r(v)
    block = min(ATTENTION_BLOCK, s)
    j = jnp.arange(s)

    def one_block(start):
        qb = r(jax.lax.dynamic_slice_in_dim(q, start, block, axis=1))
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k) / jnp.sqrt(jnp.float32(hd))
        i = start + jnp.arange(block)
        scores = jnp.where(j[None, :] <= i[:, None], scores, -jnp.inf)
        return jnp.einsum(
            "bhqs,bshd->bqhd", r(jax.nn.softmax(scores, axis=-1)), v)

    blocks = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, v.shape[-1])


def latent_mixer(lp, x, sizes=SIZES, operand_dtype=None, gated=True):
    """A latent layer's mixer on the stream ``x``: ``Mixer(N(x))``.
    ``gated`` False leaves the head-wise gate out (what a program without
    it computes)."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        w = _f32({name: lp[name] for name in ("wq", "wkv_a", "wkv_b", "wo", "w_gate")})
        b, s, _ = x.shape
        h, eps = sizes["n_heads"], sizes["norm_eps"]
        nope, rot = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
        rank, hd_v = sizes["kv_lora_rank"], sizes["v_head_dim"]
        a = norm(x, lp["ln1"], eps)
        q = (r(a) @ r(w["wq"])).reshape(b, s, h, nope + rot)
        kv = r(a) @ r(w["wkv_a"])
        c_kv = norm(kv[..., :rank], lp["kv_a_norm"], eps)
        k_r = rope(kv[..., rank:].reshape(b, s, 1, rot), sizes["rope_theta"])
        up = (r(c_kv) @ r(w["wkv_b"])).reshape(b, s, h, nope + hd_v)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], sizes["rope_theta"])], axis=-1)
        k = jnp.concatenate(
            [up[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rot))], axis=-1)
        out = attention(q, k, up[..., nope:], r)
        if gated:
            out = out * head_gate(w, a, r)
        return r(out.reshape(b, s, h * hd_v)) @ r(w["wo"])


# ---- the feed-forward parts ----------------------------------------------------


def gated(p, u, r):
    """``Wd (silu(Wg u) * (Wu u))``: the dense layer, an expert, the shared
    expert."""
    hidden = jax.nn.silu(r(u) @ r(p["w_gate"])) * (r(u) @ r(p["w_up"]))
    return r(hidden) @ r(p["w_down"])


def _best(x, count):
    """[.., n] -> bool [.., n]: the ``count`` largest, ties to the lower
    index (an entry's place among its row's, by a stable sort)."""
    place = jnp.argsort(jnp.argsort(-x, axis=-1, stable=True), axis=-1)
    return place < count


def group_scores(sel, sizes):
    """[n, n_group]: the sum of each group's two largest selection scores."""
    n, num_experts = sel.shape
    groups = sel.reshape(n, sizes["n_group"], -1)
    return jnp.where(_best(groups, 2), groups, 0.0).sum(axis=-1)


def selection(sel, sizes):
    """``sel`` [n, E] with the experts outside a token's ``topk_group`` best
    groups at minus infinity."""
    kept = _best(group_scores(sel, sizes), sizes["topk_group"])  # [n, n_group]
    return jnp.where(
        jnp.repeat(kept, sel.shape[1] // sizes["n_group"], axis=1), sel, -jnp.inf)


def router(moe, u, sizes):
    """``u`` [n, d] -> ``(logits, scores, chosen [n, E] bool, gates [n, E])``
    in float32: gates are 0 off the chosen."""
    logits = u @ moe["gate"]
    s = jax.nn.sigmoid(logits)
    chosen = _best(selection(s + moe["router_bias"], sizes),
                   sizes["experts_per_token"])
    g = jnp.where(chosen, s, 0.0)
    if sizes["norm_topk_prob"]:
        g = g / g.sum(axis=-1, keepdims=True)
    return logits, s, chosen, g * sizes["routed_scaling_factor"]


def routed_part(moe, u, sizes, r=lambda a: a, held=None):
    """What the experts in the tree add for ``u`` [n, d]: the held ones'
    gate-weighted outputs, gates over all E (:func:`router`).  ``held``
    overrides ``sizes["held"]`` (the share test's eight shares)."""
    g = router(moe, u, sizes)[3]
    first, count = held or sizes["held"] or (0, g.shape[1])

    @jax.checkpoint  # a backward pass makes an expert's hidden rows again
    def added(e):
        w_gate, w_up, w_down, g_e = e
        hidden = jax.nn.silu(r(u) @ r(w_gate)) * (r(u) @ r(w_up))
        return g_e[:, None] * (r(hidden) @ r(w_down))

    y, _ = jax.lax.scan(
        lambda y, e: (y + added(e), None), jnp.zeros_like(u),
        (moe["w_gate"], moe["w_up"], moe["w_down"],
         g[:, first:first + count].T))
    return y


def ffn_part(lp, h, sizes=SIZES, operand_dtype=None, held=None):
    """A layer's feed-forward part on the stream ``h`` its mixer left: ``(y,
    aux_loss, router_z_loss)``; the two are 0 for a dense layer."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        b, s, d = h.shape
        m = norm(h, lp["ln2"], sizes["norm_eps"]).reshape(b * s, d)
        if "ffn" in lp:
            zero = jnp.float32(0)
            return h + gated(_f32(lp["ffn"]), m, r).reshape(b, s, d), zero, zero
        moe = _f32(lp["moe"])
        y = gated(_f32(lp["shared"]), m, r) + routed_part(moe, m, sizes, r, held)
        logits, scores, chosen, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        p = scores / scores.sum(axis=-1, keepdims=True)
        sel = jnp.where(chosen, scores + moe["router_bias"], -jnp.inf)
        top1 = _best(sel, 1).astype(jnp.float32)  # the first of the k chosen
        aux = num_experts * jnp.sum(p.mean(axis=0) * top1.mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return h + y.reshape(b, s, d), aux, z


def router_logits(lp, h, sizes=SIZES):
    """[B * S, E]: the router's logits on the stream ``h`` [B, S, d] the
    layer's mixer left."""
    with jax.default_matmul_precision("highest"):
        m = norm(h, lp["ln2"], sizes["norm_eps"])
        return m.reshape(-1, h.shape[-1]) @ jnp.asarray(
            lp["moe"]["gate"], jnp.float32)


def router_margin(lp, h, sizes=SIZES):
    """[B * S]: how firmly this share's part of a token's result is decided:
    the lesser of (a) by how much the token's ``topk_group``-th best group
    score exceeds the next (a whole group in or out changes the chosen and
    with them every gate's normaliser) and (b) by how much its k-th largest
    selection score inside the kept groups exceeds its (k+1)-th, where one
    of those two experts is HELD (infinite where neither is: whichever is
    chosen, the experts here compute the same for the token)."""
    sel = jax.nn.sigmoid(router_logits(lp, h, sizes)) + jnp.asarray(
        lp["moe"]["router_bias"], jnp.float32)
    groups = jnp.sort(group_scores(sel, sizes), axis=-1)
    kept = sizes["topk_group"]
    group_margin = groups[:, -kept] - groups[:, -kept - 1]
    inside = selection(sel, sizes)
    k = sizes["experts_per_token"]
    order = jnp.argsort(inside, axis=-1)
    pair = order[:, -k - 1:-k + 1 or None]  # the (k+1)-th and the k-th
    ranked = jnp.take_along_axis(inside, pair, axis=-1)
    first, count = sizes["held"] or (0, sel.shape[1])
    held = ((pair >= first) & (pair < first + count)).any(axis=-1)
    return jnp.minimum(
        group_margin, jnp.where(held, ranked[:, 1] - ranked[:, 0], jnp.inf))


# ---- the layer and the stack ---------------------------------------------------


def mixer_part(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """``(Mixer(N(x)), the KDA state after the last position or None)``."""
    if kind(sizes, index) == "kda":
        return kda_part(lp, x, sizes, operand_dtype)
    return latent_mixer(lp, x, sizes, operand_dtype), None


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None, held=None):
    """Layer ``index`` on the stream ``x`` [B, S, d]: ``(y, aux_loss,
    router_z_loss)``; ``lp`` is a layer of the program's tree."""
    out, _ = mixer_part(lp, x, sizes, index, operand_dtype)
    return ffn_part(lp, x + out, sizes, operand_dtype, held)


def embed(params, token_ids):
    return jnp.asarray(params["embed"], jnp.float32)[token_ids]


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm and the untied head on ``x`` [.., n, d]: logits [.., n, V]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        final = norm(x, params["ln_f"], sizes["norm_eps"])
        return r(final) @ r(jnp.asarray(params["lm_head"], jnp.float32))


def ce_sum_of_logits(logits, targets):
    """Sum over the positions given of the next-token cross-entropy."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def sparse_layers(params) -> int:
    return sum("moe" in lp for lp in params["layers"])


def total_loss(ce_mean, aux_sum, z_sum, n_sparse, sizes=SIZES):
    return (ce_mean + sizes["aux_loss_weight"] * aux_sum / n_sparse
            + sizes["router_z_weight"] * z_sum / n_sparse)


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
    load-balance and router z losses (means over the mixture layers)."""
    logits, aux_sum, z_sum = forward(params, token_ids, sizes, operand_dtype)
    return total_loss(
        ce_sum_of_logits(logits, targets) / targets.size, aux_sum, z_sum,
        sparse_layers(params), sizes)


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
