"""Plain reference for the ``nemotron-labs-twotower-30b-a3b`` configuration:
the hybrid tower that ``config.json`` of
https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
describes (``model_type`` ``nemotron_h``), forward, loss and gradients, in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no chunks, no sort, no grouped matmul, no buffer, no remat: the
state-space recurrence is a ``lax.scan`` over the POSITIONS that carries the
state ``h_t`` (the program computes it in chunks: the two share no code and
no algorithm), the mixture a scan over the held experts with a boolean
mask.  It imports nothing of the program and takes the program's parameter
tree (any dtype; cast here to float32, a layer at a time), so seeded
weights serve both.

Every layer is ONE mixer behind ONE norm, ``x_out = x + Mixer(rms(x, g))``
(eps ``norm_eps``); ``sizes["pattern"][index]`` says which::

    M  (Mamba-2; H heads of P, G groups, state N, K taps; u = rms(x, g))
       [z | xBC | dt] = u W_in            widths H P | H P + 2 G N | H
       xBC[t] = silu(b + sum_{j<K} w[:, j] xBC[t - (K-1) + j])   zeros before 0
       x [S, H, P], B [S, G, N], C [S, G, N] = the parts of xBC;
       head h reads group h // (H / G)
       dt = softplus(dt + dt_bias) [S, H];  A = -exp(A_log) [H]
       h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T      h_{-1} = 0, [H, P, N]
       y_t = h_t C_t + D x_t
       y = rms_grouped(y * silu(z), g_n)   the gate FIRST, then the norm over
                                           each of the G groups of H P / G
       Mixer = y W_out
    *  (attention; u = rms(x, g))
       q = heads(Wq u) [S, Hq, hd]; k, v = heads(Wk u), heads(Wv u) [S, Hkv, hd]
       no positions at all;  allowed(i, j) = j <= i
       query head h reads key/value head h // (Hq / Hkv)
       Mixer = Wo concat_h softmax(q_h k^T / sqrt(hd)) v
    E  (mixture; m = rms(x, g))
       s = sigmoid(Wr m) [E], float32
       T = the k largest of s + b        b selects and does not weigh
       g_e = scale * s_e / sum_{j in T} s_j   for e in T
       Expert(m) = Wd relu(Wu m)^2       no gate branch, no bias
       Mixer = Shared(m) + sum_{e in T, e held} g_e Expert_e(m)
    logits = Wlm rms(x_L, gf)

**The share** (``sizes["held"] = (first, count)``): as
``k_exaone_236b_a23b_reference``: the tree holds ``count`` of a layer's
``E`` experts, the router keeps its ``E`` outputs and its ``k``, the gates
are normalised over all ``k`` chosen, and what the absent experts would
have added is left out.  ``held = None``: every expert is in the tree.
The vocabulary held is whatever the embedding and the head span.

Departures from the published description and assumptions, each because
``config.json`` has no key for it (the configuration file lists them under
``assumed``): the attention applies no rotation (``rope_theta`` and
``partial_rotary_factor`` are read by nothing); ``d_inner`` is heads x head
size (``expand`` is read by nothing); the gated norm gates before it
normalizes, over groups of ``d_inner / n_groups``; ``n_groups`` is the
mixer's and ``n_group`` the router's; the selection bias ``b`` [E] is a
parameter no gradient reaches; ``time_step_limit`` (0, inf) clamps
nothing.  The second (denoiser) tower is not built.  The losses beside the
cross-entropy are this repository's (as ``k_exaone``'s reference words
them), and weigh 0 in this configuration.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the
keys, the caller runs a layer at a time, and the head and the
cross-entropy take a block of positions at a time (:func:`head`).

``operand_dtype`` rounds every matmul's operands (weights and
activations; in the recurrence ``dt x``, ``B``, ``C`` and the state where
``C`` reads it) to that dtype and back to float32: the same mathematics at
a lower precision, for showing that a tolerance tells the stated precision
from the one below it.  The router and every decay stay in float32, as the
program's do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    pattern="MEMEM*EME", n_heads=32, n_kv_heads=2, head_dim=128,
    mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
    conv_kernel=4, experts_per_token=6, norm_eps=1e-5,
    routed_scaling_factor=2.5, norm_topk_prob=True, held=(0, 32),
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


def kind(sizes, index: int) -> str:
    """``M``, ``*`` or ``E``."""
    return sizes["pattern"][index]


# ---- M: the Mamba-2 mixer ------------------------------------------------


def step_sizes(dt, dt_bias):
    """``dt = softplus(dt + dt_bias)``: positive, no clamp."""
    return jax.nn.softplus(dt + dt_bias)


def skip_term(d, x):
    """``D x``: one ``D`` a head, on ``x`` [B, S, H, P]."""
    return d[:, None] * x


def gated_norm(y, z, scale, groups, eps):
    """``rms_grouped(y * silu(z))``: the gate FIRST, then the norm over
    each of the ``groups`` groups of channels, one scale over them all."""
    b, s, d_inner = y.shape
    y = y * jax.nn.silu(z)
    return rms(
        y.reshape(b, s, groups, d_inner // groups), 1.0, eps
    ).reshape(b, s, d_inner) * scale


def activation(hidden):
    """``relu(.)^2``: the experts' and the shared expert's."""
    return jnp.square(jax.nn.relu(hidden))


def ssm_inputs(p, u, sizes, r=lambda a: a):
    """``(z, x [B,S,H,P], B [B,S,G,N], C [B,S,G,N], dt [B,S,H], A [H])`` from
    the normalized stream ``u`` [B, S, d]: the in-projection, the causal
    depthwise convolution and its SiLU, the step sizes."""
    b, s, _ = u.shape
    h, hp = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n, taps = sizes["n_groups"], sizes["ssm_state_size"], sizes["conv_kernel"]
    d_inner, conv_dim = h * hp, h * hp + 2 * g * n
    zxbcdt = r(u) @ r(p["w_in"])
    z = zxbcdt[..., :d_inner]
    xbc_in = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    # xBC[t] = b + sum_j w[:, j] xBC_in[t - (K-1) + j]: position t reads
    # itself and the K-1 before it, zeros before the sequence
    before = jnp.concatenate(
        [jnp.zeros((b, taps - 1, conv_dim), jnp.float32), xbc_in], axis=1)
    conv = p["conv_b"]
    for j in range(taps):
        conv = conv + p["conv_w"][:, j] * before[:, j:j + s]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_inner].reshape(b, s, h, hp)
    bmat = xbc[..., d_inner:d_inner + g * n].reshape(b, s, g, n)
    cmat = xbc[..., d_inner + g * n:].reshape(b, s, g, n)
    return z, x, bmat, cmat, step_sizes(dt, p["dt_bias"]), -jnp.exp(p["A_log"])


def recurrence(x, dt, a, bmat, cmat, r=lambda a: a):
    """The recurrence AS WRITTEN, a position at a time: ``(y [B,S,H,P]``
    without the ``D x`` term, ``h_{S-1} [B,H,P,N])``."""
    b, s, h, hp = x.shape
    g, n = bmat.shape[2:]
    per_group = h // g
    # head h reads group h // per_group
    b_heads = jnp.repeat(bmat, per_group, axis=2)  # [B, S, H, N]
    c_heads = jnp.repeat(cmat, per_group, axis=2)

    def one_position(state, at):
        x_t, dt_t, b_t, c_t = at  # [B,H,P], [B,H], [B,H,N], [B,H,N]
        decay = jnp.exp(dt_t * a)  # [B, H]
        dtx = r(dt_t[..., None] * x_t)
        state = decay[..., None, None] * state + (
            dtx[..., :, None] * r(b_t)[..., None, :])
        y_t = jnp.sum(r(state) * r(c_t)[..., None, :], axis=-1)  # [B, H, P]
        return state, y_t

    def time_first(t):
        return jnp.moveaxis(t, 1, 0)

    final, y = jax.lax.scan(
        one_position, jnp.zeros((b, h, hp, n), jnp.float32),
        tuple(map(time_first, (x, dt, b_heads, c_heads))),
    )
    return jnp.moveaxis(y, 0, 1), final


def ssm_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A layer ``M``'s mixer on the stream ``x`` [B, S, d]: ``(Mixer(rms(x))
    [B, S, d], the state after the last position [B, H, P, N])``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        p = _f32(lp["ssm"])
        b, s, _ = x.shape
        g = sizes["n_groups"]
        u = rms(x, jnp.asarray(lp["norm"]["scale"], jnp.float32),
                sizes["norm_eps"])
        z, xh, bmat, cmat, dt, a = ssm_inputs(p, u, sizes, r)
        y, final = recurrence(xh, dt, a, bmat, cmat, r)
        y = (y + skip_term(p["D"], xh)).reshape(b, s, -1)
        y = gated_norm(y, z, p["gate_norm"]["scale"], g, sizes["norm_eps"])
        return r(y) @ r(p["w_out"]), final


# ---- *: attention -------------------------------------------------------------


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

    blocks = jax.lax.map(one_block, jnp.arange(0, s, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, hd)


def attention_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A layer ``*``'s mixer on the stream ``x``: ``Mixer(rms(x))``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        lp = _f32(lp)
        b, s, _ = x.shape
        heads, kv_heads, hd = (
            sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"])
        u = rms(x, lp["norm"]["scale"], sizes["norm_eps"])
        q = (r(u) @ r(lp["wq"])).reshape(b, s, heads, hd)
        k = (r(u) @ r(lp["wk"])).reshape(b, s, kv_heads, hd)
        v = (r(u) @ r(lp["wv"])).reshape(b, s, kv_heads, hd)
        attn = attention(q, k, v, r)
        return r(attn.reshape(b, s, heads * hd)) @ r(lp["wo"])


# ---- E: the mixture -------------------------------------------------------


def relu2_mlp(w_up, w_down, u, r):
    """``Wd relu(Wu u)^2``: an expert, the shared expert."""
    return r(activation(r(u) @ r(w_up))) @ r(w_down)


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
        w_up, w_down, g_e = e
        return y + g_e[:, None] * relu2_mlp(w_up, w_down, u, r), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (moe["w_up"], moe["w_down"], g[:, first:first + count].T),
    )
    return y


def _mixture_input(lp, x, sizes):
    return rms(x, jnp.asarray(lp["norm"]["scale"], jnp.float32),
               sizes["norm_eps"]).reshape(-1, x.shape[-1])


def moe_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A layer ``E``'s mixer on the stream ``x``: ``(Mixer(rms(x)),
    aux_loss, router_z_loss)``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        m = _mixture_input(lp, x, sizes)
        moe, shared = _f32(lp["moe"]), _f32(lp["shared"])
        y = relu2_mlp(shared["w_up"], shared["w_down"], m, r) + routed_part(
            moe, m, sizes, r)

        logits, scores, _, _ = router(moe, m, sizes)
        num_experts = logits.shape[1]
        first_choice = jnp.argmax(scores + moe["router_bias"], axis=-1)
        p = scores / scores.sum(axis=-1, keepdims=True)
        aux = num_experts * jnp.sum(
            p.mean(axis=0) * jax.nn.one_hot(first_choice, num_experts).mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return y.reshape(x.shape), aux, z


def router_scores(lp, x, sizes=SIZES):
    """[B * S, E]: ``s + b``, what the choice of experts is made on, on the
    stream ``x`` [B, S, d] that enters the layer."""
    with jax.default_matmul_precision("highest"):
        moe = _f32(lp["moe"])
        return jax.nn.sigmoid(
            _mixture_input(lp, x, sizes) @ moe["gate"]) + moe["router_bias"]


def router_margin(lp, x, sizes=SIZES):
    """[B * S]: by how much a token's k-th largest ``s + b`` exceeds its
    (k+1)-th, where one of those two experts is HELD: how firmly this
    share's part of the token's result is decided.  Infinite where neither
    is held: whichever of the two is chosen, the experts here compute the
    same for the token."""
    scores = router_scores(lp, x, sizes)
    k = sizes["experts_per_token"]
    order = jnp.argsort(scores, axis=-1)
    pair = order[:, -k - 1:-k + 1 or None]  # the (k+1)-th and the k-th
    ranked = jnp.take_along_axis(scores, pair, axis=-1)
    first, count = sizes["held"] or (0, scores.shape[1])
    held = ((pair >= first) & (pair < first + count)).any(axis=-1)
    return jnp.where(held, ranked[:, 1] - ranked[:, 0], jnp.inf)


# ---- the stack ------------------------------------------------------------


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """Layer ``index`` of the stack: ``(x_out, aux_loss, router_z_loss)``,
    both losses 0 for a layer that routes nothing.  ``lp`` is a layer of
    the program's parameter tree."""
    which = kind(sizes, index)
    if which == "M":
        return x + ssm_part(lp, x, sizes, operand_dtype)[0], 0.0, 0.0
    if which == "*":
        return x + attention_part(lp, x, sizes, operand_dtype), 0.0, 0.0
    y, aux, z = moe_part(lp, x, sizes, operand_dtype)
    return x + y, aux, z


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
    return sizes["pattern"][: len(params["layers"])].count("E")


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
