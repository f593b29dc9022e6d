"""Plain reference for the ``olmo-hybrid-7b`` configuration: the language
model that ``config.json`` of https://huggingface.co/allenai/Olmo-Hybrid-7B
describes (``model_type`` ``olmo_hybrid``), forward, loss and gradients, in
straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no chunks, no triangular solve, no remat: the delta rule is a
``lax.scan`` over the POSITIONS that carries the state ``S_t`` (the program
computes it in chunks with a solve inside each: the two share no code and
no algorithm).  It imports nothing of the program and takes the program's
parameter tree (any dtype; cast here to float32, a layer at a time), so
seeded weights serve both.

Every layer is a mixer and a gated block, the RMSNorm (eps ``norm_eps``,
scale alone) on each part's OUTPUT, both parts reading the stream as it
is::

    h = x + rms(Mixer(x), g1)
    y = h + rms(Wd (silu(Wg h) * (Wu h)), g2)
    logits = Wlm rms(x_L, gf)

``sizes["layer_types"][index]`` says which mixer::

    linear_attention  (the gated delta rule; H heads, keys of dk, values of
                       dv, K taps; S_t in R^{dk x dv} a head, S_0 = 0)
       [q~ | k~ | v~ | z | b | a] = x W_in     widths H dk | H dk | H dv | H dv | H | H
       q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                 conv(u)[t] = sum_{j<K} w[:, j] u[t - (K-1) + j], zeros
                 before position 0, a channel at a time, no bias
       q_t <- q_t / sqrt(sum q_t^2 + 1e-6) / sqrt(dk)     a head
       k_t <- k_t / sqrt(sum k_t^2 + 1e-6)
       beta_t = 2 sigmoid(b_t)                 the 2: linear_allow_neg_eigval
       g_t = -exp(A_log) softplus(a_t + dt_bias);  alpha_t = exp(g_t)
       S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
       o_t = S_t^T q_t
       y_t = rms(o_t, gn) * silu(z_t)          the norm FIRST (a head's dv,
                                               one scale shared by the
                                               heads), then the gate
       Mixer = concat_h(y) W_out
    full_attention    (H heads of hd)
       q = rms(Wq x, gq), k = rms(Wk x, gk)    over the WHOLE H hd, before
                                               the split into heads
       v = Wv x;  no rotation;  allowed(i, j) = j <= i
       Mixer = Wo concat_h softmax(q_h k_h^T / sqrt(hd)) v_h

The vocabulary held is whatever the embedding and the head span; the
layers run are the tree's.

Departures from the published description and assumptions, each because
``config.json`` has no key for it (the configuration file lists them under
``assumed``): the norm sits on each part's output (Olmo 2's and Olmo 3's
order); the queries and keys of a full layer are normalized whole (the
family's); ``rope_theta`` null is read as no rotation; the convolutions
have no bias; the unit-length epsilon is 1e-6; the output norm's scale is
one a head channel, shared by the heads.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the
keys, the caller runs a layer at a time, and the head and the
cross-entropy take a block of positions at a time (:func:`head`).

``operand_dtype`` rounds every matmul's operands (weights and
activations; in the recurrence the state where a key or a query reads it,
the keys, the queries and what is written) to that dtype and back to
float32: the same mathematics at a lower precision, for showing that a
tolerance tells the stated precision from the one below it.  Every decay
and every write strength stays float32, as the program's do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    layer_types=("linear_attention",) * 3 + ("full_attention",),
    n_heads=30, head_dim=128, linear_key_head_dim=96,
    linear_value_head_dim=192, conv_kernel=4, norm_eps=1e-6,
)
ATTENTION_BLOCK = 256  # queries a block
UNIT_EPS = 1e-6


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rounder(operand_dtype):
    if operand_dtype is None:
        return lambda a: a
    return lambda a: a.astype(operand_dtype).astype(jnp.float32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def kind(sizes, index: int) -> str:
    """``linear_attention`` or ``full_attention``: the pattern repeats."""
    types = sizes["layer_types"]
    return types[index % len(types)]


# ---- the gated delta rule --------------------------------------------------


def write_strength(b):
    """``beta = 2 sigmoid(b)``: up to 2, so that the transition ``alpha (I -
    beta k k^T)`` can have a negative eigenvalue."""
    return 2.0 * jax.nn.sigmoid(b)


def output_gate(o, z, scale, eps):
    """``rms(o) * silu(z)``: the norm FIRST, over each head's values
    (``o`` [B, S, H, dv], one scale [dv] for all heads), then the gate."""
    return rms(o, scale, eps) * jax.nn.silu(z)


def causal_conv(u, w):
    """``conv(u)[t] = sum_j w[:, j] u[t - (K-1) + j]``: position t reads
    itself and the K-1 before it, zeros before the sequence; no bias."""
    b, s, channels = u.shape
    taps = w.shape[1]
    before = jnp.concatenate(
        [jnp.zeros((b, taps - 1, channels), jnp.float32), u], axis=1)
    return sum(w[:, j] * before[:, j:j + s] for j in range(taps))


def delta_inputs(p, x, sizes, r=lambda a: a):
    """``(q, k [B,S,H,dk], v [B,S,H,dv], z [B,S,H,dv], beta [B,S,H], g
    [B,S,H])`` from the stream ``x`` [B, S, d]: the in-projection, the
    convolutions with their SiLU, unit lengths, write strengths, decays."""
    b, s, _ = x.shape
    h, dk, dv = (sizes["n_heads"], sizes["linear_key_head_dim"],
                 sizes["linear_value_head_dim"])
    proj = r(x) @ r(p["w_in"])
    edges = (0, h * dk, 2 * h * dk, 2 * h * dk + h * dv, 2 * h * (dk + dv),
             2 * h * (dk + dv) + h, 2 * h * (dk + dv) + 2 * h)
    q, k, v, z, write, step = (
        proj[..., lo:hi] for lo, hi in zip(edges, edges[1:]))
    filters = p["conv_w"]
    q = jax.nn.silu(causal_conv(q, filters[:edges[1]]))
    k = jax.nn.silu(causal_conv(k, filters[edges[1]:edges[2]]))
    v = jax.nn.silu(causal_conv(v, filters[edges[2]:]))
    q, k = q.reshape(b, s, h, dk), k.reshape(b, s, h, dk)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + UNIT_EPS)

    q = unit(q) / jnp.sqrt(jnp.float32(dk))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(step + p["dt_bias"])
    return (q, unit(k), v.reshape(b, s, h, dv), z.reshape(b, s, h, dv),
            write_strength(write), g)


def delta_recurrence(q, k, v, g, beta, r=lambda a: a):
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

    final, o = jax.lax.scan(
        one_position, jnp.zeros((b, h, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1), final


def delta_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A linear layer's mixer on the stream ``x`` [B, S, d]: ``(Mixer(x)
    [B, S, d], the state after the last position [B, H, dk, dv])``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        p = _f32(lp["delta"])
        b, s, _ = x.shape
        q, k, v, z, beta, g = delta_inputs(p, x, sizes, r)
        o, final = delta_recurrence(q, k, v, g, beta, r)
        y = output_gate(o, z, p["gate_norm"]["scale"], sizes["norm_eps"])
        return r(y.reshape(b, s, -1)) @ r(p["w_out"]), final


# ---- full attention -----------------------------------------------------------


def attention(q, k, v, r):
    """q, k, v [B, S, H, hd] -> [B, S, H, hd]: causal; a block of queries
    at a time against all the keys."""
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

    blocks = jax.lax.map(one_block, jnp.arange(0, s, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, hd)


def attention_part(lp, x, sizes=SIZES, operand_dtype=None):
    """A full layer's mixer on the stream ``x``: ``Mixer(x)``."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        lp = _f32({name: lp[name] for name in (
            "wq", "wk", "wv", "wo", "q_norm", "k_norm")})
        b, s, _ = x.shape
        heads, hd, eps = sizes["n_heads"], sizes["head_dim"], sizes["norm_eps"]
        q = rms(r(x) @ r(lp["wq"]), lp["q_norm"]["scale"], eps)
        k = rms(r(x) @ r(lp["wk"]), lp["k_norm"]["scale"], eps)
        v = (r(x) @ r(lp["wv"])).reshape(b, s, heads, hd)
        # no rotation: rope_theta is null
        attn = attention(
            q.reshape(b, s, heads, hd), k.reshape(b, s, heads, hd), v, r)
        return r(attn.reshape(b, s, heads * hd)) @ r(lp["wo"])


# ---- the layer and the stack ---------------------------------------------


def mixer_part(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    if kind(sizes, index) == "linear_attention":
        return delta_part(lp, x, sizes, operand_dtype)[0]
    return attention_part(lp, x, sizes, operand_dtype)


def residual(x, out, scale, eps):
    """A part's place in the stream: the norm on the part's OUTPUT."""
    return x + rms(out, scale, eps)


def gated_block(ffn, h, r=lambda a: a):
    """``Wd (silu(Wg h) * (Wu h))``."""
    return r(jax.nn.silu(r(h) @ r(ffn["w_gate"])) * (r(h) @ r(ffn["w_up"]))
             ) @ r(ffn["w_down"])


def finish(lp, x, mixer_out, sizes=SIZES, operand_dtype=None):
    """The layer's output from the stream ``x`` it was given and what its
    mixer made of it: the mixer's norm and residual, then the gated block
    with its own."""
    with jax.default_matmul_precision("highest"):
        eps = sizes["norm_eps"]
        h = residual(
            x, mixer_out, jnp.asarray(lp["ln1"]["scale"], jnp.float32), eps)
        out = gated_block(_f32(lp["ffn"]), h, _rounder(operand_dtype))
        return residual(h, out, jnp.asarray(lp["ln2"]["scale"], jnp.float32), eps)


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """Layer ``index`` of the stack on the stream ``x`` [B, S, d]; ``lp`` is
    a layer of the program's parameter tree."""
    return finish(lp, x, mixer_part(lp, x, sizes, index, operand_dtype),
                  sizes, operand_dtype)


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


def forward(params, token_ids, sizes=SIZES, operand_dtype=None):
    """Logits [B, S, V]: everything at once, for sizes at which whole
    logits fit."""
    x = embed(params, token_ids)
    for index, lp in enumerate(params["layers"]):
        x = layer(lp, x, sizes, index, operand_dtype)
    return head(params, x, sizes, operand_dtype)


def loss(params, token_ids, targets, sizes=SIZES, operand_dtype=None):
    """The training loss: mean next-token cross-entropy, nothing beside it
    (no layer routes)."""
    logits = forward(params, token_ids, sizes, operand_dtype)
    return ce_sum_of_logits(logits, targets) / targets.size


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
