"""Plain reference for the ``smallthinker-21b-a3b`` configuration: the
SmallThinker-21BA3B-Instruct block (``config.json`` of
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct), forward,
loss and gradients, in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.

No kernel, no sort, no grouped matmul, no remat: a scan over all the
experts with a boolean mask.  It imports nothing of the program and takes
the program's parameter tree (any dtype; cast here to float32, a layer at
a time), so seeded weights serve both.

Layer ``index`` (0-based) of the stack, residual stream ``x`` [B, S, d],
``window = sizes["sliding_window_layout"][index]`` and ``rot =
sizes["rope_layout"][index]``::

    a  = rms(x, g1)
    q  = heads(Wq a) [S, H, hd];  k = heads(Wk a), v = heads(Wv a) [S, Hkv, hd]
    if rot:  q, k = rope(q), rope(k)        rotate-half over the whole head
    query head h reads key/value head h // (H / Hkv)
    allowed(i, j) = j <= i and (not window or j > i - W)
    h  = x + Wo concat_h softmax(q_h k^T / sqrt(hd)) v
    r  = Wr a                               router on the ATTENTION's input
    T  = the k largest of r;  g = softmax(r[T]) over the chosen
    u  = rms(h, g2)
    y  = h + sum_{e in T} g_e Wdown_e(relu(Wgate_e u) * (Wup_e u))
    logits = Wlm rms(x_L, gf)

No biases, no query/key norm, no token dropped.

It is written in blocks so that it fits one chip at 16,384 tokens: the
attention takes ``ATTENTION_BLOCK`` queries at a time against all the keys
(a [28, 1024, 16384] float32 score block is 1.9 GB, the whole 30 GB), the
caller runs a layer at a time (one layer's float32 weights live), and the
head and the cross-entropy take a block of positions at a time
(:func:`head`: [16384, 151936] float32 logits are 10 GB, never whole).

Departures from the published training recipe, which ``config.json`` does
not hold: the losses beside the cross-entropy are this repository's
(load balance E * sum_e mean_gate_e * top-1-load_e with the gates a
softmax over ALL experts, and mean(logsumexp(router logits)^2); per
layer, mean over layers; weights 1e-2 and 1e-3).

``operand_dtype`` rounds every matmul's operands (weights and
activations) to that dtype and back to float32: the same mathematics at
a lower precision, for showing that a tolerance tells the stated
precision from the one below it.  The router stays in float32, as the
program's does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_PERIOD = [0, 1, 1, 1]
SIZES = dict(
    n_heads=28, n_kv_heads=4, head_dim=128, experts_per_token=6,
    norm_eps=1e-6, rope_theta=1.5e6, sliding_window_size=4096,
    sliding_window_layout=_PERIOD * 13, rope_layout=_PERIOD * 13,
    aux_loss_weight=1e-2, router_z_weight=1e-3,
)
ATTENTION_BLOCK = 1024  # queries a block


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


def layer(lp, x, sizes=SIZES, index=0, operand_dtype=None):
    """Block ``index`` of the stack: ``(x_out, aux_loss, router_z_loss)``.
    ``lp`` is a layer of the program's parameter tree."""
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        r = _rounder(operand_dtype)
        b, s, d = x.shape
        heads, kv_heads, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        eps, k_top = sizes["norm_eps"], sizes["experts_per_token"]
        window = (sizes["sliding_window_size"]
                  if sizes["sliding_window_layout"][index] else None)

        a = rms(x, lp["ln1"]["scale"], eps)
        q = (r(a) @ r(lp["wq"])).reshape(b, s, heads, hd)
        k = (r(a) @ r(lp["wk"])).reshape(b, s, kv_heads, hd)
        v = (r(a) @ r(lp["wv"])).reshape(b, s, kv_heads, hd)
        if sizes["rope_layout"][index]:
            q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
        attn = attention(q, k, v, window, r)
        h = x + r(attn.reshape(b, s, heads * hd)) @ r(lp["wo"])

        moe = lp["moe"]
        router_logits = a.reshape(b * s, d) @ moe["gate"]  # float32 always
        num_experts = router_logits.shape[1]
        # the k largest: an expert is chosen when fewer than k logits beat
        # it (ties toward the lower index, as lax.top_k breaks them)
        rl = router_logits
        beats = (rl[:, None, :] > rl[:, :, None]) | (
            (rl[:, None, :] == rl[:, :, None])
            & (jnp.arange(num_experts)[None, None, :]
               < jnp.arange(num_experts)[None, :, None])
        )
        chosen = beats.sum(axis=-1) < k_top  # [n, E] bool
        g = jax.nn.softmax(jnp.where(chosen, rl, -jnp.inf), axis=-1)  # 0 off T

        u = rms(h, lp["ln2"]["scale"], eps).reshape(b * s, d)

        def one_expert(y, e):
            w_gate, w_up, w_down, g_e = e
            hidden = jax.nn.relu(r(u) @ r(w_gate)) * (r(u) @ r(w_up))
            return y + g_e[:, None] * (r(hidden) @ r(w_down)), None

        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(u),
            (moe["w_gate"], moe["w_up"], moe["w_down"], g.T),
        )
        x_out = h + y.reshape(b, s, d)

        p = jax.nn.softmax(router_logits, axis=-1)  # over ALL experts
        top1 = jax.nn.one_hot(jnp.argmax(p, axis=-1), num_experts)
        aux = num_experts * jnp.sum(p.mean(axis=0) * top1.mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(router_logits, axis=-1) ** 2)
        return x_out, aux, z


def router_margin(lp, x, sizes=SIZES):
    """[B * S]: by how much a token's k-th largest router logit exceeds
    its (k+1)-th, on the stream ``x`` [B, S, d] entering the layer: how
    firmly the set of chosen experts is decided."""
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        a = rms(x, lp["ln1"]["scale"], sizes["norm_eps"])
        logits = a.reshape(-1, x.shape[-1]) @ lp["moe"]["gate"]
        ranked = jnp.sort(logits, axis=-1)
        k = sizes["experts_per_token"]
        return ranked[:, -k] - ranked[:, -k - 1]


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
    load-balance and router z losses."""
    logits, aux_sum, z_sum = forward(params, token_ids, sizes, operand_dtype)
    return total_loss(
        ce_sum_of_logits(logits, targets) / targets.size, aux_sum, z_sum,
        len(params["layers"]), sizes,
    )


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
