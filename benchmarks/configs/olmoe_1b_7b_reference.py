"""Plain reference for the ``olmoe-1b-7b`` configuration: the OLMoE block
(Hugging Face model type ``olmoe``; OLMoE-1B-7B-0125-Instruct's
``config.json``), forward, loss and gradients, in straightforward
``jax.numpy`` at float32 under ``jax.default_matmul_precision("highest")``.

No sort, no grouped matmul, no kernels, no remat, no chunking: a loop over
the experts with a boolean mask, full ``[n, V]`` logits.  It imports
nothing of the program and takes the program's parameter tree (any dtype;
cast here to float32), so seeded weights serve both.

The layer, for a residual stream ``x`` [B, S, d]::

    n1 = rms(x, g1)
    q  = rope(heads(rms(Wq n1, gq)));  k = rope(heads(rms(Wk n1, gk)))
    h  = x + Wo attn(q, k, heads(Wv n1))      causal, softmax(q.k / sqrt(hd))
    n2 = rms(h, g2)
    p  = softmax_f32(Wr n2)                   over ALL experts
    y  = h + sum over the k largest p_e of  p_e Wdown_e(silu(Wgate_e n2) * (Wup_e n2))
    logits = Wlm rms(x_L, gf)

``rms(q, gq)`` normalises over the WHOLE d-wide projection, before the
split into heads; ``rope`` is the rotate-half convention on each head;
the ``p_e`` are NOT renormalised (``norm_topk_prob`` false); no biases;
no token is dropped.

Departures from the published training recipe, which is not part of
``config.json``: the load-balance loss is this repository's (Shazeer /
GShard: E * sum_e mean_gate_e * top-1-load_e, per layer, mean over
layers), where OLMoE's counts all 8 choices into the load; the router
z-loss is mean(logsumexp(router logits)^2) as published.  Weights 1e-2
and 1e-3.

``operand_dtype`` rounds every matmul's operands (weights and
activations) to that dtype and back to float32: the same mathematics at
a lower precision, for showing that a tolerance tells the stated
precision from the one below it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(
    n_heads=16, experts_per_token=8, norm_eps=1e-5, rope_theta=10000.0,
    aux_loss_weight=1e-2, router_z_weight=1e-3,
)


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


def layer(lp, x, sizes=SIZES, operand_dtype=None):
    """One block: ``(x_out, aux_loss, router_z_loss)``.  ``lp`` is a
    layer of the program's parameter tree."""
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        r = _rounder(operand_dtype)
        b, s, d = x.shape
        heads, eps = sizes["n_heads"], sizes["norm_eps"]
        hd = d // heads
        k_top = sizes["experts_per_token"]

        n1 = rms(x, lp["ln1"]["scale"], eps)
        q = rms(r(n1) @ r(lp["wq"]), lp["q_norm"]["scale"], eps)
        k = rms(r(n1) @ r(lp["wk"]), lp["k_norm"]["scale"], eps)
        v = r(n1) @ r(lp["wv"])
        q = rope(q.reshape(b, s, heads, hd), sizes["rope_theta"])
        k = rope(k.reshape(b, s, heads, hd), sizes["rope_theta"])
        v = v.reshape(b, s, heads, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / jnp.sqrt(
            jnp.float32(hd)
        )
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attn = jnp.einsum(
            "bhqk,bkhd->bqhd", r(jax.nn.softmax(scores, axis=-1)), r(v)
        )
        h = x + r(attn.reshape(b, s, d)) @ r(lp["wo"])

        n2 = rms(h, lp["ln2"]["scale"], eps).reshape(b * s, d)
        moe = lp["moe"]
        router_logits = n2 @ moe["gate"]  # float32 whatever operand_dtype
        p = jax.nn.softmax(router_logits, axis=-1)  # [n, E], all experts
        num_experts = p.shape[1]
        # the k largest: an expert is chosen when fewer than k gates beat it
        # (ties toward the lower index, as lax.top_k breaks them)
        beats = (p[:, None, :] > p[:, :, None]) | (
            (p[:, None, :] == p[:, :, None])
            & (jnp.arange(num_experts)[None, None, :]
               < jnp.arange(num_experts)[None, :, None])
        )
        chosen = beats.sum(axis=-1) < k_top  # [n, E] bool
        y = jnp.zeros_like(n2)
        for e in range(num_experts):
            hidden = jax.nn.silu(r(n2) @ r(moe["w_gate"][e])) * (
                r(n2) @ r(moe["w_up"][e])
            )
            out = r(hidden) @ r(moe["w_down"][e])
            y = y + jnp.where(chosen[:, e, None], p[:, e, None] * out, 0.0)
        x_out = h + y.reshape(b, s, d)

        top1 = jax.nn.one_hot(jnp.argmax(p, axis=-1), num_experts)
        aux = num_experts * jnp.sum(p.mean(axis=0) * top1.mean(axis=0))
        z = jnp.mean(jax.scipy.special.logsumexp(router_logits, axis=-1) ** 2)
        return x_out, aux, z


def embed(params, token_ids):
    return jnp.asarray(params["embed"], jnp.float32)[token_ids]


def head(params, x, sizes=SIZES, operand_dtype=None):
    """Final norm and the untied head: logits [B, S, V]."""
    with jax.default_matmul_precision("highest"):
        r = _rounder(operand_dtype)
        final = rms(
            x, jnp.asarray(params["ln_f"]["scale"], jnp.float32),
            sizes["norm_eps"],
        )
        return r(final) @ r(jnp.asarray(params["lm_head"], jnp.float32))


def forward(params, token_ids, sizes=SIZES, operand_dtype=None):
    """``(logits [B, S, V], mean aux loss, mean router z-loss)``."""
    x = embed(params, token_ids)
    aux_sum = z_sum = 0.0
    for lp in params["layers"]:
        x, aux, z = layer(lp, x, sizes, operand_dtype)
        aux_sum, z_sum = aux_sum + aux, z_sum + z
    n_layers = len(params["layers"])
    return (head(params, x, sizes, operand_dtype),
            aux_sum / n_layers, z_sum / n_layers)


def ce_of_logits(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, token_ids, targets, sizes=SIZES, operand_dtype=None):
    """The training loss: mean next-token cross-entropy plus the weighted
    load-balance and router z losses."""
    logits, aux, z = forward(params, token_ids, sizes, operand_dtype)
    return (ce_of_logits(logits, targets)
            + sizes["aux_loss_weight"] * aux + sizes["router_z_weight"] * z)


def loss_and_grads(params, token_ids, targets, sizes=SIZES):
    return jax.value_and_grad(loss)(_f32(params), token_ids, targets, sizes)
