"""Shared transformer trunk pieces used by both deployment modes.

Pod mode (models/transformer.py, sharded MoE) and swarm mode
(models/transformer_swarm.py, remote MoE) must stay numerically identical
in everything but the FFN — LN epsilon, causal masking, attention math
live HERE once so they cannot drift.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from learning_at_home_tpu.ops.attention_backward import (
    resident_attention,
    resident_backward_fits,
)
from learning_at_home_tpu.ops.band_attention import band_attention, band_kernel_fits
from learning_at_home_tpu.ops.delta_rule import gated_delta_chunked
from learning_at_home_tpu.ops.gate_norm import gated_rms_norm
from learning_at_home_tpu.ops.short_conv import gated_short_conv
from learning_at_home_tpu.ops.ssd import ssd_chunked
from learning_at_home_tpu.ops.ssm_conv import causal_conv_silu
from learning_at_home_tpu.ops.stream_mix import stream_read, stream_write, token_stats


def layer_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Pre-LN in float32, cast back to the input dtype."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rms_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in float32 (no mean, no bias), cast back to the input dtype.
    What ``p`` holds says which: ``scale``, the multiplier itself, or
    ``offset``, a multiplier of ``1 + offset`` (Qwen3-Next's norms: the
    parameter starts at zero)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    if "offset" in p:
        return (x32 * jax.lax.rsqrt(ms + eps)
                * (1.0 + p["offset"].astype(jnp.float32))).astype(x.dtype)
    return (x32 * jax.lax.rsqrt(ms + eps) * p["scale"]).astype(x.dtype)


def norm_width(p: dict) -> int:
    """How many channels an RMSNorm's parameter spans."""
    return p["offset" if "offset" in p else "scale"].shape[-1]


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN's numbers as a ``config.json`` gives them under
    ``rope_scaling`` (``type`` ``yarn``; arXiv:2309.00071, as the
    DeepSeek-V3 family writes it): :func:`yarn_inv_freq` makes the rotated
    pairs' frequencies of them, :func:`yarn_scales` what multiplies the
    cosines and sines and the softmax's scale."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_inv_freq(dim: int, theta: float, scaling: RopeScaling) -> np.ndarray:
    """The ``dim / 2`` frequencies of a rotated part of ``dim`` columns
    under YaRN: pair ``i`` turns at ``1 / theta^(2i/dim)`` where it makes
    more than ``beta_fast`` turns over the original context (left as it
    is), at that over ``factor`` where it makes fewer than ``beta_slow``
    (interpolated), and on a linear ramp between the two pairs where those
    counts fall (floor and ceiling).  float32, on the host: a function of
    the configuration alone."""
    half = dim // 2
    plain = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / dim)

    def pair_of(turns: float) -> float:
        return dim * math.log(
            scaling.original_max_position_embeddings / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_of(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_of(scaling.beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(half, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / scaling.factor * ramp + plain * (1 - ramp)).astype(np.float32)


def yarn_scales(scaling: RopeScaling) -> tuple:
    """``(what multiplies the cosines and sines, what multiplies the
    softmax's 1 / sqrt(head))``: with ``m(s, w) = 0.1 w ln s + 1`` the
    first is ``m(factor, mscale) / m(factor, mscale_all_dim)`` and the
    second ``m(factor, mscale_all_dim)`` squared."""

    def m(weight: float) -> float:
        if scaling.factor <= 1:
            return 1.0
        return 0.1 * weight * math.log(scaling.factor) + 1.0

    return m(scaling.mscale) / m(scaling.mscale_all_dim), m(
        scaling.mscale_all_dim) ** 2


def rotary(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0,
    scaling: RopeScaling | None = None,
) -> jax.Array:
    """Rotary position embedding, rotate-half convention, on [B,S,H,hd]
    heads; ``positions`` [S] are the tokens' positions in the sequence
    (not their place in the buffer).  Angles in float32.  With ``scaling``
    the frequencies are :func:`yarn_inv_freq`'s and the cosines and sines
    are multiplied by :func:`yarn_scales`' first."""
    hd = x.shape[-1]
    if scaling is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        )
        amplitude = 1.0
    else:
        inv_freq = jnp.asarray(yarn_inv_freq(hd, theta, scaling))
        amplitude = yarn_scales(scaling)[0]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def rotary_first(
    x: jax.Array, positions: jax.Array, theta: float, rotary_dim: int
) -> jax.Array:
    """:func:`rotary` of the FIRST ``rotary_dim`` columns of each head
    (pairs ``(j, j + rotary_dim / 2)``, frequencies over ``rotary_dim``);
    the other columns pass as they are, to the bit (Qwen3-Next: 64 of a
    head's 256)."""
    return jnp.concatenate(
        [rotary(x[..., :rotary_dim], positions, theta), x[..., rotary_dim:]],
        axis=-1)


# The name the attention part gives the results of its matrix products:
# ``x @ wq``, ``x @ wk``, ``x @ wv`` and ``out @ wo``; in the latent form
# the two products down to the latents and ``out @ wo``.  The backward
# pass reads them (the queries' and keys' norm its input, the kernel q, k
# and v, the feed-forward part the stream after the add), none for its own
# gradient.  A ``jax.checkpoint`` whose policy saves this name (the layer's
# remat: ``DMoETransformerLM._hidden``) keeps them, so the backward pass
# runs none of these products a second time and rebuilds the rest from
# them (norm, reshape, rotation, the add); outside a checkpoint the name is
# the identity.  The latent form's three products UP from the latents are
# not named: kept, their 461 MB a layer cost more than their second run
# (GLM-4.7-Flash's cell on the chip, PERF.md section 6, PR 53).
ATTENTION_PRODUCTS = "attention_products"


def qkv_projections(
    lp: dict, x: jax.Array, n_heads: int,
    positions: jax.Array | None = None,
    rope_theta: float = 10000.0, norm_eps: float = 1e-5,
    rotary_dim: int | None = None,
):
    """Shared Q/K/V projections: [B,S,d] → q [B,S,H,hd] and k, v
    [B,S,Hkv,hd], finished for any attention core.  The block's own
    parameters say the rest: the head size is ``wq``'s output width over
    ``n_heads`` (not ``d / n_heads``: 28 heads of 128 leave a stream of
    2560), the key/value head count ``wk``'s width over the head size
    (query head h reads key/value head ``h // (H / Hkv)``);
    ``q_norm``/``k_norm`` in ``lp`` → RMSNorm of the queries and keys, over
    what its scale spans: the WHOLE projection, before the split into
    heads (OLMoE: a scale as wide as the projection), or each head's own
    ``hd`` (one scale of ``hd`` shared by the heads); ``positions`` [S] →
    rotary embedding of q and k after it, of the whole head or of its
    first ``rotary_dim`` columns.  A layer whose ``wq`` is twice as wide as
    ``wo`` is tall has a GATED output (:func:`gated_qkv_projections` hands
    the gate over; here it is left out)."""
    return gated_qkv_projections(
        lp, x, n_heads, positions, rope_theta, norm_eps, rotary_dim)[:3]


def gated_qkv_projections(
    lp: dict, x: jax.Array, n_heads: int,
    positions: jax.Array | None = None,
    rope_theta: float = 10000.0, norm_eps: float = 1e-5,
    rotary_dim: int | None = None,
):
    """:func:`qkv_projections` and, fourth, the output's gate [B,S,H,hd]
    before its sigmoid, or None: where ``wq`` is twice as wide as ``wo`` is
    tall (Qwen3-Next's full-attention layers), a head's ``2 hd`` columns of
    ``x @ wq`` are its query's ``hd`` then its gate's ``hd``, one product
    for both; the norm and the rotation are the query's alone, and
    :func:`output_projection` multiplies ``sigmoid(gate)`` onto the core's
    output."""
    b, s, _ = x.shape
    gated = "wo" in lp and lp["wq"].shape[-1] == 2 * lp["wo"].shape[0]
    hd = lp["wq"].shape[-1] // (2 * n_heads if gated else n_heads)

    def heads(y: jax.Array, size: int = hd) -> jax.Array:
        return y.reshape(b, s, y.shape[-1] // size, size)

    def project(w: str, norm: str | None, size: int = hd) -> tuple:
        """``(x @ lp[w]``, named; whether ``norm`` spans it whole)``."""
        with jax.named_scope("proj"):
            y = x @ lp[w].astype(x.dtype)
            whole = norm in lp and norm_width(lp[norm]) == y.shape[-1]
            # named in the shape its reader takes it (the whole
            # projection's norm; else a head's norm or the kernel), so
            # that the product writes the kept array in that layout
            return checkpoint_name(
                y if whole else heads(y, size), ATTENTION_PRODUCTS), whole

    def finish(y: jax.Array, whole: bool, norm: str | None) -> jax.Array:
        if norm in lp:
            with jax.named_scope("qk_norm"):
                y = rms_norm(lp[norm], y, norm_eps)
        if whole:
            with jax.named_scope("proj"):
                y = heads(y)
        return y

    gate = None
    if gated:  # a head's columns: [query | gate]
        q, whole = project("wq", None, 2 * hd)
        with jax.named_scope("gate"):
            q, gate = q[..., :hd], q[..., hd:]
        q = finish(q, whole, "q_norm")
    else:
        q = finish(*project("wq", "q_norm"), "q_norm")
    k = finish(*project("wk", "k_norm"), "k_norm")
    v = finish(*project("wv", None), None)
    if positions is not None:
        with jax.named_scope("rope"):
            if rotary_dim is None:
                q = rotary(q, positions, rope_theta)
                k = rotary(k, positions, rope_theta)
            else:
                q = rotary_first(q, positions, rope_theta, rotary_dim)
                k = rotary_first(k, positions, rope_theta, rotary_dim)
    return q, k, v, gate


def latent_qkv_projections(
    lp: dict, x: jax.Array, n_heads: int,
    positions: jax.Array | None = None,
    rope_theta: float = 10000.0, norm_eps: float = 1e-5,
    rope_scaling: RopeScaling | None = None,
):
    """Q/K/V expanded from low-rank latents (multi-head latent attention,
    the form training and prefill run): [B,S,d] → q, k [B,S,H,hd] and v
    [B,S,H,hd_v], finished for any attention core.  ``c_q = rms(x Wqa)`` is expanded by
    ``Wqb`` to the heads' queries; ``x Wkva`` is the keys' and values'
    latent ``c_kv`` (normalized) beside ONE rotated key part a token,
    ``k_r``, that every head shares; ``Wkvb`` expands ``c_kv`` to each
    head's ``[k_nope | v]``.  A head's query and key are ``[nope | rope]``:
    the last ``rope`` of the query and ``k_r`` are rotated (``positions``
    [S]; None = no rotation), the rest carries no position.  A block with
    no ``wq_a`` has NO query latent: its queries are ``x Wq``, one plain
    product and no norm (DeepSeek-V2-Lite's form, ``q_lora_rank`` null).
    The block's own parameters say the sizes: the latent's width is
    ``kv_a_norm``'s scale, the rotated part what ``wkv_a`` gives beyond it,
    a head's query/key size ``wq_b``'s (or ``wq``'s) width over
    ``n_heads``, its value size what
    ``wkv_b`` gives a head beyond the unrotated key part (as wide as the
    keys or not: GLM-4.7-Flash's 256 beside keys of 256, or 128 beside keys
    of 192).  ``rope_scaling``: :func:`rotary`'s.  No biases."""
    b, s, _ = x.shape
    kv_rank = lp["kv_a_norm"]["scale"].shape[-1]
    rope_dim = lp["wkv_a"].shape[-1] - kv_rank
    q_latent = "wq_a" in lp
    hd = lp["wq_b" if q_latent else "wq"].shape[-1] // n_heads
    nope = hd - rope_dim
    with jax.named_scope("latent_down"):
        if q_latent:
            c_q = rms_norm(lp["q_a_norm"], checkpoint_name(
                x @ lp["wq_a"].astype(x.dtype), ATTENTION_PRODUCTS), norm_eps)
        kv = checkpoint_name(x @ lp["wkv_a"].astype(x.dtype), ATTENTION_PRODUCTS)
        c_kv = rms_norm(lp["kv_a_norm"], kv[..., :kv_rank], norm_eps)
        k_rope = kv[..., kv_rank:].reshape(b, s, 1, rope_dim)
    if not q_latent:
        with jax.named_scope("proj"):
            q = checkpoint_name(
                (x @ lp["wq"].astype(x.dtype)).reshape(b, s, n_heads, hd),
                ATTENTION_PRODUCTS)
    with jax.named_scope("latent_up"):
        if q_latent:
            q = (c_q @ lp["wq_b"].astype(x.dtype)).reshape(b, s, n_heads, hd)
        # the expansion's columns a head are [k_nope | v]: split the
        # matrix, not the [B,S,H,.] result
        wkv_b = lp["wkv_b"].astype(x.dtype).reshape(kv_rank, n_heads, -1)
        k_nope = jnp.einsum("bsc,chd->bshd", c_kv, wkv_b[..., :nope])
        v = jnp.einsum("bsc,chd->bshd", c_kv, wkv_b[..., nope:])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    if positions is not None:
        with jax.named_scope("rope"):
            q_rope = rotary(q_rope, positions, rope_theta, rope_scaling)
            k_rope = rotary(k_rope, positions, rope_theta, rope_scaling)
    with jax.named_scope("latent_up"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, n_heads, rope_dim))],
            axis=-1,
        )
    return q, k, v


def head_gate(lp: dict, x: jax.Array) -> jax.Array | None:
    """The output's gate of a block that holds ``w_gate`` [d, H], before its
    sigmoid: ``x w_gate`` [B,S,H,1] float32, ONE number a head a token
    (Ling-3.0's ``head_wise`` gate), which :func:`output_projection`
    broadcasts over the head's values; None where the block holds none.
    Scope ``gate``."""
    if "w_gate" not in lp:
        return None
    with jax.named_scope("gate"):
        return jnp.einsum(
            "bsd,dh->bsh", x, lp["w_gate"].astype(x.dtype),
            preferred_element_type=jnp.float32)[..., None]


def output_projection(
    lp: dict, out: jax.Array, gate: jax.Array | None = None
) -> jax.Array:
    """[B,S,H,hd] → [B,S,d] @ wo.  Scope ``out_proj``.  With ``gate``
    [B,S,H,hd] (:func:`gated_qkv_projections`) or [B,S,H,1]
    (:func:`head_gate`) the core's output is multiplied by
    ``sigmoid(gate)`` first, in float32 (scope ``gate``)."""
    b, s, h, hd = out.shape
    if gate is not None:
        with jax.named_scope("gate"):
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(out.dtype)
    with jax.named_scope("out_proj"):
        return checkpoint_name(
            out.reshape(b, s, h * hd) @ lp["wo"].astype(out.dtype),
            ATTENTION_PRODUCTS)


def hc_coefficients(
    p: dict, x: jax.Array, sinkhorn_iters: int, eps: float,
    res_clamp: tuple, norm_eps: float,
):
    """What one part of a layer reads and writes of a residual stream of
    ``n`` streams a token (manifold-constrained hyper-connections,
    arXiv:2512.24880 over arXiv:2409.19606) ``x`` [B, S, n, C]: ``(pre [n,
    B, S], post [n, B, S], res [n, n, B, S], the largest |row or column
    sum - 1| of any token's res)``, float32, the streams' axes LEADING (a
    token's sixteen numbers lie a token apart, so every array here is
    dense over the tokens).  ``p`` holds ``phi`` [n C, 2 n + n^2], ``b``
    [2 n + n^2] and ``alpha`` [3]::

        m     = (vec(x) / sqrt(mean(vec(x)^2) + norm_eps)) phi   one norm over
                the n C numbers of a token, no learned scale
        pre   = sigmoid(alpha[0] m[:n] + b[:n])
        post  = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])
        res_0 = exp(clip(alpha[2] mat(m[2n:]) + mat(b[2n:]), *res_clamp)),
                row i the stream WRITTEN
        res_t = rows(columns(res_{t-1})), t = 1 .. sinkhorn_iters:
                columns divides each column by (its sum + eps), rows each
                row alike, so res is doubly stochastic to the iteration's
                accuracy (the Sinkhorn-Knopp projection)

    The norm's scalar a token is taken out of the product: ``(x phi) *
    rsqrt(..)``, the stream's own dtype times ``phi`` in it, summed in
    float32.  The mean square and the product are ONE pass over the streams
    (:func:`~learning_at_home_tpu.ops.stream_mix.token_stats`: on a TPU the
    kernel ``stream_stats_fwd`` where ``stream_mix_fits`` takes the shape,
    its backward ``stream_stats_bwd``; the plain form on the CPU and for
    every other shape, in which XLA passes over the streams once for the
    norm and once for the product, through a float32 copy of them, three
    times a part a step: 132 ms of the ``xing4.0-29b-a4b`` step where a
    read of the streams a pass is 21, PERF.md section 6, PR 64 and PR 65);
    everything after it is XLA's, on 24 numbers a token.  Scopes
    ``hc/coeff`` (the statistics, the sigmoids) and ``hc/sinkhorn``."""
    n = x.shape[2]
    f32 = jnp.float32
    with jax.named_scope("hc"):
        with jax.named_scope("coeff"):
            ms, m = token_stats(x, p["phi"])
            m = m * jax.lax.rsqrt(ms + norm_eps)  # [B, S]
            alpha, b = p["alpha"].astype(f32), p["b"].astype(f32)[:, None, None]
            pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
            post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
            logits = (alpha[2] * m[2 * n:] + b[2 * n:]).reshape(
                n, n, *m.shape[1:])
        with jax.named_scope("sinkhorn"):
            res = sinkhorn(jnp.exp(jnp.clip(logits, *res_clamp)),
                           sinkhorn_iters, eps)
            error = jnp.maximum(
                jnp.max(jnp.abs(res.sum(axis=0) - 1.0)),
                jnp.max(jnp.abs(res.sum(axis=1) - 1.0)))
    return pre, post, res, error


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` rounds of: each column of ``m`` [n, n, ..] (axis 0 the
    row, axis 1 the column) over its sum plus ``eps``, then each row
    alike.  The sums are written term by term, so a round is elementwise
    work on [n, ..] slabs.  The rounds are a ``lax.scan`` of four a trip,
    not ``iters`` copies of the round: written out, twelve parts' twenty
    rounds and their transposes were two thirds of the compiled step's
    instructions and of its compile time (137 s against 57 here for the
    described chip, PR 64)."""
    n = m.shape[0]

    def one_round(m, _):
        m = m / (sum(m[i] for i in range(n)) + eps)[None]
        m = m / (sum(m[:, j] for j in range(n)) + eps)[:, None]
        return m, None

    return jax.lax.scan(one_round, m, None, length=iters, unroll=4)[0]


def hc_pre(x: jax.Array, pre: jax.Array) -> jax.Array:
    """What the part reads of the streams ``x`` [B, S, n, C]: ``sum_j
    pre[j] x[:, :, j]`` [B, S, C], summed in float32 and rounded to the
    stream's dtype.  Scope ``hc/pre``.
    :func:`~learning_at_home_tpu.ops.stream_mix.stream_read`: on a TPU the
    kernels ``stream_read_fwd`` / ``stream_read_bwd`` where
    ``stream_mix_fits`` takes the shape, one read of the streams each; the
    plain form (a product broadcast over the channels and a sum over the
    streams' axis) on the CPU and for every other shape.  XLA does NOT fuse
    the plain form into one pass on the chip: the trace of the
    ``xing4.0-29b-a4b`` step read 39 ms a step under this scope where the
    reads and the write are 17 at the HBM's peak (PERF.md section 6, PR 64
    and PR 65)."""
    with jax.named_scope("hc"), jax.named_scope("pre"):
        return stream_read(x, pre)


def hc_post(
    x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array
) -> jax.Array:
    """The streams after the part gave ``y`` [B, S, C]: ``x'[:, :, i] =
    sum_j res[i, j] x[:, :, j] + post[i] y``, float32 inside, the
    stream's dtype out.  Scope ``hc/post``.
    :func:`~learning_at_home_tpu.ops.stream_mix.stream_write`: on a TPU the
    kernels ``stream_write_fwd`` / ``stream_write_bwd`` where
    ``stream_mix_fits`` takes the shape, one read of each array and one
    write of each result; the plain form, written as :func:`hc_pre`'s,
    elsewhere.  Of the plain form XLA made fusions that took the time of 29
    passes over a stream set where the work is 2.25 reads and a write (199
    ms of the ``xing4.0-29b-a4b`` step: PERF.md section 6, PR 64 and PR
    65)."""
    with jax.named_scope("hc"), jax.named_scope("post"):
        return stream_write(x, y, post, res)


def squared_relu(h: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(h))


def gate_activation(expert_kind: str):
    """The activation of the blocks without biases (an expert, a dense
    layer's block, the shared expert): on the gate branch of the gated
    kinds, on the one branch of 'relu2' (its square)."""
    return {"gated_relu": jax.nn.relu, "relu2": squared_relu}.get(
        expert_kind, jax.nn.silu)


def gated_mlp(p: dict, x: jax.Array, act=jax.nn.silu) -> jax.Array:
    """A dense feed-forward block on [.., d], no biases (a layer's dense
    feed-forward part, or the shared expert every token passes beside the
    routed ones).  Its parameters say which: with ``w_gate`` the gated
    form ``(act(x Wg) * (x Wu)) Wd``, without it the un-gated
    ``act(x Wu) Wd`` (``act`` then the squared ReLU)."""
    if "w_gate" in p:  # traced gate first, as ever: the same program
        h = act(x @ p["w_gate"].astype(x.dtype)) * (
            x @ p["w_up"].astype(x.dtype))
    else:
        h = act(x @ p["w_up"].astype(x.dtype))
    return h @ p["w_down"].astype(x.dtype)


def ssm_mixer(
    p: dict, u: jax.Array, n_heads: int, n_groups: int, chunk: int,
    eps: float = 1e-5, decay_dtype=jnp.float32,
):
    """The Mamba-2 mixer on the normalized stream ``u`` [B, S, d]:
    ``(out [B, S, d], the recurrent state after the last position
    [B, H, P, N] float32, the smallest decay exp(dt A) any position saw)``.

    ``[z | xBC | dt] = u W_in`` (no bias); ``xBC = silu(conv(xBC))``, a
    causal depthwise convolution with bias over the channels (``xBC[t] =
    b + sum_j w[:, j] xBC_in[t - (K - 1) + j]``, zeros before the
    sequence: :func:`~learning_at_home_tpu.ops.ssm_conv.causal_conv_silu`,
    one call for each of the three parts); ``x`` [S, H, P], ``B`` and ``C``
    [S, G, N] its three parts, head ``h`` reading group ``h // (H / G)``;
    ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the recurrence ``h_t = exp(dt_t A)
    h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t`` in chunks of
    ``chunk`` (:func:`~learning_at_home_tpu.ops.ssd.ssd_chunked`, which
    returns ``h_t C_t``); ``y = RMSNorm((y + D x) * silu(z))``, the gate
    FIRST, then the norm over each of the ``G`` groups of channels under a
    scale a channel (:func:`~learning_at_home_tpu.ops.gate_norm.
    gated_rms_norm` with the skip: on a TPU one pass each way, ``z`` read
    where the in-projection left it); ``out = y W_out``.
    ``decay_dtype`` is ``ssd_chunked``'s: float32 in every step.  The
    parameters say the sizes: the heads' size ``P`` is ``w_out``'s input
    width over ``n_heads``, the state's ``N`` what ``conv_w``'s channels
    leave beyond ``x`` over ``2 G``.  Sub-scopes ``in_proj``, ``conv``,
    ``scan``, ``gate_norm``, ``out_proj``."""
    b, s, _ = u.shape
    f32 = jnp.float32
    d_inner = p["w_out"].shape[0]
    head_dim = d_inner // n_heads
    conv_dim = p["conv_w"].shape[0]
    n_state = (conv_dim - d_inner) // (2 * n_groups)
    with jax.named_scope("in_proj"):
        zxbcdt = u @ p["w_in"].astype(u.dtype)
        dt = zxbcdt[..., d_inner + conv_dim:]
    with jax.named_scope("conv"):  # of x, B and C apart (a channel at a
        # time, so the same numbers): each is read where the product left it
        # and written as the array the scan takes, and nothing is sliced
        edges = (0, d_inner, d_inner + n_groups * n_state, conv_dim)
        x, b_in, c_out = (
            causal_conv_silu(zxbcdt, p["conv_w"][lo:hi], p["conv_b"][lo:hi],
                             first=d_inner + lo)
            for lo, hi in zip(edges, edges[1:]))
    with jax.named_scope("scan"):
        x = x.reshape(b, s, n_heads, head_dim)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        a = -jnp.exp(p["A_log"].astype(f32))
        y, state = ssd_chunked(
            x, dt, a, b_in.reshape(b, s, n_groups, n_state),
            c_out.reshape(b, s, n_groups, n_state), chunk, decay_dtype)
        decay_min = jnp.exp(jnp.min(dt * a))
    with jax.named_scope("gate_norm"):  # y + D x, the gate, the norm: one
        # pass, z read where the product left it
        y = gated_rms_norm(
            y.reshape(b, s, d_inner), zxbcdt, p["gate_norm"]["scale"],
            d_inner // n_groups, eps, gate_first=True,
            skip=(x.reshape(b, s, d_inner), p["D"]))
    with jax.named_scope("out_proj"):
        return y @ p["w_out"].astype(u.dtype), state, decay_min


def delta_mixer(
    p: dict, x: jax.Array, n_heads: int, chunk: int, eps: float = 1e-5,
    decay_dtype=jnp.float32, neg_eigval: bool = True,
    decay_floor: float | None = None,
):
    """The gated delta-rule mixer (Gated DeltaNet, arXiv:2412.06464) on the
    stream ``x`` [B, S, d] as the layer hands it over: ``(out [B, S, d],
    the recurrent state after the last position [B, H, dk, dv] float32,
    the smallest decay alpha any position saw, the largest write strength
    beta)``.

    ``n_heads`` is the heads of ``q`` and ``k``; ``v``, ``z``, ``b`` and
    ``a`` have as many heads as ``b`` has columns, the same or a multiple
    (Qwen3-Next: 32 value heads over 16 key heads), and value head ``j``
    reads query/key head ``j // (Hv / H)``: the convolved ``q`` and ``k``
    are repeated a value head each before the rule, which runs over the
    value heads (the gradient's sum over a key head's value heads is
    autodiff's).

    ``[q | k | v | z | b | a] = x W_in`` (no bias; ``b`` and ``a`` in
    float32, the rest in ``x``'s dtype); ``q, k, v =
    silu(conv(.))``, a causal depthwise convolution WITHOUT a bias, zeros
    before the sequence (:func:`~learning_at_home_tpu.ops.ssm_conv.
    causal_conv_silu`: ``[q | k]`` one call at column 0, ``v`` one at the
    column where it lies, so each is read where the product left it);
    per head ``q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk)`` and ``k <- k /
    sqrt(sum k^2 + 1e-6)`` (:func:`~learning_at_home_tpu.ops.delta_rule.
    unit_length`, which the rule applies); ``beta = 2 sigmoid(b)`` (the 2 lets the
    transition ``alpha (I - beta k k^T)`` have a negative eigenvalue,
    arXiv:2411.12537; ``neg_eigval`` False: ``beta = sigmoid(b)``); ``g =
    -exp(A_log) softplus(a + dt_bias)``, float32,
    ``alpha = exp(g)``; the recurrence ``S_t = alpha_t S_{t-1} + beta_t
    k_t (v_t - (alpha_t S_{t-1})^T k_t)^T``, ``o_t = S_t^T q_t`` in chunks
    of ``chunk`` (:func:`~learning_at_home_tpu.ops.delta_rule.
    gated_delta_chunked`: on a TPU its two Pallas kernels, forward and
    backward, where the call's shapes fit their tiles, the plain form
    elsewhere; ``decay_dtype`` other than float32 is the plain form's,
    for the probes); ``y = RMSNorm(o) * silu(z)``, the norm FIRST
    (over each head's ``dv``, one scale shared by the heads), then the
    gate (Mamba-2's mixer gates first; :func:`~learning_at_home_tpu.ops.
    gate_norm.gated_rms_norm` in its other order, ``z`` read at its column
    of the in-projection); ``out = y W_out``.  The parameters
    say the sizes: the value heads ``Hv`` are half of what ``w_in`` gives
    beyond ``q``, ``k``, ``v`` and ``z``, a head's value size ``dv``
    ``w_out``'s input width over ``Hv``, its key size ``dk`` what the
    convolution's channels leave beyond ``v`` over ``2 H``.  Sub-scopes ``in_proj``, ``conv``,
    ``core``, ``gate_norm``, ``out_proj``.

    A mixer that holds ``w_decay`` is the CHANNEL-DECAYED form (Kimi Delta
    Attention, arXiv:2510.26692: :func:`channel_delta_mixer`), whose gate's
    bound is ``decay_floor``: the parameters say which, as they say the
    sizes."""
    if "w_decay" in p:
        return channel_delta_mixer(
            p, x, n_heads, chunk, eps, decay_dtype, neg_eigval, decay_floor)
    b, s, _ = x.shape
    f32 = jnp.float32
    d_v = p["w_out"].shape[0]
    d_qk = p["conv_w"].shape[0] - d_v  # q's and k's channels together
    n_value = (p["w_in"].shape[-1] - d_qk - 2 * d_v) // 2
    dk, dv = d_qk // (2 * n_heads), d_v // n_value
    with jax.named_scope("in_proj"):
        w_in = p["w_in"].astype(x.dtype)
        proj = x @ w_in
        # what the write strengths and the decays are made of leaves its
        # product in float32 (2 H columns: a product of their own): rounded
        # to bf16, a pre-activation of 10 is off by 0.03, and sigmoid and
        # exp turn that into 3 % of a small strength and, times exp(A_log),
        # half of a decay's logarithm, which the state then carries
        write, step = jnp.split(jnp.einsum(
            "bsd,dn->bsn", x, w_in[:, d_qk + 2 * d_v:],
            preferred_element_type=f32), 2, axis=-1)
    with jax.named_scope("conv"):
        qk = causal_conv_silu(proj, p["conv_w"][:d_qk], None)
        v = causal_conv_silu(proj, p["conv_w"][d_qk:], None, first=d_qk)
    with jax.named_scope("core"):
        qk = qk.reshape(b, s, 2, n_heads, dk)
        if n_value != n_heads:  # value head j reads key head j // ratio
            qk = jnp.repeat(qk, n_value // n_heads, axis=3)
        beta = jax.nn.sigmoid(write)
        if neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            step + p["dt_bias"].astype(f32))
        # the rule makes q and k unit-length itself (float32, rounded to
        # x's dtype): its kernel does so in VMEM, on what the convolution
        # wrote
        o, state = gated_delta_chunked(
            qk[:, :, 0], qk[:, :, 1], v.reshape(b, s, n_value, dv), g, beta,
            chunk, decay_dtype, unit=True)
        decay_min, beta_max = jnp.exp(jnp.min(g)), jnp.max(beta)
    with jax.named_scope("gate_norm"):  # z read where the product left it
        y = gated_rms_norm(
            o.reshape(b, s, d_v), proj, p["gate_norm"]["scale"], dv, eps,
            gate_first=False, first=d_qk + d_v)
    with jax.named_scope("out_proj"):
        out = y @ p["w_out"].astype(x.dtype)
    return out, state, decay_min, beta_max


def channel_delta_mixer(
    p: dict, x: jax.Array, n_heads: int, chunk: int, eps: float,
    decay_dtype, neg_eigval: bool, decay_floor: float,
):
    """:func:`delta_mixer` whose decay is a number a KEY CHANNEL (Kimi
    Delta Attention), ``H`` heads of keys ``dk`` and values ``dv``, what it
    returns the same.

    ``[q | k | v] = x W_in``, then the convolutions and their SiLU, unit
    lengths and ``beta`` as there, ``beta``'s pre-activation ``x W_beta``
    [d, H] a product of its own in float32; the decay ``f = x W_decay`` [d,
    H dk] (float32 accumulation: it is an exponent), ``g = decay_floor *
    sigmoid(exp(A_log_h) (f + dt_bias))`` in ``(decay_floor, 0)``,
    ``dt_bias`` a channel (scope ``decay``); ``S_t = (I - beta_t k_t k_t^T)
    Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``
    (:func:`~learning_at_home_tpu.ops.delta_rule.gated_delta_chunked` with
    a decay of rank 4); ``y = RMSNorm(o) * sigmoid(x W_gate)_h``, the norm
    over a head's ``dv`` under one scale shared by the heads, the gate ONE
    number a head a token, float32 (scope ``gate_norm``: ``ops.gate_norm.
    gated_rms_norm`` under a gate a group, on a TPU one pass each way);
    ``out = y W_out``.  The bounded gate is what lets 16 positions'
    log-decays be summed and exponentiated apart
    (``channel_decay_fits``)."""
    b, s, _ = x.shape
    f32 = jnp.float32
    d_v = p["w_out"].shape[0]
    d_qk = p["conv_w"].shape[0] - d_v
    dk, dv = d_qk // (2 * n_heads), d_v // n_heads
    with jax.named_scope("in_proj"):
        proj = x @ p["w_in"].astype(x.dtype)
        write = jnp.einsum(
            "bsd,dn->bsn", x, p["w_beta"].astype(x.dtype),
            preferred_element_type=f32)
    with jax.named_scope("conv"):
        qk = causal_conv_silu(proj, p["conv_w"][:d_qk], None)
        v = causal_conv_silu(proj, p["conv_w"][d_qk:], None, first=d_qk)
    with jax.named_scope("decay"):
        step = jnp.einsum(
            "bsd,dn->bsn", x, p["w_decay"].astype(x.dtype),
            preferred_element_type=f32).reshape(b, s, n_heads, dk)
        g = decay_floor * jax.nn.sigmoid(
            jnp.exp(p["A_log"].astype(f32))[:, None]
            * (step + p["dt_bias"].astype(f32).reshape(n_heads, dk)))
    with jax.named_scope("core"):
        qk = qk.reshape(b, s, 2, n_heads, dk)
        beta = jax.nn.sigmoid(write)
        if neg_eigval:
            beta = 2.0 * beta
        o, state = gated_delta_chunked(
            qk[:, :, 0], qk[:, :, 1], v.reshape(b, s, n_heads, dv), g, beta,
            chunk, decay_dtype, unit=True, decay_floor=decay_floor)
        decay_min, beta_max = jnp.exp(jnp.min(g)), jnp.max(beta)
    with jax.named_scope("gate_norm"):  # the gate ONE number a head: its shape says so
        y = gated_rms_norm(
            o.reshape(b, s, d_v), head_gate(p, x)[..., 0],
            p["gate_norm"]["scale"], dv, eps, gate_first=False, gate="sigmoid")
    with jax.named_scope("out_proj"):
        out = y @ p["w_out"].astype(x.dtype)
    return out, state, decay_min, beta_max


# The name the conv mixer gives the result of its gated convolution, ``C *
# conv(B * u)`` [B, S, d]: the out-projection's backward reads it.  A
# ``jax.checkpoint`` whose policy saves this name (the layer's remat:
# ``DMoETransformerLM._hidden``) keeps it, so the backward pass holds no
# second call of the forward kernel: 67 MB a layer for 0.28 ms, +0.25 % of
# LFM2-8B-A1B's step on the chip (PERF.md section 6, PR 61; the Mamba-2
# mixer's convolution, kept, gave +0.034 % for 0.81 GB and is not: PR 41).
SHORT_CONV_RESULT = "short_conv_result"


def short_conv_mixer(p: dict, x: jax.Array) -> jax.Array:
    """The gated short convolution that is a layer's WHOLE token mixer
    (LFM2's ``conv`` layers) on the normalized stream ``x`` [B, S, d]: no
    scores, no recurrent state, no decay, no norm or gate after it.
    ``[B | C | u] = x W_in`` (the three thirds in that order, no bias); ``v =
    B * u``; ``c[t] = sum_j w[:, j] v[t - (K - 1) + j]``, a causal depthwise
    convolution a channel at a time with NO bias and NO activation, zeros
    before position 0 of every row; ``out = (C * c) W_out``
    (:func:`~learning_at_home_tpu.ops.short_conv.gated_short_conv`: on a TPU
    one pass each way, the three thirds read where the product left them).
    The parameters say the sizes: the channels are ``w_out``'s input width,
    the taps ``conv_w``'s second axis.  Sub-scopes ``in_proj``, ``core``,
    ``out_proj``."""
    with jax.named_scope("in_proj"):
        bcu = x @ p["w_in"].astype(x.dtype)
    with jax.named_scope("core"):
        y = checkpoint_name(
            gated_short_conv(bcu, p["conv_w"]), SHORT_CONV_RESULT)
    with jax.named_scope("out_proj"):
        return y @ p["w_out"].astype(x.dtype)


def causal_attention(
    lp: dict, x: jax.Array, n_heads: int, impl: str = "xla"
) -> jax.Array:
    """Multi-head causal self-attention; the head size is ``wq``'s output
    width over ``n_heads`` (:func:`qkv_projections`).

    impl="xla": ``jax.nn.dot_product_attention`` (f32 softmax, 1/sqrt(hd)
    scale).  NB: jax 0.9's default implementation still materializes the
    [B,H,S,S] scores — the API is used so future jax releases/backends
    can substitute fused kernels, NOT for a memory win today.

    impl="flash": the TPU Pallas blocked kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention`` under a causal
    mask) — O(S) memory, block-streamed online softmax on the MXU, with
    the tiles :func:`flash_block_sizes` gives for the call's shape.  A call the
    kernel cannot take (another backend than ``tpu``, a length its tiles
    do not divide) runs the ``xla`` core instead.

    For sequences split ACROSS chips use the ring path
    (parallel/ring_attention.py), which shares :func:`qkv_projections` /
    :func:`output_projection` and replaces only this dense core.
    """
    q, k, v = qkv_projections(lp, x, n_heads)
    return output_projection(lp, attention_core(q, k, v, impl))


# Tile sizes of the blocked kernel (splash attention), two regimes read
# from the mask's window.  Under a causal mask, or a window no shorter
# than the key block: the fastest of the sweep on a TPU v5e at [4, 16,
# 4096, 128] bf16 under a causal mask, fused backward (PERF.md section 6
# "PR 28"; tools/attention_probe.py splash): forward 2.72 ms, forward and
# backward 8.84 ms, against 68.3 ms for the xla core.  Each is ``min(size,
# S)``.
_FLASH_TILES = dict(
    block_q=1024, block_kv=1024, block_kv_compute=512,
    block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512,
)
# Heads of 256 (queries, keys and values alike: latent attention expanded,
# 20 heads): the same blocks, the forward's compute tile 256.  The fastest
# of the sweep on a TPU v5e at [1, 20, 16384, 256] bf16 under a causal
# mask (PERF.md section 6 "PR 37"; tools/attention_probe.py latent):
# forward 17.76 ms (78.6 % of the bf16 peak on the admitted elements) and
# forward + backward 64.87 ms, where the tiles above take 18.42 and 65.57;
# the unfused backward's best is 75.9.  Wider blocks do not fit VMEM at
# this head size (a 2048-wide query or key block, or a 1024-wide compute
# tile in the fused backward, is refused), narrower ones visit more grid
# steps: 512-wide key blocks 20.49 and 73.7.
_FLASH_TILES_256 = dict(_FLASH_TILES, block_kv_compute=256)
# Queries and keys of 192 over values of 128 (latent attention expanded, 32
# heads: a head's 128 unrotated beside 64 rotated): the kernel takes the
# two sizes as they come (its ``head_dim_v`` is the values' own; Mosaic
# lowers the 192-wide contraction).  The FORWARD is the library's at these
# tiles, the fastest of the sweep on a TPU v5e at [1, 32, 16384, 192 | 128]
# bf16 under a causal mask (PERF.md section 6 "PR 64";
# tools/attention_probe.py latent all xing4): 24.74 ms (56.4 % of the bf16
# peak on the admitted elements); a compute tile of 512 takes 25.86, and a
# 2,048-wide query block or a 4,096-wide key block is refused (VMEM).  The
# BACKWARD of a call ``resident_backward_fits`` takes (a causal mask, as
# many key heads as query heads) is the repo's own ONE call a layer,
# ``ops/attention_backward.py`` (PR 69), in place of the library's two
# ways: its fused kernel writes S / block_kv float32 partials of the
# queries' gradient, 8 GB at [32, 16384, 192] and key blocks of 1,024 (4 GB
# at 2,048), which do not fit a step that holds four residual streams a
# layer (85.4 ms forward + backward at key blocks of 2,048, 88.8 at 1,024,
# and no step at all), and its unfused pair, which this pair of sizes ran
# until PR 69, computes the scores twice (100.23 ms).  The one call reads
# 45.9 ms alone and 70.1 ms with the forward (PERF.md section 6 "PR 69";
# tools/attention_probe.py latent resident xing4), 42.5 ms a layer in
# ``xing4``'s step where the pair took 71.8.  A call of these sizes
# that the rule refuses (fewer key heads, a length whose ``dq`` a head's
# VMEM cannot hold) gets the library's fused backward at the tiles below:
# no cell of the benchmark.
_FLASH_TILES_192_128 = dict(_FLASH_TILES, block_kv_compute=256)
# the pairs (queries' and keys' head size, values') the kernel was run at
_FLASH_HEADS = {
    (64, 64): _FLASH_TILES, (128, 128): _FLASH_TILES,
    (256, 256): _FLASH_TILES_256, (192, 128): _FLASH_TILES_192_128,
}
# Under a shorter window the backward is unfused (a dK/dV and a dQ kernel
# over grids that shrink to the mask; the fused kernel's cannot, and it
# writes S / block_kv partials of the queries' gradient, which XLA sums
# afterwards) and every block, of the three kernels' queries and keys, is
# this: the fastest of the sweep on a TPU v5e at [1, 64 over 8, 16384, 128]
# bf16 under a window of 128 (PERF.md section 6 "PR 36";
# tools/attention_probe.py window): forward 5.62 ms and forward + backward
# 21.5 ms where the tiles above take 8.39 and 39.4.  A block visited is a
# block computed whatever its mask admits, so the key block is the
# window's cover, but no narrower than this: a grid step costs about 0.6
# us before it computes, and 128-wide key blocks (8.35 and 32.5 ms) run no
# faster than 1024-wide ones.  A query block then visits its own key block
# and the one before under ANY window up to the block, so the reading at
# 128 is the reading up to 512.  Measured at windows of 128 and 4,096 and
# S 16,384; nothing between.  Since PR 63 this regime is what a short
# window gets that the band kernel refuses (``band_kernel_fits``: windows
# of 513 to 1,023, heads of 64 or 256, a group it was not run at): no cell
# of the benchmark.
_FLASH_WINDOW_TILE = 512
# The name the kernel's forward gives its output [H, S, hd] and its
# float32 row sums (logsumexp [H, S]): the two arrays its backward kernels
# read beside q, k and v.  A ``jax.checkpoint`` whose policy saves this
# name (the layer's remat: ``DMoETransformerLM._hidden``) keeps them, so
# the backward pass does not run the forward kernel a second time; outside
# a checkpoint the name is the identity.
FLASH_RESIDUALS = "flash_attention_residuals"


def block_diffusion_mask(q_ids, kv_ids, half: int, block: int):
    """Block diffusion's attention mask over a doubled row ``[x_t | x_0]``
    of ``2 * half`` positions, the noised copy then the clean copy of the
    same ``half`` tokens (BD3-LMs' vectorised training, arXiv:2503.09573):
    whether query ``q_ids`` sees key ``kv_ids`` (integer arrays that
    broadcast; numpy, jax or the kernel's own).  With ``blk(i) = (i mod
    half) // block``: a noised query sees its own noised block, both
    directions, and the clean blocks BEFORE its own; a clean query sees
    the clean blocks up to and including its own; no query sees a noised
    key outside its own block, and a clean query sees no noised key at
    all.  Operators alone, so one function serves the three callers;
    ``block`` divides ``half``."""
    shift = block.bit_length() - 1
    blocks = half // block  # a block's index over the doubled row: noised
    # 0 .. blocks - 1, clean blocks .. 2 blocks - 1.  A shift where the block
    # length allows: the kernel evaluates this a tile at a time on its
    # vector unit, ten integer operations an element as written here
    if block == 1 << shift:
        q_blk, k_blk = q_ids >> shift, kv_ids >> shift
    else:
        q_blk, k_blk = q_ids // block, kv_ids // block
    # the last clean block a query sees: its own (a clean query's index is
    # its block's), or the one before its own (a noised query's, offset)
    last = q_blk + (blocks - 1) * (q_blk < blocks)
    return (q_blk == k_blk) | ((k_blk >= blocks) & (k_blk <= last))


def block_diffusion_admitted_pairs(half: int, block: int) -> int:
    """Pairs (query, key) :func:`block_diffusion_mask` admits, a head: a
    query of block ``b``, noised or clean, sees ``block * (b + 1)`` keys."""
    blocks = half // block
    return block * block * blocks * (blocks + 1)


@functools.cache
def _block_diffusion_mask_class():
    """:func:`block_diffusion_mask` as a computable mask of the blocked
    kernel's library, which is imported by the calls that run the kernel
    alone: the class is made on first use."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as mask_lib,
    )

    class BlockDiffusionMask(mask_lib._ComputableMask):
        def __init__(self, shape: tuple, block: int):
            self.block = block
            half = shape[0] // 2
            super().__init__(
                shape=shape,
                mask_function=lambda q_ids, kv_ids: block_diffusion_mask(
                    q_ids, kv_ids, half, block),
            )

        def __eq__(self, other):
            if not isinstance(other, type(self)):
                return NotImplemented
            return self.shape == other.shape and self.block == other.block

        def __hash__(self):
            return hash((type(self), self.shape, self.block))

    return BlockDiffusionMask


def _block_diffusion_splash_mask(s: int, block: int):
    """The kernel's mask over a doubled row of ``s`` positions: evaluated
    in the kernel a tile at a time, and a block at a time on the host for
    the table of blocks it visits (a block the mask leaves empty is never
    visited)."""
    return _block_diffusion_mask_class()((s, s), block)


@functools.cache  # the table is built on the host: once a shape and tiles
def block_diffusion_visited_pairs(
    shape: tuple, impl: str, backend: str, block: int
) -> int:
    """Pairs a head that the core's FORWARD computes for q/k/v of ``shape``
    [B, 2 * half, H, hd] under :func:`block_diffusion_mask`: the kernel's
    table of visited blocks (a block visited is a block computed whatever
    its mask admits) times a block's area; every pair where the ``xla``
    core runs.  Static: a function of the shape and the tiles."""
    _, s, _, _ = shape
    sizes = flash_block_sizes(shape, backend) if impl == "flash" else None
    if sizes is None:
        return s * s
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as mask_lib,
        splash_attention_mask_info as mask_info_lib,
    )

    info, _ = mask_info_lib.process_mask(
        mask_lib.MultiHeadMask([_block_diffusion_splash_mask(s, block)]),
        (sizes.block_q, sizes.block_kv),
    )
    visited = int(np.count_nonzero(np.asarray(info.block_mask)))
    return visited * sizes.block_q * sizes.block_kv


def _dividing_tiles(s: int, at_least: int, at_most: int) -> list:
    """The multiples of the 128 lanes from ``at_least`` to ``at_most``
    that divide ``s``, ascending."""
    first = -(-at_least // 128) * 128
    return [t for t in range(first, at_most + 1, 128) if s % t == 0]


def flash_block_sizes(
    shape: tuple, backend: str, window: int | None = None,
    value_dim: int | None = None,
):
    """The blocked kernel's ``BlockSizes`` for q and k of ``shape`` [B, S,
    H, hd] and values of ``value_dim`` a head (None: as wide as the keys)
    on ``backend`` under a causal mask, or under a ``window`` of that
    many keys, or None where the kernel cannot run: a backend other than
    ``tpu`` (Mosaic lowering), a length that is no multiple of the
    128-lane tile or that a tile does not divide, a pair of head sizes the
    kernel was never run at (none of which asks for the window).  A pure
    function of what the call can see."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    _, s, _, hd = shape
    heads = (hd, hd if value_dim is None else value_dim)
    if backend != "tpu" or s % 128 or heads not in _FLASH_HEADS:
        return None
    measured = _FLASH_HEADS[heads]
    tiles = {name: min(size, s) for name, size in measured.items()}
    if any(s % size for size in tiles.values()):
        return None
    if window is None or window >= tiles["block_kv"]:
        return BlockSizes(use_fused_bwd_kernel=True, **tiles)
    # the narrowest key block that covers the window and the measured
    # tile (the block of the other regime divides s and covers both, so
    # there is one); the widest query block within the tile.  Measured at
    # heads of 128; at 256 these blocks compile and run, untimed under a
    # window
    tile = min(_FLASH_WINDOW_TILE, s)
    kv = _dividing_tiles(s, max(window, tile), tiles["block_kv"])[0]
    q = _dividing_tiles(s, 128, tile)[-1]
    return BlockSizes(
        use_fused_bwd_kernel=False,
        block_q=q, block_kv=kv, block_kv_compute=kv,
        block_q_dkv=q, block_kv_dkv=kv, block_kv_dkv_compute=kv,
        block_q_dq=q, block_kv_dq=kv,
    )


def attention_core(
    q: jax.Array, k: jax.Array, v: jax.Array, impl: str = "xla",
    window: int | None = None, diffusion_block: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """The causal attention math on pre-projected q [B,S,H,hd], k
    [B,S,Hkv,hd] and v [B,S,Hkv,hd_v] (as wide as the keys or not: the
    result is [B,S,H,hd_v]) — shared by :func:`causal_attention` and the
    KV-cache decoder's prefill so the two paths cannot diverge numerically
    per ``impl``.  ``scale`` multiplies the scores (None: ``1 /
    sqrt(hd)``; YaRN's is that times :func:`yarn_scales`' second).  Both cores take bf16 operands to float32 scores, softmax
    and accumulators; ``flash`` is a kernel where one takes the call and
    ``xla`` where none does: the band kernel
    (:func:`~learning_at_home_tpu.ops.band_attention.band_attention`)
    where :func:`band_kernel_fits` says so (a window shorter than the
    blocked kernel's key block, on the arrays as they come), else the
    blocked kernel where :func:`flash_block_sizes` has tiles, its backward
    ONE call a layer
    (:func:`~learning_at_home_tpu.ops.attention_backward.resident_attention`)
    where :func:`resident_backward_fits` says so.  All take
    fewer key/value heads than query heads as they come (query head h
    reads key/value head ``h // (H / Hkv)``; no copy
    of K or V is made), and a ``window``: query i sees the ``window``
    keys that end with its own, ``i - window < j <= i``.  With
    ``diffusion_block`` the mask is neither: the row is a doubled one,
    ``[x_t | x_0]``, under :func:`block_diffusion_mask` with blocks of that
    many tokens (no window then) -- the kernel takes it as a computable
    mask, the ``xla`` core as a boolean array."""
    if impl not in ("xla", "flash"):
        raise ValueError(f"impl must be 'xla' or 'flash', got {impl!r}")
    if diffusion_block is not None and (window is not None or q.shape[1] % 2):
        raise ValueError(
            "diffusion_block masks a doubled row (an even length) and takes "
            f"no window, got length {q.shape[1]} and window={window}"
        )
    backend = jax.default_backend()
    hd, value_dim = q.shape[-1], v.shape[-1]
    plain = scale is None and value_dim == hd  # what the band kernel takes
    if scale is None:
        scale = 1.0 / hd ** 0.5
    if impl == "flash" and plain and band_kernel_fits(
            q.shape, k.shape[2], window, backend):
        # the arrays as they come: no scope ``flash/layout`` on this path
        with jax.named_scope("flash"):
            return band_attention(q, k, v, window, FLASH_RESIDUALS)
    sizes = flash_block_sizes(
        q.shape, backend, window, value_dim) if impl == "flash" else None
    if sizes is not None:
        from jax.experimental.pallas.ops.tpu import splash_attention as splash

        _, s, h, hd = q.shape
        if resident_backward_fits(
                q.shape, k.shape[2], value_dim, window, diffusion_block,
                backend, q.dtype.itemsize):
            # the library's forward at ``sizes``, ONE backward call
            kernel = functools.partial(
                resident_attention, sizes=sizes, residuals=FLASH_RESIDUALS)
        else:
            if diffusion_block is not None:
                mask = _block_diffusion_splash_mask(s, diffusion_block)
            elif window is None:
                mask = splash.CausalMask((s, s))
            else:
                mask = splash.LocalMask((s, s), (window - 1, 0), offset=0)
            kernel = jax.vmap(splash.make_splash_mha_single_device(
                mask=splash.MultiHeadMask([mask] * h), block_sizes=sizes,
                residual_checkpoint_name=FLASH_RESIDUALS,
            ))

        # kernel convention: one batch row [H, S, hd] (k and v [Hkv, S,
        # hd]), the scale already on q; blocks the mask leaves empty
        # (above the diagonal, behind the window) are never visited
        def heads_first(x):
            return x.transpose(0, 2, 1, 3)

        # scope ``flash/layout``: the four transposes and the scale; the
        # kernel's calls stay directly under ``flash``
        with jax.named_scope("flash"):
            with jax.named_scope("layout"):
                q, k, v = (
                    heads_first(q) * scale, heads_first(k), heads_first(v),
                )
            out = kernel(q, k, v)
            with jax.named_scope("layout"):
                return heads_first(out)
    how = {} if plain else dict(scale=scale)
    if value_dim != hd:
        # values narrower than the keys: the library's core wants one head
        # size, so the values ride in zero columns up to the keys' and
        # their part of the result is cut out again (exact: a zero column
        # of v is a zero column of the weighted sum)
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, hd - value_dim),))
    if diffusion_block is not None:
        ids = jnp.arange(q.shape[1], dtype=jnp.int32)
        out = jax.nn.dot_product_attention(
            q, k, v, mask=block_diffusion_mask(
                ids[:, None], ids[None, :], q.shape[1] // 2, diffusion_block
            )[None, None], **how,
        )
    elif window is not None:
        out = jax.nn.dot_product_attention(
            q, k, v, is_causal=True, local_window_size=(window - 1, 0), **how
        )
    else:
        out = jax.nn.dot_product_attention(q, k, v, is_causal=True, **how)
    return out if value_dim == hd else out[..., :value_dim]


def one_query_attention(
    lp: dict, q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, t
) -> jax.Array:
    """Attention for ONE query position per row over a KV cache.

    q [B,1,H,hd]; caches [B,S,H,hd] (positions > t are garbage and
    masked).  f32 softmax, 1/sqrt(hd) scale — the same numerics as
    ``jax.nn.dot_product_attention`` in the full forward.

    ``t`` is either a scalar (pod decode: every row sits at the same
    position) or anything broadcastable against the [B,H,Q,S] score mask
    — the swarm KV decoder (models/swarm_decoder.py) passes [B,1,1,1]
    per-slot positions so one continuous batch can hold streams at
    different depths, and its chunked prefill passes Q > 1 queries with
    [1,1,Q,1] per-query positions (the einsums generalize over Q
    untouched).  Shared here so the pod decoder and the gateway's swarm
    decoder cannot drift numerically.
    """
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bshd->bhqs", q, k_cache, preferred_element_type=jnp.float32
    ) * (1.0 / np.sqrt(hd))
    s = k_cache.shape[1]
    mask = jnp.arange(s, dtype=jnp.int32)[None, None, None, :] <= t
    scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v_cache)
    return output_projection(lp, out)


def gather_kv_pages(
    pool: jax.Array, page_tables: jax.Array
) -> jax.Array:
    """[num_pages,P,H,hd] pool + [B,n] int32 page tables → a [B,n*P,H,hd]
    contiguous per-row KV view.  A static-shape gather — jit-friendly
    int32 indirection, no data-dependent shapes.  Unmapped table entries
    point at scratch page 0; its (finite) garbage sits at positions the
    caller's ``t`` mask excludes, so the softmax sees weight exactly 0
    there and the output is bitwise what a dense cache would produce.
    """
    b, n = page_tables.shape
    num_pages, page_len, h, hd = pool.shape
    return pool[page_tables].reshape(b, n * page_len, h, hd)


def paged_one_query_attention(
    lp: dict,
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_tables: jax.Array,
    t,
) -> jax.Array:
    """:func:`one_query_attention` over a PAGED KV cache: per-row caches
    are materialized from the shared page pool via int32 page-table
    gathers, then the identical masked-softmax core runs on the view —
    paged decode is bitwise-equal to dense decode by construction (the
    tier-1 parity contract).  A fused TPU kernel (Pallas paged_attention,
    /opt/skills/guides/boom_attention_tricks.md §8) would stream pages
    without materializing the view; this path keeps the same [pages,
    page table] layout so that swap stays a kernel substitution.
    """
    k = gather_kv_pages(k_pool, page_tables)
    v = gather_kv_pages(v_pool, page_tables)
    return one_query_attention(lp, q, k, v, t)
