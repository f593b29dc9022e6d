"""Ring attention: sequence/context parallelism over the ICI ring.

The reference has NO sequence parallelism (SURVEY.md §5.7 — its scale axis
is the expert dimension), but long-context is first-class in this
framework: sequences longer than one chip's HBM are sharded over a ``seq``
mesh axis, and attention runs as a ring — each device holds one Q chunk
resident and streams K/V chunks around the ring with ``lax.ppermute``,
accumulating output with the online-softmax (flash) recurrence.  Compute
for chunk r overlaps the transfer of chunk r+1 on TPU (XLA schedules the
collective-permute concurrently with the einsums).

Memory per device: O(S_local * d + S_local^2 / n) instead of O(S^2);
communication: n-1 permutes of the K/V chunk, bandwidth-optimal on a ring.

Causal masking across chunks is by chunk index: a Q chunk attends fully to
earlier K/V chunks, triangularly to its own, not at all to later ones —
masked lanes still run (SPMD) but contribute -inf scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def _online_softmax_update(o, l, m, scores, v_chunk):
    """One flash-attention accumulation step.

    o: [B, Sq, H, hd] running (unnormalized) output
    l: [B, H, Sq]     running softmax denominator
    m: [B, H, Sq]     running max
    scores: [B, H, Sq, Sk]; v_chunk: [B, Sk, H, hd]
    """
    m_new = jnp.maximum(m, scores.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])  # [B, H, Sq, Sk]
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_chunk)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o_new, l_new, m_new


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
) -> jax.Array:
    """Attention over a sequence sharded on ``axis_name``.

    Call INSIDE shard_map.  q/k/v: [B, S_local, H, hd]; returns the local
    output chunk [B, S_local, H, hd].
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, s_local, h, hd = q.shape
    scale = 1.0 / np.sqrt(hd)

    q32 = q.astype(jnp.float32)
    o0 = jnp.zeros((b, s_local, h, hd), jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    m0 = jnp.full((b, h, s_local), -jnp.inf, jnp.float32)
    tri = jnp.tril(jnp.ones((s_local, s_local), bool))

    def body(r, carry):
        o, l, m, kc, vc = carry
        src = (my - r) % n  # which global chunk kc/vc currently is
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q32, kc.astype(jnp.float32)) * scale
        )
        if causal:
            full = src < my  # earlier chunk: attend to everything
            diag = src == my  # own chunk: lower-triangular
            mask = jnp.where(
                full, True, jnp.where(diag, tri[None, None], False)
            )
            scores = jnp.where(mask, scores, -jnp.inf)
        o, l, m = _online_softmax_update(o, l, m, scores, vc)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return o, l, m, kc, vc

    o, l, m, _, _ = lax.fori_loop(0, n, body, (o0, l0, m0, k, v))
    # fully-masked rows (can't happen with causal diag) would give l=0
    denom = jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
    out = o / denom.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_local_zigzag(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
) -> jax.Array:
    """Causal ring attention over the ZIGZAG chunk layout — balanced work.

    The contiguous layout computes every (Q-chunk, K-chunk) block and
    masks the acausal half: ~2× the necessary FLOPs, and skipping the
    masked blocks does not help wall time because every ring step still
    has at least one device with a live block (steps are lock-stepped by
    the ppermute).  The zigzag layout (each device holds global chunks
    ``i`` and ``2n-1-i``) makes the live-block count UNIFORM: every
    device computes exactly one half-chunk block against the arriving
    K/V pair each step (plus the triangular diagonals on step 0), so the
    causal FLOPs savings become wall-clock savings.

    Call INSIDE shard_map.  q/k/v: [B, 2c, H, hd] where the local rows
    are the concatenation (chunk ``my``, chunk ``2n-1-my``) — callers
    permute the global sequence into this layout (``make_ring_attention``
    with ``layout="zigzag"`` does it).  Returns the local output in the
    same zigzag layout.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, s2, h, hd = q.shape
    c = s2 // 2
    scale = 1.0 / np.sqrt(hd)
    tri = jnp.tril(jnp.ones((c, c), bool))
    neg = -jnp.inf

    q32 = q.astype(jnp.float32)
    q_lo, q_hi = q32[:, :c], q32[:, c:]

    def blk(qh, kc_, vc_, olm, mask=None):
        o, l, m = olm
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qh, kc_.astype(jnp.float32)) * scale
        )
        if mask is not None:
            scores = jnp.where(mask[None, None], scores, neg)
        return _online_softmax_update(o, l, m, scores, vc_)

    def zeros_olm():
        return (
            jnp.zeros((b, c, h, hd), jnp.float32),
            jnp.zeros((b, h, c), jnp.float32),
            jnp.full((b, h, c), -jnp.inf, jnp.float32),
        )

    def body(r, carry):
        lo, hi, kc, vc = carry
        src = (my - r) % n  # the device whose chunk pair just arrived
        klo, khi = kc[:, :c], kc[:, c:]
        vlo, vhi = vc[:, :c], vc[:, c:]
        # chunk indices: Q = (my, 2n-1-my); K = (src, 2n-1-src).
        # q_hi vs klo: klo's index src < n <= 2n-1-my — ALWAYS full attend
        hi = blk(q_hi, klo, vlo, hi)
        # exactly one more block is causally live:
        #   src == my: both diagonals (step 0)
        #   src <  my: q_lo vs klo, full   (klo earlier than chunk my)
        #   src >  my: q_hi vs khi, full   (2n-1-src < 2n-1-my)
        def diag_case(lo, hi):
            return blk(q_lo, klo, vlo, lo, tri), blk(q_hi, khi, vhi, hi, tri)

        def off_diag(lo, hi):
            return lax.cond(
                src < my,
                lambda lo, hi: (blk(q_lo, klo, vlo, lo), hi),
                lambda lo, hi: (lo, blk(q_hi, khi, vhi, hi)),
                lo, hi,
            )

        lo, hi = lax.cond(src == my, diag_case, off_diag, lo, hi)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return lo, hi, kc, vc

    lo, hi, _, _ = lax.fori_loop(0, n, body, (zeros_olm(), zeros_olm(), k, v))

    def norm(olm):
        o, l, m = olm
        denom = jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
        return o / denom.transpose(0, 2, 1)[..., None]

    return jnp.concatenate([norm(lo), norm(hi)], axis=1).astype(q.dtype)


def zigzag_indices(seq_len: int, n_shards: int) -> np.ndarray:
    """Global row order realizing the zigzag layout: device i's contiguous
    shard = (chunk i, chunk 2n-1-i), chunk size seq_len/(2n)."""
    if seq_len % (2 * n_shards):
        raise ValueError(
            f"zigzag needs seq_len divisible by 2*{n_shards}, got {seq_len}"
        )
    c = seq_len // (2 * n_shards)
    order = []
    for i in range(n_shards):
        order.append(np.arange(i * c, (i + 1) * c))
        j = 2 * n_shards - 1 - i
        order.append(np.arange(j * c, (j + 1) * c))
    return np.concatenate(order)


def make_ring_attention(
    mesh: Mesh, axis_name: str = "seq", causal: bool = True,
    layout: str = "contiguous", pre_permuted: bool = False,
):
    """shard_map-wrapped ring attention over global [B, S, H, hd] arrays
    sharded on the sequence axis.

    ``layout="zigzag"`` (causal only) runs the balanced minimum-FLOPs
    ring over the zigzag chunk layout (~2× less attention compute at
    scale).  By default each call permutes q/k/v in and the output back
    (4 cross-shard gathers per call); models with several attention
    layers should instead permute the residual stream ONCE at the model
    boundary (see ``DMoETransformerLM.apply``) and pass
    ``pre_permuted=True`` so the ring consumes and produces the zigzag
    order directly.  ``"contiguous"`` is the straightforward ring
    (computes and masks every block; supports non-causal)."""
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"mesh has no {axis_name!r} axis (axes: {mesh.axis_names})"
        )
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"layout must be contiguous|zigzag, got {layout!r}")
    if layout == "zigzag" and not causal:
        raise ValueError("zigzag layout only balances CAUSAL attention")
    n_shards = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)

    local_fn = (
        functools.partial(ring_attention_local_zigzag, axis_name=axis_name)
        if layout == "zigzag"
        else functools.partial(
            ring_attention_local, axis_name=axis_name, causal=causal
        )
    )
    inner = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )

    def fn(q, k, v):
        if not (q.shape == k.shape == v.shape):
            raise ValueError(
                f"q/k/v shapes must match, got {q.shape}/{k.shape}/{v.shape}"
            )
        if q.shape[1] % n_shards:
            raise ValueError(
                f"sequence length {q.shape[1]} must divide across the "
                f"{n_shards} shards of mesh axis {axis_name!r}"
            )
        if layout == "zigzag" and q.shape[1] % (2 * n_shards):
            # also guards the pre_permuted path: each shard needs an even
            # local chunk to split into its lo/hi halves
            raise ValueError(
                f"zigzag needs seq_len divisible by 2*{n_shards} shards, "
                f"got {q.shape[1]}"
            )
        if layout == "zigzag" and not pre_permuted:
            zig = zigzag_indices(q.shape[1], n_shards)
            inv = np.argsort(zig)
            out = inner(q[:, zig], k[:, zig], v[:, zig])
            return out[:, inv]
        return inner(q, k, v)

    return fn
