"""Pallas fused softmax cross-entropy: logits never touch HBM.

Why.  The flagship's chunked CE (``transformer.loss_fn``) bounds logits
MEMORY to one [chunk, V] f32 buffer, but the HBM TRAFFIC remains: every
chunk's logits are written + read in forward, rewritten by the
``jax.checkpoint`` recompute, and its cotangent written + read twice in
backward — ~0.9 GB per 1024-token chunk at V=32768, ~41 GB ≈ 50 ms/step
at the 45k-token flagship batch.  tools/roofline.py shows the step is NOT
param-bandwidth-bound; this logits traffic is the largest single item in
the ~165 ms residual between the measured 273 ms and the compute floor.

How.  The flash-attention trick applied to the vocabulary axis: tile V,
keep a running (max, sum-exp, target-logit) per row in VMEM scratch, and
never materialize a logits tile outside VMEM.

- forward: one MXU matmul per (row-tile, vocab-tile); outputs only
  ``ce [n]`` and the ``lse [n]`` residual (n floats instead of n×V).
- backward: recomputes each logits tile from (x, head, lse) — the same
  recompute the checkpointed chunk already paid — and feeds
  ``dlogits = (softmax − onehot) · dce`` straight into the two backward
  matmuls while the tile is still in VMEM.  Two passes with opposite
  grid orders solve the accumulation directions: dx accumulates over
  vocab tiles (row-tile-major grid), dhead over row tiles
  (vocab-tile-major grid).

Net: ±0 algorithmic FLOPs vs the checkpointed chunk (one extra head
matmul in backward, ~7 ms at peak, against ~50 ms of eliminated HBM
traffic).  All reductions and accumulators are f32 regardless of the
bf16 storage dtype, so numerics match the chunked path to f32 tolerance
(asserted in tests/test_ops.py).

Status (TPU v5e, jax 0.9.0 / libtpu 0.0.34, ``tools/chip_probe.py
kernels``, CHANGES.md PR 21): all three kernels compile under Mosaic at
the flagship's n = 45056, d = 512, V = 32768, blocks 128/1024.  bf16
operands: normalized max error against an exact f32 reference is 6e-7
(ce), 4.5e-3 (dx), 3.9e-3 (dhead) — one bf16 ulp, and closer to exact on
dhead than the chunked scan (2e-2: it sums per-chunk contributions in
bf16).  f32 operands: 7e-4 / 4.6e-3 / 4.1e-3, exactly XLA's own
default-precision error — the MXU multiplies f32 operands in bf16 passes
under Mosaic and XLA alike, so on the chip "f32 tolerance" holds only
against an XLA reference at the same precision.  Through the flagship
train step the losses equal the chunked path's to 4 decimals over 4
steps, and the step took 243 ms fused against 241 ms chunked: the ~50
ms/step saving predicted above did NOT appear (four steps each, not a
benchmark; ROADMAP Design 4 settles the path).  Interpret mode on the
CPU checks the arithmetic only.  ``ce_impl="fused"`` raises with
``_check``'s reason when a constraint fails; nothing falls back.

Reference contract: the reference has no fused loss (SURVEY.md §2 — its
training loss is plain torch ``F.cross_entropy``); this is a TPU-side
performance design, cited against BASELINE.md round-5's roofline rows.

Constraints: n % block_n == 0, V % block_v == 0, d % 128 == 0 (lane
dim), 2-D operands.  Scalars ride as (n, 1) blocks — Mosaic restricts
sub-1024-element 1-D VMEM slices (see pallas_dispatch.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_V = 1024


def _fwd_kernel(x_ref, head_ref, tgt_ref, ce_ref, lse_ref, m_ref, s_ref,
                t_ref, *, block_v: int, n_v: int):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    logits = jnp.dot(
        x_ref[...], head_ref[...], preferred_element_type=jnp.float32
    )  # [bn, bv] f32, VMEM-resident only
    m_prev, s_prev = m_ref[...], s_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    s_ref[...] = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    m_ref[...] = m_new
    # target logit: the one column (if any) of this vocab tile that is the
    # row's label.  2-D iota: Mosaic rejects 1-D iota (pallas guide).
    local = tgt_ref[...] - j * block_v  # [bn, 1] int32
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked = jnp.sum(
        jnp.where(cols == local, logits, 0.0), axis=1, keepdims=True
    )
    hit = (local >= 0) & (local < block_v)
    t_ref[...] = t_ref[...] + jnp.where(hit, picked, 0.0)

    @pl.when(j == n_v - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(s_ref[...])
        lse_ref[...] = lse
        ce_ref[...] = lse - t_ref[...]


def _dx_kernel(x_ref, head_ref, tgt_ref, lse_ref, dce_ref, dx_ref, acc_ref,
               *, block_v: int, n_v: int):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    logits = jnp.dot(
        x_ref[...], head_ref[...], preferred_element_type=jnp.float32
    )
    p = jnp.exp(logits - lse_ref[...])  # softmax tile, recomputed in VMEM
    local = tgt_ref[...] - j * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    dl = (p - jnp.where(cols == local, 1.0, 0.0)) * dce_ref[...]
    # dl [bn, bv] @ head.T [bv, d]: contract the vocab axes
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        dl, head_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_v - 1)
    def _finish():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _dhead_kernel(x_ref, head_ref, tgt_ref, lse_ref, dce_ref, dh_ref,
                  acc_ref, *, block_v: int, n_n: int):
    import jax.experimental.pallas as pl

    j = pl.program_id(0)  # vocab tile (major: dhead accumulates over rows)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    logits = jnp.dot(
        x_ref[...], head_ref[...], preferred_element_type=jnp.float32
    )
    p = jnp.exp(logits - lse_ref[...])
    local = tgt_ref[...] - j * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    dl = (p - jnp.where(cols == local, 1.0, 0.0)) * dce_ref[...]
    # x.T [d, bn] @ dl [bn, bv]: contract the row axes
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        x_ref[...], dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == n_n - 1)
    def _finish():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def _check(x, head, targets, block_n, block_v) -> str | None:
    """Single source of truth for the kernel's preconditions: None, or
    the reason they do not hold.  Callers raise with it (the model's
    multi-device path passes per-shard ShapeDtypeStructs)."""
    n, d = x.shape
    d2, v = head.shape
    if d != d2:
        return f"x d={d} vs head d={d2}"
    if targets.shape != (n,):
        return f"targets shape {targets.shape} != ({n},)"
    if n % block_n or v % block_v:
        return f"n={n} % {block_n} or V={v} % {block_v} != 0"
    if d % 128:
        return f"d={d} % 128 != 0 (lane dim)"
    if block_v % 128:
        return f"block_v={block_v} % 128 != 0 (lane dim of the logits tile)"
    if block_n % 8:
        return f"block_n={block_n} % 8 != 0 (sublane dim)"
    return None


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5)
)
def fused_softmax_ce(x, head, targets, block_n: int = DEFAULT_BLOCK_N,
                     block_v: int = DEFAULT_BLOCK_V,
                     interpret: bool = False):
    """Per-row softmax CE of ``x @ head`` vs integer ``targets``.

    x [n, d] (f32/bf16), head [d, V], targets [n] int32 → ce [n] f32.
    Differentiable in x and head; logits stay in VMEM throughout."""
    return _fwd(x, head, targets, block_n, block_v, interpret)[0]


def _pallas_common(x, head, targets, block_n, block_v):
    import jax.experimental.pallas as pl

    n, d = x.shape
    v = head.shape[1]
    grid_nv = (n // block_n, v // block_v)
    tgt2 = targets.astype(jnp.int32).reshape(n, 1)
    specs = {
        "x": pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        "head": pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
        "col": pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
    }
    return pl, n, d, v, grid_nv, tgt2, specs


def _fwd(x, head, targets, block_n, block_v, interpret):
    err = _check(x, head, targets, block_n, block_v)
    if err:
        raise ValueError(f"fused_softmax_ce: {err}")
    pl, n, d, v, grid, tgt2, sp = _pallas_common(
        x, head, targets, block_n, block_v
    )
    from jax.experimental.pallas import tpu as pltpu

    ce2, lse2 = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_v=block_v, n_v=grid[1]
        ),
        grid=grid,
        in_specs=[sp["x"], sp["head"], sp["col"]],
        out_specs=[sp["col"], sp["col"]],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32) for _ in range(3)
        ],
        interpret=interpret,
    )(x, head, tgt2)
    return ce2.reshape(n), lse2.reshape(n)


def _vjp_fwd(x, head, targets, block_n, block_v, interpret):
    ce, lse = _fwd(x, head, targets, block_n, block_v, interpret)
    return ce, (x, head, targets, lse)


def _vjp_bwd(block_n, block_v, interpret, res, g):
    x, head, targets, lse = res
    pl, n, d, v, grid, tgt2, sp = _pallas_common(
        x, head, targets, block_n, block_v
    )
    from jax.experimental.pallas import tpu as pltpu

    lse2 = lse.reshape(n, 1)
    g2 = g.astype(jnp.float32).reshape(n, 1)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, block_v=block_v, n_v=grid[1]),
        grid=grid,
        in_specs=[sp["x"], sp["head"], sp["col"], sp["col"], sp["col"]],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        interpret=interpret,
    )(x, head, tgt2, lse2, g2)
    grid_vn = (grid[1], grid[0])  # vocab-major: dhead accumulates over rows
    dhead = pl.pallas_call(
        functools.partial(_dhead_kernel, block_v=block_v, n_n=grid[0]),
        grid=grid_vn,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, v), head.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_v), jnp.float32)],
        interpret=interpret,
    )(x, head, tgt2, lse2, g2)
    import numpy as np

    # integer targets carry a float0 cotangent, not None
    dt = np.zeros(targets.shape, jax.dtypes.float0)
    return dx, dhead, dt


fused_softmax_ce.defvjp(_vjp_fwd, _vjp_bwd)
