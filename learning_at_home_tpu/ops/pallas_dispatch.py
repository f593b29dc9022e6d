"""Pallas TPU kernel for MoE token dispatch (experimental; no caller).

The gather-based dispatch (``ops/moe_dispatch.py``) already removed the
one-hot einsum FLOPs; this kernel is the next rung — a hand-scheduled
row-gather that PrefetchScalarGridSpec drives directly from the
:class:`IndexDispatchPlan` indices, one grid step per expert slot:

    x [n, d]  +  token_for_slot [E*C]  →  x_send [E*C, d]

Each program DMAs its source token's row from HBM into VMEM and writes the
output block (the Mosaic-lowerable pattern for dynamically-indexed HBM
reads); empty slots write zeros.

Status: compiles under jax 0.9.0's Mosaic on a TPU v5e at n = 4096,
slots = 10240, d = 512 (bf16) and matches the XLA gather exactly
(``tools/chip_probe.py kernels``, CHANGES.md PR 21); equivalence-tested
in interpret mode on the CPU.  It has NO caller: on a v5e it measured
slower than both XLA dispatches at that shape (1,897 µs against 881
one-hot and 1,539 gather — BASELINE.md round 2, a builder's figure older
than most of the code), because of the 8× read amplification described
below.  ROADMAP Design 4 decides whether it stays.

Constraints for the kernel itself: ``d % 128 == 0`` (lane dimension).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from learning_at_home_tpu.ops.moe_dispatch import IndexDispatchPlan


# Slots per grid step.  The TPU lowering requires the output block's
# sublane dim divisible by 8; batching 8 row-DMAs per step also lets them
# overlap in flight before the single blocked VMEM→HBM write.
_SLOT_BLOCK = 8


def _dispatch_kernel(idx_ref, x_hbm_ref, out_ref, chunks_vmem, sems):
    """One program per _SLOT_BLOCK expert slots.

    Mosaic forbids single-row (1, d) slices of a (8, 128)-tiled HBM
    memref and sub-1024-element slices of 1-D VMEM, so a row-exact DMA is
    unimplementable; instead each slot DMAs the 8-row ALIGNED chunk
    containing its token (8× read amplification — the price of the tiling
    rule) and selects the row in VMEM with a masked sum over the sublane
    axis (dynamic sublane indexing is also restricted).  All DMAs start
    before any wait, so the 8 chunk fetches overlap in flight."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    base = pl.program_id(0) * _SLOT_BLOCK
    for j in range(_SLOT_BLOCK):
        token = idx_ref[base + j]

        @pl.when(token >= 0)
        def _start(j=j, token=token):
            chunk = (token // 8) * 8
            pltpu.make_async_copy(
                x_hbm_ref.at[pl.ds(chunk, 8), :],
                chunks_vmem.at[j],
                sems.at[j],
            ).start()

    for j in range(_SLOT_BLOCK):
        token = idx_ref[base + j]

        @pl.when(token >= 0)
        def _select(j=j, token=token):
            chunk = (token // 8) * 8
            pltpu.make_async_copy(
                x_hbm_ref.at[pl.ds(chunk, 8), :],
                chunks_vmem.at[j],
                sems.at[j],
            ).wait()
            rows = chunks_vmem[j]  # (8, d)
            sub = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
            mask = (sub == token % 8).astype(rows.dtype)
            out_ref[j, :] = jnp.sum(rows * mask, axis=0)

        @pl.when(token < 0)
        def _zero(j=j):
            out_ref[j, :] = jnp.zeros((out_ref.shape[-1],), out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dispatch_tokens_pallas(
    x: jax.Array, plan: IndexDispatchPlan, interpret: bool = False
) -> jax.Array:
    """Pallas scatter of tokens into capacity buckets: [n,d] → [E,C,d].

    Equivalent to ``dispatch_tokens_indexed``; ``interpret=True`` runs the
    kernel in the Pallas interpreter (CPU tests).  Raises on unsupported
    shapes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_experts, capacity = plan.token_for_slot.shape
    n, d = x.shape
    if d % 128:
        raise ValueError(f"pallas dispatch needs d % 128 == 0, got d={d}")
    slots = num_experts * capacity
    if slots % _SLOT_BLOCK:
        raise ValueError(
            f"pallas dispatch needs E*C % {_SLOT_BLOCK} == 0, got {slots}"
        )
    if n % 8:
        raise ValueError(f"pallas dispatch needs n % 8 == 0, got n={n}")
    flat_idx = plan.token_for_slot.reshape(-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the slot→token index array
        grid=(slots // _SLOT_BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # x stays in HBM
        out_specs=pl.BlockSpec((_SLOT_BLOCK, d), lambda i, idx_ref: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((_SLOT_BLOCK, 8, d), x.dtype),
            pltpu.SemaphoreType.DMA((_SLOT_BLOCK,)),
        ],
    )
    out = pl.pallas_call(
        _dispatch_kernel,
        out_shape=jax.ShapeDtypeStruct((slots, d), x.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(flat_idx, x)
    return out.reshape(num_experts, capacity, d)
