"""The sorted expert layer's sum over a token's ``k`` rows, as a Pallas TPU
kernel where the compiler's own form is slow.

Measured on a TPU v5e (PERF.md section 6, PR 50): a row gather out of HBM
costs the same 4.1 to 4.6 ms whatever follows it (131,072 rows of 2,048
bf16; 0.9 where its ``[n, d]`` source fits VMEM), and XLA's gather is the
only form of it there is (a Pallas DMA moves no fewer than the 8 rows of an
HBM tile).  What a kernel can win is the pass behind ``ys[inverse]`` and
``g[inverse]``, the sum over a token's ``k`` adjacent rows::

    out[t] = sum over j of w[t, j] * rows[t * k + j]        (w: 1 without)

float32 products added in the order ``j = 0 .. k - 1``, one cast at the
end (``masked``: a row whose weight is 0 is not read into the sum, whatever
it holds; a share's rows, PR 60).  Where ``k`` is a multiple of the 8
sublanes XLA's reduction over ``[n, k, d]`` runs at HBM's rate (0.92 ms at k = 8); where it is not it
costs 2.2 ms (k = 4) to 5.0 (k = 6).  ``moe_rows_sum`` reads the rows as
32-bit words (an even row's element under the odd row's below it), a
strided load a pair of choices, so that token ``t``'s ``j``-th row arrives
on sublane ``t`` and nothing moves across sublanes: two integer operations
widen a word's halves (1.6 ms at k = 4, 2.2 at k = 6: the strided loads
bound it).

One rule between the two forms (:func:`sum_rows_fits`, a pure function of
what the call can see, as ``ops.gate_norm.gate_norm_fits``): the kernel on
a ``tpu`` backend for bf16 rows whose shapes fit the tiles,
:func:`sum_rows_plain` everywhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # a block's columns: a strided load reads a ref one lane tile wide
_STRIP = 16  # tokens a step works on at a time: a packed bf16 tile of the result
# Tokens of a grid step's block (its rows ``k`` times as many).  128 to 2,048
# read within 6 % of each other on a TPU v5e (PERF.md section 6, PR 50;
# tools/grouped_matmul_probe.py rows).
_TOKENS = 512


def sum_rows_fits(n: int, k: int, d: int, dtype, backend: str) -> bool:
    """Whether :func:`sum_rows_kernel` takes ``[n * k, d]`` rows of
    ``dtype``: a ``tpu`` backend (Mosaic lowering), bf16 rows (the halves of
    a 32-bit word), an even ``k`` (a word holds two of a token's rows) that
    is no multiple of 8 (a token's rows are whole sublane tiles there, and
    the compiler's own reduction runs at HBM's rate: 0.92 ms against the
    kernel's 1.93 at 131,072 rows of 2,048), whole lane tiles and whole
    token blocks of whole strips."""
    tokens = min(_TOKENS, n)
    return (
        backend == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
        and k % 2 == 0 and k % 8 != 0 and d % _LANES == 0
        and n % tokens == 0 and tokens % _STRIP == 0
    )


def sum_rows(
    rows: jax.Array, weights: jax.Array | None, n: int, k: int, dtype,
    masked: bool = False,
) -> jax.Array:
    """``[n * k, d]`` rows, a token's ``k`` adjacent (and ``weights`` [n, k]
    float32, or None for a plain sum) → ``[n, d]`` of ``dtype``: the kernel
    where :func:`sum_rows_fits` says so, the plain form elsewhere.
    ``masked``: a row of weight 0 adds 0 even where it holds no number
    (0 x NaN is NaN), by selection."""
    if sum_rows_fits(n, k, rows.shape[-1], rows.dtype, jax.default_backend()):
        return sum_rows_kernel(rows, weights, n, k, dtype, masked=masked)
    return sum_rows_plain(rows, weights, n, k, dtype, masked)


def sum_rows_plain(
    rows: jax.Array, weights: jax.Array | None, n: int, k: int, dtype,
    masked: bool = False,
) -> jax.Array:
    """:func:`sum_rows` in plain ``jax.numpy``: what ``ops.moe_dispatch``
    computed before this module, operation for operation."""
    per_choice = rows.reshape(n, k, rows.shape[-1]).astype(jnp.float32)
    if weights is None:
        return per_choice.sum(axis=1).astype(dtype)
    if masked:
        weights = weights[:, :, None]
        return jnp.where(
            weights != 0, weights * per_choice, 0.0).sum(axis=1).astype(dtype)
    return jnp.einsum("nk,nkd->nd", weights, per_choice).astype(dtype)


def _sum_kernel(*refs, k: int, masked: bool):
    *w_ref, rows_ref, out_ref = refs  # the weights' block first, where there are weights
    # word-row s: row 2s in the low half, row 2s + 1 in the high half
    words = rows_ref.bitcast(jnp.uint32)
    pairs = k // 2

    def widen(halves):
        return jax.lax.bitcast_convert_type(halves, jnp.float32)

    def a_strip(i, carry):
        at = pl.multiple_of(i * _STRIP, _STRIP)
        w = w_ref[0][pl.ds(at, _STRIP), :] if w_ref else None
        acc = None
        for pair in range(pairs):
            both = words[pl.ds(at * pairs + pair, _STRIP, stride=pairs), :]
            for j, row in ((2 * pair, widen(both << 16)),
                           (2 * pair + 1, widen(both & jnp.uint32(0xFFFF0000)))):
                if w is not None and masked:  # a lane tile a choice: no broadcast here
                    scale = w[:, j * _LANES:(j + 1) * _LANES]
                    row = jnp.where(scale != 0, scale * row, 0.0)
                elif w is not None:
                    row = w[:, j:j + 1] * row
                acc = row if acc is None else acc + row
        out_ref[pl.ds(at, _STRIP), :] = acc.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // _STRIP, a_strip, 0)


def sum_rows_kernel(
    rows: jax.Array, weights: jax.Array | None, n: int, k: int, dtype,
    interpret: bool = False, masked: bool = False,
) -> jax.Array:
    """:func:`sum_rows` as the Pallas TPU kernel ``moe_rows_sum`` for shapes
    :func:`sum_rows_fits` admits; ``interpret`` runs it on any backend.  No
    gradient of its own: its callers' ``custom_vjp`` rules own both ways."""
    d = rows.shape[-1]
    tokens = min(_TOKENS, n)
    operands = [rows]
    specs = [pl.BlockSpec((tokens * k, _LANES), lambda t, c: (t, c))]
    if weights is not None:
        weights = weights.astype(jnp.float32)
        if masked:
            # Broadcasting a token's weight over the lanes inside the kernel
            # is 1.0 of its 1.65 ms at k = 4 (v5e, PERF.md PR 60): the masked
            # form takes it spread over a lane tile a choice, fetched once a
            # token block (its block index does not move with the columns).
            weights = jnp.repeat(weights, _LANES, axis=1)
        operands.insert(0, weights)
        specs.insert(0, pl.BlockSpec((tokens, weights.shape[1]), lambda t, c: (t, 0)))
    return pl.pallas_call(
        functools.partial(_sum_kernel, k=k, masked=masked),
        grid=(n // tokens, d // _LANES),
        in_specs=specs,
        out_specs=pl.BlockSpec((tokens, _LANES), lambda t, c: (t, c)),
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="moe_rows_sum",
    )(*operands)
