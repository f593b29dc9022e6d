"""The Mamba-2 mixer's causal depthwise convolution with its bias and its
SiLU, ``[B, S, C] -> [B, S, C]``::

    y[t] = silu(b + sum_j w[:, j] x[t - (K - 1) + j]),   x[t < 0] = 0

a channel at a time (``w`` [C, K], ``b`` [C]), zeros before the first
position of EVERY row of the batch.  The arithmetic is float32 between an
input and an output of ``x``'s dtype (bf16 in a train step): every element
is widened before the first multiply, the taps are summed in the order
above, the SiLU is float32, one rounding at the end; the gradients of
``w`` and ``b`` are float32 sums over all the rows.

Two forms of it, one rule between them (:func:`causal_conv_silu`,
:func:`conv_kernel_fits`: a pure function of what the call can see, as
``ops.ssd.ssd_chunked`` chooses its kernel):

* :func:`causal_conv_silu_kernel`, where the backend is ``tpu`` and the
  shapes fit the tiles: ONE pass forward (``ssm_conv_fwd``) and, behind a
  ``jax.custom_vjp``, ONE pass backward (``ssm_conv_bwd``).  A grid step
  holds a block of rows of a block of channels; the rows come from HBM
  once in ``x``'s dtype (out of a wider array where the channels lie in
  one: the mixer's in-projection, from which ``trunk.ssm_mixer`` takes
  ``x``, ``B`` and ``C`` by a call each, so no slice is ever written), the
  sublane tile of rows BEFORE the block comes with them as a halo (a
  second ``BlockSpec`` on the same array; zeros where the block starts a
  sequence), and the widened copy, the shifted products, the bias and the
  SiLU live and die in VMEM.  The backward reads ``x`` and the cotangent
  once with a halo AFTER the block as well (zeros after the last
  position), makes the pre-activation again in VMEM (nothing float32 is
  kept as a residual), writes ``dx`` once and adds ``dw`` and ``db`` into
  a float32 block it revisits along the rows.
* :func:`causal_conv_silu_plain`, everywhere else (the CPU, a shape the
  tiles refuse): plain ``jax.numpy`` over a padded float32 copy, the
  backward autodiff's.  It is the kernel's reference in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# The rows a halo block holds: the sublane tile of a 16-bit dtype (a
# float32 one is half of it), so one BlockSpec serves both.
_HALO = 16
# A grid step's block: rows x channels (each ``min(.., dim)``; the channel
# block is the largest multiple of the lanes that divides C up to this),
# and the rows of a strip, what a step holds in registers at a time.  The
# fastest of the probe's at [1, 16384, 6144] bf16 on a TPU v5e (PERF.md
# section 6, PR 41; tools/smallthinker_probe.py conv).
_ROWS, _CHANNELS, _STRIP = 1024, 512, 32


def causal_conv_silu(
    x: jax.Array, w: jax.Array, b: jax.Array | None, first: int = 0,
) -> jax.Array:
    """``silu(conv(x) + b)`` as above, in ``x``'s dtype, of the ``C``
    channels of ``x`` [B, S, >= first + C] that start at ``first`` (a
    projection's output, read where the projection left it: the kernel
    takes its blocks from the wider array and no slice is written).  ``b``
    None: a convolution without a bias (zeros that are no parameter: they
    are made here and get no gradient a caller could see).  The kernel
    where :func:`conv_kernel_fits` says so, the plain form (on the slice)
    elsewhere."""
    if b is None:
        b = jnp.zeros((w.shape[0],), jnp.float32)
    shape = (*x.shape[:2], w.shape[0])
    if conv_kernel_fits(shape, w.shape[1], jax.default_backend(), first):
        return causal_conv_silu_kernel(x, w, b, first)
    return causal_conv_silu_plain(x[..., first:first + w.shape[0]], w, b)


def conv_kernel_fits(shape, taps: int, backend: str, first: int = 0) -> bool:
    """Whether :func:`causal_conv_silu_kernel` takes a call over ``shape``
    = [B, S, C] (the channels convolved, which start at ``first`` of the
    array they lie in): a ``tpu`` backend (Mosaic lowering), channels a
    multiple of the 128 lanes that start on a channel block's edge, a
    length its row block divides (the block a multiple of the halo and
    of its strips), and no more taps before a position than a sublane
    tile holds.  A pure function of what the call can see."""
    _, s, c = shape
    rows = min(_ROWS, s)
    return (
        backend == "tpu" and c % _LANES == 0 and first % _blocks(shape)[1] == 0
        and s % rows == 0 and rows % _HALO == 0 and rows % _strip(rows) == 0
        and 1 <= taps - 1 <= _SUBLANES
    )


def causal_conv_silu_plain(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """:func:`causal_conv_silu` in plain ``jax.numpy``: ``K`` shifted
    multiply-adds over a float32 copy padded with ``K - 1`` zero rows; its
    backward is autodiff's."""
    f32 = jnp.float32
    s, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(x.astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(f32)
    return jax.nn.silu(b.astype(f32) + sum(
        w[:, j] * padded[:, j:j + s] for j in range(taps)
    )).astype(x.dtype)


# ---- the kernel: a block of rows of a block of channels a grid step ----
#
# A step first widens its block, with the halo before it (and in the
# backward the one after it), into a float32 VMEM scratch.  It then walks
# the block a STRIP of rows at a time, so that everything a strip makes
# stays in vector registers: an aligned load of the strip and the sublane
# tile before it, the taps' shifts as rolls of that along the sublanes
# (what wraps around lands in the tile before, which is cut off), the
# multiply-adds, the SiLU, one store.  (The whole block as one value runs
# half as fast again, and a shifted window cannot be loaded at an offset
# Mosaic cannot prove aligned: PERF.md section 6, PR 41.)  The backward
# walks twice: ``g = dy * silu'(pre)`` into a second scratch with the
# sums for ``dw`` and ``db`` on the way, then ``dx[t] = sum_j w[:, j] g[t +
# K - 1 - j]`` from windows of that scratch shifted the other way.


def _windows_back(ref, start, n, k):
    """``[ref[start - k + j : start - k + j + n] for j in 0..k]``: the
    ``k + 1`` windows of ``n`` rows that END ``k - j`` rows before ``start
    + n``; ``start`` a multiple of the sublane tile."""
    wide = ref[pl.ds(start - _SUBLANES, n + _SUBLANES), :]
    return [(pltpu.roll(wide, k - j, 0) if j < k else wide)[_SUBLANES:]
            for j in range(k + 1)]


def _windows_ahead(ref, start, n, k):
    """``[ref[start + j : start + j + n] for j in 0..k]``."""
    wide = ref[pl.ds(start, n + _SUBLANES), :]
    return [(pltpu.roll(wide, n + _SUBLANES - j, 0) if j else wide)[:n]
            for j in range(k + 1)]


def _weighed_sum(w_ref, windows):
    """``sum_j w[j] windows[j]``, in that order."""
    acc = w_ref[0:1, :] * windows[0]
    for j in range(1, len(windows)):
        acc = acc + w_ref[j:j + 1, :] * windows[j]
    return acc


def _strip(rows: int) -> int:
    return min(_STRIP, rows)


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, y_ref, xe, *, taps):
    f32 = jnp.float32
    rows, strip = x_ref.shape[0], _strip(x_ref.shape[0])
    xe[:_HALO, :] = jnp.where(
        pl.program_id(2) == 0, 0.0, before_ref[...].astype(f32))
    xe[_HALO:, :] = x_ref[...].astype(f32)

    def a_strip(i, carry):
        at = pl.multiple_of(i * strip, strip)
        pre = b_ref[...] + _weighed_sum(
            w_ref, _windows_back(xe, _HALO + at, strip, taps - 1))
        y_ref[pl.ds(at, strip), :] = jax.nn.silu(pre).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // strip, a_strip, 0)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref, b_ref,
                dx_ref, sums_ref, xe, ge, *, taps):
    f32 = jnp.float32
    block, blocks = pl.program_id(2), pl.num_programs(2)
    rows, strip = x_ref.shape[0], _strip(x_ref.shape[0])
    first, last = block == 0, block == blocks - 1

    @pl.when(first)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    xe[:_HALO, :] = jnp.where(first, 0.0, before_ref[...].astype(f32))
    xe[_HALO:_HALO + rows, :] = x_ref[...].astype(f32)
    xe[_HALO + rows:, :] = jnp.where(last, 0.0, after_ref[...].astype(f32))

    def g_of(dy, windows):  # dy silu'(pre)
        pre = b_ref[...] + _weighed_sum(w_ref, windows)
        sig = jax.nn.sigmoid(pre)
        return dy * (sig * (1.0 + pre * (1.0 - sig)))

    def tiles_added(v):  # [n, c] -> [8, c]: the rows added tile on tile
        return jnp.sum(v.reshape(v.shape[0] // _SUBLANES, _SUBLANES, -1), axis=0)

    # g on the block's rows; dw[j] += sum_t g[t] x[t - (K - 1) + j] and db
    # += sum_t g[t] over them, eight partial sums a channel (a sublane
    # tile; the eight are added outside)
    def a_strip_of_g(i, sums):
        at = pl.multiple_of(i * strip, strip)
        windows = _windows_back(xe, _HALO + at, strip, taps - 1)
        g = g_of(dy_ref[pl.ds(at, strip), :].astype(f32), windows)
        ge[pl.ds(at, strip), :] = g
        return (*(acc + tiles_added(g * v) for acc, v in zip(sums, windows)),
                sums[taps] + tiles_added(g))

    nothing = jnp.zeros((_SUBLANES, x_ref.shape[1]), f32)
    sums = jax.lax.fori_loop(
        0, rows // strip, a_strip_of_g, (nothing,) * (taps + 1))
    for j, total in enumerate(sums):
        sums_ref[j * _SUBLANES:(j + 1) * _SUBLANES, :] += total
    # g on the halo after the block: zeros after the last position
    ge[rows:, :] = g_of(
        jnp.where(last, 0.0, dy_after_ref[...].astype(f32)),
        _windows_back(xe, _HALO + rows, _HALO, taps - 1))

    def a_strip_of_dx(i, carry):
        at = pl.multiple_of(i * strip, strip)
        ahead = _windows_ahead(ge, at, strip, taps - 1)
        dx_ref[pl.ds(at, strip), :] = _weighed_sum(
            w_ref, ahead[::-1]).astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // strip, a_strip_of_dx, 0)


def _blocks(shape):
    """``(rows, channels)`` of a grid step's block for a call's shape."""
    _, s, c = shape
    channels = max(
        n for n in range(_LANES, min(_CHANNELS, c) + 1, _LANES) if c % n == 0)
    return min(_ROWS, s), channels


def _specs(shape, first=0):
    """The grid ``(B, channel blocks, row blocks)`` of a call over
    ``shape`` = [B, S, C], its block ``(rows, channels)`` and its block
    specs: a block of rows, the halo tile before it and the one after it
    (clamped at a sequence's ends, where the kernels put zeros), the same
    three of an array whose channels ``first ..`` are the C (``x_rows``,
    ``x_before``, ``x_after``), a per-channel row vector [n, channels]."""
    bsz, s, c = shape
    rows, channels = _blocks(shape)
    per, tiles, skip = rows // _HALO, s // _HALO, first // channels

    def of_rows(skip):
        return pl.BlockSpec(
            (None, rows, channels), lambda b, ch, r: (b, r, skip + ch))

    def before(skip):
        return pl.BlockSpec(
            (None, _HALO, channels),
            lambda b, ch, r: (b, jnp.maximum(r * per - 1, 0), skip + ch))

    def after(skip):
        return pl.BlockSpec(
            (None, _HALO, channels),
            lambda b, ch, r: (b, jnp.minimum((r + 1) * per, tiles - 1), skip + ch))

    return (bsz, c // channels, s // rows), (rows, channels), {
        "rows": of_rows(0), "before": before(0), "after": after(0),
        "x_rows": of_rows(skip), "x_before": before(skip), "x_after": after(skip),
        "channel": lambda n: pl.BlockSpec((n, channels), lambda b, ch, r: (0, ch)),
        "sums": lambda n: pl.BlockSpec(
            (None, n, channels), lambda b, ch, r: (b, 0, ch)),
    }


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _tap_rows(w):
    """[C, K] -> [8 or 16, C]: tap j a row, zeros below."""
    return jnp.pad(w.T, ((0, -w.shape[1] % _SUBLANES), (0, 0)))


def _forward(x, w, b, first, interpret):
    shape = (*x.shape[:2], w.shape[0])
    grid, (rows, channels), spec = _specs(shape, first)
    tap_rows = _tap_rows(w)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=w.shape[1]),
        grid=grid,
        in_specs=[spec["x_rows"], spec["x_before"],
                  spec["channel"](len(tap_rows)), spec["channel"](1)],
        out_specs=spec["rows"],
        out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, channels), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssm_conv_fwd",
    )(x, x, tap_rows, b[None, :])


def _backward(x, w, b, dy, first, interpret):
    """``(dx [B, S, C] in x's dtype, dw [C, K] float32, db [C] float32)``."""
    bsz, c, taps = x.shape[0], *w.shape
    grid, (rows, channels), spec = _specs(dy.shape, first)
    n = (taps + 1) * _SUBLANES
    tap_rows = _tap_rows(w)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps),
        grid=grid,
        in_specs=[spec["x_rows"], spec["x_before"], spec["x_after"],
                  spec["rows"], spec["after"],
                  spec["channel"](len(tap_rows)), spec["channel"](1)],
        out_specs=[spec["rows"], spec["sums"](n)],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, n, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows + 2 * _HALO, channels), jnp.float32),
                        pltpu.VMEM((rows + _HALO, channels), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssm_conv_bwd",
    )(x, x, x, dy, dy, tap_rows, b[None, :])
    sums = jnp.sum(sums.reshape(bsz, taps + 1, _SUBLANES, c), axis=(0, 2))
    return dx, sums[:taps].T, sums[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, w, b, first, interpret):
    return _forward(x, w, b, first, interpret)


def _conv_fwd(x, w, b, first, interpret):
    return _forward(x, w, b, first, interpret), (x, w, b)


def _conv_bwd(first, interpret, residuals, dy):
    x, w, _ = residuals
    dx, dw, db = _backward(*residuals, dy, first, interpret)
    # the channels beside the C read nothing here: zeros, as a slice's
    # transpose gives them
    after = x.shape[2] - first - w.shape[0]
    return jnp.pad(dx, ((0, 0), (0, 0), (first, after))), dw, db


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu_kernel(
    x: jax.Array, w: jax.Array, b: jax.Array, first: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """:func:`causal_conv_silu` as two Pallas TPU kernels (``ssm_conv_fwd``,
    and ``ssm_conv_bwd`` behind a ``jax.custom_vjp``) for shapes
    :func:`conv_kernel_fits` admits; ``interpret`` runs them on any
    backend.  ``w`` and ``b`` are widened here, so their gradients'
    way back to the parameters' dtype is autodiff's."""
    return _conv(
        x, w.astype(jnp.float32), b.astype(jnp.float32), first, interpret)
