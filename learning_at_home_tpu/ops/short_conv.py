"""The gated short convolution that is a layer's whole token mixer (LFM2's
``conv`` layers), on the mixer's in-projection ``[B | C | u]`` [B, S, 3 Ch]
(three thirds, in that order), ``-> [B, S, Ch]``::

    v[t] = B[t] * u[t]
    y[t] = C[t] * sum_j w[:, j] v[t - (K - 1) + j],   v[t < 0] = 0

a channel at a time (``w`` [Ch, K]), zeros before the first position of
EVERY row of the batch; no bias, no activation, no state between rows.
The arithmetic is float32 between an input and an output of the input's
dtype (bf16 in a train step): every element is widened before the first
multiply, ``v`` is never rounded, the taps are summed in the order above,
one rounding at the end; the gradient of ``w`` is a float32 sum over all
the rows.

Two forms of it, one rule between them (:func:`gated_short_conv`,
:func:`short_conv_fits`: a pure function of what the call can see, as
``ops.ssm_conv.causal_conv_silu`` chooses its kernel; that module's
helpers are used here as they are):

* :func:`gated_short_conv_kernel`, where the backend is ``tpu`` and the
  shapes fit the tiles: ONE pass forward (``short_conv_fwd``) and, behind a
  ``jax.custom_vjp``, ONE pass backward (``short_conv_bwd``).  A grid step
  holds a block of rows of a block of channels; ``B``, ``C`` and ``u`` come
  from HBM once in the input's dtype, each where the in-projection left it
  (three ``BlockSpec`` s on the one array: no slice is ever written), the
  sublane tile of rows BEFORE the block comes with ``B`` and ``u`` as a
  halo (zeros where the block starts a sequence), and ``v``, the shifted
  products and the gate live and die in VMEM.  The backward reads the
  three and the cotangent once, with ``C``'s and the cotangent's halo
  AFTER the block as well (zeros after the last position), makes ``v`` and
  the convolution again in VMEM (nothing float32 is kept as a residual),
  writes ``d[B | C | u]`` once, as ONE array (the grid's last axis walks
  the three thirds of a block: the first step computes all three and
  writes ``dB``, the next two hand over what it left in VMEM), and adds
  ``dw`` into a float32 block it revisits along the rows.
* :func:`gated_short_conv_plain`, everywhere else (the CPU, a shape the
  tiles refuse): plain ``jax.numpy`` over a padded float32 copy, the
  backward autodiff's.  It is the kernel's reference in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learning_at_home_tpu.ops.ssm_conv import (
    _HALO,
    _LANES,
    _SUBLANES,
    _tap_rows,
    _weighed_sum,
    _windows_ahead,
    _windows_back,
)

# A grid step's block: rows x channels (each ``min(.., dim)``; the channel
# block is the largest multiple of the lanes that divides Ch up to this),
# and the rows of a strip, what a step holds in registers at a time.  The
# fastest of the probe's at [1, 16384, 6144] bf16 on a TPU v5e (PERF.md
# section 6, PR 61; tools/smallthinker_probe.py conv gated).
_ROWS, _CHANNELS, _STRIP = 512, 512, 32


def gated_short_conv(bcu: jax.Array, w: jax.Array) -> jax.Array:
    """``C * conv(B * u)`` as above, in ``bcu``'s dtype, of ``bcu`` [B, S,
    3 Ch] (a projection's output, read where the projection left it) under
    the filters ``w`` [Ch, K].  The kernel where :func:`short_conv_fits`
    says so, the plain form elsewhere."""
    shape = (*bcu.shape[:2], w.shape[0])
    if short_conv_fits(shape, w.shape[1], jax.default_backend()):
        return gated_short_conv_kernel(bcu, w)
    return gated_short_conv_plain(bcu, w)


def short_conv_fits(shape, taps: int, backend: str) -> bool:
    """Whether :func:`gated_short_conv_kernel` takes a call whose result is
    ``shape`` = [B, S, Ch]: a ``tpu`` backend (Mosaic lowering), channels a
    multiple of the 128 lanes (so each third starts on a channel block's
    edge), a length its row block divides (the block a multiple of the
    halo and of its strips), and no more taps before a position than a
    sublane tile holds.  A pure function of what the call can see."""
    _, s, c = shape
    rows = min(_ROWS, s)
    return (
        backend == "tpu" and c % _LANES == 0
        and s % rows == 0 and rows % _HALO == 0 and rows % _strip(rows) == 0
        and 1 <= taps - 1 <= _SUBLANES
    )


def gated_short_conv_plain(bcu: jax.Array, w: jax.Array) -> jax.Array:
    """:func:`gated_short_conv` in plain ``jax.numpy``: ``K`` shifted
    multiply-adds over a float32 ``B * u`` padded with ``K - 1`` zero rows;
    its backward is autodiff's."""
    f32 = jnp.float32
    s, taps = bcu.shape[1], w.shape[1]
    b, c, u = jnp.split(bcu.astype(f32), 3, axis=-1)
    padded = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(f32)
    return (c * sum(
        w[:, j] * padded[:, j:j + s] for j in range(taps)
    )).astype(bcu.dtype)


# ---- the kernel: a block of rows of a block of channels a grid step ----
#
# As ``ops/ssm_conv.py``'s: a step widens what it convolves, with the halo
# before it, into a float32 VMEM scratch, then walks the block a STRIP of
# rows at a time so that what a strip makes stays in vector registers (the
# taps' shifts are rolls along the sublanes of an aligned load).  Here the
# scratch holds ``v = B * u``; the backward holds ``g = dy * C`` (the
# convolution's cotangent) in a second scratch with the halo after the
# block, and one walk makes ``dC = dy * conv(v)``, the sums for ``dw`` and
# ``dv[t] = sum_j w[:, j] g[t + K - 1 - j]``, from which ``dB = dv * u`` and
# ``du = dv * B``.


def _strip(rows: int) -> int:
    return min(_STRIP, rows)


def _fwd_kernel(b_ref, b_before, c_ref, u_ref, u_before, w_ref, y_ref, ve, *,
                taps):
    f32 = jnp.float32
    rows, strip = b_ref.shape[0], _strip(b_ref.shape[0])
    ve[:_HALO, :] = jnp.where(
        pl.program_id(2) == 0, 0.0,
        b_before[...].astype(f32) * u_before[...].astype(f32))
    ve[_HALO:, :] = b_ref[...].astype(f32) * u_ref[...].astype(f32)

    def a_strip(i, carry):
        at = pl.multiple_of(i * strip, strip)
        conv = _weighed_sum(
            w_ref, _windows_back(ve, _HALO + at, strip, taps - 1))
        y_ref[pl.ds(at, strip), :] = (
            c_ref[pl.ds(at, strip), :].astype(f32) * conv).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // strip, a_strip, 0)


def _bwd_kernel(b_ref, b_before, c_ref, c_after, u_ref, u_before, dy_ref,
                dy_after, w_ref, d_ref, sums_ref, ve, ge, dc_keep, du_keep, *,
                taps):
    f32 = jnp.float32
    block, blocks, part = pl.program_id(2), pl.num_programs(2), pl.program_id(3)
    rows, strip = b_ref.shape[0], _strip(b_ref.shape[0])
    first, last = block == 0, block == blocks - 1

    @pl.when((part == 0) & first)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def tiles_added(v):  # [n, c] -> [8, c]: the rows added tile on tile
        return jnp.sum(v.reshape(v.shape[0] // _SUBLANES, _SUBLANES, -1), axis=0)

    @pl.when(part == 0)
    def _():
        ve[:_HALO, :] = jnp.where(
            first, 0.0, b_before[...].astype(f32) * u_before[...].astype(f32))
        ve[_HALO:, :] = b_ref[...].astype(f32) * u_ref[...].astype(f32)
        ge[:rows, :] = dy_ref[...].astype(f32) * c_ref[...].astype(f32)
        ge[rows:, :] = jnp.where(  # zeros after the last position
            last, 0.0, dy_after[...].astype(f32) * c_after[...].astype(f32))

        # dw[j] += sum_t g[t] v[t - (K - 1) + j] over the block's rows, eight
        # partial sums a channel (a sublane tile; the eight are added outside)
        def a_strip(i, sums):
            at = pl.multiple_of(i * strip, strip)
            here = pl.ds(at, strip)
            windows = _windows_back(ve, _HALO + at, strip, taps - 1)
            dc_keep[here, :] = (dy_ref[here, :].astype(f32) * _weighed_sum(
                w_ref, windows)).astype(dc_keep.dtype)
            ahead = _windows_ahead(ge, at, strip, taps - 1)
            dv = _weighed_sum(w_ref, ahead[::-1])
            d_ref[here, :] = (dv * u_ref[here, :].astype(f32)).astype(d_ref.dtype)
            du_keep[here, :] = (
                dv * b_ref[here, :].astype(f32)).astype(du_keep.dtype)
            return tuple(
                acc + tiles_added(ahead[0] * v) for acc, v in zip(sums, windows))

        nothing = jnp.zeros((_SUBLANES, b_ref.shape[1]), f32)
        sums = jax.lax.fori_loop(0, rows // strip, a_strip, (nothing,) * taps)
        for j, total in enumerate(sums):
            sums_ref[j * _SUBLANES:(j + 1) * _SUBLANES, :] += total

    @pl.when(part == 1)
    def _():
        d_ref[...] = dc_keep[...]

    @pl.when(part == 2)
    def _():
        d_ref[...] = du_keep[...]


def _blocks(shape):
    """``(rows, channels)`` of a grid step's block for a result's shape."""
    _, s, c = shape
    channels = max(
        n for n in range(_LANES, min(_CHANNELS, c) + 1, _LANES) if c % n == 0)
    return min(_ROWS, s), channels


def _specs(shape):
    """The grid's first three axes ``(B, channel blocks, row blocks)`` for a
    result of ``shape`` = [B, S, Ch], its block ``(rows, channels)`` and its
    block specs (their index maps read those three axes; a fourth is the
    backward's walk over the thirds): ``rows(third)``, ``before(third)``
    and ``after(third)`` of the in-projection's third ``third`` (0 ``B``, 1
    ``C``, 2 ``u``) or of a [B, S, Ch] array (third 0): a block of rows, the
    halo tile before it and the one after it, clamped at a sequence's ends,
    where the kernels put zeros."""
    bsz, s, c = shape
    rows, channels = _blocks(shape)
    per, tiles, n_ch = rows // _HALO, s // _HALO, c // channels

    def of_rows(third=0):
        return pl.BlockSpec(
            (None, rows, channels),
            lambda b, ch, r, *_: (b, r, third * n_ch + ch))

    def before(third=0):
        return pl.BlockSpec(
            (None, _HALO, channels),
            lambda b, ch, r, *_: (
                b, jnp.maximum(r * per - 1, 0), third * n_ch + ch))

    def after(third=0):
        return pl.BlockSpec(
            (None, _HALO, channels),
            lambda b, ch, r, *_: (
                b, jnp.minimum((r + 1) * per, tiles - 1), third * n_ch + ch))

    return (bsz, n_ch, s // rows), (rows, channels), {
        "rows": of_rows, "before": before, "after": after,
        "taps": lambda n: pl.BlockSpec(
            (n, channels), lambda b, ch, r, *_: (0, ch)),
        "sums": lambda n: pl.BlockSpec(
            (None, n, channels), lambda b, ch, r, *_: (b, 0, ch)),
        # the backward's one output [B, S, 3 Ch]: the third its last axis says
        "thirds": pl.BlockSpec(
            (None, rows, channels),
            lambda b, ch, r, part: (b, r, part * n_ch + ch)),
    }


def _forward(bcu, w, interpret):
    shape = (*bcu.shape[:2], w.shape[0])
    grid, (rows, channels), spec = _specs(shape)
    tap_rows = _tap_rows(w)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=w.shape[1]),
        grid=grid,
        in_specs=[spec["rows"](0), spec["before"](0), spec["rows"](1),
                  spec["rows"](2), spec["before"](2),
                  spec["taps"](len(tap_rows))],
        out_specs=spec["rows"](),
        out_shape=jax.ShapeDtypeStruct(shape, bcu.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="short_conv_fwd",
    )(bcu, bcu, bcu, bcu, bcu, tap_rows)


def _backward(bcu, w, dy, interpret):
    """``(d[B | C | u] [B, S, 3 Ch] in bcu's dtype, dw [Ch, K] float32)``."""
    bsz, c, taps = bcu.shape[0], *w.shape
    grid, (rows, channels), spec = _specs(dy.shape)
    n = taps * _SUBLANES
    tap_rows = _tap_rows(w)
    d_bcu, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps),
        grid=(*grid, 3),
        in_specs=[spec["rows"](0), spec["before"](0), spec["rows"](1),
                  spec["after"](1), spec["rows"](2), spec["before"](2),
                  spec["rows"](), spec["after"](),
                  spec["taps"](len(tap_rows))],
        out_specs=[spec["thirds"], spec["sums"](n)],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((bsz, n, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, channels), jnp.float32),
                        pltpu.VMEM((rows + _HALO, channels), jnp.float32),
                        pltpu.VMEM((rows, channels), bcu.dtype),
                        pltpu.VMEM((rows, channels), bcu.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="short_conv_bwd",
    )(bcu, bcu, bcu, bcu, bcu, bcu, dy, dy, tap_rows)
    return d_bcu, jnp.sum(
        sums.reshape(bsz, taps, _SUBLANES, c), axis=(0, 2)).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated(bcu, w, interpret):
    return _forward(bcu, w, interpret)


def _gated_fwd(bcu, w, interpret):
    return _forward(bcu, w, interpret), (bcu, w)


def _gated_bwd(interpret, residuals, dy):
    return _backward(*residuals, dy, interpret)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_short_conv_kernel(
    bcu: jax.Array, w: jax.Array, interpret: bool = False,
) -> jax.Array:
    """:func:`gated_short_conv` as two Pallas TPU kernels
    (``short_conv_fwd``, and ``short_conv_bwd`` behind a ``jax.custom_vjp``)
    for shapes :func:`short_conv_fits` admits; ``interpret`` runs them on
    any backend.  ``w`` is widened here, so its gradient's way back to the
    parameter's dtype is autodiff's."""
    return _gated(bcu, w.astype(jnp.float32), interpret)
