"""The three stages of a hyper-connection part that pass over the residual
streams ``x`` [B, S, n, C] (``n`` streams a token; ``models/trunk.py``
``hc_coefficients``, ``hc_pre``, ``hc_post``)::

    token_stats(x, phi)           ms[t]   = mean over a token's n C numbers of x^2
                                  m[o, t] = sum_{j c} x[t, j, c] phi[j C + c, o]
    stream_read(x, pre)           h[t]    = sum_j pre[j, t] x[t, j]
    stream_write(x, y, post, res) x'[t, i] = sum_j res[i, j, t] x[t, j] + post[i, t] y[t]

The arithmetic is float32 between the load of a stream in its own dtype
(bf16 in a train step) and the one rounding of each output to it; the sums
over the streams run ``j = 0, 1, ..`` and the write's ``y`` term is added
last; the per-token numbers (``pre`` [n, B, S], ``post`` [n, B, S], ``res``
[n, n, B, S], ``ms`` [B, S], ``m`` [o, B, S]: the streams' axes LEADING, as
``hc_coefficients`` lays them) and their gradients are float32; ``phi`` [n
C, o] multiplies in the stream's dtype and is summed in float32.

Two forms of each, ONE rule between them (:func:`stream_mix_fits`: a pure
function of what the call can see, as ``ops.gate_norm.gate_norm_fits``):

* the kernels, where the backend is ``tpu`` and the shapes fit the tiles,
  in the view ``[B S, n C]``: a token's ``n C`` numbers one row, a stream
  whole lane tiles of it, a grid step a block of whole 16-row sublane tiles
  of tokens.  Every stage is ONE pass over the streams a direction: the
  streams come from HBM once in their dtype, the widened copies and the
  products live and die in vector registers a strip of rows and a chunk of
  lanes at a time, each output is written once.  Behind a
  ``jax.custom_vjp`` each, whose residuals are the inputs (which a layer's
  remat recomputes) and the per-token numbers: nothing of a stream's size
  is kept that the plain form does not keep.

  - ``stream_write_fwd``; ``stream_write_bwd`` reads ``x``, ``y`` and the
    cotangent ``dx'`` once and leaves ``res^T dx'`` (the streams' gradient
    through the mix), ``dy = sum_i post[i] dx'[i]`` and the per-token
    float32 sums over the channels ``dres[i, j] = <dx'[i], x[j]>`` and
    ``dpost[i] = <dx'[i], y>``.
  - ``stream_read_fwd``; ``stream_read_bwd`` reads ``x`` and ``dh`` and
    leaves ``dpre[j] = <dh, x[j]>`` a token.  The streams' gradient through
    the read, ``pre[j] dh``, is left to XLA, which fuses the broadcast
    product into the ONE add of the three gradients of a part's ``x``
    (autodiff's ``add_any``): a kernel's output there would be a write and
    a read of the streams more.
  - ``stream_stats_fwd``: the sum of squares on the VPU and ``x phi`` on
    the MXU (``phi`` padded to a lane tile of columns, the sum of squares
    in the column after the last) from one read; ``stream_stats_bwd``
    reads ``x`` once and leaves ``2 x d(sumsq) + dm phi^T`` and ``dphi =
    x^T dm``, added in float32 into a block the grid revisits.

* the plain forms (:func:`token_stats_plain`, :func:`stream_read_plain`,
  :func:`stream_write_plain`), everywhere else (the CPU, a shape the tiles
  refuse): plain ``jax.numpy``, ``trunk``'s arithmetic of before this
  module operation for operation, the backward autodiff's.  They are the
  kernels' reference in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import jaxpr_as_fun

from learning_at_home_tpu.ops.gate_norm import _TILE
from learning_at_home_tpu.ops.ssm_conv import _LANES

# A grid step's block of rows (tokens; ``min(.., B S)``), the rows of a
# strip and the lanes of a chunk (the widest whole lane tiles up to this
# that divide a stream), what a step of the mixing kernels works on at a
# time, and the statistics' kernels' own block of rows (the MXU wants more
# rows a weight tile than the VPU's kernels want in VMEM).  At [1, 16384,
# 4, 3584] bf16 on a TPU v5e every block the probe tried (32 to 128 rows,
# chunks of 128 to 1,792 lanes, 128 to 512 rows for the statistics) reads
# within 3 % of these: the calls wait for the HBM.  The chunk is as wide
# as it is for the TRACE: a kernel's body is as many operations as a
# stream has chunks, and at 128 lanes (28 copies) tracing and lowering the
# kernels was a quarter of the step's (PERF.md section 6, PR 65;
# tools/smallthinker_probe.py streams).
_ROWS, _STRIP, _CHUNK, _STATS_ROWS = 64, 16, 1792, 256
_F32 = jnp.float32


def stream_mix_fits(shape, dtype, backend: str) -> bool:
    """Whether the kernels take a call over streams of ``shape`` = [B, S,
    n, C] and ``dtype``: a ``tpu`` backend (Mosaic lowering), two streams
    or more, channels a multiple of the 128 lanes (a stream is whole lane
    tiles of a token's row), bf16 or float32, ``B S`` tokens that the
    row blocks divide, each whole 16-row sublane tiles, and a row of ``n
    C`` numbers narrow enough that the widest call's blocks fit the VMEM a
    call may ask for (:func:`_vmem_bytes`, which the calls ask by).  A
    pure function of what the call can see."""
    b, s, n, c = shape
    blocks = (_rows(b * s, _ROWS), _rows(b * s, _STATS_ROWS))
    return (
        backend == "tpu" and n >= 2 and c % _LANES == 0
        and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
        and all((b * s) % rows == 0 and rows % _TILE == 0 for rows in blocks)
        and _vmem_bytes(_widest_blocks(b * s, n, c, dtype)) <= _VMEM_MOST
    )


def _rows(tokens: int, most: int) -> int:
    return min(most, tokens)


def _lanes(columns: int) -> int:
    """``columns`` rounded up to whole lane tiles."""
    return -(-columns // _LANES) * _LANES


def _chunk(lanes: int, most: int | None = None) -> int:
    """The widest whole lane tiles up to ``most`` that divide ``lanes``."""
    most = min(most or _CHUNK, lanes)
    return max(n for n in range(_LANES, most + 1, _LANES) if lanes % n == 0)


# ---- the three stages: the rule's answer, then the form ----


def token_stats(x: jax.Array, phi: jax.Array) -> tuple:
    """``(ms [B, S], m [o, B, S])``, float32, of ``x`` [B, S, n, C] and
    ``phi`` [n C, o]: the mean square of a token's ``n C`` numbers and its
    row times ``phi`` (in ``x``'s dtype, summed in float32)."""
    if stream_mix_fits(x.shape, x.dtype, jax.default_backend()):
        return token_stats_kernel(x, phi)
    return token_stats_plain(x, phi)


def stream_read(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``sum_j pre[j] x[:, :, j]`` [B, S, C] in ``x``'s dtype (``pre`` [n,
    B, S] float32)."""
    if stream_mix_fits(x.shape, x.dtype, jax.default_backend()):
        return stream_read_kernel(x, pre)
    return stream_read_plain(x, pre)


def stream_write(
    x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array
) -> jax.Array:
    """``x'[:, :, i] = sum_j res[i, j] x[:, :, j] + post[i] y`` [B, S, n,
    C] in ``x``'s dtype (``y`` [B, S, C]; ``post`` [n, B, S], ``res`` [n,
    n, B, S] float32)."""
    if stream_mix_fits(x.shape, x.dtype, jax.default_backend()):
        return stream_write_kernel(x, y, post, res)
    return stream_write_plain(x, y, post, res)


def token_stats_plain(x: jax.Array, phi: jax.Array) -> tuple:
    n, c = x.shape[2:]
    x32 = x.astype(_F32)
    return jnp.mean(x32 * x32, axis=(2, 3)), jnp.einsum(
        "bsnc,nco->obs", x, phi.astype(x.dtype).reshape(n, c, -1),
        preferred_element_type=_F32)


def stream_read_plain(x: jax.Array, pre: jax.Array) -> jax.Array:
    """Written as a product broadcast over the channels and a sum over the
    streams' axis (a sum of slices transposes to pads and adds of whole
    float32 streams: PERF.md section 6, PR 64)."""
    weights = jnp.moveaxis(pre, 0, -1)[..., None]  # [B, S, n, 1]
    return jnp.sum(weights * x.astype(_F32), axis=2).astype(x.dtype)


def stream_write_plain(
    x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array
) -> jax.Array:
    mix = jnp.moveaxis(res, (0, 1), (2, 3))[..., None]  # [B, S, n, n, 1]
    write = jnp.moveaxis(post, 0, -1)[..., None]  # [B, S, n, 1]
    mixed = jnp.sum(mix * x.astype(_F32)[:, :, None], axis=3)  # over j
    return (mixed + write * y.astype(_F32)[:, :, None]).astype(x.dtype)


# ---- the mixing kernels: a block of tokens a grid step ----
#
# A step walks its block a STRIP of rows at a time and a strip a CHUNK of
# lanes at a time: aligned loads of the chunk of each stream in the
# operands' dtype, the widening, the products under the strip's per-token
# numbers (a column [strip, 1] each, broadcast along the lanes), one store
# an output.  A per-token sum over the channels is added chunk on chunk
# elementwise and summed along the lanes once a strip.


def _columns(ref, at, strip):
    """The per-token numbers of a strip, a column [strip, 1] each."""
    block = ref[pl.ds(at, strip), :]
    return [block[:, k:k + 1] for k in range(block.shape[1])]


def _chunk_of(ref, at, strip, first, lanes):
    return ref[pl.ds(at, strip), first:first + lanes].astype(_F32)


def _in_columns(sums, shape):
    """[strip, len(sums)] whose column ``k`` is the sum along the lanes of
    ``sums[k]`` [strip, lanes]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    out = jnp.zeros(shape, _F32)
    for k, total in enumerate(sums):
        out = jnp.where(lane == k, jnp.sum(total, axis=1, keepdims=True), out)
    return out


def _strips(rows, strip, a_strip):
    def body(i, carry):
        a_strip(pl.multiple_of(i * strip, strip))
        return carry

    jax.lax.fori_loop(0, rows // strip, body, 0)


def _read_fwd_kernel(x_ref, pre_ref, h_ref, *, strip, chunk):
    c, n = h_ref.shape[1], pre_ref.shape[1]

    def a_strip(at):
        pre = _columns(pre_ref, at, strip)
        for k in range(0, c, chunk):
            h = pre[0] * _chunk_of(x_ref, at, strip, k, chunk)
            for j in range(1, n):
                h = h + pre[j] * _chunk_of(x_ref, at, strip, j * c + k, chunk)
            h_ref[pl.ds(at, strip), k:k + chunk] = h.astype(h_ref.dtype)

    _strips(h_ref.shape[0], strip, a_strip)


def _read_bwd_kernel(x_ref, dh_ref, dpre_ref, *, strip, chunk):
    c, n = dh_ref.shape[1], dpre_ref.shape[1]

    def a_strip(at):
        sums = [jnp.zeros((strip, chunk), _F32)] * n
        for k in range(0, c, chunk):
            dh = _chunk_of(dh_ref, at, strip, k, chunk)
            sums = [sums[j] + dh * _chunk_of(x_ref, at, strip, j * c + k, chunk)
                    for j in range(n)]
        dpre_ref[pl.ds(at, strip), :] = _in_columns(sums, (strip, n))

    _strips(dh_ref.shape[0], strip, a_strip)


def _write_fwd_kernel(x_ref, y_ref, coeff_ref, out_ref, *, strip, chunk):
    """``coeff`` [rows, n + n n]: ``post`` then ``res`` row by row."""
    c = y_ref.shape[1]
    n = x_ref.shape[1] // c

    def a_strip(at):
        coeff = _columns(coeff_ref, at, strip)
        post, res = coeff[:n], coeff[n:]
        for k in range(0, c, chunk):
            y = _chunk_of(y_ref, at, strip, k, chunk)
            xs = [_chunk_of(x_ref, at, strip, j * c + k, chunk) for j in range(n)]
            for i in range(n):
                mixed = res[i * n] * xs[0]
                for j in range(1, n):
                    mixed = mixed + res[i * n + j] * xs[j]
                out_ref[pl.ds(at, strip), i * c + k:i * c + k + chunk] = (
                    mixed + post[i] * y).astype(out_ref.dtype)

    _strips(y_ref.shape[0], strip, a_strip)


def _write_bwd_kernel(
    x_ref, y_ref, coeff_ref, dout_ref, dx_ref, dy_ref, dcoeff_ref, *,
    strip, chunk,
):
    c = y_ref.shape[1]
    n = x_ref.shape[1] // c

    def a_strip(at):
        coeff = _columns(coeff_ref, at, strip)
        post, res = coeff[:n], coeff[n:]
        sums = [jnp.zeros((strip, chunk), _F32)] * (n + n * n)
        for k in range(0, c, chunk):
            y = _chunk_of(y_ref, at, strip, k, chunk)
            xs = [_chunk_of(x_ref, at, strip, j * c + k, chunk) for j in range(n)]
            ds = [_chunk_of(dout_ref, at, strip, i * c + k, chunk) for i in range(n)]
            dy = post[0] * ds[0]
            for i in range(1, n):
                dy = dy + post[i] * ds[i]
            dy_ref[pl.ds(at, strip), k:k + chunk] = dy.astype(dy_ref.dtype)
            for j in range(n):
                dx = res[j] * ds[0]
                for i in range(1, n):
                    dx = dx + res[i * n + j] * ds[i]
                dx_ref[pl.ds(at, strip), j * c + k:j * c + k + chunk] = (
                    dx.astype(dx_ref.dtype))
            sums = [sums[i] + ds[i] * y for i in range(n)] + [
                sums[n + i * n + j] + ds[i] * xs[j]
                for i in range(n) for j in range(n)]
        dcoeff_ref[pl.ds(at, strip), :] = _in_columns(sums, (strip, n + n * n))

    _strips(y_ref.shape[0], strip, a_strip)


# ---- the statistics' kernels: the MXU's product beside the VPU's sum ----


def _stats_fwd_kernel(x_ref, phi_ref, out_ref, *, o, strip, chunk):
    """``out`` [rows, lanes of phi]: ``x phi`` and, in column ``o`` (where
    the padded ``phi`` holds zeros), the sum of the row's squares."""
    out_ref[...] = jnp.dot(
        x_ref[...], phi_ref[...], preferred_element_type=_F32)

    def a_strip(at):
        squares = jnp.zeros((strip, chunk), _F32)
        for k in range(0, x_ref.shape[1], chunk):
            x = _chunk_of(x_ref, at, strip, k, chunk)
            squares = squares + x * x
        m = out_ref[pl.ds(at, strip), :]
        lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
        out_ref[pl.ds(at, strip), :] = jnp.where(
            lane == o, jnp.sum(squares, axis=1, keepdims=True), m)

    _strips(x_ref.shape[0], strip, a_strip)


def _stats_bwd_kernel(
    x_ref, phi_t_ref, dout_ref, dx_ref, dphi_t_ref, *, o, chunk,
):
    """``dx = 2 x dout[:, o] + dout phi^T`` (``phi_t``'s rows from ``o`` on
    are zeros: the padding's and the sum of squares' columns multiply
    nothing) and ``dphi^T += dout^T x``, the block the grid revisits."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_t_ref[...] = jnp.zeros_like(dphi_t_ref)

    dout = dout_ref[...]
    twice = 2.0 * dout[:, o:o + 1]
    dm = dout.astype(x_ref.dtype)
    dm_t = dout.T[:dphi_t_ref.shape[0]].astype(x_ref.dtype)
    for k in range(0, x_ref.shape[1], chunk):
        x = x_ref[:, k:k + chunk]
        dx = jnp.dot(dm, phi_t_ref[:, k:k + chunk], preferred_element_type=_F32)
        dx_ref[:, k:k + chunk] = (dx + twice * x.astype(_F32)).astype(dx_ref.dtype)
        dphi_t_ref[:, k:k + chunk] += jnp.dot(dm_t, x, preferred_element_type=_F32)


# ---- the calls ----


# What a call may ask of a core's 128 MiB of VMEM.
_VMEM_MOST = 100 << 20


def _vmem_bytes(blocks) -> int:
    """The VMEM a call over ``blocks`` ((shape, dtype) each) asks for: two
    buffers of every block and the temporaries of a step."""
    held = sum(
        2 * shape[0] * shape[1] * jnp.dtype(dtype).itemsize
        for shape, dtype in blocks)
    return int(held * 1.25) + (16 << 20)


def _blocks(arrays, rows: int, whole) -> list:
    """What a grid step holds of 2-D ``arrays``: ``rows`` of each, all of
    those at the positions ``whole``."""
    return [(a.shape if at in whole else (rows, a.shape[1]), a.dtype)
            for at, a in enumerate(arrays)]


def _widest_blocks(tokens: int, n: int, c: int, dtype) -> list:
    """The blocks of the call that holds most, ``stream_stats_bwd``'s over
    a hyper-connection's ``phi`` (:func:`_stats_bwd_arrays`): two blocks
    of 256 rows of the streams, ``phi^T`` and ``dphi^T`` whole.  The
    mixing's widest, ``stream_write_bwd``, holds three and a half blocks
    of 64 rows; fewer tokens than a block shrink both alike."""
    o = 2 * n + n * n  # a part's numbers a token: pre, post [n], res [n, n]
    arrays = _stats_bwd_arrays(tokens, n * c, dtype, o, _lanes(o + 1))
    return _blocks(arrays, _rows(tokens, _STATS_ROWS), _STATS_BWD_WHOLE)


def _params(semantics: str, blocks):
    """The compiler's parameters of a call whose grid walks the row
    blocks.  A call the rule did not see (a ``*_kernel`` form called by
    hand) is refused here, by name, and not in Mosaic."""
    asked = _vmem_bytes(blocks)
    if asked > _VMEM_MOST:
        raise ValueError(
            f"a grid step's blocks {blocks} ask {asked} bytes of VMEM, over "
            f"{_VMEM_MOST}: stream_mix_fits refuses such streams")
    return pltpu.CompilerParams(
        dimension_semantics=(semantics,), vmem_limit_bytes=asked)


def _call(kernel, name, rows, operands, outs, interpret, semantics="parallel",
          whole=(), **static):
    """``kernel`` over the row blocks of 2-D ``operands`` and of results
    like ``outs``: each walks with the grid but those named in ``whole``
    (positions among operands + outs), which a step sees entire."""
    like = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in operands)
    return _traced_call(
        kernel, name, rows, like, tuple(outs), interpret, semantics,
        tuple(whole), tuple(sorted(static.items())))(*operands)


@functools.lru_cache(maxsize=None)
def _traced_call(kernel, name, rows, operands, outs, interpret, semantics,
                 whole, static):
    """:func:`_call`'s ``pallas_call`` as ONE jitted function for every
    call of its sizes, the kernel's body traced HERE, once: a step's
    twelve parts call each kernel forward, under remat and backward, in
    tracing contexts of their own (a layer's checkpoint, its transpose,
    the prediction block's).  The ``jax.jit`` lets the calls of one
    context share a lowering (37 kernel instances in the ``xing4.0-29b-
    a4b`` step's StableHLO where 120 calls are made); it does NOT keep the
    body's trace across the contexts (19 traces of the six bodies, 3 s of
    that cell's 44 s of set-up on the chip's machine: PERF.md section 6,
    PR 65), so the jaxpr is made here and the jit wraps its evaluation."""
    arrays = [*operands, *outs]
    specs = [
        pl.BlockSpec(a.shape, lambda r: (0, 0)) if at in whole
        else pl.BlockSpec((rows, a.shape[1]), lambda r: (r, 0))
        for at, a in enumerate(arrays)]
    call = pl.pallas_call(
        functools.partial(kernel, **dict(static)),
        grid=(operands[0].shape[0] // rows,),
        in_specs=specs[:len(operands)], out_specs=specs[len(operands):],
        out_shape=list(outs),
        compiler_params=_params(semantics, _blocks(arrays, rows, whole)),
        interpret=interpret, name=name,
    )
    return jax.jit(jaxpr_as_fun(jax.make_jaxpr(call)(*operands)))


def _mix_call(kernel, name, c, operands, outs, interpret):
    """A mixing kernel over its operands' tokens and streams of ``c``
    channels: the module's row block, strip and chunk."""
    rows = _rows(operands[0].shape[0], _ROWS)
    return _call(kernel, name, rows, operands, outs, interpret,
                 strip=min(_STRIP, rows), chunk=_chunk(c))


def _like(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _read(x, pre, interpret):
    """``x`` [T, n C], ``pre`` [T, n] -> [T, C]."""
    t, n = pre.shape
    c = x.shape[1] // n
    return _mix_call(
        _read_fwd_kernel, "stream_read_fwd", c, [x, pre],
        [_like((t, c), x.dtype)], interpret)[0]


def _read_fwd(x, pre, interpret):
    return _read(x, pre, interpret), (x, pre)


def _read_bwd(interpret, residuals, dh):
    x, pre = residuals
    (dpre,) = _mix_call(
        _read_bwd_kernel, "stream_read_bwd", dh.shape[1], [x, dh],
        [_like(pre.shape, _F32)], interpret)
    return _spread(pre, dh), dpre


def _spread(pre, dh):
    """``pre[:, j] dh`` under stream ``j``'s lanes, [T, n C] in ``dh``'s
    dtype: the streams' gradient through the read, left to XLA.  Written
    as ONE elementwise expression of the row ``[dh | dh | ..]`` (the
    stream's number chosen by the lane), which XLA fuses into the add of
    x's three gradients; written as a product broadcast to [T, n, C] and
    folded, it is computed in that shape's tiling, written, copied to the
    fold's and read again (PERF.md section 6, PR 65)."""
    n, c = pre.shape[1], dh.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n * c), 1)
    weight = pre[:, :1]
    for j in range(1, n):
        weight = jnp.where(lane >= j * c, pre[:, j:j + 1], weight)
    row = jnp.concatenate([dh] * n, axis=1).astype(_F32)
    return (weight * row).astype(dh.dtype)


_read.defvjp(_read_fwd, _read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _write(x, y, coeff, interpret):
    """``x`` [T, n C], ``y`` [T, C], ``coeff`` [T, n + n n] -> [T, n C]."""
    return _mix_call(
        _write_fwd_kernel, "stream_write_fwd", y.shape[1], [x, y, coeff],
        [_like(x.shape, x.dtype)], interpret)[0]


def _write_fwd(x, y, coeff, interpret):
    return _write(x, y, coeff, interpret), (x, y, coeff)


def _write_bwd(interpret, residuals, dout):
    x, y, coeff = residuals
    return tuple(_mix_call(
        _write_bwd_kernel, "stream_write_bwd", y.shape[1], [x, y, coeff, dout],
        [_like(x.shape, x.dtype), _like(y.shape, y.dtype),
         _like(coeff.shape, _F32)], interpret))


_write.defvjp(_write_fwd, _write_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _stats(x, phi, o, interpret):
    """``x`` [T, n C], ``phi`` [n C, lanes] (zeros from column ``o`` on)
    -> [T, lanes] float32: ``x phi``, the sum of squares in column ``o``."""
    t = x.shape[0]
    rows = _rows(t, _STATS_ROWS)
    return _call(
        _stats_fwd_kernel, "stream_stats_fwd", rows, [x, phi],
        [_like((t, phi.shape[1]), _F32)], interpret, whole=(1,),
        o=o, strip=min(_STRIP, rows), chunk=_chunk(x.shape[1]))[0]


def _stats_fwd(x, phi, o, interpret):
    return _stats(x, phi, o, interpret), (x, phi)


# ``stream_stats_bwd``'s arrays, operands then results, and which of them a
# grid step sees whole: ``phi^T`` and ``dphi^T``.
_STATS_BWD_WHOLE = (1, 4)


def _stats_bwd_arrays(t: int, width: int, dtype, o: int, lanes: int) -> list:
    held = -(-o // _TILE) * _TILE  # whole sublane tiles of dphi^T's rows
    return [_like((t, width), dtype), _like((lanes, width), dtype),
            _like((t, lanes), _F32),
            _like((t, width), dtype), _like((held, width), _F32)]


def _stats_bwd(o, interpret, residuals, dout):
    x, phi = residuals
    t, width = x.shape
    dx, dphi_t = _call(
        _stats_bwd_kernel, "stream_stats_bwd", _rows(t, _STATS_ROWS),
        [x, phi.T, dout],
        _stats_bwd_arrays(t, width, x.dtype, o, phi.shape[1])[3:],
        interpret, semantics="arbitrary", whole=_STATS_BWD_WHOLE, o=o,
        chunk=_chunk(width, 4 * _LANES))
    dphi = jnp.zeros(phi.shape, _F32).at[:, :o].set(dphi_t[:o].T)
    return dx, dphi.astype(phi.dtype)


_stats.defvjp(_stats_fwd, _stats_bwd)


# ---- the kernel forms on the trunk's arrays ----


def token_stats_kernel(
    x: jax.Array, phi: jax.Array, interpret: bool = False
) -> tuple:
    """:func:`token_stats` as ``stream_stats_fwd`` / ``stream_stats_bwd``
    for shapes :func:`stream_mix_fits` admits; ``interpret`` runs them on
    any backend.  ``phi`` is rounded to the stream's dtype and padded to
    whole lane tiles of columns here, one beyond its own for the sum of
    squares, so its gradient's way back is autodiff's."""
    b, s, n, c = x.shape
    o = phi.shape[1]
    padded = jnp.pad(phi.astype(x.dtype), ((0, 0), (0, _lanes(o + 1) - o)))
    out = _stats(x.reshape(b * s, n * c), padded, o, interpret)
    return (out[:, o].reshape(b, s) * (1.0 / (n * c)),
            out[:, :o].T.reshape(o, b, s))


def stream_read_kernel(
    x: jax.Array, pre: jax.Array, interpret: bool = False
) -> jax.Array:
    """:func:`stream_read` as ``stream_read_fwd`` / ``stream_read_bwd``."""
    b, s, n, c = x.shape
    return _read(
        x.reshape(b * s, n * c), pre.reshape(n, b * s).T, interpret,
    ).reshape(b, s, c)


def stream_write_kernel(
    x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """:func:`stream_write` as ``stream_write_fwd`` / ``stream_write_bwd``."""
    b, s, n, c = x.shape
    coeff = jnp.concatenate(
        [post.reshape(n, b * s), res.reshape(n * n, b * s)]).T
    return _write(
        x.reshape(b * s, n * c), y.reshape(b * s, c), coeff, interpret,
    ).reshape(x.shape)
