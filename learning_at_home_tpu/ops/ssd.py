"""The selective state-space recurrence of Mamba-2 (state-space duality,
arXiv:2405.21060) in its chunked, matmul-shaped form.

Per head, with the state ``h_t`` in ``R^{P x N}`` and ``h_{-1} = 0``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t

``x`` [B, S, H, P] are the heads' inputs, ``dt`` [B, S, H] the (positive)
step sizes, ``A`` [H] negative, ``B`` and ``C`` [B, S, G, N] the input and
output projections of ``G`` groups: head ``h`` reads group ``h // (H / G)``.

Nothing here walks the sequence a position at a time.  The sequence is cut
into chunks of ``Q`` positions.  With ``a = cumsum(dt A)`` inside a chunk
(float32, inclusive):

* inside a chunk, ``Y_intra = (L * (C B^T)) (dt x)`` with the
  lower-triangular decay ``L[i, j] = exp(a_i - a_j)`` (a difference of the
  cumulative sums, never a quotient of exponentials: ``exp(-a_j)``
  overflows where ``exp(a_i - a_j)`` is at most 1);
* a chunk's own state, ``sum_j exp(a_last - a_j) dt_j x_j B_j^T``;
* the chunk states carried from chunk to chunk by a scan over the ``S / Q``
  chunks (``h <- exp(a_last) h + state``), float32;
* ``Y_inter = exp(a_i) (C_i h_prev)``, what the state entering the chunk
  adds at position ``i``.

The products take operands of ``x``'s dtype (bf16 in a train step) and
accumulate in float32; every decay and the carried state are float32.

Two forms of it, one rule between them (:func:`ssd_chunked`,
:func:`kernel_fits`: a pure function of what the call can see, as
``trunk.attention_core`` chooses its kernel):

* :func:`ssd_chunked_kernel`, where the backend is ``tpu``, the decays
  float32 and the shapes fit the tiles (chunk, state and a group's
  channels multiples of 128): two Pallas kernels, ``ssd_chunk_fwd`` and,
  behind a ``jax.custom_vjp``, ``ssd_chunk_bwd``.  A grid row is one group
  of heads and walks its chunks in sequence, the group's state [N, hg P]
  float32 carried in VMEM (the backward walks them in reverse and carries
  the state's cotangent, seeded with the last state's).  ``L``, ``C B^T``,
  the scores, the weighed ``dt x`` and a chunk's own state live and die in
  VMEM; ``x``, ``B``, ``C``, ``dt``, the cumulative sums and ``y`` cross
  HBM once, and the states entering the chunks once more as the backward's
  residual.  The products that do not hold a head's decay inside them (``C
  h_prev``, ``B^T (weighed dt x)``) run for the group's heads at once.  It
  rounds where the plain form rounds.  Its output and residual carry the
  name :data:`SSD_RESIDUALS` for a remat policy to keep.
* :func:`ssd_chunked_plain`, everywhere else (the CPU, a ``decay_dtype``
  that is being probed, a shape the tiles refuse): plain ``jax.numpy``,
  every intermediate an array of its own, the backward autodiff's.  It is
  the kernel's reference in the tests.

The cumulative sums are XLA's in both, and their gradient back to ``dt``
and ``A`` autodiff's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The name the kernel's forward gives its output ``y`` and the float32
# states entering the chunks: the two arrays its backward kernel reads
# beside the inputs.  A ``jax.checkpoint`` whose policy saves this name
# keeps them, so the recompute holds no scan at all; outside a checkpoint
# the name is the identity.
SSD_RESIDUALS = "ssd_scan_residuals"
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a b^T: both contract their last axis
_TN = (((0,), (0,)), ((), ()))  # a^T b: both contract their first axis


def ssd_chunked(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    chunk: int, decay_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """``(y [B, S, H, P] in x's dtype, the state after the last position
    [B, H, P, N] float32)`` of the recurrence above, ``chunk`` positions at
    a time (``min(chunk, S)``; it must divide ``S``).  ``decay_dtype``:
    the dtype the decays' arithmetic runs in; float32 always, but for
    showing what a lower one reads (tools/smallthinker_probe.py).  The
    kernel where :func:`kernel_fits` says so, the plain form elsewhere."""
    if kernel_fits(x.shape, b.shape, chunk, jax.default_backend(), decay_dtype):
        return ssd_chunked_kernel(x, dt, a, b, c, chunk)
    return ssd_chunked_plain(x, dt, a, b, c, chunk, decay_dtype)


def kernel_fits(x_shape, b_shape, chunk, backend, decay_dtype=jnp.float32) -> bool:
    """Whether :func:`ssd_chunked_kernel` takes a call: a ``tpu`` backend
    (Mosaic lowering), float32 decays, and shapes its tiles divide: the
    chunk, the state and a group's channels multiples of the 128 lanes, a
    head a multiple of them or a divisor.  Two refusals are the
    compiler's (AOT compiles for a described v5e, PERF.md section 6, PR
    40): a step's float32 temporaries [Q, hg P] and [N, hg P] beyond 2^17
    elements pass the 16 MB of scoped VMEM, and a group of ONE head makes
    a [1, 1] decay that Mosaic cannot broadcast along both axes.  A pure
    function of what the call can see."""
    _, s, h, p = x_shape
    g, n = b_shape[2:]
    q = min(chunk, s)
    if s % q or h % g:
        return False
    hg = h // g
    return (
        backend == "tpu" and jnp.dtype(decay_dtype) == jnp.float32
        and q % _LANES == 0 and n % _LANES == 0 and (hg * p) % _LANES == 0
        and (p % _LANES == 0 or _LANES % p == 0)
        and 2 <= hg <= _LANES // 2 and max(q, n) * hg * p <= 2 ** 17
    )


def _chunk_sums(dt, a, nc, q, decay_dtype=jnp.float32):
    """The decays' exponents: cumulative sums of ``dt A`` inside each
    chunk, [B, nc, Q, H]."""
    bsz, _, h = dt.shape
    f32 = jnp.float32
    da = (dt.astype(f32) * a.astype(f32)).astype(decay_dtype)
    return jnp.cumsum(da.reshape(bsz, nc, q, h), axis=2)


def _sizes(x, b, chunk):
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    q = min(chunk, s)
    if s % q or h % g:
        raise ValueError(
            f"the chunked scan needs chunk={q} to divide S={s} and the "
            f"{g} groups the {h} heads"
        )
    return bsz, s, h, p, g, n, q


def ssd_chunked_plain(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    chunk: int, decay_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """:func:`ssd_chunked` in plain ``jax.numpy``, every intermediate an
    array of its own; its backward is autodiff's."""
    bsz, s, h, p, g, n, q = _sizes(x, b, chunk)
    nc, hg = s // q, h // g
    compute = x.dtype
    f32 = jnp.float32

    cum = _chunk_sums(dt, a, nc, q, decay_dtype)  # [B, nc, Q, H]
    last = cum[:, :, -1]  # [B, nc, H]
    i = jnp.arange(q)
    lower = (i[:, None] >= i[None, :])[None, None, None]  # [.., Q, Q]
    heads_first = cum.transpose(0, 1, 3, 2)  # [B, nc, H, Q]
    # exp of a masked difference: above the diagonal the difference is
    # positive and its exp may overflow before the mask would drop it
    decay = jnp.exp(jnp.where(
        lower, heads_first[..., :, None] - heads_first[..., None, :], -jnp.inf
    )).astype(f32)  # L [B, nc, H, Q, Q]
    to_end = jnp.exp(last[:, :, None] - cum).astype(f32)  # [B, nc, Q, H]
    from_start = jnp.exp(cum).astype(f32)  # [B, nc, Q, H]

    # by chunk, the heads by group: h = g * hg + j reads group g
    dtx = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(
        bsz, nc, q, g, hg, p)
    bc = b.reshape(bsz, nc, q, g, n)
    cc = c.reshape(bsz, nc, q, g, n)

    # inside the chunks: (L * (C B^T)) (dt x)
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc, preferred_element_type=f32)
    scores = (
        decay.reshape(bsz, nc, g, hg, q, q) * cb[:, :, :, None]
    ).astype(compute)
    y = jnp.einsum(
        "bcghij,bcjghp->bcighp", scores, dtx.astype(compute),
        preferred_element_type=f32,
    )

    # each chunk's own state, then the states carried over the chunks
    weighed = (dtx * to_end.reshape(bsz, nc, q, g, hg, 1)).astype(compute)
    states = jnp.einsum(
        "bcjgn,bcjghp->bcghpn", bc, weighed, preferred_element_type=f32)
    chunk_decay = jnp.exp(last).astype(f32).reshape(bsz, nc, g, hg, 1, 1)

    def carry_over(entering, chunk_of):
        decay_c, state_c = chunk_of
        return decay_c * entering + state_c, entering

    final, entering = jax.lax.scan(
        carry_over, jnp.zeros((bsz, g, hg, p, n), f32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, G, hg, P, N]

    # what the state entering a chunk adds at each of its positions
    y = y + jnp.einsum(
        "bcign,bcghpn->bcighp", cc, entering.astype(compute),
        preferred_element_type=f32,
    ) * from_start.reshape(bsz, nc, q, g, hg, 1)
    return (
        y.reshape(bsz, s, h, p).astype(compute),
        final.reshape(bsz, h, p, n),
    )


# ---- the kernel: one group of heads a grid row, the chunks in sequence ----
#
# A grid step holds one chunk of one group: ``x`` [Q, D] (D = the group's
# hg heads of P channels side by side), ``B`` and ``C`` [Q, N], the
# float32 ``dt`` and cumulative sums a head a ROW ([2 hg, Q]: dense in HBM,
# where [Q, hg] would be padded to the 128 lanes), and the group's state
# [N, D] float32 in a VMEM scratch that the next step of the row finds as
# this one left it.  What a chunk makes on the way (``L``, ``C B^T``, the
# scores, the weighed ``dt x``, its own state) never leaves VMEM.


def _columns(rows: jax.Array) -> jax.Array:
    """[R, Q] -> [Q, 128]: row r as lane r, by one transpose of whole
    tiles (the rows below R are zeros)."""
    r, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((_LANES - r, q), rows.dtype)], axis=0).T


def _head_tile(h: int, p: int) -> tuple:
    """Where head h's p channels lie in [.., hg * p]: ``(the first lane of
    the slice that holds them, its width, the first and last + 1 of its
    lanes that are the head's own)``.  The slice starts and ends on a
    128-lane tile's edge: a head narrower than a tile shares it with its
    neighbours, and nothing is cut or joined off an edge."""
    if p % _LANES == 0:
        return h * p, p, (0, p)
    per = _LANES // p
    return h // per * _LANES, _LANES, (h % per * p, (h % per + 1) * p)


def _tile(v: jax.Array, h: int, p: int) -> jax.Array:
    """The slice of ``v`` [R, hg * p] that holds head h."""
    first, width, _ = _head_tile(h, p)
    return v[:, first:first + width]


def _own(tile: jax.Array, h: int, p: int, other) -> jax.Array:
    """``tile`` (head h's slice of something) in the head's own lanes,
    ``other`` in its neighbours'."""
    _, width, (lo, hi) = _head_tile(h, p)
    if hi - lo == width:
        return tile
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where((lane >= lo) & (lane < hi), tile, other)


def _join(parts: list, p: int) -> jax.Array:
    """``parts[h]``, each the width of head h's slice and right in the
    head's own lanes -> [R, hg * p], every head's lanes from its part."""
    tiles: dict = {}
    for h, part in enumerate(parts):
        first = _head_tile(h, p)[0]
        tiles[first] = _own(part, h, p, tiles[first]) if first in tiles else part
    return jnp.concatenate([tiles[first] for first in sorted(tiles)], axis=1)


def _expand(cols: jax.Array, first: int, hg: int, p: int) -> jax.Array:
    """[R, 128] with head h's value in lane ``first + h`` -> [R, hg * p] a
    channel: head h's value under each of its p channels."""
    r = cols.shape[0]
    return _join([
        jnp.broadcast_to(
            cols[:, first + h:first + h + 1], (r, _head_tile(h, p)[1]))
        for h in range(hg)], p)


def _head_sums(v: jax.Array, first: int, hg: int, p: int) -> jax.Array:
    """[R, hg * p] a channel -> [R, 128]: the sum over head h's channels
    in lane ``first + h``, zeros in the other lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], _LANES), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for h in range(hg):
        total = jnp.sum(_own(_tile(v, h, p), h, p, 0.0), axis=1, keepdims=True)
        out = jnp.where(lane == first + h, total, out)
    return out


def _masked_decay(rows: jax.Array, cols: jax.Array, keep: jax.Array) -> jax.Array:
    """``exp(rows - cols)`` where ``keep``, 0 elsewhere: the mask BEFORE
    the exponential (the difference is positive on the other side and its
    exp may overflow before a mask would drop it)."""
    return jnp.exp(jnp.where(keep, rows - cols, -jnp.inf))


def _fwd_kernel(x_ref, heads_ref, b_ref, c_ref, y_ref, entering_ref, final_ref,
                state, *, hg, p):
    f32 = jnp.float32
    chunk_of_row = pl.program_id(1)

    @pl.when(chunk_of_row == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x, bm, cm = x_ref[...], b_ref[...], c_ref[...]
    compute = x.dtype
    rows = heads_ref[...]  # dt, then the cumulative sums: [2 hg, Q]
    cols = _columns(rows)  # lane h: dt of head h; lane hg + h: its sums
    q = x.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=f32)
    dtx = x.astype(f32) * _expand(cols, 0, hg, p)
    dtx_c = dtx.astype(compute)

    # inside the chunk: (L * (C B^T)) (dt x), a head at a time.  A head's
    # product is taken over its whole tile of ``dt x`` and read in its own
    # lanes: the MXU's columns are there either way, and no operand is cut
    parts = []
    for h in range(hg):
        decay = _masked_decay(
            cols[:, hg + h:hg + h + 1], rows[hg + h:hg + h + 1, :], i >= j)
        scores = (decay * cb).astype(compute)
        parts.append(jnp.dot(
            scores, _tile(dtx_c, h, p), preferred_element_type=f32))
    y = _join(parts, p)

    # what the state entering the chunk adds, the group's heads at once
    entering = state[...]
    entering_ref[...] = entering
    from_start = _expand(jnp.exp(cols), hg, hg, p)
    y = y + from_start * jnp.dot(
        cm, entering.astype(compute), preferred_element_type=f32)
    y_ref[...] = y.astype(compute)

    # the state leaving it: the chunk's decay is the last position's
    to_end = _expand(jnp.exp(cols[q - 1:q, :] - cols), hg, hg, p)
    leaving = from_start[q - 1:q, :] * entering + jax.lax.dot_general(
        bm, (dtx * to_end).astype(compute), _TN, preferred_element_type=f32)
    state[...] = leaving

    @pl.when(chunk_of_row == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = leaving


def _bwd_kernel(x_ref, heads_ref, b_ref, c_ref, entering_ref, dy_ref, dfinal_ref,
                dx_ref, dheads_ref, db_ref, dc_ref, dstate, *, hg, p):
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)  # the row's LAST chunk: they run in reverse
    def _():
        dstate[...] = dfinal_ref[...]

    x, bm, cm = x_ref[...], b_ref[...], c_ref[...]
    compute = x.dtype
    rows = heads_ref[...]
    cols = _columns(rows)
    q = x.shape[0]
    x32 = x.astype(f32)
    dy_c = dy_ref[...]
    dy = dy_c.astype(f32)
    dt_wide = _expand(cols, 0, hg, p)
    dtx = x32 * dt_wide
    to_end = _expand(jnp.exp(cols[q - 1:q, :] - cols), hg, hg, p)
    from_start = _expand(jnp.exp(cols), hg, hg, p)
    chunk_decay = from_start[q - 1:q, :]  # the last position's: [1, D]
    weighed = dtx * to_end
    entering = entering_ref[...]
    entering_c = entering.astype(compute)
    dleaving = dstate[...]
    dleaving_c = dleaving.astype(compute)

    # through the state leaving the chunk and the state entering it
    dinter = (dy * from_start).astype(compute)  # the cotangent of C h_prev
    dweighed = jnp.dot(bm, dleaving_c, preferred_element_type=f32)
    dc = jax.lax.dot_general(dinter, entering_c, _NT, preferred_element_type=f32)
    db = jax.lax.dot_general(
        weighed.astype(compute), dleaving_c, _NT, preferred_element_type=f32)
    dstate[...] = chunk_decay * dleaving + jax.lax.dot_general(
        cm, dinter, _TN, preferred_element_type=f32)

    # inside the chunk, transposed: rows are the positions j that are
    # read, columns the positions i that read them
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (2 * hg, q), 0)
    cbt = jax.lax.dot_general(bm, cm, _NT, preferred_element_type=f32)
    dcbt = jnp.zeros((q, q), f32)
    dcols = jnp.zeros((q, _LANES), f32)  # as ``cols``: lane h of dt, hg + h of the sums
    drows = jnp.zeros((2 * hg, q), f32)  # as ``rows``
    parts = []
    for h in range(hg):
        decay = _masked_decay(
            rows[hg + h:hg + h + 1, :], cols[:, hg + h:hg + h + 1], i >= j)
        scores = decay * cbt
        dy_h = _tile(dy_c, h, p)
        parts.append(jnp.dot(
            scores.astype(compute), dy_h, preferred_element_type=f32))
        # the head's own lanes of ``dt x`` alone meet ``dy`` here
        dscores = jax.lax.dot_general(
            _own(_tile(dtx, h, p), h, p, 0.0).astype(compute), dy_h, _NT,
            preferred_element_type=f32)
        dcbt = dcbt + decay * dscores
        # d/d(a_i - a_j) [j, i]: position i's sum raises what i reads,
        # position j's lowers what j hands on.  Both are sums of the same
        # entries, so what rounding does to one it does to the other
        through_decay = scores * dscores
        dcols = jnp.where(
            lane == hg + h,
            -jnp.sum(through_decay, axis=1, keepdims=True), dcols)
        drows = jnp.where(
            row == hg + h, jnp.sum(through_decay, axis=0, keepdims=True), drows)
    ddtx = _join(parts, p) + dweighed * to_end
    dx_ref[...] = (ddtx * dt_wide).astype(compute)
    dcbt = dcbt.astype(compute)
    db_ref[...] = (
        db + jnp.dot(dcbt, cm, preferred_element_type=f32)).astype(compute)
    dc_ref[...] = (dc + jax.lax.dot_general(
        dcbt, bm, _TN, preferred_element_type=f32)).astype(compute)

    # dt where it weighs x; the sums where they are not a difference
    # inside the chunk: position i's raises what it reads of the entering
    # state, position j's lowers what j hands to the leaving state, the
    # last one's raises the leaving state whole
    inter = jnp.dot(cm, entering_c, preferred_element_type=f32) * from_start
    dlast = _head_sums(
        jnp.sum(dweighed * weighed, axis=0, keepdims=True)
        + chunk_decay * jnp.sum(dleaving * entering, axis=0, keepdims=True),
        hg, hg, p)  # [1, 128]
    position = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 0)
    dcols = (
        dcols + _head_sums(ddtx * x32, 0, hg, p)
        + _head_sums(dy * inter - dweighed * weighed, hg, hg, p)
        + jnp.where(position == q - 1, dlast, 0.0))
    dheads_ref[...] = drows + dcols.T[:2 * hg, :]


def _layout(x, dt, b, q: int, g: int, order) -> tuple:
    """``(B, S, H, nc, N, hg, P)`` of a call and its block specs for grid
    row ``r = b * G + g`` and step ``c``, which holds chunk ``order(c)``:
    of ``x``'s kind [B, S, G * hg * P], of ``B``'s [B, S, G * N], of the
    heads' rows [B, G, 2 hg, S], of the entering states [B * G, nc, N, hg
    * P] and of one state a row."""
    bsz, s, hp = x.shape
    h = dt.shape[2]
    p, n, nc, hg = hp // h, b.shape[2] // g, s // q, h // g
    return (bsz, s, h, nc, n, hg, p), {
        "x": pl.BlockSpec((None, q, hg * p), lambda r, c: (r // g, order(c), r % g)),
        "bc": pl.BlockSpec((None, q, n), lambda r, c: (r // g, order(c), r % g)),
        "heads": pl.BlockSpec(
            (None, None, 2 * hg, q), lambda r, c: (r // g, r % g, 0, order(c))),
        "entering": pl.BlockSpec(
            (None, None, n, hg * p), lambda r, c: (r, order(c), 0, 0)),
        "state": pl.BlockSpec((None, n, hg * p), lambda r, c: (r, 0, 0)),
    }


def _head_rows(dt: jax.Array, cum: jax.Array, g: int) -> jax.Array:
    """Two [B, S, H] a head -> [B, G, 2 hg, S]: a group's heads a row
    each, ``dt``'s rows then ``cum``'s."""
    bsz, s, h = dt.shape
    both = jnp.stack([dt, cum], axis=2).reshape(bsz, s, 2, g, h // g)
    return both.transpose(0, 3, 2, 4, 1).reshape(bsz, g, 2 * h // g, s)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _forward(x, dt, cum, b, c, q, g, interpret):
    """``(y [B, S, H * P], the state entering each chunk [B * G, nc, N, hg
    * P] float32, the state after the last [B * G, N, hg * P])``."""
    (bsz, _, _, nc, n, hg, p), spec = _layout(x, dt, b, q, g, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hg=hg, p=p),
        grid=(bsz * g, nc),
        in_specs=[spec["x"], spec["heads"], spec["bc"], spec["bc"]],
        out_specs=[spec["x"], spec["entering"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz * g, nc, n, hg * p), jnp.float32),
            jax.ShapeDtypeStruct((bsz * g, n, hg * p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, hg * p), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_chunk_fwd",
    )(x, _head_rows(dt, cum, g), b, c)


def _backward(x, dt, cum, b, c, entering, dy, dfinal, q, g, interpret):
    """The gradients of ``x``, ``dt``, ``cum``, ``B`` and ``C``, the
    chunks in reverse, the state's cotangent carried as the forward
    carries the state."""
    nc = x.shape[1] // q
    (bsz, s, h, _, n, hg, p), spec = _layout(
        x, dt, b, q, g, lambda c: nc - 1 - c)
    dx, dheads, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, hg=hg, p=p),
        grid=(bsz * g, nc),
        in_specs=[spec["x"], spec["heads"], spec["bc"], spec["bc"],
                  spec["entering"], spec["x"], spec["state"]],
        out_specs=[spec["x"], spec["heads"], spec["bc"], spec["bc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, g, 2 * hg, s), jnp.float32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((n, hg * p), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_chunk_bwd",
    )(x, _head_rows(dt, cum, g), b, c, entering, dy, dfinal)
    # [B, G, 2 hg, S] -> two [B, S, H]
    dheads = dheads.reshape(bsz, g, 2, hg, s).transpose(2, 0, 4, 1, 3)
    ddt, dcum = dheads.reshape(2, bsz, s, h)
    return dx, ddt, dcum, db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, cum, b, c, q, g, interpret):
    y, _, final = _forward(x, dt, cum, b, c, q, g, interpret)
    return y, final


def _scan_fwd(x, dt, cum, b, c, q, g, interpret):
    y, entering, final = _forward(x, dt, cum, b, c, q, g, interpret)
    y = checkpoint_name(y, SSD_RESIDUALS)
    entering = checkpoint_name(entering, SSD_RESIDUALS)
    return (y, final), (x, dt, cum, b, c, entering)


def _scan_bwd(q, g, interpret, residuals, cotangents):
    return _backward(*residuals, *cotangents, q, g, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_chunked_kernel(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    chunk: int, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """:func:`ssd_chunked` as two Pallas TPU kernels (``ssd_chunk_fwd``,
    and ``ssd_chunk_bwd`` behind a ``jax.custom_vjp``) for shapes
    :func:`kernel_fits` admits; ``interpret`` runs them on any backend.
    The cumulative sums are made here, in XLA, as the plain form makes
    them, and their gradient back to ``dt`` and ``A`` is autodiff's."""
    bsz, s, h, p, g, n, q = _sizes(x, b, chunk)
    cum = _chunk_sums(dt, a, s // q, q).reshape(bsz, s, h)
    y, final = _scan(
        x.reshape(bsz, s, h * p), dt.astype(jnp.float32), cum,
        b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n), q, g, interpret)
    # [B * G, N, hg * P] -> [B, H, P, N]
    final = final.reshape(bsz, g, n, h // g, p).transpose(0, 1, 3, 4, 2)
    return y.reshape(bsz, s, h, p), final.reshape(bsz, h, p, n)
