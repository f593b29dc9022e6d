"""The recurrent mixers' last elementwise stage, the gate and the grouped
RMSNorm between the recurrence and the out-projection, ``[B, S, C] -> [B,
S, C]``, in the orders and under the gates the mixers are published with::

    gate_first:      out = rms_g(y * silu(z)) * scale        (Mamba-2)
    not gate_first:  out = rms_g(y) * scale * silu(z)        (Gated DeltaNet)
    not gate_first:  out = rms_g(y) * scale * sigmoid(a_g)   (Kimi Delta
                     Attention as Ling-3.0 gates it: ``gate="sigmoid"``, ONE
                     float32 number a group a token)

    rms_g(u) = u / sqrt(mean over the group's channels of u^2 + eps)

``y`` [B, S, C] is the recurrence's output, ``z`` the gate's C channels of
the in-projection's output from column ``first`` on, a group ``group``
consecutive channels (Mamba-2: ``C / n_groups``; Gated DeltaNet: a head's
values), ``scale`` one number a channel [C] or one a channel of a group
[group] shared by the groups.  With ``skip=(x, D)`` (Mamba-2's ``D x``: ``x``
[B, S, C] the recurrence's input, ``D`` [H] one number a head of ``C / H``
channels) ``y + D[h] x`` stands for ``y``.  The gate's SHAPE says its width:
``z`` [B, S, C / group] is one number a GROUP (a head-wise gate, a product of
its own, float32 both ways: neither it nor its gradient is rounded to ``y``'s
dtype), which stands after the norm, at column 0, without a skip
(:func:`gate_a_group`); the mixer names the squashing function (``gate``:
``"silu"`` or ``"sigmoid"``) as it names the order.  The arithmetic is float32
between inputs and an output of ``y``'s dtype (bf16 in a train step): every
element is widened before the first multiply, the mean is float32 over the
group, ``eps`` stands inside the root, one rounding at the end; the
gradients of ``scale`` and ``D`` are float32 sums over all the rows.

Two forms of it, one rule between them (:func:`gated_rms_norm`,
:func:`gate_norm_fits`: a pure function of what the call can see, as
``ops.ssm_conv.causal_conv_silu`` chooses its kernel):

* :func:`gated_rms_norm_kernel`, where the backend is ``tpu`` and the shapes
  fit the tiles: ONE pass forward (``gate_norm_fwd``) and, behind a
  ``jax.custom_vjp``, ONE pass backward (``gate_norm_bwd``).  A grid step
  holds a block of rows of a block of channels that holds whole groups;
  ``y``, ``z`` (out of the wider array, so no slice is ever written) and
  ``x`` come from HBM once in their dtype, and the widened copies, the
  skip, the SiLU, the squares, the groups' means and roots and the scale
  live and die in vector registers, a strip of rows at a time.  The
  backward reads them and the cotangent once, makes the row statistics
  again (nothing float32 is kept as a residual), writes ``dy``, ``dz`` and
  the skip's ``dx`` once each, rounded once from float32, and leaves
  ``dscale`` and ``dD`` as float32 partial sums a row block (eight a
  channel, a sublane tile), added outside.  Under a gate a group a block
  holds ALL the channels of fewer rows beside the rows' whole gate [rows, C
  / group], a step walks it a group at a time, a group's root times its
  gate is one column before the one broadcast the norm makes anyway, and the
  backward's one group sum serves ``dy`` and the gate's gradient.
* :func:`gated_rms_norm_plain`, everywhere else (the CPU, a shape the
  tiles refuse): plain ``jax.numpy``, the mixers' arithmetic of before this
  module, the backward autodiff's.  It is the kernel's reference in the
  tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learning_at_home_tpu.ops.ssm_conv import _LANES, _SUBLANES

_TILE = 16  # the rows of a 16-bit dtype's sublane tile: a block's are whole ones
# A grid step's block: rows x channels (rows ``min(.., S)``; the channel
# block the largest multiple of whole groups and whole lane tiles that
# divides C up to this: 512 for Mamba-2's groups of 512, 384 for two
# heads of 192), and the rows of a strip, what a step works on at a time
# (``min(.., rows)``).  The fastest of the probe's at
# [1, 16384, 4096] and [1, 16384, 5760] bf16 on a TPU v5e (PERF.md section
# 6, PR 47; tools/smallthinker_probe.py gate_norm).
_ROWS, _CHANNELS, _STRIP = 512, 512, 64
# Under a gate a group: the rows of a block of ALL the channels (up to
# ``_GROUP_CHANNELS``: a wider call is the plain form's), walked whole.  Of
# the probe's at [1, 16384, 4096] bf16 under a gate [1, 16384, 32] float32
# the smallest within 4 % of the fastest (PERF.md section 6, PR 67).
_GROUP_ROWS, _GROUP_CHANNELS = 256, 4096
_GATES = ("silu", "sigmoid")


def gate_a_group(y_shape, z_shape, group: int) -> bool:
    """Whether ``z`` is one number a GROUP of ``y``'s channels (``[B, S, C
    / group]``) and not one a channel (``[B, S, >= first + C]``): the
    gate's shape says its width."""
    return z_shape[-1] == y_shape[-1] // group != y_shape[-1]


def gated_rms_norm(
    y: jax.Array, z: jax.Array, scale: jax.Array, group: int, eps: float,
    gate_first: bool, first: int = 0, skip: tuple | None = None,
    gate: str = "silu",
) -> jax.Array:
    """The gated grouped RMSNorm as above, in ``y``'s dtype, of ``y`` [B,
    S, C] under the gate in the ``C`` channels of ``z`` [B, S, >= first +
    C] that start at ``first`` (a projection's output, read where the
    projection left it), or under ``z`` [B, S, C / group], one number a
    group.  ``gate`` names the gate's squashing function.  The kernel where
    :func:`gate_norm_fits` says so, the plain form (on the slice)
    elsewhere."""
    a_group = gate_a_group(y.shape, z.shape, group)
    if gate not in _GATES:
        raise ValueError(f"gate={gate!r}: one of {_GATES}")
    if a_group and (gate_first or first or skip is not None):
        raise ValueError(
            "a gate a group stands after the norm, at column 0, without a "
            f"skip (gate_first={gate_first}, first={first}, skip={skip is not None})")
    if gate_norm_fits(y.shape, group, jax.default_backend(), first, a_group):
        return gated_rms_norm_kernel(
            y, z, scale, group, eps, gate_first, first, skip, gate)
    if not a_group:
        z = z[..., first:first + y.shape[-1]]
    return gated_rms_norm_plain(y, z, scale, group, eps, gate_first, skip, gate)


def gate_norm_fits(
    shape, group: int, backend: str, first: int = 0, a_group: bool = False,
) -> bool:
    """Whether :func:`gated_rms_norm_kernel` takes a call over ``shape`` =
    [B, S, C] (the channels normalized, whose gate starts at ``first`` of
    the array it lies in): a ``tpu`` backend (Mosaic lowering), channels a
    multiple of the 128 lanes, a channel block of whole groups and whole
    lane tiles that divides C, on whose edge the gate starts, and a length
    its row block divides (the block a multiple of a 16-bit sublane tile
    and of its strips).  Under a gate a group (``a_group``,
    :func:`gate_a_group`) the block is all the channels, walked whole:
    groups of whole lane tiles (a group is then an aligned slice of the
    block) and no more than 4,096 channels (a block of 256 rows is 2 MB in
    bf16).  A pure function of what the call can see."""
    _, s, c = shape
    rows, channels = _blocks(shape, group, a_group)
    return (
        backend == "tpu" and c % _LANES == 0 and channels is not None
        and first % channels == 0 and s % rows == 0 and rows % _TILE == 0
        and (a_group or rows % _strip(rows) == 0)
    )


def _squashed(z: jax.Array, gate: str) -> jax.Array:
    return jax.nn.silu(z) if gate == "silu" else jax.nn.sigmoid(z)


def gated_rms_norm_plain(
    y: jax.Array, z: jax.Array, scale: jax.Array, group: int, eps: float,
    gate_first: bool, skip: tuple | None = None, gate: str = "silu",
) -> jax.Array:
    """:func:`gated_rms_norm` in plain ``jax.numpy`` on ``z`` [B, S, C], the
    gate's own channels, or [B, S, C / group], one number a group: what
    ``trunk.ssm_mixer``, ``trunk.delta_mixer`` and (the gate a group under
    a sigmoid) ``trunk.channel_delta_mixer`` computed before this module
    held them, operation for operation; its backward is autodiff's."""
    f32 = jnp.float32
    b, s, c = y.shape
    out_dtype = y.dtype
    a_group = gate_a_group(y.shape, z.shape, group)
    y = y.astype(f32)
    if skip is not None:
        x, d = skip
        heads = d.shape[0]
        y = (y.reshape(b, s, heads, c // heads) + d.astype(f32)[:, None]
             * x.astype(f32).reshape(b, s, heads, c // heads)).reshape(b, s, c)
    gate = _squashed(z.astype(f32), gate)
    if gate_first:
        y = y * gate
    grouped = y.reshape(b, s, c // group, group)
    ms = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = grouped * jax.lax.rsqrt(ms + eps)
    if scale.shape[0] == c:  # one scale a channel
        y = normed.reshape(b, s, c) * scale
    else:  # one a channel of a group, shared by the groups
        y = (normed * scale).reshape(b, s, c)
    if a_group:
        y = (y.reshape(grouped.shape) * gate[..., None]).reshape(b, s, c)
    elif not gate_first:
        y = y * gate
    return y.astype(out_dtype)


# ---- the kernel: a block of rows of a block of channels a grid step ----
#
# A step walks its block a STRIP of rows at a time, so that everything a
# strip makes stays in vector registers (ops/ssm_conv.py found the whole
# block as one value half as fast again): aligned loads of the strip in the
# operands' dtype, the widening, the skip, the SiLU, the groups' sums of
# squares along the lanes, the root, the scale, one store.  A group that
# fills lane tiles (512 = 4) is summed over its own aligned slice; groups
# that do not (192 = 1.5: two to a block of 384) are summed under a mask
# of the block's lanes, a pass a group.


def _lanes_of(shape, group: int, k: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= k * group) & (lane < (k + 1) * group)


def _over_groups(v: jax.Array, group: int, of_mean=None) -> jax.Array:
    """At every lane ``of_mean`` (the identity where None) of the mean of
    ``v`` [n, channels] over the lanes of its group; ``of_mean`` runs on a
    column [n, 1] a group, never on the block.  One group: the column
    itself, which broadcasts."""
    n, channels = v.shape

    def column(part):
        mean = jnp.sum(part, axis=-1, keepdims=True) * (1.0 / group)
        return mean if of_mean is None else of_mean(mean)

    if channels == group:
        return column(v)
    if group % _LANES == 0:
        return jnp.concatenate([
            jnp.broadcast_to(column(v[:, k:k + group]), (n, group))
            for k in range(0, channels, group)], axis=1)
    out = None
    for k in reversed(range(channels // group)):
        own = _lanes_of(v.shape, group, k)
        mine = column(jnp.where(own, v, 0.0))
        out = jnp.broadcast_to(mine, v.shape) if out is None else jnp.where(own, mine, out)
    return out


def _strip(rows: int) -> int:
    return min(_STRIP, rows)


def _gate_and_slope(z, gate: str):
    """``(g(z), g'(z))`` of the gate's squashing function."""
    sig = jax.nn.sigmoid(z)
    if gate == "sigmoid":
        return sig, sig * (1.0 - sig)
    return z * sig, sig * (1.0 + z * (1.0 - sig))


def _strip_of(ref, at, strip):
    return ref[pl.ds(at, strip), :].astype(jnp.float32)


def _fwd_kernel(*refs, group, eps, gate_first, skip, gate):
    if skip:
        y_ref, z_ref, scale_ref, x_ref, d_ref, out_ref = refs
    else:
        y_ref, z_ref, scale_ref, out_ref = refs
    rows, strip = y_ref.shape[0], _strip(y_ref.shape[0])

    def a_strip(i, carry):
        at = pl.multiple_of(i * strip, strip)
        y = _strip_of(y_ref, at, strip)
        if skip:
            y = y + d_ref[...] * _strip_of(x_ref, at, strip)
        g = _squashed(_strip_of(z_ref, at, strip), gate)
        if gate_first:
            y = y * g
        out = y * _over_groups(
            y * y, group, lambda ms: jax.lax.rsqrt(ms + eps)) * scale_ref[...]
        if not gate_first:
            out = out * g
        out_ref[pl.ds(at, strip), :] = out.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // strip, a_strip, 0)


def _bwd_kernel(*refs, group, eps, gate_first, skip, gate):
    """With ``u`` what the norm reads (``y g`` or ``y``; ``y`` with the skip
    in it), ``r = rsqrt(mean_g(u^2) + eps)``, ``n = u r`` and ``t`` the
    cotangent of ``n`` (``dout scale``, times the gate where it follows):
    ``du = r (t - n mean_g(t n))``."""
    if skip:
        (y_ref, z_ref, scale_ref, x_ref, d_ref, dout_ref,
         dy_ref, dz_ref, dx_ref, sums_ref) = refs
    else:
        y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref, sums_ref = refs
    rows, strip = y_ref.shape[0], _strip(y_ref.shape[0])

    def a_strip(i, sums):
        at = pl.multiple_of(i * strip, strip)
        y = _strip_of(y_ref, at, strip)
        if skip:
            x = _strip_of(x_ref, at, strip)
            y = y + d_ref[...] * x
        z = _strip_of(z_ref, at, strip)
        dout = _strip_of(dout_ref, at, strip)
        g, slope = _gate_and_slope(z, gate)
        u = y * g if gate_first else y
        r = _over_groups(u * u, group, lambda ms: jax.lax.rsqrt(ms + eps))
        n = u * r
        t = dout * scale_ref[...]
        if gate_first:
            dscale = dout * n
        else:
            dscale = dout * n * g
            dz = t * n * slope
            t = t * g
        du = r * (t - n * _over_groups(t * n, group))
        if gate_first:
            dy, dz = du * g, du * y * slope
        else:
            dy = du
        dy_ref[pl.ds(at, strip), :] = dy.astype(dy_ref.dtype)
        dz_ref[pl.ds(at, strip), :] = dz.astype(dz_ref.dtype)
        added = (sums[0] + _tiles_added(dscale),)
        if skip:
            dx_ref[pl.ds(at, strip), :] = (dy * d_ref[...]).astype(dx_ref.dtype)
            added += (sums[1] + _tiles_added(dy * x),)
        return added

    nothing = jnp.zeros((_SUBLANES, y_ref.shape[1]), jnp.float32)
    sums = jax.lax.fori_loop(
        0, rows // strip, a_strip, (nothing,) * (2 if skip else 1))
    for j, total in enumerate(sums):
        sums_ref[j * _SUBLANES:(j + 1) * _SUBLANES, :] = total


def _tiles_added(v):  # [n, c] -> [8, c]: the rows added tile on tile
    return jnp.sum(v.reshape(v.shape[0] // _SUBLANES, _SUBLANES, -1), axis=0)


# Under a gate a group a block is [rows, C] beside the rows' gate [rows, C /
# group] (the array's whole last dimension: group k's number is column k,
# whatever the grid step), and a step walks its block a GROUP at a time, all
# the block's rows at once: the group's aligned slice comes out of the refs,
# and a group's root times its gate is a column before the broadcast the norm
# makes anyway.  A group's chain (load, lane sum, root, product, store; two
# sums backward) is as long for 16 rows as for 256, and the compiler overlaps
# little of one group's with the next's: strips of 64 rows read 0.79 | 1.35
# ms forward | backward where the whole block of 256 reads 0.43 | 0.64
# (PERF.md section 6, PR 67).


def _group_columns(a, k: int, group: int):
    """Column k of ``a`` [rows, groups] under each of a group's lanes."""
    return jnp.broadcast_to(a[:, k:k + 1], (a.shape[0], group))


def _group_root(y, group: int, eps: float):
    return jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) * (1.0 / group) + eps)


def _fwd_group_kernel(y_ref, z_ref, scale_ref, out_ref, *, group, eps, gate):
    g = _squashed(z_ref[...].astype(jnp.float32), gate)
    for k in range(y_ref.shape[1] // group):
        lanes = slice(k * group, (k + 1) * group)
        y = y_ref[:, lanes].astype(jnp.float32)
        out = y * (_group_root(y, group, eps) * _group_columns(g, k, group))
        out_ref[:, lanes] = (out * scale_ref[:, lanes]).astype(out_ref.dtype)


def _bwd_group_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref, sums_ref,
                      *, group, eps, gate):
    """With ``r`` and ``n = y r`` as above, ``t = dout scale``, ``g`` the
    group's gate and ``m = mean_g(t n)``: ``dy = r g (t - n m)``, ``dz =
    group m g'`` (the sum over the group's channels of ``t n`` times the
    slope), ``dscale = dout n g``: the ONE group sum serves both."""
    g, slope = _gate_and_slope(z_ref[...].astype(jnp.float32), gate)
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    dz = jnp.zeros_like(g)
    for k in range(y_ref.shape[1] // group):
        lanes = slice(k * group, (k + 1) * group)
        y = y_ref[:, lanes].astype(jnp.float32)
        dout = dout_ref[:, lanes].astype(jnp.float32)
        r = _group_root(y, group, eps)
        n = y * r
        t = dout * scale_ref[:, lanes]
        m = jnp.sum(t * n, axis=-1, keepdims=True) * (1.0 / group)
        rg = r * _group_columns(g, k, group)
        dy_ref[:, lanes] = (rg * (t - n * m)).astype(dy_ref.dtype)
        dz = jnp.where(lane == k, group * m, dz)
        sums_ref[:, lanes] = _tiles_added(dout * (y * rg))
    dz_ref[...] = (dz * slope).astype(dz_ref.dtype)


def _blocks(shape, group: int, a_group: bool = False):
    """``(rows, channels)`` of a grid step's block for a call's shape:
    ``channels`` None where no block of whole groups and whole lane tiles
    up to the widest divides C, or, under a gate a group, where the groups
    are no whole lane tiles or C is wider than the one block it has to
    be."""
    _, s, c = shape
    if a_group:
        whole = group % _LANES == 0 and c <= _GROUP_CHANNELS
        return min(_GROUP_ROWS, s), c if whole else None
    unit = math.lcm(group, _LANES)
    fitting = [n for n in range(unit, min(_CHANNELS, c) + 1, unit) if c % n == 0]
    return min(_ROWS, s), max(fitting, default=None)


def _specs(shape, group, first, a_group=False):
    """The grid ``(B, channel blocks, row blocks)`` of a call over
    ``shape`` = [B, S, C] and its block specs: a block of rows of the C,
    the same of an array whose channels ``first ..`` are the C (``z``; under
    a gate a group the rows' whole ``[rows, C / group]``), a per-channel row
    vector [1, channels], and the partial sums [n, channels] a row block
    leaves."""
    bsz, s, c = shape
    rows, channels = _blocks(shape, group, a_group)
    skip = first // channels
    return (bsz, c // channels, s // rows), {
        "rows": pl.BlockSpec((None, rows, channels), lambda b, ch, r: (b, r, ch)),
        "z_rows": pl.BlockSpec(
            (None, rows, c // group), lambda b, ch, r: (b, r, 0)) if a_group
        else pl.BlockSpec(
            (None, rows, channels), lambda b, ch, r: (b, r, skip + ch)),
        "channel": pl.BlockSpec((1, channels), lambda b, ch, r: (0, ch)),
        "sums": lambda n: pl.BlockSpec(
            (None, None, n, channels), lambda b, ch, r: (b, r, 0, ch)),
    }


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))
# a block of all the channels is 2 MB in bf16 and 4 in float32, and the
# backward holds three of them twice over: more than the 16 MB a kernel gets
# unasked
_GROUP_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=64 * 2 ** 20)


def _of_skip(spec, skip):
    """``(specs, operands)`` of the skip's ``x`` and ``D`` a channel; none
    without it."""
    if skip is None:
        return [], []
    return [spec["rows"], spec["channel"]], [skip[0], skip[1][None, :]]


def _body(forward: bool, a_group: bool, group, eps, gate_first, skip, gate):
    if a_group:
        return functools.partial(
            _fwd_group_kernel if forward else _bwd_group_kernel,
            group=group, eps=eps, gate=gate)
    return functools.partial(
        _fwd_kernel if forward else _bwd_kernel, group=group, eps=eps,
        gate_first=gate_first, skip=skip is not None, gate=gate)


def _forward(y, z, scale, skip, group, eps, gate_first, first, gate, interpret):
    a_group = gate_a_group(y.shape, z.shape, group)
    grid, spec = _specs(y.shape, group, first, a_group)
    extra = _of_skip(spec, skip)
    return pl.pallas_call(
        _body(True, a_group, group, eps, gate_first, skip, gate),
        grid=grid,
        in_specs=[spec["rows"], spec["z_rows"], spec["channel"], *extra[0]],
        out_specs=spec["rows"],
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_GROUP_PARAMS if a_group else _PARAMS,
        interpret=interpret, name="gate_norm_fwd",
    )(y, z, scale[None, :], *extra[1])


def _backward(y, z, scale, skip, dout, group, eps, gate_first, first, gate,
              interpret):
    """``(dy in y's dtype, dz in z's ([B, S, C], or the gate a group's [B,
    S, C / group]), dscale [C] float32, and with the skip (dx in x's dtype,
    dD [C] float32 a channel))``."""
    bsz, s, c = y.shape
    a_group = gate_a_group(y.shape, z.shape, group)
    grid, spec = _specs(y.shape, group, first, a_group)
    extra = _of_skip(spec, skip)
    n = (2 if skip else 1) * _SUBLANES
    like = [jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct(z.shape if a_group else y.shape, z.dtype)]
    if skip:
        like.append(jax.ShapeDtypeStruct(y.shape, skip[0].dtype))
    *grads, sums = pl.pallas_call(
        _body(False, a_group, group, eps, gate_first, skip, gate),
        grid=grid,
        in_specs=[spec["rows"], spec["z_rows"], spec["channel"], *extra[0],
                  spec["rows"]],
        out_specs=[spec["rows"], spec["z_rows" if a_group else "rows"],
                   *[spec["rows"]] * (len(like) - 2), spec["sums"](n)],
        out_shape=[*like, jax.ShapeDtypeStruct((bsz, grid[2], n, c), jnp.float32)],
        compiler_params=_GROUP_PARAMS if a_group else _PARAMS,
        interpret=interpret, name="gate_norm_bwd",
    )(y, z, scale[None, :], *extra[1], dout)
    sums = jnp.sum(sums.reshape(bsz, grid[2], n // _SUBLANES, _SUBLANES, c),
                   axis=(0, 1, 3))
    return (*grads[:2], sums[0], *((grads[2], sums[1]) if skip else ()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _norm(y, z, scale, skip, group, eps, gate_first, first, gate, interpret):
    return _forward(y, z, scale, skip, group, eps, gate_first, first, gate, interpret)


def _norm_fwd(y, z, scale, skip, group, eps, gate_first, first, gate, interpret):
    out = _forward(y, z, scale, skip, group, eps, gate_first, first, gate, interpret)
    return out, (y, z, scale, skip)


def _norm_bwd(group, eps, gate_first, first, gate, interpret, residuals, dout):
    y, z, _, skip = residuals
    dy, dz, dscale, *of_skip = _backward(
        *residuals, dout, group, eps, gate_first, first, gate, interpret)
    if not gate_a_group(y.shape, z.shape, group):
        # the channels beside the C read nothing here: zeros, as a slice's
        # transpose gives them
        after = z.shape[2] - first - y.shape[2]
        dz = jnp.pad(dz, ((0, 0), (0, 0), (first, after)))
    return dy, dz, dscale, (tuple(of_skip) if skip else None)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_rms_norm_kernel(
    y: jax.Array, z: jax.Array, scale: jax.Array, group: int, eps: float,
    gate_first: bool, first: int = 0, skip: tuple | None = None,
    gate: str = "silu", interpret: bool = False,
) -> jax.Array:
    """:func:`gated_rms_norm` as two Pallas TPU kernels (``gate_norm_fwd``,
    and ``gate_norm_bwd`` behind a ``jax.custom_vjp``) for shapes
    :func:`gate_norm_fits` admits; ``interpret`` runs them on any backend.
    ``scale`` and ``D`` are widened and laid out a channel here ([C]: a
    shared scale tiled over the groups, ``D`` repeated over a head's
    channels), so their gradients' way back to the parameters' shape and
    dtype is autodiff's."""
    f32 = jnp.float32
    c = y.shape[-1]
    scale = jnp.tile(scale.astype(f32), c // scale.shape[0])
    if skip is not None:
        x, d = skip
        skip = (x, jnp.repeat(d.astype(f32), c // d.shape[0]))
    return _norm(y, z, scale, skip, group, float(eps), gate_first, first, gate,
                 interpret)
