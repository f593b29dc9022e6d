"""Softmax attention under a SHORT causal window, ``i - window < j <= i``,
as the pod step's window layers call it: q [B, S, H, hd], k and v [B, S,
Hkv, hd] (query head h reads key/value head ``h // (H / Hkv)``) -> [B, S,
H, hd].

Why a kernel of its own (PERF.md section 6, PR 63): the blocked kernel
(splash attention, ``trunk.flash_block_sizes``) visits whole blocks, 512 x
1,024 elements a query block under a window of 128 where the mask admits
512 x 128, a grid step a block, and carries the online softmax's running
maximum, rescale and accumulator from step to step for a band that ends
inside one block.  Here a grid step ``(batch row, key/value head, block of
512 positions)`` holds the ``g = H / Hkv`` query heads' block, that head's
own key and value block and the ``halo`` positions before it (the window's
reach back, rounded up to a power of two of 128-lane tiles; the first
block's halo is read clamped and masked), and walks the block in
sub-blocks of 128 queries against the ``halo + 128`` keys they can see:
the scores of one head's sub-block as ``[keys, queries]`` float32 from
bf16 operands, so the row statistics are lane-dense rows ``[1, queries]``
and every reduction over the keys runs down the sublanes; the mask from
two iotas; the softmax WHOLE in that step (one maximum, one exponential,
one sum: nothing carried, nothing rescaled); the probabilities, normalised
in float32 and rounded to the values' dtype, under the values.  K and V
are read once a key/value head.  ``1 / sqrt(hd)`` is on the keys (once a
grid step, where the blocked kernel's caller puts it on the queries): bf16
operands, float32 scores, sums and accumulators, every key of the window.

**Positions last.**  The kernels read and write ``[B, heads * hd, S]``: a
head is 128 sublane rows, a position a lane.  That is the layout XLA gives
the step's projections, per-head norms and rotations on the chip (``S``
minor), so the transposes around the calls are no instruction, where a
kernel on ``[B, S, heads * hd]`` or on heads first costs a whole-array
relayout copy of ``q`` forward, of ``q`` and ``dq`` backward (three a
window layer in the compiled step, about 1.2 ms each; PERF.md section 6,
PR 63).  It also leaves the kernels no transpose of a score-sized value:
``s^T = K q^T``, ``o^T = V^T p^T`` and ``dq^T = K^T ds^T`` are plain
products of what is there, ``dv = p^T do`` and ``dk = ds^T q`` products
with a transposed right side (the MXU's own), and only the step's keys and
values (and the key block's two gradients, once) are transposed.

The backward is ONE kernel over the same band (``band_attention_bwd``): a
step recomputes a sub-block's probabilities from the saved logsumexp,
``dp = v do^T``, ``delta = sum_j p dp`` (the row is whole in the step, so
``sum(o * do)`` is never read), ``ds = p (dp - delta)``; ``dq`` of the
block is whole in its step; ``dv`` and ``dk`` add the g heads into the
step's ``halo + block`` key rows, and the rows a step shares with the
block before it go out one step LATE: step i writes key block i - 1 (its
own sums of step i - 1, kept in VMEM, plus this step's halo rows), and one
step more than there are blocks writes the last.  No array of partials,
nothing whose size grows with ``S / block``.

:func:`band_attention` is both behind a ``jax.custom_vjp`` whose forward
names the output and the logsumexp ``residuals`` (``trunk.FLASH_RESIDUALS``
from ``attention_core``), so a checkpoint policy that saves the name holds
no second forward call.  :func:`band_kernel_fits` is the rule: a pure
function of what the call can see.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learning_at_home_tpu.ops.ssm_conv import _LANES

# A grid step's block of positions and the queries of a sub-block, whose
# scores are one value.  Blocks at [1, 16384, 64 over 8, 128] bf16 under a
# window of 128 on a TPU v5e (PERF.md section 6, PR 63; ms forward | forward
# + backward): 256 1.29 | 5.16, 512 1.11 | 4.89, 1024 1.04 | the backward
# refused (VMEM).  A sub-block of 128 computes ``halo + 128`` keys a query:
# twice what a window of 128 admits, 1.25 times at 512.
_BLOCK, _SUB = 512, 128
# What the kernels were run at, under ``interpret`` and on the chip: the
# rule takes nothing else.
_HEAD, _GROUPS, _MAX_WINDOW = 128, (1, 4, 8), 512
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)  # a score the mask refuses
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def band_kernel_fits(
    shape: tuple, kv_heads: int, window: int | None, backend: str
) -> bool:
    """Whether :func:`band_attention` takes a call whose q is ``shape`` =
    [B, S, H, hd] over ``kv_heads`` key/value heads under ``window``: a
    ``tpu`` backend (Mosaic lowering), a window of 1 to 512 keys (a halo
    of one query block at most), heads of 128, a group ``H / kv_heads`` it
    was run at, a length its block divides.  A pure function of what the
    call can see."""
    _, s, h, hd = shape
    return (
        backend == "tpu" and window is not None and 1 <= window <= _MAX_WINDOW
        and hd == _HEAD and h % kv_heads == 0 and h // kv_heads in _GROUPS
        and s % _BLOCK == 0
    )


def _halo(window: int) -> int:
    """The rows before a query block that its first query can see, as
    128-lane tiles that divide the block: 128, 256 or 512."""
    tiles = max(-(-(window - 1) // _LANES), 1)
    return _LANES * (1 << (tiles - 1).bit_length())


def _rows_of(columns, dtype):
    """``columns`` [hd, n] as rows [n, hd] of ``dtype``: the transpose is
    float32's."""
    return columns.astype(jnp.float32).T.astype(dtype)


def _band(span: int, halo: int, window: int):
    """``(key, admitted)`` [span, _SUB]: the key's row of a sub-block's
    transposed scores, and whether query column r sees key row c wherever
    the sub-block lies: key ``c - halo`` of the sub-block's own count
    against query r."""
    key = jax.lax.broadcasted_iota(jnp.int32, (span, _SUB), 0)
    ahead = key - halo - jax.lax.broadcasted_iota(jnp.int32, (span, _SUB), 1)
    return key, (ahead <= 0) & (ahead > -window)


def _fwd_kernel(q_ref, k_ref, k_halo_ref, v_ref, v_halo_ref, o_ref, lse_ref,
                keys_of, values_t, *, window, halo, scale):
    f32 = jnp.float32
    hd, block = k_ref.shape
    groups, span = q_ref.shape[0] // hd, halo + _SUB
    # the step's keys as rows, scaled, and its values as they come: the
    # halo's positions then the block's
    keys_of[:halo, :] = _rows_of(k_halo_ref[...].astype(f32) * scale, keys_of.dtype)
    keys_of[halo:, :] = _rows_of(k_ref[...].astype(f32) * scale, keys_of.dtype)
    values_t[:, :halo] = v_halo_ref[...]
    values_t[:, halo:] = v_ref[...]
    key, band = _band(span, halo, window)
    first = pl.program_id(2) * block  # the block's first position
    for at in range(0, block, _SUB):
        here = slice(at, at + _SUB)
        # position 0 is key row ``halo - first - at``: the rows before it
        # are the first block's clamped halo
        admitted = band & (key >= halo - first - at)
        keys, values = keys_of[at:at + span, :], values_t[:, at:at + span]
        for h in range(groups):
            head = slice(h * hd, (h + 1) * hd)
            scores = jnp.dot(keys, q_ref[head, here], preferred_element_type=f32)
            scores = jnp.where(admitted, scores, _MASKED)
            top = jnp.max(scores, axis=0, keepdims=True)
            p = jnp.exp(scores - top)
            total = jnp.sum(p, axis=0, keepdims=True)
            lse_ref[h:h + 1, here] = top + jnp.log(total)
            p = (p * (1.0 / total)).astype(values.dtype)
            o_ref[head, here] = jnp.dot(
                values, p, preferred_element_type=f32).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, k_halo_ref, v_ref, v_halo_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, keys_of, keys_t, values_of, dk_acc,
                dv_acc, dk_own, dv_own, *, window, halo, scale):
    f32 = jnp.float32
    hd, block = k_ref.shape
    groups, span = q_ref.shape[0] // hd, halo + _SUB
    step, blocks = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(step < blocks)
    def _():
        for at, k, v in ((slice(0, halo), k_halo_ref, v_halo_ref),
                         (slice(halo, None), k_ref, v_ref)):
            scaled = k[...].astype(f32) * scale
            keys_of[at, :] = _rows_of(scaled, keys_of.dtype)
            keys_t[:, at] = scaled.astype(keys_t.dtype)
            values_of[at, :] = _rows_of(v[...], values_of.dtype)
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        key, band = _band(span, halo, window)
        first = step * block
        for at in range(0, block, _SUB):
            here = slice(at, at + _SUB)
            admitted = band & (key >= halo - first - at)
            keys, values = keys_of[at:at + span, :], values_of[at:at + span, :]
            dk = dv = jnp.zeros((span, hd), f32)
            for h in range(groups):
                head = slice(h * hd, (h + 1) * hd)
                q, do = q_ref[head, here], do_ref[head, here]  # [hd, queries]
                scores = jnp.dot(keys, q, preferred_element_type=f32)
                scores = jnp.where(admitted, scores, _MASKED)
                p = jnp.exp(scores - lse_ref[h:h + 1, here])
                dp = jnp.dot(values, do, preferred_element_type=f32)
                delta = jnp.sum(p * dp, axis=0, keepdims=True)
                ds = (p * (dp - delta)).astype(q.dtype)
                dv = dv + jax.lax.dot_general(
                    p.astype(do.dtype), do, _NT, preferred_element_type=f32)
                dk = dk + jax.lax.dot_general(
                    ds, q, _NT, preferred_element_type=f32)
                dq_ref[head, here] = jnp.dot(
                    keys_t[:, at:at + span], ds, preferred_element_type=f32
                ).astype(dq_ref.dtype)
            dk_acc[at:at + span, :] += dk
            dv_acc[at:at + span, :] += dv

    # key block ``step - 1`` is whole now: its own sums, kept from the step
    # before, and this step's halo rows on its last ``halo``; the step past
    # the last block has no halo to add
    @pl.when(step > 0)
    def _():
        head, tail = slice(0, block - halo), slice(block - halo, block)
        last = step == blocks
        for out, own, acc, by in ((dk_ref, dk_own, dk_acc, scale),
                                  (dv_ref, dv_own, dv_acc, 1.0)):
            if halo < block:
                out[:, head] = (own[head, :] * by).T.astype(out.dtype)
            out[:, tail] = ((own[tail, :] + jnp.where(
                last, 0.0, acc[:halo, :])) * by).T.astype(out.dtype)

    @pl.when(step < blocks)
    def _():
        dk_own[...] = dk_acc[halo:, :]
        dv_own[...] = dv_acc[halo:, :]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(shape, kv_heads: int, halo: int, late: bool = False):
    """The block specs of a call whose q is ``shape`` = [B, S, H, hd], over
    the arrays as ``[B, heads * hd, S]`` and the logsumexp as ``[B, Hkv, g,
    S]``, for the grid ``(B, Hkv, blocks)``: the g heads' block of q's
    like, a key/value head's block and the halo before it, the logsumexp's
    block.  ``late``: the grid has one step more than there are blocks,
    which reads what the last read, and ``keys_late`` is the block of the
    step before."""
    _, s, h, hd = shape
    groups, per, blocks = h // kv_heads, _BLOCK // halo, s // _BLOCK

    def at(i):
        return jnp.minimum(i, blocks - 1) if late else i

    return {
        "queries": pl.BlockSpec(
            (None, groups * hd, _BLOCK), lambda b, n, i: (b, n, at(i))),
        "keys": pl.BlockSpec((None, hd, _BLOCK), lambda b, n, i: (b, n, at(i))),
        "halo": pl.BlockSpec(
            (None, hd, halo),
            lambda b, n, i: (b, n, jnp.maximum(at(i) * per - 1, 0))),
        "keys_late": pl.BlockSpec(
            (None, hd, _BLOCK), lambda b, n, i: (b, n, jnp.maximum(i - 1, 0))),
        "lse": pl.BlockSpec(
            (None, None, groups, _BLOCK), lambda b, n, i: (b, n, 0, at(i))),
    }


def _positions_last(x):
    """[B, S, heads, hd] -> [B, heads * hd, S]."""
    return x.transpose(0, 2, 3, 1).reshape(x.shape[0], -1, x.shape[1])


def _positions_first(x, hd: int):
    """[B, heads * hd, S] -> [B, S, heads, hd]."""
    return x.reshape(x.shape[0], -1, hd, x.shape[2]).transpose(0, 3, 1, 2)


def _forward(q, k, v, window, interpret):
    """``(o [B, S, H, hd], logsumexp [B, Hkv, g, S] float32)``."""
    bsz, s, h, hd = q.shape
    kv_heads, halo = k.shape[2], _halo(window)
    spec = _specs(q.shape, kv_heads, halo)
    q, k, v = (_positions_last(x) for x in (q, k, v))
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, window=window, halo=halo, scale=1.0 / hd ** 0.5),
        grid=(bsz, kv_heads, s // _BLOCK),
        in_specs=[spec["queries"], spec["keys"], spec["halo"], spec["keys"],
                  spec["halo"]],
        out_specs=[spec["queries"], spec["lse"]],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, kv_heads, h // kv_heads, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((halo + _BLOCK, hd), k.dtype),
                        pltpu.VMEM((hd, halo + _BLOCK), v.dtype)],
        compiler_params=_PARAMS, interpret=interpret, name="band_attention_fwd",
    )(q, k, k, v, v)
    return _positions_first(o, hd), lse


def _backward(q, k, v, lse, do, window, interpret):
    bsz, s, h, hd = q.shape
    kv_heads, halo = k.shape[2], _halo(window)
    spec = _specs(q.shape, kv_heads, halo, late=True)
    q, k, v, do = (_positions_last(x) for x in (q, k, v, do))
    f32 = jnp.float32
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, window=window, halo=halo, scale=1.0 / hd ** 0.5),
        grid=(bsz, kv_heads, s // _BLOCK + 1),
        in_specs=[spec["queries"], spec["keys"], spec["halo"], spec["keys"],
                  spec["halo"], spec["queries"], spec["lse"]],
        out_specs=[spec["queries"], spec["keys_late"], spec["keys_late"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((halo + _BLOCK, hd), k.dtype),
                        pltpu.VMEM((hd, halo + _BLOCK), k.dtype),
                        pltpu.VMEM((halo + _BLOCK, hd), v.dtype),
                        pltpu.VMEM((halo + _BLOCK, hd), f32),
                        pltpu.VMEM((halo + _BLOCK, hd), f32),
                        pltpu.VMEM((_BLOCK, hd), f32),
                        pltpu.VMEM((_BLOCK, hd), f32)],
        compiler_params=_PARAMS, interpret=interpret, name="band_attention_bwd",
    )(q, k, k, v, v, do, lse)
    return tuple(_positions_first(x, hd) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, window, residuals, interpret):
    return _forward(q, k, v, window, interpret)[0]


def _attend_fwd(q, k, v, window, residuals, interpret):
    o, lse = _forward(q, k, v, window, interpret)
    if residuals is not None:
        o, lse = checkpoint_name(o, residuals), checkpoint_name(lse, residuals)
    return o, (q, k, v, lse)


def _attend_bwd(window, residuals, interpret, kept, do):
    return _backward(*kept, do, window, interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def band_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, window: int,
    residuals: str | None = None, interpret: bool = False,
) -> jax.Array:
    """Causal attention under ``window`` keys as two Pallas TPU kernels
    (``band_attention_fwd``, and ``band_attention_bwd`` behind a
    ``jax.custom_vjp``) for calls :func:`band_kernel_fits` admits;
    ``interpret`` runs them on any backend.  The forward's output and
    logsumexp carry the checkpoint name ``residuals`` where one is given."""
    return _attend(q, k, v, window, residuals, interpret)


def band_attention_lse(q, k, v, window: int, interpret: bool = False):
    """``(output, logsumexp [B, H, S] float32)`` of the forward kernel."""
    o, lse = _forward(q, k, v, window, interpret)
    return o, lse.reshape(q.shape[0], q.shape[2], q.shape[1])
