"""The blocked attention kernel's backward under a causal mask as ONE Pallas
call that keeps a head's reduction across grid steps in VMEM: heads first
q and k [B, H, S, hd], v [B, H, S, hd_v] (as many key heads as query heads,
the scale already on q, as the library's kernel takes them) -> dq, dk, dv
in the operands' dtype.

Why a kernel of its own (PERF.md section 6, PR 69): the library's backward
(``jax.experimental.pallas.ops.tpu.splash_attention``) is either a PAIR of
kernels, dK/dV and dQ, each of which computes the scores and the
probabilities' gradient again (7 products an element where 5 are the
mathematics), or one fused kernel whose grid puts the key blocks OUTSIDE
the heads, so a head's dq cannot stay where it is while the key blocks go
by: it writes one float32 partial a key block, ``[S / block_kv, H, S,
hd]``, and XLA sums them afterwards (8 GB at [32, 16384, 192] and key
blocks of 1,024, which a step that holds four residual streams a layer
cannot afford).  Here the heads are outermost: the grid is ``(batch row,
head, block pair)`` over the pairs ``(key block j, query block i)`` the
causal mask leaves anything in, read from a table (no grid step for an
empty pair), key blocks outer and query blocks inner.  A step computes, a
sub-block of keys at a time, ``s^T = k q^T`` [keys, queries] float32 from
the operands as they come, masks it from two iotas where the pair straddles
the diagonal (a pair under it takes the path with no mask; on a diagonal
the two blocks share, a sub-block of keys meets the queries from its own
first on and none before), ``p = exp(s^T - logsumexp)``, ``dv += p do``,
``dp = v do^T``, ``ds = p (dp - delta)``, ``dk^T += q^T ds^T``, ``dq^T[:,
query rows] += k^T ds``, once.

**The two gradients of the 192-wide operands are kept TRANSPOSED**, ``[hd,
positions]`` float32: ``ds`` [keys, queries] is then the right side of both
products as it stands (``k^T ds``) or transposed by the MXU's own load
(``q^T ds^T``), so no score-sized value is ever transposed, and the head
size is the products' ROW count, which costs its 192 rows, where as a
column count it costs two 128-wide passes (on the chip, a backward of 51.9
ms with both as ``ds^T k`` and ``ds q``, 48.5 with ``dq`` turned, 45.9 with
both).  What is transposed is a block of q and of k a step and a block of
each gradient once, on its way out.  ``dk^T`` and ``dv`` of the key block
add up over the inner axis in a block of VMEM; ``dq^T`` of the WHOLE head,
``[hd, S]`` float32 (12.6 MB at 192 x 16,384), stays resident across the
key blocks, and each query block is turned and rounded ONCE, after its last
key block, into an output block that holds the head and goes out once a
head.  No partials in HBM, no sum by XLA, no second pass over the scores.
bf16 operands, float32 scores, softmax sums and accumulators; every
admitted element computed.

``keys_outer=False`` is the other order of the same table (query blocks
outer: ``dq^T`` in a block, the head's ``dk^T`` and ``dv`` resident), which
the probe reads beside it (``tools/attention_probe.py latent all xing4``:
the same time to 0.2 %); the kernel is one, the accumulators' extents tell
it which rows a block is.

:func:`resident_attention` is the library's FORWARD kernel as it was (its
tiles, its row sums and output named ``residuals``, so a checkpoint that
saves the name holds one forward call) and this backward behind a
``jax.custom_vjp``.  :func:`resident_backward_fits` is the rule: a pure
function of what the call can see.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The kernel's instruction name: the benchmark's reader finds the attention
# kernel's calls by the prefix ``splash_mha`` and files one as backward
# unless its name has ``_fwd``.
NAME = "splash_mha_bwd_resident"
# (query block, key block, keys of one product) of a step, each ``min(size,
# S)``: the fastest of the sweep on a TPU v5e at [1, 32, 16384, 192 | 128]
# bf16 (PERF.md section 6 "PR 69"; tools/attention_probe.py latent resident
# xing4): a backward of 45.9 ms, 46.3 at products of 512 keys; 512-wide
# blocks of either kind and 2,048-wide ones are slower.
_BLOCKS = (1024, 1024, 256)
# The pairs (queries' and keys' head size, values') the kernel was run at,
# on the chip and under ``interpret``: the rule takes nothing else.
_HEADS = ((192, 128),)
# What the call asks Mosaic for (the chip has 128 MB; 16 MB are scoped
# unasked), and the part of it the head's resident arrays may take: the rest
# is the blocks, twice over, and a step's score-sized values.
_VMEM, _VMEM_RESIDENT = 96 * 2 ** 20, 48 * 2 ** 20
_MASKED = -0.7 * float(np.finfo(np.float32).max)  # a score the mask refuses
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _resident_bytes(s: int, hd: int, itemsize: int) -> int:
    """VMEM of the head's float32 ``dq^T`` [hd, S] and of its output block
    [S, hd], whose rows take whole 128-lane tiles and which the pipeline
    holds twice."""
    return s * (hd * 4 + -(-hd // 128) * 128 * 2 * itemsize)


def resident_backward_fits(
    shape: tuple, kv_heads: int, value_dim: int, window: int | None,
    diffusion_block: int | None, backend: str, itemsize: int = 2,
) -> bool:
    """Whether :func:`resident_attention` takes a call whose q is ``shape`` =
    [B, S, H, hd] over ``kv_heads`` key heads and values of ``value_dim``:
    a ``tpu`` backend (Mosaic lowering), a causal mask and no other (no
    window, no ``diffusion_block``), as many key heads as query heads, a
    head pair the kernel was run at, a length its blocks divide, and the
    head's resident ``dq`` inside its part of the VMEM the call asks for.
    A pure function of what the call can see."""
    _, s, h, hd = shape
    return (
        backend == "tpu" and window is None and diffusion_block is None
        and kv_heads == h and (hd, value_dim) in _HEADS and s % 128 == 0
        and all(s % min(size, s) == 0 for size in _BLOCKS)
        and _resident_bytes(s, hd, itemsize) <= _VMEM_RESIDENT
    )


def block_pairs(s: int, bq: int, bkv: int, keys_outer: bool = True) -> np.ndarray:
    """``(key block, query block)`` [2, pairs] int32 of the block pairs a
    causal mask over ``s`` positions leaves anything in (the pair's last
    query sees its first key), in the order the grid visits them."""
    pairs = [(j, i) for j in range(s // bkv) for i in range(s // bq)
             if i * bq + bq - 1 >= j * bkv]
    if not keys_outer:
        pairs.sort(key=lambda pair: pair[::-1])
    return np.asarray(pairs, np.int32).T


def _first(extent: int, index, rows: int):
    """The first position of block ``index`` along an axis of ``extent``:
    of an array that holds that block alone, or the whole head."""
    return 0 if extent == rows else pl.multiple_of(index * rows, rows)


def _kernel(kv_of, q_of, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, bkvc, blocks_q):
    f32 = jnp.float32
    bq, bkv = q_ref.shape[0], k_ref.shape[0]
    step = pl.program_id(2)
    j, i = kv_of[step], q_of[step]
    # where the pair's positions are: in an array of one block, or of the head
    q_acc, q_out = _first(dq_acc.shape[1], i, bq), _first(dq_ref.shape[0], i, bq)
    k_acc, k_out = _first(dk_acc.shape[1], j, bkv), _first(dk_ref.shape[0], j, bkv)

    @pl.when(j == 0)  # a query block's first key block
    def _():
        dq_acc[:, pl.ds(q_acc, bq)] = jnp.zeros((dq_acc.shape[0], bq), f32)

    @pl.when(i == (j * bkv) // bq)  # a key block's first query block
    def _():
        dk_acc[:, pl.ds(k_acc, bkv)] = jnp.zeros((dk_acc.shape[0], bkv), f32)
        dv_acc[pl.ds(k_acc, bkv), :] = jnp.zeros((bkv, dv_acc.shape[1]), f32)

    def turned(x):  # the transpose is float32's
        return x.astype(f32).T.astype(x.dtype)

    def visit(straddles: bool):
        q_t = turned(q_ref[...])  # [hd, queries]
        for at in range(0, bkv, bkvc):
            # on a diagonal the two blocks share, the keys from ``at`` on
            # are seen by the queries from ``at`` on and by none before
            lo = at if straddles and bq == bkv else 0
            q, do = q_ref[lo:, :], do_ref[lo:, :]
            lse, delta = lse_ref[:, lo:], delta_ref[:, lo:]  # [1, queries]
            k, v = k_ref[at:at + bkvc, :], v_ref[at:at + bkvc, :]
            scores = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32)
            if straddles:
                key = j * bkv + at + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 0)
                query = i * bq + lo + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 1)
                scores = jnp.where(key <= query, scores, _MASKED)
            p = jnp.exp(scores - lse)
            dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
            ds = p * (dp - delta)
            rows = pl.ds(k_acc + at, bkvc)
            dv_acc[rows, :] += jnp.dot(
                p.astype(do.dtype), do, preferred_element_type=f32)
            dk_acc[:, rows] += jax.lax.dot_general(
                q_t[:, lo:], ds.astype(q.dtype), _NT, preferred_element_type=f32)
            dq_acc[:, pl.ds(q_acc + lo, bq - lo)] += jnp.dot(
                turned(k), ds.astype(k.dtype), preferred_element_type=f32)

    # the pair's last key lies past its first query: part of it is masked
    straddles = j * bkv + bkv - 1 > i * bq
    pl.when(straddles)(functools.partial(visit, True))
    pl.when(jnp.logical_not(straddles))(functools.partial(visit, False))

    @pl.when(j == (i * bq + bq - 1) // bkv)  # a query block's last key block
    def _():
        dq_ref[pl.ds(q_out, bq), :] = dq_acc[:, pl.ds(q_acc, bq)].T.astype(dq_ref.dtype)

    @pl.when(i == blocks_q - 1)  # a key block's last query block
    def _():
        dk_ref[pl.ds(k_out, bkv), :] = dk_acc[:, pl.ds(k_acc, bkv)].T.astype(dk_ref.dtype)
        dv_ref[pl.ds(k_out, bkv), :] = dv_acc[pl.ds(k_acc, bkv), :].astype(dv_ref.dtype)


def attention_backward(
    q, k, v, o, lse, do, blocks: tuple | None = None, keys_outer: bool = True,
    interpret: bool = False,
):
    """``(dq, dk, dv)`` of causal attention on heads-first q and k [B, H, S,
    hd] and v [B, H, S, hd_v] (the scale on q) from the forward's output
    ``o``, its ``lse`` [B, H, S] float32 and the output's cotangent ``do``:
    one call of the kernel ``NAME``.  ``blocks``: (query block, key block,
    keys of one product), the module's where none is given."""
    bsz, h, s, hd = q.shape
    hdv = v.shape[-1]
    bq, bkv, bkvc = (min(size, s) for size in blocks or _BLOCKS)
    bkvc = min(bkvc, bkv)
    f32 = jnp.float32
    # delta = sum_j p dp a query, as sum(o * do): a row [1, S] a head, like
    # the logsumexp (a query is a lane in the transposed scores)
    delta = jnp.sum(o.astype(f32) * do.astype(f32), axis=-1)
    table = block_pairs(s, bq, bkv, keys_outer)

    def of_q(rows, width):
        return pl.BlockSpec(
            (None, None, rows, width), lambda b, n, t, kv_of, q_of: (b, n, q_of[t], 0))

    def of_k(rows, width):
        return pl.BlockSpec(
            (None, None, rows, width), lambda b, n, t, kv_of, q_of: (b, n, kv_of[t], 0))

    def of_head(width):
        return pl.BlockSpec(
            (None, None, s, width), lambda b, n, t, kv_of, q_of: (b, n, 0, 0))

    row = pl.BlockSpec(
        (None, None, 1, bq), lambda b, n, t, kv_of, q_of: (b, n, 0, q_of[t]))
    if keys_outer:
        out_specs = [of_head(hd), of_k(bkv, hd), of_k(bkv, hdv)]
        kept = [(hd, s), (hd, bkv), (bkv, hdv)]
    else:
        out_specs = [of_q(bq, hd), of_head(hd), of_head(hdv)]
        kept = [(hd, bq), (hd, s), (s, hdv)]
    return pl.pallas_call(
        functools.partial(_kernel, bkvc=bkvc, blocks_q=s // bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, h, table.shape[1]),
            in_specs=[of_q(bq, hd), of_k(bkv, hd), of_k(bkv, hdv),
                      of_q(bq, hdv), row, row],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(shape, f32) for shape in kept],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name=NAME,
    )(jnp.asarray(table[0]), jnp.asarray(table[1]), q, k, v, do,
      lse[:, :, None, :], delta[:, :, None, :])


def _forward(q, k, v, sizes, residuals, interpret):
    """``(o, lse [B, H, S] float32)`` of the library's forward kernel at
    ``sizes``' forward tiles, both named ``residuals``."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    s = q.shape[2]
    kernel = splash.make_splash_mha_single_device(
        mask=splash.MultiHeadMask([splash.CausalMask((s, s))] * q.shape[1]),
        block_sizes=splash.BlockSizes(
            block_q=sizes.block_q, block_kv=sizes.block_kv,
            block_kv_compute=sizes.block_kv_compute),
        save_residuals=True, interpret=interpret,
    )
    o, (lse,) = jax.vmap(kernel)(q, k, v)
    # named HERE: the library's call with its row sums is a ``custom_vjp``
    # that nothing differentiates, and a checkpoint's policy does not look
    # inside one
    if residuals is not None:
        o, lse = checkpoint_name(o, residuals), checkpoint_name(lse, residuals)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, sizes, residuals, interpret):
    return _forward(q, k, v, sizes, residuals, interpret)[0]


def _attend_fwd(q, k, v, sizes, residuals, interpret):
    o, lse = _forward(q, k, v, sizes, residuals, interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(sizes, residuals, interpret, kept, do):
    return attention_backward(*kept, do, interpret=interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def resident_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, sizes,
    residuals: str | None = None, interpret: bool = False,
) -> jax.Array:
    """Causal attention on heads-first q and k [B, H, S, hd] and v [B, H, S,
    hd_v], the scale already on q, -> [B, H, S, hd_v] for calls
    :func:`resident_backward_fits` admits: the library's forward kernel at
    ``sizes``' forward tiles (a ``BlockSizes``), its output and logsumexp
    named ``residuals`` where one is given, and :func:`attention_backward`
    behind a ``jax.custom_vjp``.  ``interpret`` runs both on any backend."""
    return _attend(q, k, v, sizes, residuals, interpret)
