"""The gated delta rule of linear attention (Gated DeltaNet,
arXiv:2412.06464; write strengths up to 2, arXiv:2411.12537) in its
chunked, matmul-shaped form, and a position at a time.

Per head, with the state ``S_t`` in ``R^{dk x dv}`` and ``S_0 = 0``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
        = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q`` and ``k`` [B, S, H, dk] are the heads' queries and keys as the rule
takes them (the mixer has made them unit-length and scaled the queries:
``trunk.delta_mixer``), ``v`` [B, S, H, dv] the values, ``g`` [B, S, H]
the decays' logarithms (``alpha = exp(g)``, ``g <= 0``, float32) and
``beta`` [B, S, H] the write strengths.  What is written at a position is
the value LESS what the decayed state already answers for the key: that
correction is what the state-space scan of ``ops/ssd.py`` does not have.

:func:`gated_delta_recurrent` is the rule as written, a ``lax.scan`` over
the positions, all float32: the chunked form's reference in the tests.

:func:`gated_delta_chunked` cuts the sequence into chunks of ``C``
positions.  With ``gamma`` the inclusive cumulative sum of ``g`` inside a
chunk and ``Gamma_ij = exp(gamma_i - gamma_j)`` for ``i >= j`` (a
difference of the sums, never a quotient of exponentials: ``exp(-gamma_j)``
overflows where ``exp(gamma_i - gamma_j)`` is at most 1), and ``S`` the
state entering the chunk::

    A      = strictly_lower(diag(beta) (K K^T * Gamma))        [C, C]
    W, U   = (I + A)^-1 diag(beta) [K * exp(gamma) | V]        the solve
    V'     = U - W S                                           [C, dv]
    O      = (Q * exp(gamma)) S + lower_incl((Q K^T) * Gamma) V'
    S_next = exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

The products take operands of ``v``'s dtype (bf16 in a train step) and
accumulate in float32; the decays, ``A``, the solve and the carried state
are float32.  Two forms of it, one rule between them
(:func:`gated_delta_chunked`, :func:`kernel_fits`: a pure function of what
the call can see, as ``ops/ssd.py`` chooses its kernel):

* :func:`gated_delta_kernel`, where the backend is ``tpu``, the decays
  float32, the solve ``blocks`` and the shapes fit the tiles
  (:func:`_grid`): two Pallas kernels, ``delta_chunk_fwd`` and, behind a
  ``jax.custom_vjp``, ``delta_chunk_bwd``.  A grid row is up to three heads
  (heads first in HBM, so a block spans an array's whole last axis and
  keys of 96 or values of 192 need no lane multiple) and walks their
  chunks in sequence, each head's state [dk, dv] float32 carried in VMEM
  (the backward walks them in reverse and carries the state's cotangent,
  seeded with the last state's).  A frame of 128 positions is taken at a
  time, its chunks side by side on the diagonal of [128, 128]: ``Gamma``,
  ``K K^T``, ``A``, the inverse of ``I + A``, ``Q K^T``, ``W``, ``U`` and
  ``V'`` live and die in VMEM; ``q``, ``k``, ``v``, the sums, ``beta`` and
  ``o`` cross HBM once.  The backward's residual is the state entering
  each GRID STEP (two frames, 256 positions: 142 MB a layer at
  Olmo-Hybrid's shape, a quarter of a state a chunk), written by the
  forward under differentiation alone; from it the backward kernel chains
  the step's chunks FORWARD in VMEM, with the forward kernel's products
  and roundings (the states it rebuilds are the forward's, to the bit),
  before it walks them in reverse.  That residual and ``o`` carry the name
  :data:`DELTA_RESIDUALS` for a remat policy to keep
  (``models/transformer.py`` ``_hidden``): the recompute then holds no
  forward kernel call (PERF.md section 6, PR 58).  The gradient through
  the solve is two more products with the same inverse (``dR = T^T dX``,
  ``dA = -dR X^T`` under the diagonal).  It rounds where the plain form
  rounds.
* :func:`gated_delta_plain`, everywhere else (the CPU, a ``decay_dtype`` or
  a ``solve`` that is being probed, a shape the tiles refuse): plain
  ``jax.numpy``, every intermediate an array of its own, the backward
  autodiff's.  What reads no entering state (``A``, the solve, the
  scores) is computed for all chunks at once; a ``lax.scan`` over the ``S
  / C`` chunks carries the state and makes each chunk's ``V'``; the
  outputs are again taken for all chunks at once.  A sequence longer than
  :data:`SEGMENT` is taken a segment at a time, the state handed from
  segment to segment, each under ``jax.checkpoint``: a backward pass then
  holds one segment's intermediates, not the sequence's.  It is the
  kernel's reference in the tests.

The decays' sums inside the chunks are XLA's in both, and their gradient
back to ``g`` autodiff's.

**The solve.**  ``I + A`` is unit lower-triangular, so ``A`` is nilpotent
and ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...``: matmuls alone, and no
way to take: the partial products grow as ``a^n binom(C, n)`` where keys
are alike (``a`` a typical entry of ``A``: up to 2 where a layer's stream
is much the same at every position and the write strengths saturate), and
the cancellation that brings the inverse back to order 1 takes every digit
float32 has; inside blocks of 16 it still took five of the seven where
``a`` nears 2, and the cell's comparison read it (PERF.md section 6, PR
45).  So the inverses of the diagonal blocks of :data:`SOLVE_BLOCK`
positions are made by forward substitution, a row at a time (15 small
steps for all blocks at once: of all chunks in the plain form; of a frame
in the kernel, there on the VPU with the frame's eight blocks side by side
on the lanes of two vector registers, :func:`_block_inverses`), and plain
forward substitution by blocks joins them (:func:`solve_unit_lower`,
``how="blocks"``; the kernel solves for the identity, the blocks doubling
from 16 to the chunk, each join two products at the highest precision over
the half of the frame's rows that it changes: :func:`_unit_lower_inverse`,
:func:`_join`): backward-stable whatever the keys.  The kernel's operands
leave out the inverse's structural zeros and nothing else: an entry's
arithmetic is the same, and so are its bits (PERF.md section 6, PR 62).
``"product"`` (the whole chunk at once) and ``"triangular"``
(``jax.scipy.linalg.solve_triangular``) are there for the probe that
times and checks the ways on the chip (tools/smallthinker_probe.py delta).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from learning_at_home_tpu.ops.ssd import _LANES, _NT, _TN, _columns

# positions of a diagonal block of the solve: (I + A)^-1 a row at a time
# inside it, forward substitution by blocks between them
SOLVE_BLOCK = 16
# positions of a segment: the chunked form's intermediates (a dozen float32
# arrays of [B, S, H, C] and [B, S, H, dk + dv]) are held for a segment at a
# time in a backward pass, and made again from the state that entered it.
# Whole, at [1, 16384, 30, 96 / 192], they are 8 GB of a 16 GB chip (the
# step compiled for a described v5e: PERF.md section 6, PR 45)
SEGMENT = 2048
_HIGHEST = jax.lax.Precision.HIGHEST
UNIT_EPS = 1e-6  # under the root of a query's or a key's length
# the kernel's frame: the positions whose scores and solve one head takes
# at once, whole chunks side by side on the diagonal of [FRAME, FRAME]
FRAME = 128
# the name of what the kernels' forward hands their backward besides its
# inputs: the output and the state entering each grid step, for a remat
# policy to keep (``save_only_these_names``), so that a recompute holds no
# forward kernel call.  Outside a checkpoint the name is the identity
DELTA_RESIDUALS = "delta_rule_residuals"


def gated_delta_recurrent(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """``(o [B, S, H, dv] float32, the state after the last position
    [B, H, dk, dv] float32)`` of the rule above, a position at a time."""
    f32 = jnp.float32
    bsz, _, h, dk = q.shape
    dv = v.shape[-1]

    def one_position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # [B, H, .]
        # a decay a head scales the state whole, one a key channel its rows
        decayed = (jnp.exp(g_t)[..., None, None] if g_t.ndim == 2
                   else jnp.exp(g_t)[..., :, None]) * state
        answered = jnp.einsum("bhkv,bhk->bhv", decayed, k_t, precision=_HIGHEST)
        state = decayed + (
            (beta_t[..., None] * k_t)[..., :, None]
            * (v_t - answered)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)

    final, o = jax.lax.scan(
        one_position, jnp.zeros((bsz, h, dk, dv), f32),
        tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1), final


def _product_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` [.., n, n]:
    ``(I - a)(I + a^2)(I + a^4)...`` up to the power that is zero."""
    n = a.shape[-1]
    inverse = jnp.eye(n, dtype=a.dtype) - a
    power, reach = a, 2  # the product so far holds the powers below reach
    while reach < n:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
        reach *= 2
    return inverse


def _substituted_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` [.., n, n] by
    forward substitution, a row at a time: row ``i`` is ``e_i - sum_{j<i}
    a[i, j] row_j``.  No partial result is larger than the inverse's own
    entries, whatever ``a`` holds."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], a.shape[:-1])]
    for i in range(1, n):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jn->...n", a[..., i, :i], jnp.stack(rows, axis=-2),
            precision=_HIGHEST))
    return jnp.stack(rows, axis=-2)


def solve_unit_lower(a: jax.Array, rhs: jax.Array, how: str = "blocks") -> jax.Array:
    """``X`` with ``(I + a) X = rhs``: ``a`` [.., C, C] strictly
    lower-triangular, ``rhs`` [.., C, n], float32 (see the module's
    docstring for the three ways)."""
    c = a.shape[-1]
    if how == "triangular":
        return jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    if how == "product":
        return jnp.matmul(_product_inverse(a), rhs, precision=_HIGHEST)
    if how != "blocks" or c % min(SOLVE_BLOCK, c):
        raise ValueError(
            f"solve {how!r} over {c} positions: 'blocks' (of {SOLVE_BLOCK}), "
            "'product' or 'triangular'")
    block = min(SOLVE_BLOCK, c)
    n = c // block
    lead = a.shape[:-2]
    blocks = a.reshape(*lead, n, block, n, block)
    inverses = _substituted_inverse(jnp.stack(
        [blocks[..., i, :, i, :] for i in range(n)], axis=-3))
    rows = rhs.reshape(*lead, n, block, rhs.shape[-1])
    solved: list = []
    for i in range(n):
        left = rows[..., i, :, :]
        if solved:  # less what the blocks before this one give its rows
            before = a[..., i * block:(i + 1) * block, :i * block]
            left = left - jnp.matmul(
                before, jnp.concatenate(solved, axis=-2), precision=_HIGHEST)
        solved.append(jnp.matmul(
            inverses[..., i, :, :], left, precision=_HIGHEST))
    return jnp.concatenate(solved, axis=-2)


def gated_delta_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int, decay_dtype=jnp.float32, solve: str = "blocks",
    segment: int = SEGMENT, unit: bool = False,
    decay_floor: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(o [B, S, H, dv] in v's dtype, the state after the last position
    [B, H, dk, dv] float32)`` of the rule above, ``chunk`` positions at a
    time (``min(chunk, S)``; it must divide ``S``).  ``g`` [B, S, H] is a
    decay a head; ``g`` [B, S, H, dk] a decay a KEY CHANNEL (Kimi Delta
    Attention, arXiv:2510.26692: ``S_t = (I - beta_t k_t k_t^T)
    Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``), whose ``decay_floor`` is
    the least a position's ``g`` can be (the mixer's bounded gate: see
    :func:`channel_decay_fits`); the decay's rank says which form runs, no
    flag.  ``decay_dtype``: the
    dtype the decays' sums are kept in; float32 always, but for showing
    what a lower one reads (tools/smallthinker_probe.py).  ``solve``:
    :func:`solve_unit_lower`'s way.  ``segment``: the positions whose
    intermediates the plain form's backward pass holds at a time.
    ``unit``: ``q`` and ``k`` come as the mixer's convolution left them and
    are made unit-length first (:func:`unit_length`).  The kernel where
    :func:`kernel_fits` says so, the plain form elsewhere."""
    channel = g.ndim == 4
    if channel and not channel_decay_fits(decay_floor, decay_dtype):
        raise ValueError(
            f"a decay a key channel of decay_floor={decay_floor} kept in "
            f"{jnp.dtype(decay_dtype).name}: {SOLVE_BLOCK} positions' sum "
            f"must stay above -{EXPONENT_MOST} (ops/delta_rule.py "
            "channel_decay_fits)")
    if kernel_fits(q.shape, v.shape, chunk, jax.default_backend(), decay_dtype,
                   solve, channel):
        return gated_delta_kernel(q, k, v, g, beta, chunk, unit=unit)
    if unit:
        q, k = unit_length(q, k)
    return gated_delta_plain(q, k, v, g, beta, chunk, decay_dtype, solve, segment)


# the largest exponent the channel decays' scores take: a 16-block's rows
# and columns are scaled by e^{gamma - gamma_r} and e^{gamma_r - gamma} with
# gamma_r the block's first row, so one of the two is up to the block's
# whole decay.  e^80 = 5.5e34 is finite in float32 and in bf16 (both end at
# 3.4e38), and a product with a row factor down to e^-80 = 1.8e-35 keeps
# every digit of an element over 6.5e-4 (the least normal number is 1.2e-38
# in both; a unit key's 128 elements are 0.09 each at the root of their mean
# square, and what falls under is lost only where 16 positions ALL sit on the
# floor: up to 2e-3 of one score, under bf16's rounding of the rest:
# tests/test_kda_rule.py, the gates saturated at the floor)
EXPONENT_MOST = 80.0


def channel_decay_fits(decay_floor, decay_dtype=jnp.float32) -> bool:
    """Whether a decay a key channel can be taken in chunks: ``decay_floor``
    (the least a position's log-decay can be: the mixer's bounded gate, -5
    for ``kda_lower_bound`` -5) keeps :data:`SOLVE_BLOCK` positions' sum
    within :data:`EXPONENT_MOST`, in a dtype with float32's exponents.  An
    unbounded gate (None) is refused, never clamped.  The ONE bound serves
    both references: the plain form's (a 16-block's first row: up to 15
    positions' decay on a factor) and the kernels' (a 32-span's middle row:
    up to 16 positions' on either side of it), so neither derives its own."""
    return (
        decay_floor is not None and decay_floor <= 0.0
        and -decay_floor * SOLVE_BLOCK <= EXPONENT_MOST
        and jnp.dtype(decay_dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)))


def unit_length(q: jax.Array, k: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The heads' queries and keys [.., dk] as the rule takes them: ``x /
    sqrt(sum x^2 + 1e-6)`` in float32, the queries times ``dk^-1/2``
    besides, rounded to the dtype they came in."""
    f32 = jnp.float32

    def unit(x):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + UNIT_EPS)

    return ((unit(q) * q.shape[-1] ** -0.5).astype(q.dtype), unit(k).astype(k.dtype))


def _grid(h: int, s: int, c: int, dk: int, dv: int):
    """``(heads a grid row, positions a grid step)`` of the kernel for
    ``h`` heads a batch row of ``s`` positions in chunks of ``c``, or None
    where its tiles refuse: the positions in frames of :data:`FRAME` (two a
    step where they come out even), a chunk that divides the frame and
    holds whole blocks of the solve, keys whole sublane tiles, and two
    heads abreast, or one, whose values side by side ([S, hg dv] of
    ``v``'s [B, S, H dv] as it stands) are whole lane tiles and whose
    states [hg, dk, dv] float32 stay within 2^16 elements (the backward at
    five heads of [96, 192] passed the 16 MB of scoped VMEM: an AOT compile
    for a described v5e, PERF.md section 6, PR 46)."""
    if s % c or s % FRAME or FRAME % c or c % SOLVE_BLOCK or dk % 8:
        return None
    for hg in (2, 1):
        if h % hg == 0 and (hg * dv) % _LANES == 0 and hg * dk * dv <= 2 ** 16:
            return hg, 2 * FRAME if s % (2 * FRAME) == 0 else FRAME
    return None


def kernel_fits(q_shape, v_shape, chunk, backend, decay_dtype=jnp.float32,
                solve: str = "blocks", channel: bool = False) -> bool:
    """Whether :func:`gated_delta_kernel` takes a call: a ``tpu`` backend
    (Mosaic lowering), float32 decays, the ``blocks`` solve (the kernel's
    own), and shapes :func:`_grid` finds tiles for; under a decay a key
    channel (``channel``) what :func:`channel_kernel_fits` asks besides.  A
    pure function of what the call can see."""
    bsz, s, h, dk = q_shape
    return (
        backend == "tpu" and jnp.dtype(decay_dtype) == jnp.float32
        and solve == "blocks"
        and _grid(h, s, min(chunk, s), dk, v_shape[-1]) is not None
        and (not channel or channel_kernel_fits(min(chunk, s), dk)))


def channel_kernel_fits(c: int, dk: int) -> bool:
    """What the kernels under a decay a key channel ask besides
    :func:`_grid`: a chunk of one span of their references or two (a
    chunk's second span refers to its first row, and a third would need
    a reference of its own against each span before it), and keys of whole
    lane tiles (the sums [S, dk] are a block of their own and ``e^{gamma_C}``
    is turned from a row into the state's rows' column)."""
    return c <= 4 * SOLVE_BLOCK and dk % _LANES == 0


def gated_delta_plain(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int, decay_dtype=jnp.float32, solve: str = "blocks",
    segment: int = SEGMENT,
) -> tuple[jax.Array, jax.Array]:
    """:func:`gated_delta_chunked` in plain ``jax.numpy``, every
    intermediate an array of its own; its backward is autodiff's.
    ``segment``: the positions whose intermediates a backward pass holds
    at a time (a multiple of the chunk that divides ``S``, or the whole
    sequence where it is no longer)."""
    bsz, s, h, dk = q.shape
    c = min(chunk, s)
    if s % c:
        raise ValueError(f"the chunked delta rule needs chunk={c} to divide S={s}")
    state = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    if s <= segment:
        return _segment(state, (q, k, v, g, beta), c, decay_dtype, solve)
    if segment % c or s % segment:
        raise ValueError(
            f"segments of {segment} positions: a multiple of chunk={c} that "
            f"divides S={s}")

    def by_segment(a):  # [B, S, ..] -> [S / segment, B, segment, ..]
        return jnp.moveaxis(
            a.reshape(bsz, s // segment, segment, *a.shape[2:]), 1, 0)

    # a segment's intermediates are made again in the backward pass, from
    # the state that entered it: what is kept is one state a segment
    final, o = jax.lax.scan(
        jax.checkpoint(lambda state, of_segment: _segment(
            state, of_segment, c, decay_dtype, solve)[::-1]),
        state, tuple(map(by_segment, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).reshape(bsz, s, h, -1), final


def _segment(state, of_segment, c: int, decay_dtype, solve: str):
    """The rule over one segment ``(q, k, v, g, beta)`` [B, S, H, ..] from
    the state that enters it: ``(o, the state that leaves it)``."""
    q, k, v, g, beta = of_segment
    if g.ndim == 4:
        return _segment_channel(state, of_segment, c, decay_dtype, solve)
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    nc = s // c
    compute = v.dtype
    f32 = jnp.float32

    def by_chunk(a):  # [B, S, H, ..] -> [B, nc, H, C, ..]
        return jnp.moveaxis(a.reshape(bsz, nc, c, *a.shape[2:]), 3, 2)

    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    beta_c = by_chunk(beta.astype(f32))  # [B, nc, H, C]
    gamma = jnp.cumsum(by_chunk(g.astype(f32)).astype(decay_dtype), axis=-1)
    last = gamma[..., -1:]
    i = jnp.arange(c)
    # exp of a masked difference: above the diagonal the difference is
    # positive and its exp may overflow before the mask would drop it
    decay = jnp.exp(jnp.where(
        i[:, None] >= i[None, :], gamma[..., :, None] - gamma[..., None, :],
        -jnp.inf)).astype(f32)  # Gamma [B, nc, H, C, C]
    from_start = jnp.exp(gamma).astype(f32)[..., None]  # [B, nc, H, C, 1]
    to_end = jnp.exp(last - gamma).astype(f32)[..., None]
    chunk_decay = jnp.exp(last).astype(f32)[..., None]  # [B, nc, H, 1, 1]

    # what no entering state is needed for, all chunks at once
    kk = jnp.einsum("bnhik,bnhjk->bnhij", kc, kc, preferred_element_type=f32)
    a = jnp.where(
        i[:, None] > i[None, :], beta_c[..., None] * kk * decay, 0.0)
    k32, v32 = kc.astype(f32), vc.astype(f32)
    wu = solve_unit_lower(a, beta_c[..., None] * jnp.concatenate(
        [k32 * from_start, v32], axis=-1), solve)
    w, u = wu[..., :dk].astype(compute), wu[..., dk:]
    k_to_end = (k32 * to_end).astype(compute)

    def one_chunk(state, of_chunk):
        w_c, u_c, k_c, decay_c = of_chunk
        entering = state.astype(compute)
        new = (u_c - jnp.einsum(
            "bhik,bhkv->bhiv", w_c, entering, preferred_element_type=f32)
        ).astype(compute)  # V' [B, H, C, dv]
        leaving = decay_c * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, new, preferred_element_type=f32)
        return leaving, (entering, new)

    final, (entering, new) = jax.lax.scan(
        one_chunk, state,
        tuple(jnp.moveaxis(t, 1, 0) for t in (w, u, k_to_end, chunk_decay)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, H, dk, dv]
    new = jnp.moveaxis(new, 0, 1)  # [B, nc, H, C, dv]

    # the outputs, all chunks at once: what the entering state answers, and
    # what the chunk's own writes up to the position do
    qk = jnp.einsum("bnhik,bnhjk->bnhij", qc, kc, preferred_element_type=f32)
    o = jnp.einsum(
        "bnhik,bnhkv->bnhiv", (qc.astype(f32) * from_start).astype(compute),
        entering, preferred_element_type=f32,
    ) + jnp.einsum(
        "bnhij,bnhjv->bnhiv", (qk * decay).astype(compute), new,
        preferred_element_type=f32)
    return jnp.moveaxis(o, 2, 3).reshape(bsz, s, h, dv).astype(compute), final


def _segment_channel(state, of_segment, c: int, decay_dtype, solve: str):
    """:func:`_segment` under a decay a KEY CHANNEL, ``g`` [B, S, H, dk]:
    ``gamma`` [.., C, dk] the sums a channel inside a chunk, and::

        A      = strictly_lower(diag(beta) sum_d K_id K_jd e^{gamma_id - gamma_jd})
        W, U   = (I + A)^-1 diag(beta) [K * e^gamma | V]
        V'     = U - W S
        O      = (Q * e^gamma) S + lower_incl(sum_d Q_id K_jd e^{gamma_id - gamma_jd}) V'
        S_next = Diag(e^{gamma_C}) S + (K * e^{gamma_C - gamma})^T V'

    ``Gamma`` is no masked difference of one sum a head but a product over
    the channels, so the decays go onto the operands: the rows of a
    :data:`SOLVE_BLOCK` of positions times ``e^{gamma - gamma_r}``,
    ``gamma_r`` the block's first row's sums (exponents in ``[-floor x 15,
    0]``), against the columns up to the block's end times ``e^{gamma_r -
    gamma}``: at most 0 before the block, at most ``floor x 15`` inside it
    (:func:`channel_decay_fits`), and the columns after it, where it would
    overflow, are not made at all (a masked infinity's gradient is not a
    number)."""
    q, k, v, g, beta = of_segment
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    nc = s // c
    compute = v.dtype
    f32 = jnp.float32
    block = min(SOLVE_BLOCK, c)
    if c % block:
        raise ValueError(
            f"a decay a key channel takes chunks of whole blocks of {block} "
            f"positions, got chunk={c}")

    def by_chunk(a):  # [B, S, H, ..] -> [B, nc, H, C, ..]
        return jnp.moveaxis(a.reshape(bsz, nc, c, *a.shape[2:]), 3, 2)

    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    beta_c = by_chunk(beta.astype(f32))  # [B, nc, H, C]
    gamma = jnp.cumsum(
        by_chunk(g.astype(f32)).astype(decay_dtype), axis=-2)  # [B, nc, H, C, dk]
    last = gamma[..., -1:, :]
    q32, k32, v32 = qc.astype(f32), kc.astype(f32), vc.astype(f32)
    i = jnp.arange(c)

    kk, qk = [], []  # a block of rows at a time: [.., block, C]
    for first in range(0, c, block):
        rows, cols = slice(first, first + block), slice(0, first + block)
        reference = gamma[..., first:first + 1, :]
        from_reference = jnp.exp(gamma[..., rows, :] - reference).astype(f32)
        to_reference = (k32[..., cols, :] * jnp.exp(
            reference - gamma[..., cols, :]).astype(f32)).astype(compute)
        beyond = [(0, 0)] * (to_reference.ndim - 2) + [(0, c - first - block), (0, 0)]
        to_reference = jnp.pad(to_reference, beyond)
        for scores, of in ((kk, k32), (qk, q32)):
            scores.append(jnp.einsum(
                "bnhik,bnhjk->bnhij",
                (of[..., rows, :] * from_reference).astype(compute),
                to_reference, preferred_element_type=f32))
    kk, qk = jnp.concatenate(kk, axis=-2), jnp.concatenate(qk, axis=-2)
    a = jnp.where(i[:, None] > i[None, :], beta_c[..., None] * kk, 0.0)
    scores = jnp.where(i[:, None] >= i[None, :], qk, 0.0)
    from_start = jnp.exp(gamma).astype(f32)  # [B, nc, H, C, dk]
    wu = solve_unit_lower(a, beta_c[..., None] * jnp.concatenate(
        [k32 * from_start, v32], axis=-1), solve)
    w, u = wu[..., :dk].astype(compute), wu[..., dk:]
    k_to_end = (k32 * jnp.exp(last - gamma).astype(f32)).astype(compute)
    chunk_decay = jnp.moveaxis(jnp.exp(last).astype(f32), -2, -1)  # [B, nc, H, dk, 1]

    def one_chunk(state, of_chunk):
        w_c, u_c, k_c, decay_c = of_chunk
        entering = state.astype(compute)
        new = (u_c - jnp.einsum(
            "bhik,bhkv->bhiv", w_c, entering, preferred_element_type=f32)
        ).astype(compute)  # V' [B, H, C, dv]
        leaving = decay_c * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_c, new, preferred_element_type=f32)
        return leaving, (entering, new)

    final, (entering, new) = jax.lax.scan(
        one_chunk, state,
        tuple(jnp.moveaxis(t, 1, 0) for t in (w, u, k_to_end, chunk_decay)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, nc, H, dk, dv]
    new = jnp.moveaxis(new, 0, 1)  # [B, nc, H, C, dv]
    o = jnp.einsum(
        "bnhik,bnhkv->bnhiv", (q32 * from_start).astype(compute),
        entering, preferred_element_type=f32,
    ) + jnp.einsum(
        "bnhij,bnhjv->bnhiv", scores.astype(compute), new,
        preferred_element_type=f32)
    return jnp.moveaxis(o, 2, 3).reshape(bsz, s, h, dv).astype(compute), final


# ---- the kernel: some heads a grid row, their chunks in sequence ----
#
# A grid step holds ``hg`` heads over ``t`` positions: their queries and
# keys heads first ([B H, S, dk]: a block spans the array's whole last axis,
# so keys of 96 need no lane multiple), their values and outputs as the
# mixer has them ([B, S, H dv]: two heads of 192 side by side are three
# lane tiles, and a head is cut out of them in VMEM), and the heads' decays'
# sums and strengths a head a ROW ([2 hg, t]: dense in HBM).  It takes them
# a FRAME of 128 positions at a time: the frame's chunks lie side by side on
# the diagonal of [128, 128] scores, so ``K K^T``, ``Gamma``, ``A``, the
# solve and ``Q K^T`` are whole-tile products for all of a frame's chunks
# at once, masked to the chunks' own blocks; then the chunks in sequence,
# the heads abreast, each against its state [dk, dv] float32 in a VMEM
# scratch that the next step of the row finds as this one left it.


def _dot(a, b, dims=(((1,), (0,)), ((), ())), precision=None):
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32)


def _as_row(col: jax.Array, eye: jax.Array) -> jax.Array:
    """[F, 1] -> [1, F] with no transpose: the diagonal of its broadcast."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _as_col(row: jax.Array, eye: jax.Array) -> jax.Array:
    """[1, F] -> [F, 1]."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _last_row(col: jax.Array, width: int) -> jax.Array:
    """The last entry of ``col`` [C, 1] as a row [1, width] (a [1, 1]
    cannot be broadcast along both axes)."""
    c = col.shape[0]
    return jnp.broadcast_to(col, (c, width))[c - 1:c, :]


def _block_inverses(a: jax.Array, i: jax.Array, j: jax.Array, block: int):
    """The inverses of ``I + a``'s diagonal blocks of ``block`` positions,
    on the diagonal of [F, F] and zeros elsewhere: forward substitution on
    the identity, a row at a time, in the PACKED layout [block, F]: row
    ``r`` of block ``b`` in lanes ``b block`` onwards, every lane in use, so
    a frame of 128 in blocks of 16 is two vector registers and a step two
    multiply-subtracts against a sublane broadcast of row ``step``.  The
    multiplier of a step is column ``step`` of each block spread over that
    block's lanes: it reads ``a`` alone, so all of them are made before the
    chain starts, ONE gather along the lanes each (exact: it moves entries
    and computes nothing; a chain of rolls costs its depth in the lane
    unit's latency: PERF.md section 6, PR 62)."""
    frame = a.shape[0]
    rows = min(8, block)  # a sublane tile: one whose rows are all final is left alone
    lane = j[:rows]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, frame), 0)
    first = lane - lane % block  # the first lane of a lane's block
    x, multipliers = [], []
    for r in range(0, block, rows):
        # rows r.. of every block side by side: the last block's, and over
        # each earlier block's lanes that block's own rows
        packed = a[frame - block + r:frame - block + r + rows]
        for n in reversed(range(0, frame - block, block)):
            packed = jnp.where(first == n, a[n + r:n + r + rows], packed)
        # the steps whose row lies above this tile's last: `a` is strictly
        # lower, so a row at or above `step` has a zero multiplier
        multipliers.append([
            jnp.take_along_axis(
                packed, first + step, axis=1, mode="promise_in_bounds")
            for step in range(min(r + rows, block) - 1)])
        x.append(jnp.where(lane - first == row + r, 1.0, 0.0))
    for step in range(block - 1):
        # the rows below `step` of every block lose a[., step] times row
        # `step`, which is final (above its diagonal `a` is zero)
        pivot = jnp.broadcast_to(
            x[step // rows][step % rows:step % rows + 1, :], (rows, frame))
        for t in range((step + 1) // rows, len(x)):
            x[t] = x[t] - multipliers[t][step] * pivot
    return jnp.where(
        i // block == j // block,
        jnp.concatenate(x * (frame // block), axis=0), 0.0)


def _join(x: jax.Array, a: jax.Array, i: jax.Array, j: jax.Array, width: int):
    """``(I + a)^-1`` inside diagonal blocks of ``2 width`` positions from
    ``x``, the same inside blocks of ``width``: ``X_21 = -T_22 A_21 T_11``.
    ``x`` is block-diagonal and ``A_21`` lies under the diagonal inside a
    pair, so the rows of a pair's FIRST block stay as they are: the two
    products at the highest precision take the second blocks' rows alone
    (whole sublane tiles, half the frame), the same dot products in the
    same order for every entry that is not a structural zero."""
    frame = a.shape[0]
    below = jnp.where(
        (i // width == j // width + 1) & (i // (2 * width) == j // (2 * width)),
        a, 0.0)
    seconds = range(width, frame, 2 * width)
    second = jnp.concatenate([x[n:n + width] for n in seconds], axis=0)
    second = second - _dot(
        _dot(second, below, precision=_HIGHEST), x, precision=_HIGHEST)
    return jnp.concatenate([
        rows for m, n in enumerate(seconds)
        for rows in (x[n - width:n], second[m * width:(m + 1) * width])], axis=0)


def _unit_lower_inverse(a: jax.Array, i: jax.Array, j: jax.Array, c: int):
    """``(I + a)^-1`` of ``a`` [F, F] float32, strictly lower-triangular
    inside diagonal blocks of ``c`` positions and zero outside them, by
    forward substitution on the identity, as :func:`solve_unit_lower`'s
    ``blocks``: a row at a time inside the diagonal blocks of
    :data:`SOLVE_BLOCK` positions (:func:`_block_inverses`: all of them at
    once, side by side on full lanes), then by blocks between them
    (:func:`_join`: two products at the highest precision a join, over the
    rows a join changes), the block doubling up to ``c``.  The arithmetic
    of an entry is the plain substitution's; no operand carries its
    structural zeros.  No partial result is larger than the inverse's own
    entries."""
    width = min(SOLVE_BLOCK, c)
    x = _block_inverses(a, i, j, width)
    while width < c:  # blocks of `width` are inverted: join them two and two
        x = _join(x, a, i, j, width)
        width *= 2
    return x


def _exact_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a b`` to float32's digits, ``a`` [F, F] float32: where ``b`` is
    bf16 (every digit it has survives a product with a bf16 part), ``a`` as
    the sum of three bf16 parts (its 24 bits of mantissa, 8 a part), each
    part's product with ``b`` exact in the float32 accumulator: ONE product
    of the parts one under the other, three passes of the MXU where the
    highest precision takes six and splits ``b`` besides."""
    if b.dtype != jnp.bfloat16:
        return _dot(a, b.astype(jnp.float32), precision=_HIGHEST)
    f32, bf16 = jnp.float32, jnp.bfloat16
    high = a.astype(bf16)
    rest = a - high.astype(f32)
    middle = rest.astype(bf16)
    low = (rest - middle.astype(f32)).astype(bf16)
    f = a.shape[0]
    parts = _dot(jnp.concatenate([high, middle, low], axis=0), b)
    return parts[:f] + parts[f:2 * f] + parts[2 * f:]


def _unit(x, scale):
    """:func:`unit_length` of one head's frame [F, dk]: ``(the rows at unit
    length times ``scale`` in x's dtype, the same in float32 before the
    scale, the lengths' inverses [F, 1])``."""
    x32 = x.astype(jnp.float32)
    inverse = jax.lax.rsqrt(jnp.sum(x32 * x32, axis=1, keepdims=True) + UNIT_EPS)
    unit = x32 * inverse
    return (unit if scale is None else unit * scale).astype(x.dtype), unit, inverse


def _through_unit(dy, unit, inverse, scale):
    """The cotangent of :func:`_unit`'s input from its output's: ``scale /
    length (dy - unit (unit . dy))``."""
    dx = inverse * (dy - unit * jnp.sum(unit * dy, axis=1, keepdims=True))
    return dx if scale is None else dx * scale


def _frame(q, k, v, gcol, bcol, i, j, c: int, unit: bool) -> dict:
    """What forward and backward both make of one head's frame (``q``,
    ``k`` [F, dk], ``v`` [F, dv], the sums ``gcol`` and the strengths
    ``bcol`` [F, 1]) before any state is read."""
    f32 = jnp.float32
    compute = v.dtype
    eye = i == j
    units = None
    if unit:  # q and k as the convolution left them
        scale = q.shape[1] ** -0.5
        q, *of_q = _unit(q, scale)
        k, *of_k = _unit(k, None)
        units = (*of_q, scale), (*of_k, None)
    grow = _as_row(gcol, eye)
    # exp of a masked difference: above the diagonal the difference is
    # positive and its exp may overflow before a mask would drop it
    decay = jnp.exp(jnp.where(
        (i // c == j // c) & (i >= j), gcol - grow, -jnp.inf))  # Gamma
    m = jnp.where(i > j, _dot(k, k, _NT) * decay, 0.0)
    a = bcol * m
    t = _unit_lower_inverse(a, i, j, c)
    k32, v32 = k.astype(f32), v.astype(f32)
    from_start = jnp.exp(gcol)  # [F, 1]
    last = jnp.concatenate([  # a chunk's last sum under each of its rows
        jnp.broadcast_to(gcol[n + c - 1:n + c, :], (c, 1))
        for n in range(0, gcol.shape[0], c)], axis=0)
    to_end = jnp.exp(last - gcol)
    k_from_start = k32 * from_start
    rk = bcol * k_from_start
    # T R with beta (and e^gamma) moved onto T's columns: the other operand
    # is then k or v as it came
    w = _exact_dot(t * _as_row(bcol * from_start, eye), k)
    return dict(
        q=q, k=k, units=units,
        gcol=gcol, bcol=bcol, eye=eye, decay=decay, m=m, a=a, t=t, k32=k32,
        v32=v32, from_start=from_start, to_end=to_end,
        k_from_start=k_from_start, rk=rk, w=w,
        u=_exact_dot(t * _as_row(bcol, eye), v), w_c=w.astype(compute),
        k_to_end=(k32 * to_end).astype(compute),
        scores=_dot(q, k, _NT) * decay,
        q_from_start=(q.astype(f32) * from_start).astype(compute))


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *rest, hg, c, keep, unit):
    entering_ref = rest[0] if keep else None
    final_ref, state = rest[-2:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if keep:  # the state entering the grid step: the backward's residual
        entering_ref[...] = state[...]
    cols = _columns(rows_ref[...])  # lane h: head h's sums; hg + h: its strengths
    dv = v_ref.shape[1] // hg
    compute = v_ref.dtype
    i = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 1)
    for first in range(0, q_ref.shape[1], FRAME):
        at = slice(first, first + FRAME)
        made = [_frame(q_ref[h, at, :], k_ref[h, at, :],
                       v_ref[at, h * dv:(h + 1) * dv], cols[at, h:h + 1],
                       cols[at, hg + h:hg + h + 1], i, j, c, unit)
                for h in range(hg)]
        news = [[] for _ in made]
        answered = [[] for _ in made]
        for n in range(0, FRAME, c):  # the chunks in sequence, the heads abreast
            of = slice(n, n + c)
            for h, x in enumerate(made):
                s = state[h]
                # what the entering state answers for the keys as the solve
                # left them and for the queries: one product
                both = _dot(jnp.concatenate(
                    [x["w_c"][of], x["q_from_start"][of]], axis=0), s.astype(compute))
                new = (x["u"][of] - both[:c]).astype(compute)  # V'
                news[h].append(new)
                answered[h].append(both[c:])
                state[h] = jnp.exp(_last_row(x["gcol"][of], dv)) * s + _dot(
                    x["k_to_end"][of], new, _TN)
        for h, x in enumerate(made):
            o_ref[at, h * dv:(h + 1) * dv] = (
                jnp.concatenate(answered[h], axis=0) + _dot(
                    x["scores"].astype(compute), jnp.concatenate(news[h], axis=0))
            ).astype(compute)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, entering_ref, do_ref, dfinal_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, dstate, states, *, hg, c, unit):
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)  # the row's LAST step: they run in reverse
    def _():
        dstate[...] = dfinal_ref[...]

    cols = _columns(rows_ref[...])
    dv = v_ref.shape[1] // hg
    compute = v_ref.dtype
    i = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (FRAME, _LANES), 1)
    ends = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    frames = range(0, q_ref.shape[1], FRAME)
    # the step's chunks FORWARD first, from the ONE state the forward kept
    # of the step: the products and roundings of ``_fwd_kernel``, so the
    # state entering each chunk is the one the forward carried, to the bit
    states[0] = entering_ref[...]
    made = {}
    for first in frames:
        at = slice(first, first + FRAME)
        made[first] = [dict(_frame(
            q_ref[h, at, :], k_ref[h, at, :], v_ref[at, h * dv:(h + 1) * dv],
            cols[at, h:h + 1], cols[at, hg + h:hg + h + 1], i, j, c, unit), news={})
            for h in range(hg)]
        for n in range(0, FRAME, c):
            of = slice(n, n + c)
            nth = (first + n) // c
            for h, x in enumerate(made[first]):
                s = states[nth, h]
                new = (x["u"][of] - _dot(x["w_c"][of], s.astype(compute))
                       ).astype(compute)  # V'
                x["news"][n] = new
                if nth + 1 < states.shape[0]:  # the step's last state: unread
                    states[nth + 1, h] = jnp.exp(
                        _last_row(x["gcol"][of], dv)) * s + _dot(
                            x["k_to_end"][of], new, _TN)
    dcols = []  # as ``cols``, a frame at a time, the last frame first
    for first in reversed(frames):
        at = slice(first, first + FRAME)
        for h, x in enumerate(made[first]):
            do = do_ref[at, h * dv:(h + 1) * dv]
            # what the chunks' own writes hand back, before any state
            x.update(do=do, chain={},
                     dnew=_dot(x["scores"].astype(compute), do, _TN))
        for n in reversed(range(0, FRAME, c)):  # the chunks in reverse
            of = slice(n, n + c)
            for h, x in enumerate(made[first]):
                s = states[(first + n) // c, h]
                entering = s.astype(compute)
                w_c = x["w_c"][of]
                new = x["news"][n]
                dleaving = dstate[h]
                dleaving_c = dleaving.astype(compute)
                dnew = x["dnew"][of] + _dot(x["k_to_end"][of], dleaving_c)
                # the cotangents of the queries and of the keys as the solve
                # left them: one product with the entering state; the
                # entering state's own: one more
                upon = jnp.concatenate([x["do"][of], -dnew.astype(compute)], axis=0)
                both = _dot(upon, entering, _NT)  # [2 C, dk]
                chunk_decay = jnp.exp(_last_row(x["gcol"][of], dv))  # [1, dv]
                dstate[h] = chunk_decay * dleaving + _dot(jnp.concatenate(
                    [x["q_from_start"][of], w_c], axis=0), upon, _TN)
                x["chain"][n] = dict(
                    dnew=dnew, dk_to_end=_dot(new, dleaving_c, _NT),
                    dq_from_start=both[:c], dw=both[c:],
                    # the chunk's last sum scales the leaving state whole
                    dlast=jnp.sum(
                        jnp.sum(dleaving * s, axis=0, keepdims=True) * chunk_decay,
                        axis=1, keepdims=True))
        dcol = jnp.zeros((FRAME, _LANES), f32)
        for h, x in enumerate(made[first]):
            q, k, bcol, decay = x["q"], x["k"], x["bcol"], x["decay"]
            chain = [x["chain"][n] for n in range(0, FRAME, c)]
            new = jnp.concatenate(
                [x["news"][n] for n in range(0, FRAME, c)], axis=0)
            dnew, dk_to_end, dq_from_start, dw = (
                jnp.concatenate([of[name] for of in chain], axis=0)
                for name in ("dnew", "dk_to_end", "dq_from_start", "dw"))
            dscores = _dot(x["do"], new, _NT)  # [F, F]

            # through the solve: two more with the same factor
            drk = _dot(x["t"], dw, _TN, precision=_HIGHEST)
            drv = _dot(x["t"], dnew, _TN, precision=_HIGHEST)
            da = -jnp.where(
                i > j,
                _dot(drk, x["w"], _NT, precision=_HIGHEST)
                + _dot(drv, x["u"], _NT, precision=_HIGHEST), 0.0)
            dbeta = (
                jnp.sum(drk * x["k_from_start"], axis=1, keepdims=True)
                + jnp.sum(drv * x["v32"], axis=1, keepdims=True)
                + jnp.sum(da * x["m"], axis=1, keepdims=True))
            dv_ref[at, h * dv:(h + 1) * dv] = (bcol * drv).astype(compute)
            dgamma = jnp.sum(drk * x["rk"], axis=1, keepdims=True)
            dk32 = drk * (bcol * x["from_start"])

            # through the scores inside the chunks and their decays: what
            # passes d/d(gamma_i - gamma_j) raises what i reads and lowers
            # what j hands on.  Both are sums of the same entries
            dkk = (da * bcol * decay).astype(compute)
            through_decay = da * x["a"] + dscores * x["scores"]
            dgamma = dgamma + jnp.sum(through_decay, axis=1, keepdims=True) - _as_col(
                jnp.sum(through_decay, axis=0, keepdims=True), x["eye"])
            dqk = (dscores * decay).astype(compute)
            dk32 = dk32 + _dot(dkk, k) + _dot(dkk, k, _TN) + _dot(dqk, q, _TN)
            dq32 = _dot(dqk, k) + dq_from_start * x["from_start"]
            dgamma = dgamma + jnp.sum(
                dq_from_start * q.astype(f32) * x["from_start"], axis=1, keepdims=True)
            dk32 = dk32 + dk_to_end * x["to_end"]
            # a position's sum lowers what it hands to the leaving state; the
            # chunk's last one raises all of it
            handed_on = jnp.sum(
                dk_to_end * x["k32"] * x["to_end"], axis=1, keepdims=True)
            dgamma = dgamma - handed_on + jnp.concatenate([
                jnp.where(ends, jnp.broadcast_to(
                    of["dlast"] + jnp.sum(
                        handed_on[n:n + c], axis=0, keepdims=True), (c, 1)), 0.0)
                for n, of in zip(range(0, FRAME, c), chain)], axis=0)
            if unit:
                dq32 = _through_unit(dq32, *x["units"][0])
                dk32 = _through_unit(dk32, *x["units"][1])
            dq_ref[h, at, :] = dq32.astype(compute)
            dk_ref[h, at, :] = dk32.astype(compute)
            dcol = jnp.where(lane == h, dgamma, dcol)
            dcol = jnp.where(lane == hg + h, dbeta, dcol)
        dcols.append(dcol)
    drows_ref[...] = jnp.concatenate(dcols[::-1], axis=0).T[:2 * hg, :]


# ---- the kernels under a decay a KEY CHANNEL ----
#
# ``Gamma`` is no masked difference of one sum a head: the decays go onto the
# operands.  A SPAN of ``2 SOLVE_BLOCK`` positions (32) takes ONE reference,
# the sums of its middle row: rows times ``e^{gamma - gamma_mid}``, columns
# times ``e^{gamma_mid - gamma}``, both within ``e^{+-16 floor}``
# (``channel_decay_fits``: 80), their product over the channels the exact
# ``e^{gamma_i - gamma_j} <= 1`` wherever ``i >= j``; the pairs of a chunk's
# second span with its first take the second span's FIRST row as reference
# (both exponents non-positive).  So a frame's scores are two whole-tile
# products (``[K; Q]`` stacked against the columns), masked to the entries
# each is right for; what they hold elsewhere (sums of products up to
# ``e^160``: infinities, not-a-numbers) is dropped by ``where``, never
# multiplied, and the backward is written out, so no mask's gradient is ever
# taken.  The state's decay is a ROW scaling, ``Diag(e^{gamma_C}) S``.


def _rows_of(gam: jax.Array, firsts, span: int) -> jax.Array:
    """``gam`` [F, dk]'s row ``firsts[n]`` under each of the ``span`` rows of
    span ``n``."""
    return jnp.concatenate([
        jnp.broadcast_to(gam[m:m + 1, :], (span, gam.shape[1])) for m in firsts],
        axis=0)


def _channel_frame(q, k, v, gam, bcol, i, j, c: int, unit: bool) -> dict:
    """:func:`_frame` under a decay a key channel: ``gam`` [F, dk] the sums
    a channel inside the frame's chunks."""
    f32 = jnp.float32
    compute = v.dtype
    frame = gam.shape[0]
    eye = i == j
    units = None
    if unit:  # q and k as the convolution left them
        scale = q.shape[1] ** -0.5
        q, *of_q = _unit(q, scale)
        k, *of_k = _unit(k, None)
        units = (*of_q, scale), (*of_k, None)
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    span = min(2 * SOLVE_BLOCK, c)
    starts = range(0, frame, span)
    mid = _rows_of(gam, [n + span // 2 for n in starts], span)
    e_rd, e_cd = jnp.exp(gam - mid), jnp.exp(mid - gam)
    same_chunk = i // c == j // c
    same_span = i // span == j // span
    stacked = jnp.concatenate(
        [(k32 * e_rd).astype(compute), (q32 * e_rd).astype(compute)], axis=0)
    k_cd = (k32 * e_cd).astype(compute)
    both = _dot(stacked, k_cd, _NT)  # [2 F, F]
    kk = jnp.where(same_span & (i > j), both[:frame], 0.0)
    qk = jnp.where(same_span & (i >= j), both[frame:], 0.0)
    made = dict(e_rd=e_rd, e_cd=e_cd, rows_d=stacked, k_cd=k_cd, same_span=same_span)
    if span < c:  # a chunk's second span against its first
        first = _rows_of(gam, starts, span)  # a span's first row's sums
        following = _rows_of(  # the NEXT span's, under a chunk's first span
            gam, [n + span if (n + span) % c else n for n in starts], span)
        e_ro = jnp.exp(gam - first)
        leads = jax.lax.broadcasted_iota(jnp.int32, (frame, 1), 0) % c < span
        e_co = jnp.where(leads, jnp.exp(following - gam), 0.0)
        stacked_o = jnp.concatenate(
            [(k32 * e_ro).astype(compute), (q32 * e_ro).astype(compute)], axis=0)
        k_co = (k32 * e_co).astype(compute)
        both_o = _dot(stacked_o, k_co, _NT)
        off = same_chunk & ~same_span & (i > j)
        kk = kk + jnp.where(off, both_o[:frame], 0.0)
        qk = qk + jnp.where(off, both_o[frame:], 0.0)
        made.update(e_ro=e_ro, e_co=e_co, rows_o=stacked_o, k_co=k_co, off=off)
    a = bcol * kk
    t = _unit_lower_inverse(a, i, j, c)
    from_start = jnp.exp(gam)
    last = _rows_of(gam, [n + c - 1 for n in range(0, frame, c)], c)
    to_end = jnp.exp(last - gam)
    k_from_start = k32 * from_start
    t_beta = t * _as_row(bcol, eye)
    w = _exact_dot(t_beta, k_from_start.astype(compute))
    return dict(
        made, q=q, k=k, q32=q32, k32=k32, v32=v32, units=units, gam=gam,
        bcol=bcol, eye=eye, m=kk, a=a, t=t, from_start=from_start,
        to_end=to_end, k_from_start=k_from_start, rk=bcol * k_from_start, w=w,
        u=_exact_dot(t_beta, v), w_c=w.astype(compute),
        k_to_end=(k32 * to_end).astype(compute), scores=qk,
        q_from_start=(q32 * from_start).astype(compute))


def _chunk_decay(x: dict, of: slice, eye_k: jax.Array) -> jax.Array:
    """``e^{gamma_C}`` of the chunk ``of`` as a column [dk, 1]: what scales
    the state's rows."""
    return _as_col(jnp.exp(x["gam"][of.stop - 1:of.stop, :]), eye_k)


def _channel_fwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, o_ref, *rest,
                        hg, c, keep, unit):
    entering_ref = rest[0] if keep else None
    final_ref, state = rest[-2:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if keep:  # the state entering the grid step: the backward's residual
        entering_ref[...] = state[...]
    cols = _columns(rows_ref[...])  # lane hg + h: head h's strengths
    dk, dv = q_ref.shape[2], v_ref.shape[1] // hg
    compute = v_ref.dtype
    i = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 1)
    eye_k = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    for first in range(0, q_ref.shape[1], FRAME):
        at = slice(first, first + FRAME)
        made = [_channel_frame(
            q_ref[h, at, :], k_ref[h, at, :], v_ref[at, h * dv:(h + 1) * dv],
            g_ref[h, at, :], cols[at, hg + h:hg + h + 1], i, j, c, unit)
            for h in range(hg)]
        news = [[] for _ in made]
        answered = [[] for _ in made]
        for n in range(0, FRAME, c):  # the chunks in sequence, the heads abreast
            of = slice(n, n + c)
            for h, x in enumerate(made):
                s = state[h]
                both = _dot(jnp.concatenate(
                    [x["w_c"][of], x["q_from_start"][of]], axis=0), s.astype(compute))
                new = (x["u"][of] - both[:c]).astype(compute)  # V'
                news[h].append(new)
                answered[h].append(both[c:])
                state[h] = _chunk_decay(x, of, eye_k) * s + _dot(
                    x["k_to_end"][of], new, _TN)
        for h, x in enumerate(made):
            o_ref[at, h * dv:(h + 1) * dv] = (
                jnp.concatenate(answered[h], axis=0) + _dot(
                    x["scores"].astype(compute), jnp.concatenate(news[h], axis=0))
            ).astype(compute)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        final_ref[...] = state[...]


def _channel_bwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, entering_ref,
                        do_ref, dfinal_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                        drows_ref, dstate, states, *, hg, c, unit):
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)  # the row's LAST step: they run in reverse
    def _():
        dstate[...] = dfinal_ref[...]

    cols = _columns(rows_ref[...])
    dk, dv = q_ref.shape[2], v_ref.shape[1] // hg
    compute = v_ref.dtype
    i = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (FRAME, FRAME), 1)
    eye_k = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (FRAME, _LANES), 1)
    ends = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    same_chunk = i // c == j // c
    frames = range(0, q_ref.shape[1], FRAME)
    chunks = range(0, FRAME, c)
    # the step's chunks FORWARD first, from the ONE state the forward kept
    # of the step, with the forward kernel's products and roundings
    states[0] = entering_ref[...]
    made = {}
    for first in frames:
        at = slice(first, first + FRAME)
        made[first] = [dict(_channel_frame(
            q_ref[h, at, :], k_ref[h, at, :], v_ref[at, h * dv:(h + 1) * dv],
            g_ref[h, at, :], cols[at, hg + h:hg + h + 1], i, j, c, unit), news={})
            for h in range(hg)]
        for n in chunks:
            of = slice(n, n + c)
            nth = (first + n) // c
            for h, x in enumerate(made[first]):
                s = states[nth, h]
                new = (x["u"][of] - _dot(x["w_c"][of], s.astype(compute))
                       ).astype(compute)  # V'
                x["news"][n] = new
                if nth + 1 < states.shape[0]:  # the step's last state: unread
                    states[nth + 1, h] = _chunk_decay(x, of, eye_k) * s + _dot(
                        x["k_to_end"][of], new, _TN)
    dcols = []  # the strengths' gradients, a frame at a time, the last first
    for first in reversed(frames):
        at = slice(first, first + FRAME)
        for h, x in enumerate(made[first]):
            do = do_ref[at, h * dv:(h + 1) * dv]
            x.update(do=do, chain={},
                     dnew=_dot(x["scores"].astype(compute), do, _TN))
        for n in reversed(chunks):  # the chunks in reverse
            of = slice(n, n + c)
            for h, x in enumerate(made[first]):
                s = states[(first + n) // c, h]
                entering = s.astype(compute)
                w_c = x["w_c"][of]
                new = x["news"][n]
                dleaving = dstate[h]
                dleaving_c = dleaving.astype(compute)
                dnew = x["dnew"][of] + _dot(x["k_to_end"][of], dleaving_c)
                upon = jnp.concatenate([x["do"][of], -dnew.astype(compute)], axis=0)
                both = _dot(upon, entering, _NT)  # [2 C, dk]
                chunk_decay = _chunk_decay(x, of, eye_k)  # [dk, 1]
                dstate[h] = chunk_decay * dleaving + _dot(jnp.concatenate(
                    [x["q_from_start"][of], w_c], axis=0), upon, _TN)
                x["chain"][n] = dict(
                    dnew=dnew, dk_to_end=_dot(new, dleaving_c, _NT),
                    dq_from_start=both[:c], dw=both[c:],
                    # the chunk's last sums scale the leaving state's rows
                    dlast=_as_row(jnp.sum(
                        dleaving * s, axis=1, keepdims=True) * chunk_decay, eye_k))
        dcol = jnp.zeros((FRAME, _LANES), f32)
        for h, x in enumerate(made[first]):
            q32, k32, bcol = x["q32"], x["k32"], x["bcol"]
            chain = [x["chain"][n] for n in chunks]
            new = jnp.concatenate([x["news"][n] for n in chunks], axis=0)
            dnew, dk_to_end, dq_from_start, dw = (
                jnp.concatenate([of[name] for of in chain], axis=0)
                for name in ("dnew", "dk_to_end", "dq_from_start", "dw"))
            dscores = jnp.where(same_chunk & (i >= j), _dot(x["do"], new, _NT), 0.0)

            # through the solve: two more with the same factor
            drk = _dot(x["t"], dw, _TN, precision=_HIGHEST)
            drv = _dot(x["t"], dnew, _TN, precision=_HIGHEST)
            da = -jnp.where(
                same_chunk & (i > j),
                _dot(drk, x["w"], _NT, precision=_HIGHEST)
                + _dot(drv, x["u"], _NT, precision=_HIGHEST), 0.0)
            dbeta = (
                jnp.sum(drk * x["k_from_start"], axis=1, keepdims=True)
                + jnp.sum(drv * x["v32"], axis=1, keepdims=True)
                + jnp.sum(da * x["m"], axis=1, keepdims=True))
            dv_ref[at, h * dv:(h + 1) * dv] = (bcol * drv).astype(compute)
            dgam = drk * x["rk"]
            dk32 = drk * (bcol * x["from_start"])

            # through the scores: dM's and dP's rows against the columns'
            # operand, their transposes against the rows', a reference each
            dm = da * bcol

            def through(which, rows, cols_of, e_r, e_c):
                dkk = jnp.where(which, dm, 0.0).astype(compute)
                dqk = jnp.where(which, dscores, 0.0).astype(compute)
                by_row = _dot(jnp.concatenate([dkk, dqk], axis=0), cols_of)
                by_col = _dot(jnp.concatenate([dkk, dqk], axis=0), rows, _TN)
                return (by_row[:FRAME] * e_r, by_row[FRAME:] * e_r, by_col * e_c)

            g_k, g_q, g_col = through(
                x["same_span"], x["rows_d"], x["k_cd"], x["e_rd"], x["e_cd"])
            if "off" in x:
                more = through(x["off"], x["rows_o"], x["k_co"], x["e_ro"], x["e_co"])
                g_k, g_q, g_col = g_k + more[0], g_q + more[1], g_col + more[2]
            dk32 = dk32 + g_k + g_col
            dq32 = g_q + dq_from_start * x["from_start"]
            dgam = dgam + k32 * (g_k - g_col) + q32 * g_q
            dgam = dgam + dq_from_start * q32 * x["from_start"]
            dk32 = dk32 + dk_to_end * x["to_end"]
            # a position's sums lower what it hands to the leaving state; the
            # chunk's last raise all of it
            handed_on = dk_to_end * k32 * x["to_end"]  # [F, dk]
            dgam = dgam - handed_on + jnp.concatenate([
                jnp.where(ends, of["dlast"] + jnp.sum(
                    handed_on[n:n + c], axis=0, keepdims=True), 0.0)
                for n, of in zip(chunks, chain)], axis=0)
            if unit:
                dq32 = _through_unit(dq32, *x["units"][0])
                dk32 = _through_unit(dk32, *x["units"][1])
            dq_ref[h, at, :] = dq32.astype(compute)
            dk_ref[h, at, :] = dk32.astype(compute)
            dg_ref[h, at, :] = dgam
            dcol = jnp.where(lane == hg + h, dbeta, dcol)
        dcols.append(dcol)
    drows_ref[...] = jnp.concatenate(dcols[::-1], axis=0).T[:2 * hg, :]


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
# the channel form's frame holds a dozen [F, dk] float32 arrays more a head
# (the operands' scalings both ways): beyond the 16 MB Mosaic scopes by
# default, within the chip's 128 MB of VMEM
_CHANNEL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=96 * 1024 * 1024)


def _layout(q, v, c: int, order) -> tuple:
    """``(hg, steps a row, dv)`` of a call on ``q`` [B H, S, dk] and ``v``
    [B, S, H dv] and its block specs for grid row ``r`` (``hg`` heads of one
    batch row) and step ``n``, which holds positions ``order(n) t``
    onwards: of ``q``'s kind, of ``v``'s, of the heads' rows [B H / hg, 2
    hg, S], of the states entering the steps [B H / hg, S / t, hg, dk, dv]
    (a step's own: [hg, dk, dv]) and of one state a head."""
    rows, s, dk = q.shape
    h = rows // v.shape[0]
    dv = v.shape[2] // h
    hg, t = _grid(h, s, c, dk, dv)
    return (hg, s // t, dv), {
        "qk": pl.BlockSpec((hg, t, dk), lambda r, n: (r, order(n), 0)),
        "v": pl.BlockSpec(
            (None, t, hg * dv),
            lambda r, n: (r // (h // hg), order(n), r % (h // hg))),
        "rows": pl.BlockSpec((None, 2 * hg, t), lambda r, n: (r, 0, order(n))),
        "entering": pl.BlockSpec(
            (None, None, hg, dk, dv), lambda r, n: (r, order(n), 0, 0, 0)),
        "state": pl.BlockSpec((hg, dk, dv), lambda r, n: (r, 0, 0)),
    }


def _head_rows(gamma: jax.Array, beta: jax.Array, hg: int) -> jax.Array:
    """Two [B H, S] a head -> [B H / hg, 2 hg, S]: a grid row's heads a
    row each, ``gamma``'s rows then ``beta``'s."""
    rows, s = gamma.shape
    return jnp.concatenate(
        [gamma.reshape(rows // hg, hg, s), beta.reshape(rows // hg, hg, s)], axis=1)


def _forward(q, k, v, gamma, beta, c, unit, keep, interpret):
    """``(o [B, S, H dv], the state after the last position [B H, dk, dv]
    float32)`` and, between them where ``keep``, the state entering each
    grid step [B H / hg, S / t, hg, dk, dv] float32: the backward's
    residual (``t`` the step's positions: :func:`_grid`)."""
    rows, s, dk = q.shape
    (hg, steps, dv), spec = _layout(q, v, c, lambda n: n)
    entering = [(spec["entering"], jax.ShapeDtypeStruct(
        (rows // hg, steps, hg, dk, dv), jnp.float32))] if keep else []
    out_specs, out_shape = zip(
        (spec["v"], jax.ShapeDtypeStruct(v.shape, v.dtype)), *entering,
        (spec["state"], jax.ShapeDtypeStruct((rows, dk, dv), jnp.float32)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hg=hg, c=c, keep=keep, unit=unit),
        grid=(rows // hg, steps),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["rows"]],
        out_specs=list(out_specs), out_shape=list(out_shape),
        scratch_shapes=[pltpu.VMEM((hg, dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="delta_chunk_fwd",
    )(q, k, v, _head_rows(gamma, beta, hg))


def _backward(q, k, v, gamma, beta, entering, do, dfinal, c, unit, interpret):
    """The gradients of ``q``, ``k``, ``v``, ``gamma`` and ``beta``, the
    chunks in reverse, the state's cotangent carried as the forward
    carries the state; ``entering`` the state entering each grid step, from
    which a step rebuilds its chunks' in a VMEM scratch."""
    rows, s, _ = q.shape
    (hg, steps, _), spec = _layout(q, v, c, lambda n: steps - 1 - n)
    dq, dk, dv, drows = pl.pallas_call(
        functools.partial(_bwd_kernel, hg=hg, c=c, unit=unit),
        grid=(rows // hg, steps),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["rows"],
                  spec["entering"], spec["v"], spec["state"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((rows // hg, 2 * hg, s), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM(entering.shape[2:], jnp.float32),  # the state's cotangent
            pltpu.VMEM((s // steps // c, *entering.shape[2:]), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="delta_chunk_bwd",
    )(q, k, v, _head_rows(gamma, beta, hg), entering, do, dfinal)
    dgamma, dbeta = drows.reshape(rows // hg, 2, hg, s).swapaxes(0, 1)
    return dq, dk, dv, dgamma.reshape(rows, s), dbeta.reshape(rows, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, gamma, beta, c, unit, interpret):
    return _forward(q, k, v, gamma, beta, c, unit, False, interpret)


def _rule_fwd(q, k, v, gamma, beta, c, unit, interpret):
    o, entering, final = _forward(q, k, v, gamma, beta, c, unit, True, interpret)
    o = checkpoint_name(o, DELTA_RESIDUALS)
    entering = checkpoint_name(entering, DELTA_RESIDUALS)
    return (o, final), (q, k, v, gamma, beta, entering)


def _rule_bwd(c, unit, interpret, residuals, cotangents):
    return _backward(*residuals, *cotangents, c, unit, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _channel_forward(q, k, v, gamma, beta, c, unit, keep, interpret):
    """:func:`_forward` under a decay a key channel: ``gamma`` [B H, S, dk]
    the sums a channel, a block of ``q``'s kind."""
    rows, s, dk = q.shape
    (hg, steps, dv), spec = _layout(q, v, c, lambda n: n)
    entering = [(spec["entering"], jax.ShapeDtypeStruct(
        (rows // hg, steps, hg, dk, dv), jnp.float32))] if keep else []
    out_specs, out_shape = zip(
        (spec["v"], jax.ShapeDtypeStruct(v.shape, v.dtype)), *entering,
        (spec["state"], jax.ShapeDtypeStruct((rows, dk, dv), jnp.float32)))
    return pl.pallas_call(
        functools.partial(_channel_fwd_kernel, hg=hg, c=c, keep=keep, unit=unit),
        grid=(rows // hg, steps),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["rows"]],
        out_specs=list(out_specs), out_shape=list(out_shape),
        scratch_shapes=[pltpu.VMEM((hg, dk, dv), jnp.float32)],
        compiler_params=_CHANNEL_PARAMS, interpret=interpret,
        name="delta_channel_fwd",
    )(q, k, v, gamma, _head_rows(beta, beta, hg))


def _channel_backward(q, k, v, gamma, beta, entering, do, dfinal, c, unit,
                      interpret):
    """:func:`_backward` under a decay a key channel: the sums' gradient is
    an array of ``gamma``'s shape."""
    rows, s, _ = q.shape
    (hg, steps, _), spec = _layout(q, v, c, lambda n: steps - 1 - n)
    dq, dk, dv, dgamma, drows = pl.pallas_call(
        functools.partial(_channel_bwd_kernel, hg=hg, c=c, unit=unit),
        grid=(rows // hg, steps),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["rows"],
                  spec["entering"], spec["v"], spec["state"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["qk"], spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gamma.shape, jnp.float32),
                   jax.ShapeDtypeStruct((rows // hg, 2 * hg, s), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM(entering.shape[2:], jnp.float32),  # the state's cotangent
            pltpu.VMEM((s // steps // c, *entering.shape[2:]), jnp.float32)],
        compiler_params=_CHANNEL_PARAMS, interpret=interpret,
        name="delta_channel_bwd",
    )(q, k, v, gamma, _head_rows(beta, beta, hg), entering, do, dfinal)
    dbeta = drows.reshape(rows // hg, 2, hg, s)[:, 1]
    return dq, dk, dv, dgamma, dbeta.reshape(rows, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _channel_rule(q, k, v, gamma, beta, c, unit, interpret):
    return _channel_forward(q, k, v, gamma, beta, c, unit, False, interpret)


def _channel_rule_fwd(q, k, v, gamma, beta, c, unit, interpret):
    o, entering, final = _channel_forward(
        q, k, v, gamma, beta, c, unit, True, interpret)
    o = checkpoint_name(o, DELTA_RESIDUALS)
    entering = checkpoint_name(entering, DELTA_RESIDUALS)
    return (o, final), (q, k, v, gamma, beta, entering)


def _channel_rule_bwd(c, unit, interpret, residuals, cotangents):
    return _channel_backward(*residuals, *cotangents, c, unit, interpret)


_channel_rule.defvjp(_channel_rule_fwd, _channel_rule_bwd)


def gated_delta_kernel(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int, interpret: bool = False, unit: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """:func:`gated_delta_chunked` as two Pallas TPU kernels
    (``delta_chunk_fwd`` and, behind a ``jax.custom_vjp``,
    ``delta_chunk_bwd``) for shapes :func:`kernel_fits` admits;
    ``interpret`` runs them on any backend.  The decays' sums inside the
    chunks are made here, in XLA, as the plain form makes them, and their
    gradient back to ``g`` is autodiff's; so are the transposes that put
    the heads of ``q`` and ``k`` first (``v`` and ``o`` stay as they are).
    Under ``unit`` the kernels make ``q`` and ``k`` unit-length in VMEM, so
    what XLA transposes is the convolution's bf16 output as it stands: left
    to make them itself and hand them over heads first, it copies the
    [B, S, 2 H dk] float32 twice a call (377 MB each in the cell)."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    if _grid(h, s, c, dk, dv) is None:
        raise ValueError(
            f"the delta rule's kernel has no tiles for {q.shape} / {v.shape} "
            f"in chunks of {c} (ops/delta_rule.py _grid)")
    f32 = jnp.float32

    def heads_first(a):  # [B, S, H, ..] -> [B H, S, ..]
        return jnp.moveaxis(a, 2, 1).reshape(bsz * h, s, *a.shape[3:])

    if g.ndim == 4:  # a decay a key channel: the sums are a block of q's kind
        if not channel_kernel_fits(c, dk):
            raise ValueError(
                f"the channel-decayed kernel takes chunks of one or two spans "
                f"of {2 * SOLVE_BLOCK} positions, got {c}")
        gamma = jnp.cumsum(
            g.astype(f32).reshape(bsz, s // c, c, h, dk), axis=2
        ).reshape(bsz, s, h, dk)
        o, final = _channel_rule(
            heads_first(q), heads_first(k), v.reshape(bsz, s, h * dv),
            heads_first(gamma), heads_first(beta.astype(f32)), c, unit, interpret)
        return o.reshape(bsz, s, h, dv), final.reshape(bsz, h, dk, dv)
    gamma = jnp.cumsum(
        g.astype(f32).reshape(bsz, s // c, c, h), axis=2).reshape(bsz, s, h)
    o, final = _rule(
        heads_first(q), heads_first(k), v.reshape(bsz, s, h * dv),
        heads_first(gamma), heads_first(beta.astype(f32)), c, unit, interpret)
    return o.reshape(bsz, s, h, dv), final.reshape(bsz, h, dk, dv)
