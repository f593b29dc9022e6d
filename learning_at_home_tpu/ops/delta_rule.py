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

Everything that does not read the entering state (``A``, the solve, the
scores) is computed for all chunks at once; a ``lax.scan`` over the ``S /
C`` chunks carries the state and makes each chunk's ``V'``; the outputs
are again taken for all chunks at once.  A sequence longer than
:data:`SEGMENT` is taken a segment at a time, the state handed from
segment to segment, each under ``jax.checkpoint``: a backward pass then
holds one segment's intermediates, not the sequence's.  The products take operands of
``v``'s dtype (bf16 in a train step) and accumulate in float32; the
decays, the solve and the carried state are float32.  Plain
``jax.numpy``, every intermediate an array of its own; the backward is
autodiff's (no ``custom_vjp``: the chunked form is a composition of
matmuls, masks and one scan, which autodiff transposes as they stand,
and a hand-written backward belongs to the kernel that ROADMAP.md Reach
3(f) queues, which will keep its intermediates in VMEM).

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
steps for all blocks of all chunks at once), and plain forward
substitution by blocks joins them (:func:`solve_unit_lower`,
``how="blocks"``): backward-stable whatever the keys.  ``"product"`` (the
whole chunk at once) and ``"triangular"``
(``jax.scipy.linalg.solve_triangular``) are there for the probe that
times and checks the three on the chip (tools/smallthinker_probe.py
delta).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
        decayed = jnp.exp(g_t)[..., None, None] * state
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
    segment: int = SEGMENT,
) -> tuple[jax.Array, jax.Array]:
    """``(o [B, S, H, dv] in v's dtype, the state after the last position
    [B, H, dk, dv] float32)`` of the rule above, ``chunk`` positions at a
    time (``min(chunk, S)``; it must divide ``S``).  ``decay_dtype``: the
    dtype the decays' sums are kept in; float32 always, but for showing
    what a lower one reads (tools/smallthinker_probe.py).  ``solve``:
    :func:`solve_unit_lower`'s way.  ``segment``: the positions whose
    intermediates a backward pass holds at a time (a multiple of the chunk
    that divides ``S``, or the whole sequence where it is no longer)."""
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
