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
accumulate in float32; every decay is float32.  Differentiable as written:
the backward is autodiff's through these products and the short scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunked(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    chunk: int, decay_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """``(y [B, S, H, P] in x's dtype, the state after the last position
    [B, H, P, N] float32)`` of the recurrence above, ``chunk`` positions at
    a time (``min(chunk, S)``; it must divide ``S``).  ``decay_dtype``:
    the dtype the decays' arithmetic runs in; float32 always, but for
    showing what a lower one reads (tools/smallthinker_probe.py)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    q = min(chunk, s)
    if s % q or h % g:
        raise ValueError(
            f"the chunked scan needs chunk={q} to divide S={s} and the "
            f"{g} groups the {h} heads"
        )
    nc, hg = s // q, h // g
    compute = x.dtype
    f32 = jnp.float32

    # the decays: cumulative sums of dt A inside each chunk
    da = (dt.astype(f32) * a.astype(f32)).astype(decay_dtype)
    cum = jnp.cumsum(da.reshape(bsz, nc, q, h), axis=2)  # [B, nc, Q, H]
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
