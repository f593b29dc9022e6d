"""Token→expert dispatch math: top-k gating with capacity buckets.

This is the SPMD replacement for the reference's per-request routing
(``hivemind/client/moe.py`` beam search + k-of-n gather — SURVEY.md §2):
inside one XLA program, fault tolerance becomes *capacity dropping* —
tokens beyond an expert's capacity slot are dropped (their combine weight
is zero), which is the collective-friendly analogue of the reference
dropping straggler experts (SURVEY.md §7 "k-of-n inside a collective").

All shapes are static (XLA requirement): for ``n`` tokens, ``E`` experts,
capacity ``C``, the dispatch/combine tensors are ``[n, E, C]``.  The
one-hot formulation matmuls cleanly onto the MXU; the index form moves
rows instead, and ``choose_dispatch_impl`` picks between them by shape.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from learning_at_home_tpu.ops.moe_rows import sum_rows, sum_rows_plain


class DispatchPlan(NamedTuple):
    """Static-shape routing decision for one token shard."""

    combine: jax.Array  # [n, E, C] float — gate weight at the token's slot
    dispatch: jax.Array  # [n, E, C] bool — membership mask
    aux_loss: jax.Array  # [] load-balance auxiliary (Shazeer-style)
    dropped_fraction: jax.Array  # [] fraction of (token, choice) pairs dropped


class IndexDispatchPlan(NamedTuple):
    """Compact index form of the same routing decision.

    The one-hot [n, E, C] form burns O(n*E*C*d) MXU FLOPs on what is
    really data movement; this form drives gathers/scatters instead:
    O(E*C*d) for dispatch and O(n*k*d) for combine.
    """

    token_for_slot: jax.Array  # [E, C] int32 — source token per slot, -1 empty
    slot_for_token: jax.Array  # [n, k] int32 — flat slot e*C+c per choice, -1 dropped
    weights: jax.Array  # [n, k] float — renormalized gate weight per choice
    aux_loss: jax.Array  # []
    dropped_fraction: jax.Array  # []


def compute_capacity(
    n_tokens: int, n_experts: int, k: int, capacity_factor: float = 1.25
) -> int:
    """Slots per expert so that on-balance routing fits with headroom."""
    return max(1, math.ceil(n_tokens * k * capacity_factor / n_experts))


def choose_dispatch_impl(n_tokens: int, n_slots: int) -> str:
    """Static (trace-time) choice between the two dispatch implementations.

    Measured on a real TPU v5e with fetch-forced timing (BASELINE.md
    round-2 "TPU dispatch profile" row — the authoritative numbers): the
    one-hot einsum (O(n·slots·d) MXU FLOPs) beats the row gather
    (O(slots·d) random-row HBM traffic) when the token×slot product is
    small — 881 vs 1539 µs at n=4096/slots=10240/d=512 — and loses when
    it is large — 2863 vs 1634 µs at n=8192/slots=20480/d=1024 and
    4513 vs 1673 µs at n=16384/slots=40960/d=512.  Equating the two cost
    models (MXU FLOP rate vs effective random-row bandwidth; d and dtype
    cancel) puts the crossover at a harmonic mean n·slots/(n+slots)
    ≈ 4000, which classifies all three measured points correctly."""
    harmonic = n_tokens * n_slots / (n_tokens + n_slots)
    return "onehot" if harmonic < 4000 else "gather"


def _expert_positions(
    top_i: jax.Array, num_experts: int, valid: jax.Array | None = None
) -> jax.Array:
    """Slot position of each (token, choice) within its chosen expert.

    Token-order claims, counts carried across the k choices — THE slot
    assignment both gating implementations share (identical by
    construction, asserted by tests).  [n, k] int32.

    ``valid`` [n] bool: tokens marked False claim NO slots (their onehot
    rows are zeroed, so they neither occupy capacity nor advance the
    counts) — the batched-decode padding fix: a row's right-padding must
    not exhaust expert capacity ahead of later rows' real tokens.  Their
    own reported position is 0; callers must AND ``valid`` into ``fits``.
    """
    n, k = top_i.shape
    counts = jnp.zeros((num_experts,), jnp.int32)
    cols = []
    for j in range(k):  # k is small and static — unrolled at trace time
        onehot = jax.nn.one_hot(top_i[:, j], num_experts, dtype=jnp.int32)
        if valid is not None:
            onehot = onehot * valid.astype(jnp.int32)[:, None]
        pos_in_expert = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        cols.append(jnp.sum(pos_in_expert * onehot, axis=1))
        counts = counts + jnp.sum(onehot, axis=0, dtype=jnp.int32)
    return jnp.stack(cols, axis=1)


def _load_balance_loss(
    gates: jax.Array, top_i: jax.Array, valid: jax.Array | None = None
) -> jax.Array:
    """Shazeer/GShard auxiliary: E * <importance> . <top-1 load>.
    ``valid`` restricts both statistics to real (non-padding) tokens."""
    num_experts = gates.shape[1]
    load_oh = jax.nn.one_hot(top_i[:, 0], num_experts, dtype=gates.dtype)
    if valid is None:
        importance = gates.mean(axis=0)
        load = load_oh.mean(axis=0)
    else:
        v = valid.astype(gates.dtype)[:, None]
        denom = jnp.maximum(v.sum(), 1.0)
        importance = (gates * v).sum(axis=0) / denom
        load = (load_oh * v).sum(axis=0) / denom
    return num_experts * jnp.sum(importance * load)


def _small_top_k(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k along the last axis by k sequential argmax passes.

    ``jax.lax.top_k`` lowers to a full sort on TPU — measured 10.5 ms/step
    on the 256-expert flagship (two f32+s32 [45k, 256] sorts per layer,
    device trace 2026-07-29) for a k=2 selection.  k argmax passes are
    O(k·n·E) elementwise reads instead.  Matches top_k for finite inputs
    (descending values, ties toward the lower index) with ONE deviation:
    input values equal to ``finfo.min`` collide with the internal mask
    sentinel and may yield duplicate indices — fine for the router's
    softmax gates (strictly positive), not for pre-masked logits.
    """
    if k > x.shape[-1]:
        raise ValueError(
            f"k={k} > last-dim size {x.shape[-1]} (lax.top_k parity: "
            "argmax over a fully-masked row would silently duplicate)"
        )
    g = x
    ws, is_ = [], []
    for _ in range(k):
        i = jnp.argmax(g, axis=-1)
        ws.append(jnp.take_along_axis(x, i[:, None], axis=-1)[:, 0])
        is_.append(i)
        if len(is_) < k:  # mask the winner out for the next pass
            g = jnp.where(
                jax.nn.one_hot(i, x.shape[-1], dtype=bool),
                jnp.finfo(g.dtype).min,
                g,
            )
    return jnp.stack(ws, axis=1), jnp.stack(is_, axis=1).astype(jnp.int32)


# up to this k sequential argmax passes win over a real sort, whatever E
_SMALL_TOPK_MAX_K = 4
# and up to this k where a row holds this many scores or more: a sort's
# passes grow with E log^2 E where k argmax passes read k E (k = 8 of 512:
# +2.1 % of a step end to end, and 170 -> 20 s of the levelling's 1,200
# moves over [131072, 512]; my chip runs, PR 66).  Narrower rows at k = 8
# (64, 128 experts) are not measured yet: ROADMAP.md queues them
_WIDE_TOPK_MAX_K, _WIDE_TOPK_MIN_E = 8, 512


def _top_k(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """NOT a general ``lax.top_k`` drop-in: where it takes the argmax
    passes (k <= _SMALL_TOPK_MAX_K, or k <= _WIDE_TOPK_MAX_K over
    _WIDE_TOPK_MIN_E scores or more: decided from k and E alone) inputs
    must not contain ``finfo.min`` (it collides with the argmax mask
    sentinel and can duplicate indices — see ``_small_top_k``).  Every
    call site here feeds softmax gates or sigmoid scores plus a bias,
    which are finite, or those with whole groups at minus infinity (below
    the sentinel; the kept groups hold k finite scores at least);
    pre-masked logits must use ``jax.lax.top_k`` directly."""
    if k <= _SMALL_TOPK_MAX_K or (
            k <= _WIDE_TOPK_MAX_K and x.shape[-1] >= _WIDE_TOPK_MIN_E):
        return _small_top_k(x, k)
    return jax.lax.top_k(x, k)


def _topk_weights(
    gates: jax.Array, k: int, renormalize: bool, jitter: float = 0.0,
    jitter_salt: jax.Array | int = 0,
):
    """Top-k selection with optional jitter.  Jitter perturbs ONLY which
    experts are selected; the combine weights always come from the clean
    gates, so the fixed noise pattern never biases the output mixture."""
    if jitter:
        _, top_i = _top_k(router_jitter(gates, jitter, jitter_salt), k)
        top_w = jnp.take_along_axis(gates, top_i, axis=-1)
    else:
        top_w, top_i = _top_k(gates, k)
    if renormalize:
        top_w = top_w / jnp.maximum(
            top_w.sum(axis=-1, keepdims=True), jnp.finfo(top_w.dtype).tiny
        )
    return top_w, top_i


def router_jitter(
    gates: jax.Array, jitter: float, salt: jax.Array | int = 0
) -> jax.Array:
    """Switch-Transformer-style multiplicative routing noise,
    U(1-jitter, 1+jitter) per (row, expert) — but DETERMINISTIC: the
    pattern comes from a fixed PRNG key, not threaded randomness.

    Why it exists: with byte-level data a batch holds ~84 unique tokens,
    and near init attention homogenizes the stream, so thousands of
    near-identical rows tie-break to the SAME top-k experts — measured
    0.73 dropped fraction on the 256-expert flagship at init.  Per-row
    noise splits those ties.  Why deterministic is enough: the batcher
    shuffles text across rows every step, so a fixed row↦noise map is
    uncorrelated with content; and the backward's re-forward (remat,
    custom_vjp) reproduces the identical routing, which threaded
    randomness would make harder to guarantee.

    ``salt`` (static int or traced scalar — e.g. the layer index carried
    through a ``lax.scan`` over layers) decorrelates the row↦noise map
    across call sites: without it every layer reuses one pattern, so the
    same row positions get the same selection bias everywhere, weakening
    the tie-breaking the noise exists to provide (round-2 advisor
    finding)."""
    if not jitter:
        return gates
    key = jax.random.fold_in(jax.random.PRNGKey(0x5EED), salt)
    noise = jax.random.uniform(
        key, gates.shape,
        dtype=gates.dtype, minval=1.0 - jitter, maxval=1.0 + jitter,
    )
    return gates * noise


def _mask_fits(
    fits: jax.Array, token_mask: jax.Array | None, n: int, k: int
) -> tuple[jax.Array, jax.Array]:
    """Apply the padding mask to the slot-fit matrix and return it with
    the dropped-fraction denominator (real routable choices) — the one
    place both gating forms share this logic, so they cannot drift."""
    if token_mask is None:
        return fits, jnp.float32(n * k)
    return (
        fits & token_mask[:, None],
        jnp.maximum(token_mask.sum().astype(jnp.float32) * k, 1.0),
    )


def top_k_gating(
    logits: jax.Array, k: int, capacity: int, renormalize: bool = True,
    jitter: float = 0.0, jitter_salt: jax.Array | int = 0,
    token_mask: jax.Array | None = None,
) -> DispatchPlan:
    """Route each token to its top-k experts, bucketed to static capacity.

    logits: [n, E] raw gate scores.  Tokens claim expert slots in token
    order (deterministic); a token whose chosen expert is already full has
    that choice dropped — its combine weight mass is lost, matching the
    reference's drop-straggler semantics rather than re-routing.

    ``token_mask`` [n] bool (optional, traced): False = padding token —
    routed nowhere, claims no capacity, excluded from the aux loss and the
    dropped-fraction denominator (the batched-decode fix).
    """
    n, num_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)  # [n, E]
    top_w, top_i = _topk_weights(gates, k, renormalize, jitter, jitter_salt)
    pos = _expert_positions(top_i, num_experts, token_mask)  # [n, k]
    fits, n_routable = _mask_fits(pos < capacity, token_mask, n, k)

    combine = jnp.zeros((n, num_experts, capacity), gates.dtype)
    dispatch = jnp.zeros((n, num_experts, capacity), bool)
    for j in range(k):  # k is small and static — unrolled at trace time
        expert_onehot = jax.nn.one_hot(top_i[:, j], num_experts, dtype=gates.dtype)
        slot_onehot = jax.nn.one_hot(pos[:, j], capacity, dtype=gates.dtype)
        mask = expert_onehot[:, :, None] * slot_onehot[:, None, :]
        mask = mask * fits[:, j][:, None, None].astype(gates.dtype)
        combine = combine + top_w[:, j][:, None, None] * mask
        dispatch = dispatch | (mask > 0)

    aux_loss = _load_balance_loss(gates, top_i, token_mask)
    dropped = 1.0 - fits.sum().astype(jnp.float32) / n_routable
    return DispatchPlan(combine, dispatch, aux_loss, dropped)


def dispatch_tokens(x: jax.Array, plan: DispatchPlan) -> jax.Array:
    """Scatter tokens into per-expert capacity buckets: [n,d] → [E,C,d]."""
    return jnp.einsum("nec,nd->ecd", plan.dispatch.astype(x.dtype), x)


def combine_outputs(y: jax.Array, plan: DispatchPlan) -> jax.Array:
    """Gather expert outputs back per token, gate-weighted: [E,C,d] → [n,d]."""
    return jnp.einsum("nec,ecd->nd", plan.combine.astype(y.dtype), y)


def top_k_gating_indices(
    logits: jax.Array, k: int, capacity: int, renormalize: bool = True,
    jitter: float = 0.0, jitter_salt: jax.Array | int = 0,
    token_mask: jax.Array | None = None,
) -> IndexDispatchPlan:
    """Index-form routing: same semantics as :func:`top_k_gating`
    (token-order slot claims, capacity dropping, renormalized weights,
    optional padding ``token_mask``) without ever materializing [n, E, C]
    tensors."""
    n, num_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = _topk_weights(gates, k, renormalize, jitter, jitter_salt)
    pos = _expert_positions(top_i, num_experts, token_mask)  # [n, k]
    fits, n_routable = _mask_fits(pos < capacity, token_mask, n, k)

    slot_for_token = jnp.where(
        fits, top_i * capacity + pos, -1
    ).astype(jnp.int32)
    weights = jnp.where(fits, top_w, 0.0)

    token_ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    token_for_slot = (
        jnp.full((num_experts * capacity,), -1, jnp.int32)
        .at[jnp.where(fits, slot_for_token, num_experts * capacity)]
        .set(token_ids, mode="drop")
        .reshape(num_experts, capacity)
    )

    aux_loss = _load_balance_loss(gates, top_i, token_mask)
    dropped = 1.0 - fits.sum().astype(jnp.float32) / n_routable
    return IndexDispatchPlan(token_for_slot, slot_for_token, weights, aux_loss, dropped)


def dispatch_tokens_indexed(x: jax.Array, plan: IndexDispatchPlan) -> jax.Array:
    """Gather-based dispatch: [n,d] → [E,C,d] with O(E*C*d) data movement."""
    num_experts, capacity = plan.token_for_slot.shape
    flat = plan.token_for_slot.reshape(-1)
    rows = x[jnp.clip(flat, 0, None)]
    rows = jnp.where((flat >= 0)[:, None], rows, 0)
    return rows.reshape(num_experts, capacity, x.shape[-1])


def combine_outputs_indexed(y: jax.Array, plan: IndexDispatchPlan) -> jax.Array:
    """Gather-based combine: [E,C,d] → [n,d] with O(n*k*d) data movement."""
    e, c, d = y.shape
    y_flat = y.reshape(e * c, d)
    slots = plan.slot_for_token  # [n, k]
    picked = y_flat[jnp.clip(slots, 0, None)]  # [n, k, d]
    # plan.weights is already zero wherever slots == -1 (set at plan build)
    return jnp.einsum("nk,nkd->nd", plan.weights.astype(y.dtype), picked)


# ---- dropless routing: sort by expert, grouped matmul, unsort ----


class DroplessPlan(NamedTuple):
    """Routing decision with no capacity: every (token, choice) pair is
    computed.  The n*k assignments, flattened token-major, are sorted by
    expert; expert e's rows are the ``group_sizes[e]`` consecutive sorted
    rows after those of the experts before it."""

    order: jax.Array  # [n*k] int32 — flat assignment (token*k + choice) per sorted row
    inverse: jax.Array  # [n*k] int32 — sorted row of each flat assignment
    group_sizes: jax.Array  # [E] int32 — rows per expert; sums to n*k
    weights: jax.Array  # [n, k] float32 — gate weight per choice
    aux_loss: jax.Array  # [] load-balance auxiliary, as the capacity plans'


def _expert_counts(top_i: jax.Array, num_experts: int) -> jax.Array:
    """[E] int32: assignments per expert of the choices ``top_i`` [n, k]."""
    return jnp.sum(
        jax.nn.one_hot(top_i.reshape(-1), num_experts, dtype=jnp.int32), axis=0
    )


def kept_groups(selection: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """[n, n_group] bool: the ``topk_group`` groups a token may choose in
    (DeepSeek-V3's group-limited routing).  The ``E`` selection scores
    ``selection`` [n, E] (the sigmoid scores plus the selection bias) are
    ``n_group`` groups of ``E / n_group`` CONSECUTIVE experts; a group's
    score is the sum of its two largest; the ``topk_group`` best groups are
    kept, ties to the lower group.  float32, selection only.  Maxima and
    masks, no sort: ``lax.top_k`` is a full sort on a TPU (the levelling
    pass of 131,072 tokens by 512 experts took 140 ms a move with it, 170 s
    of a run's set-up: my chip runs, PR 66)."""
    n, num_experts = selection.shape
    if num_experts % n_group or not 0 < topk_group <= n_group:
        raise ValueError(
            f"{n_group} groups of {num_experts} experts, {topk_group} kept: "
            "the groups are equal and 1..n_group of them are kept")
    groups = selection.reshape(n, n_group, -1)
    best = jnp.max(groups, axis=-1, keepdims=True)
    # the second largest: the largest with the FIRST of the largest taken out
    first = jnp.argmax(groups, axis=-1)
    rest = jnp.where(
        jax.nn.one_hot(first, groups.shape[-1], dtype=bool), -jnp.inf, groups)
    scores = best[..., 0] + jnp.max(rest, axis=-1)
    return jnp.any(jax.nn.one_hot(
        _small_top_k(scores, topk_group)[1], n_group, dtype=bool), axis=1)


def group_limited(
    selection: jax.Array, n_group: int = 1, topk_group: int = 1
) -> jax.Array:
    """``selection`` [n, E] with every expert outside a token's kept groups
    (:func:`kept_groups`) at minus infinity; as it came where ``n_group``
    is 1 (no groups: every other router of the repo)."""
    if n_group == 1:
        return selection
    with jax.named_scope("groups"):
        kept = kept_groups(selection, n_group, topk_group)
        return jnp.where(
            jnp.repeat(kept, selection.shape[1] // n_group, axis=1),
            selection, -jnp.inf)


def router_choice(
    logits: jax.Array, k: int, renormalize: bool = True,
    score: str = "softmax", bias: jax.Array | None = None,
    scale: float = 1.0, n_group: int = 1, topk_group: int = 1,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """logits [n, E] float32 → ``(gates [n, E], top_w [n, k], top_i [n, k])``:
    the k experts a token is sent to, the weight of each, and the scores
    over ALL experts that the load-balance auxiliary reads (they sum to 1).

    ``score="softmax"``: the k largest of the softmax over all experts,
    as they come or renormalised to sum to 1.  ``score="sigmoid"``: every
    expert scored on its own, ``s = sigmoid(logits)``; the k largest of
    ``s + bias`` are chosen and weighed by ``s`` ALONE (``bias`` [E]
    selects and does not weigh: it enters nothing differentiable, so its
    gradient is zero), renormalised over the chosen, times ``scale``.
    ``n_group`` > 1 (the sigmoid router's): the k are chosen inside the
    ``topk_group`` best of ``n_group`` groups of consecutive experts
    (:func:`group_limited`; scope ``groups``)."""
    if score == "softmax":
        if bias is not None or n_group != 1:
            raise ValueError(
                "a selection bias and groups go with score='sigmoid'")
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = _topk_weights(gates, k, renormalize)
    elif score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        selection = group_limited(
            s if bias is None else s + bias, n_group, topk_group)
        _, top_i = _top_k(selection, k)
        top_w = jnp.take_along_axis(s, top_i, axis=-1)
        if renormalize:
            top_w = top_w / jnp.maximum(
                top_w.sum(axis=-1, keepdims=True), jnp.finfo(top_w.dtype).tiny
            )
        gates = s / s.sum(axis=-1, keepdims=True)
    else:
        raise ValueError(f"score must be 'softmax' or 'sigmoid', got {score!r}")
    if scale != 1.0:
        top_w = top_w * scale
    return gates, top_w, top_i


def _groups(n_group: int, topk_group: int) -> dict:
    """:func:`router_choice`'s group arguments, named, and none at all for a
    router without groups, which is then called with the six arguments it
    always had: a stand-in with that signature is put in its place by a
    benchmark runner's wrong program (``train_recipe_lfm2``'s
    ``biased_weights``, which tier-1 runs in ``tests/test_lfm2.py``), and a
    PR that adds a configuration edits no file the benchmark has.  Once
    that stand-in takes ``**kwargs`` (ROADMAP.md) the two callers pass the
    arguments straight through and this goes."""
    return {} if n_group == 1 else dict(n_group=n_group, topk_group=topk_group)


def dropless_routing(
    logits: jax.Array, k: int, renormalize: bool = True,
    token_mask: jax.Array | None = None, score: str = "softmax",
    bias: jax.Array | None = None, scale: float = 1.0,
    n_group: int = 1, topk_group: int = 1,
) -> DroplessPlan:
    """logits [n, E] float32 → the k experts per token and their weights
    (:func:`router_choice`) and the expert-sorted order.
    ``token_mask`` [n] bool: padding tokens are computed like any other
    (there is no capacity for them to claim) with weight 0, and stay out
    of the aux loss."""
    num_experts = logits.shape[1]
    gates, top_w, top_i = router_choice(
        logits, k, renormalize, score, bias, scale,
        **_groups(n_group, topk_group)
    )
    if token_mask is not None:
        top_w = jnp.where(token_mask[:, None], top_w, 0.0)
    flat = top_i.reshape(-1)
    # stable: within an expert rows keep token order, so the plan (and the
    # sums the grouped matmul's backward makes) is a function of the
    # routing alone
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    group_sizes = jnp.sum(
        jax.nn.one_hot(flat, num_experts, dtype=jnp.int32), axis=0
    )
    return DroplessPlan(
        order, inverse, group_sizes, top_w,
        _load_balance_loss(gates, top_i, token_mask),
    )


# ---- a share of the experts: route over all, compute the held ones' part ----


class SharePlan(NamedTuple):
    """Routing decision of a layer that holds ``G`` consecutive experts of
    the ``E`` its router scores.  Every token is routed over all ``E``; the
    assignments that fall on a held expert are sorted by expert into a
    buffer of ``R`` rows (a static size: how many land here is decided by
    the data), expert g's rows the ``group_sizes[g]`` consecutive ones
    after those of the held experts before it.  Assignments beyond the
    buffer are dropped and counted; buffer rows beyond the assignments
    are empty (``valid`` False, weight 0).  ``slot`` is the same sort read
    the other way, token-major: the buffer row of every assignment, ``R``
    (one past the buffer) for one that has none, absent or dropped."""

    token: jax.Array  # [R] int32 — the token each buffer row computes
    weight: jax.Array  # [R] float32 — its gate weight, as normalised over all k chosen
    valid: jax.Array  # [R] bool — the row holds an assignment
    group_sizes: jax.Array  # [G] int32 — rows per held expert; sums to at most R
    counts: jax.Array  # [E] int32 — assignments per expert, held or not
    routed_here: jax.Array  # [] int32 — assignments that fall on a held expert
    aux_loss: jax.Array  # [] load-balance auxiliary over all E, as the other plans'
    slot: jax.Array  # [n, k] int32 — the buffer row of each assignment; R where it has none
    slot_weight: jax.Array  # [n, k] float32 — ``weight`` in that order; 0 where it has none


def share_buffer_rows(n: int, k: int, held: int, num_experts: int) -> int:
    """Rows of a share's sorted buffer, from the shape alone: twice the
    level share of the ``n * k`` assignments (``held / num_experts`` of
    them), whole row tiles of the grouped matmul (256) where the rule
    gives tiles at all."""
    rows = -(-2 * n * k * held // num_experts)
    if rows >= GROUPED_MATMUL_MIN_ROWS:
        rows = -(-rows // 256) * 256
    return min(rows, n * k)


def share_routing(
    logits: jax.Array, k: int, first: int, held: int, rows: int,
    renormalize: bool = True, token_mask: jax.Array | None = None,
    score: str = "softmax", bias: jax.Array | None = None,
    scale: float = 1.0, n_group: int = 1, topk_group: int = 1,
) -> SharePlan:
    """logits [n, E] float32 → the plan of the share that holds experts
    ``first .. first + held - 1`` in a buffer of ``rows`` rows.  The gates
    are :func:`router_choice`'s over all ``E``: what the absent experts
    would have added is left out, not renormalised away."""
    n, num_experts = logits.shape
    gates, top_w, top_i = router_choice(
        logits, k, renormalize, score, bias, scale,
        **_groups(n_group, topk_group)
    )
    if token_mask is not None:
        top_w = jnp.where(token_mask[:, None], top_w, 0.0)
    local = top_i.reshape(-1) - first
    # an assignment to an absent expert sorts behind every held one
    key = jnp.where((local >= 0) & (local < held), local, held)
    # stable: by held expert, then by token
    ranked = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = ranked[:rows]
    counts = _expert_counts(top_i, num_experts)
    here = jax.lax.dynamic_slice_in_dim(counts, first, held)
    # the buffer takes the first ``rows``: the groups end where it ends
    ends = jnp.minimum(jnp.cumsum(here), rows)
    group_sizes = jnp.diff(ends, prepend=0)
    valid = jnp.arange(rows, dtype=jnp.int32) < ends[-1]
    # the sort's inverse: where each assignment went, if it went anywhere
    rank = jnp.argsort(ranked).astype(jnp.int32).reshape(n, k)
    kept = rank < ends[-1]
    return SharePlan(
        order // k, jnp.where(valid, top_w.reshape(-1)[order], 0.0), valid,
        group_sizes, counts, here.sum(),
        _load_balance_loss(gates, top_i, token_mask),
        jnp.where(kept, rank, rows), jnp.where(kept, top_w, 0.0),
    )


# The share's row movements as the chip runs them where the buffer is no
# smaller than a measured share of the assignments (``share_gather_fits``):
# every one a gather.  A scatter-add of R rows costs the TPU several times
# the gather of as many (PERF.md section 6, PR 27, PR 50, PR 60), so the sum
# over a token's assignments reads them token-major through ``plan.slot``
# (n*k rows out of the buffer; an assignment with none reads any row, and
# its weight of 0 masks it) and adds a token's k adjacent rows
# (``ops.moe_rows``), as the dropless path does since PR 50.  Where n*k is
# many times R that gather costs more than the scatter-add it replaces, and
# the form stays what it was.


def share_gather_fits(
    n: int, k: int, rows: int, d: int, dtype, backend: str
) -> bool:
    """Whether a share's sums over a token's assignments (the combine, and
    the sort's backward) run as a gather of the ``n * k`` assignments' rows
    out of the buffer of ``rows`` and a sum of ``k`` adjacent ones, in place
    of a scatter-add of ``rows`` rows of ``d``: bf16 rows on a ``tpu``
    backend, where it was measured, and no more assignments than
    ``SHARE_GATHER_MOST_ASSIGNMENTS_A_ROW`` times the buffer's rows (``d``
    moved no answer at 2,048, 2,688 and 6,144)."""
    return (
        backend == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
        and n * k <= SHARE_GATHER_MOST_ASSIGNMENTS_A_ROW * rows
    )


# tools/grouped_matmul_probe.py rows --buffer-rows, v5e (PERF.md section 6,
# PR 60): a pass in the gather form takes 0.44 to 0.50 of its scatter-add's
# time at n*k = R, 0.65 to 0.88 at 2 R, 1.44 to 1.67 at 4 R, 1.45 to 1.74 at
# 8 R
SHARE_GATHER_MOST_ASSIGNMENTS_A_ROW = 2


def _rows_of_slots(rows: jax.Array, slot: jax.Array) -> jax.Array:
    """[R, d] buffer rows → [n*k, d], token-major: the row of every
    assignment.  One with none (``slot == R``) reads SOME row, a different
    one each (65,536 reads of one row cost the gather 0.8 ms more than
    reads of rows apart, v5e, PR 60), and what it read is anything: a row
    outside every group holds whatever was left there, so the sum that
    follows is ``masked`` (0 x NaN is NaN)."""
    anywhere = jnp.arange(slot.size, dtype=slot.dtype).reshape(slot.shape) % rows.shape[0]
    return rows[jnp.where(slot < rows.shape[0], slot, anywhere).reshape(-1)]


@jax.custom_vjp
def _share_rows_to_buffer(x, token, valid, slot):
    return jnp.where(valid[:, None], x[token], 0)


def _share_rows_to_buffer_fwd(x, token, valid, slot):
    return _share_rows_to_buffer(x, token, valid, slot), slot


def _share_rows_to_buffer_bwd(slot, g):
    n, k = slot.shape
    with jax.named_scope("sum"):
        held = (slot < g.shape[0]).astype(jnp.float32)  # a weight of 1 or 0
        d_x = sum_rows(_rows_of_slots(g, slot), held, n, k, g.dtype, masked=True)
    return d_x, None, None, None


_share_rows_to_buffer.defvjp(_share_rows_to_buffer_fwd, _share_rows_to_buffer_bwd)


def share_sort_tokens(x: jax.Array, plan: SharePlan) -> jax.Array:
    """[n, d] → [R, d]: the token of every assignment held here, rows
    grouped by expert; empty rows zero (and their cotangent ignored)."""
    n, k = plan.slot.shape
    if share_gather_fits(
        n, k, plan.token.shape[0], x.shape[-1], x.dtype, jax.default_backend()
    ):
        return _share_rows_to_buffer(x, plan.token, plan.valid, plan.slot)
    return jnp.where(plan.valid[:, None], x[plan.token], 0)


def _share_scatter_add(ys, weight, token, valid, n):
    weighted = jnp.where(
        valid[:, None], weight[:, None] * ys.astype(jnp.float32), 0.0,
    )
    return jnp.zeros((n, ys.shape[-1]), jnp.float32).at[token].add(weighted)


# The combine's two forms under one backward.  The backward gathers the
# token cotangents ONCE, in the layer's dtype (an [n, d] source: 67 MB of
# bf16 fits VMEM where autodiff's float32 cotangents, 134 MB, do not), scales
# a row by its gate weight in buffer order and takes the weights' gradient
# from dot products with ``ys`` where the grouped matmul left it: the
# gathered [n*k, d] rows are no residual.  ``weight`` and ``slot_weight``
# are one set of numbers in two orders; the gradient goes back through the
# buffer's, so the router's backward is one program under both forms.


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _share_combine(ys, weight, token, valid, slot, slot_weight, dtype, gathered):
    n, k = slot.shape
    if gathered:
        with jax.named_scope("gather"):
            picked = _rows_of_slots(ys, slot)
        with jax.named_scope("sum"):
            return sum_rows(picked, slot_weight, n, k, dtype, masked=True)
    return _share_scatter_add(ys, weight, token, valid, n).astype(dtype)


def _share_combine_fwd(ys, weight, token, valid, slot, slot_weight, dtype, gathered):
    out = _share_combine(ys, weight, token, valid, slot, slot_weight, dtype, gathered)
    return out, (ys, weight, token, valid)


def _share_combine_bwd(dtype, gathered, residuals, g):
    ys, weight, token, valid = residuals
    g_rows = g[token].astype(jnp.float32)  # [R, d]
    d_ys = weight[:, None] * g_rows  # an empty row's weight is 0
    dots = jnp.sum(g_rows * ys.astype(jnp.float32), axis=-1)  # and its ys anything
    return (d_ys.astype(ys.dtype), jnp.where(valid, dots, 0.0),
            None, None, None, None)


_share_combine.defvjp(_share_combine_fwd, _share_combine_bwd)


def share_combine(
    ys: jax.Array, plan: SharePlan, n: int, dtype=jnp.float32
) -> jax.Array:
    """[R, d] sorted outputs of the held experts → [n, d] of ``dtype``: each
    token's gate-weighted sum over its assignments here (zero for a token
    with none), float32 products and sums cast once.  Rows outside every
    group hold whatever the grouped matmul left there: they are masked, not
    multiplied by 0."""
    backend = jax.default_backend()
    if combine_sorted_fits(ys.dtype, backend):
        return _share_combine(
            ys, plan.weight, plan.token, plan.valid, plan.slot,
            plan.slot_weight, dtype,
            share_gather_fits(
                n, plan.slot.shape[1], ys.shape[0], ys.shape[-1], ys.dtype,
                backend),
        )
    return _share_scatter_add(
        ys, plan.weight, plan.token, plan.valid, n).astype(dtype)


def balanced_bias(bias: jax.Array, counts: jax.Array, rate: float) -> jax.Array:
    """One move of a router's selection bias toward level loads (the
    auxiliary-loss-free rule, arXiv:2408.15664): ``rate`` up for every
    expert under the mean of ``counts`` [.., E], ``rate`` down for every
    one over it."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(
        counts.mean(axis=-1, keepdims=True) - counts
    ).astype(bias.dtype)


@functools.partial(
    jax.jit, static_argnames=("k", "moves", "n_group", "topk_group"))
def _level_moves(scores, bias, best, best_bias, rate, k: int, moves: int,
                 n_group: int = 1, topk_group: int = 1):
    """``moves`` moves of :func:`balanced_bias` at ``rate`` on the counts of
    the k largest of ``scores + bias`` (inside a token's kept groups where
    the router has groups); beside the bias they end at, the lowest
    largest-load-over-mean seen (``best``) and the bias that read it."""
    num_experts = scores.shape[1]

    def move(_, carry):
        bias, best, best_bias = carry
        counts = _expert_counts(_top_k(group_limited(
            scores + bias, n_group, topk_group), k)[1], num_experts)
        load = jnp.max(counts) * (num_experts / (scores.shape[0] * k))
        best_bias = jnp.where(load < best, bias, best_bias)
        return balanced_bias(bias, counts, rate), jnp.minimum(load, best), best_bias

    return jax.lax.fori_loop(0, moves, move, (bias, best, best_bias))


def level_bias(
    scores: jax.Array, bias: jax.Array, k: int,
    rate: float = 0.02, moves: int = 24, floor: float = 1e-4,
    n_group: int = 1, topk_group: int = 1,
) -> tuple[jax.Array, list]:
    """The selection bias that levels the loads of ``scores`` [n, E] (a
    router's sigmoid scores on a pool of tokens): :func:`balanced_bias`
    again and again on the counts of the k largest of ``scores + bias``,
    ``moves`` moves at a rate from the best bias so far, then at half the
    rate, until two rates in a row bring the largest load over the mean no
    lower (or the rate falls under ``floor``).  Returns the bias under
    which it was lowest, and that load before and after."""
    inf = jnp.float32(jnp.inf)
    # a rate of 0 moves nothing: one move reads the load under ``bias``
    start = best = float(_level_moves(
        scores, bias, inf, bias, 0.0, k, 1, n_group, topk_group)[1])
    best_bias, stalled = bias, 0
    while rate >= floor and stalled < 2:
        _, low, best_bias = _level_moves(
            scores, best_bias, jnp.float32(best), best_bias, jnp.float32(rate),
            k, moves + 1,  # the last move's bias is read by the one after it
            n_group, topk_group)
        stalled = 0 if float(low) < best else stalled + 1
        best, rate = float(low), rate / 2
    return best_bias, [start, best]


# The two row gathers below move rows along a permutation, so each one's
# transpose is the gather along the inverse permutation.  Left to autodiff
# it is a scatter-add of n*k rows, which the TPU runs ten times slower
# than the gather (10.6 against 0.9 ms for 131,072 rows of 2048 bf16,
# v5e, PERF.md PR 27).


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_sorted(x, order, inverse, k):
    return x[order // k]


def _rows_to_sorted_fwd(x, order, inverse, k):
    return x[order // k], (inverse, x.shape[0])


def _rows_to_sorted_bwd(k, residuals, g):
    inverse, n = residuals
    return sum_rows(g[inverse], None, n, k, g.dtype), None, None


_rows_to_sorted.defvjp(_rows_to_sorted_fwd, _rows_to_sorted_bwd)


@jax.custom_vjp
def _rows_from_sorted(ys, order, inverse):
    return ys[inverse]


def _rows_from_sorted_fwd(ys, order, inverse):
    return ys[inverse], order


def _rows_from_sorted_bwd(order, g):
    return g[order], None, None


_rows_from_sorted.defvjp(_rows_from_sorted_fwd, _rows_from_sorted_bwd)


def sort_tokens(x: jax.Array, plan: DroplessPlan) -> jax.Array:
    """[n, d] → [n*k, d]: one copy of a token per choice, rows grouped by
    expert."""
    return _rows_to_sorted(x, plan.order, plan.inverse, plan.weights.shape[1])


# The weights' gradient of a grouped matmul: rows [m, a] and row cotangents
# [m, b], contracted over the ragged rows, a group at a time → [G, a, b]
# (the operation autodiff makes of ``ragged_dot``'s second operand).
WEIGHTS_GRADIENT = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[],
)

# the fewest rows tools/grouped_matmul_probe.py measured (the tiles below
# won at every row count from here to the cell's 131,072): under it the
# compiler's own 512 x 512 x 512 stays
GROUPED_MATMUL_MIN_ROWS = 512


def _largest_lane_divisor(dim: int, most: int) -> int:
    """The largest multiple of the 128-lane width that divides ``dim`` and
    is at most ``most``; 0 where there is none."""
    return next(
        (t for t in range(min(dim, most) // 128 * 128, 0, -128) if dim % t == 0), 0
    )


def _lane_cover(dim: int) -> int:
    """A width that ends half-way through a lane tile (``dim % 128 == 64``:
    1,856 is 14.5 of them) at its cover, the next multiple of 128; any
    other width as it is.  Such a width has no dividing tile, and a tile
    that does not divide is padded: measured at 1,856 (cover 1,920) alone,
    where other remainders were not, and keep no tiles."""
    return dim + 64 if dim % 128 == 64 else dim


def grouped_matmul_tiles(
    m: int, k: int, n: int, dtype, weights_gradient: bool = False
) -> tuple[int, int, int] | None:
    """Tile sizes ``(tm, tk, tn)`` of one grouped-matmul call on the TPU
    (over the rows ``m``, the lhs's other dimension ``k``, the rhs's last
    dimension ``n``), read from the call's shape; ``None``: no attribute,
    the compiler's own (512, 512, 512).

    A call ``[m, k] x [G, k, n] → [m, n]`` (the forward call, and the
    rows' gradient with ``k`` and ``n`` exchanged) holds in VMEM, twice
    each, a row tile ``[tm, tk]``, a weight tile ``[tk, tn]`` and a result
    tile ``[tm, tn]``, and a float32 ``[tm, tn]`` accumulator unless
    ``tk`` is all of ``k``.  Fastest (v5e, PERF.md section 6, PR 30 and
    PR 32): 256 rows, because every group boundary inside a row tile costs
    one more visit of the whole tile and real loads put 63 of them
    anywhere; and a weight tile of up to 2 Mi elements, which at OLMoE's
    widths (2048 x 1024) and at SmallThinker's (2560 x 768) is an expert's
    whole matrix: fetched once a group, no accumulator.  The weights'
    gradient ``[m, k], [m, n] → [G, k, n]`` holds its result tile
    ``[tk, tn]`` twice and again in float32, so half as large a tile.  Of
    16 MB of VMEM both take 10 to 12 (a tile of twice the size is refused).

    A tile divides its dimension (one that does not is padded, not
    clipped) and is a multiple of the 128 lanes: ``tk`` is the largest
    such divisor of ``k`` up to the longest measured, 2560 (1280 for the
    weights' gradient), then ``tn`` the largest such divisor of ``n`` that
    keeps ``tk * tn`` within the 2 Mi (1 Mi); for a power of two that is
    ``min(dim, cap)``.

    A width no multiple of 128 divides because it ends half-way through a
    lane tile (Nemotron-H's experts: 1,856 = 14.5 x 128) is tiled at its
    cover (``_lane_cover``: 1,920), the last tile padded.  There the
    compiler's own choice is (512, 128, 128), and on a share's buffer of
    49,152 rows in 32 groups (v5e, PERF.md section 6, PR 39) the six calls
    of a layer read 13.3 to 17.8 ms each where this rule's tiles read 2.56
    to 4.41: forward 2688 x 1856 at (256, 896, 1920) 3.82 (best of the
    sweep 3.63), 1856 x 2688 at (256, 1920, 896) 2.56 (the best), the
    weights' gradients at (256, 896, 640) 4.41 (4.22) and (256, 640, 896)
    3.34 (3.13).

    ``None`` where no multiple of 128 divides a width (but for that
    cover) or 256 the rows,
    for operands other than bf16 (the tiles were measured, and their VMEM
    counted, at two bytes an element) and under
    ``GROUPED_MATMUL_MIN_ROWS`` rows."""
    if jnp.dtype(dtype) != jnp.bfloat16 or m < GROUPED_MATMUL_MIN_ROWS or m % 256:
        return None
    budget = 1 << 20 if weights_gradient else 1 << 21
    k, n = _lane_cover(k), _lane_cover(n)
    tk = _largest_lane_divisor(k, 1280 if weights_gradient else 2560)
    tn = _largest_lane_divisor(n, budget // tk) if tk else 0
    return (256, tk, tn) if tn else None


def ragged_dot_tiling(tiles: tuple[int, int, int] | None):
    """Context under which a ``ragged_dot`` is traced with the frontend
    attribute ``ragged_dot_tiling``, which the TPU compiler reads as its
    kernel's tile sizes (the instruction stays ``ragged-dot-none.<n>``);
    no attribute for ``None``.  Other backends ignore the attribute."""
    if tiles is None:
        return contextlib.nullcontext()
    return set_xla_metadata(ragged_dot_tiling=",".join(map(str, tiles)))


@jax.custom_vjp
def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array
) -> jax.Array:
    """lhs [m, a] (rows grouped as ``group_sizes`` says) x rhs [G, a, b] →
    [m, b]: row i meets the matrix of its group.  ``jax.lax.ragged_dot``:
    one native grouped-matmul call on the TPU, any row count, any
    platform.  The operation count is dense whatever the group sizes:
    2·m·a·b, as every row meets exactly one matrix.

    On the TPU the call and its two gradients (the two operations autodiff
    makes of a ``ragged_dot``, written out so that each sits under its own
    attribute) run at the tiles ``grouped_matmul_tiles`` reads from their
    shapes; results on other backends are bitwise ``ragged_dot``'s."""
    m, a = lhs.shape
    with ragged_dot_tiling(grouped_matmul_tiles(m, a, rhs.shape[-1], lhs.dtype)):
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype
        )


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(residuals, g):
    lhs, rhs, group_sizes = residuals
    (m, a), b = lhs.shape, rhs.shape[-1]
    # the weights' gradient first, as autodiff orders the two
    with ragged_dot_tiling(
        grouped_matmul_tiles(m, a, b, lhs.dtype, weights_gradient=True)
    ):
        d_rhs = jax.lax.ragged_dot_general(
            lhs, g, group_sizes, WEIGHTS_GRADIENT,
            preferred_element_type=rhs.dtype,
        )
    rhs_t = jnp.swapaxes(rhs, 1, 2)
    with ragged_dot_tiling(grouped_matmul_tiles(m, b, a, g.dtype)):
        d_lhs = jax.lax.ragged_dot(
            g, rhs_t, group_sizes, preferred_element_type=lhs.dtype
        )
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# The combine as the chip runs it.  A row gather out of HBM costs 4.3 to
# 4.6 ms there (131,072 rows of 2048 bf16; 0.9 where its [n, d] source fits
# VMEM: PERF.md PR 50), so the backward makes ONE, of the token cotangents
# into sorted order (a source of n rows), scales a row by its gate weight
# there, and takes the weights' gradient from the rows' dot products with
# ``ys`` in sorted order: the gathered ``ys[inverse]`` is no residual (remat
# recomputed its gather), and no [n*k, d] product is gathered.  The sum is
# ``ops.moe_rows``'s, a kernel where its rule says so.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_sorted(ys, weights, order, inverse, dtype):
    n, k = weights.shape
    return sum_rows(ys[inverse], weights, n, k, dtype)


def _combine_sorted_fwd(ys, weights, order, inverse, dtype):
    out = _combine_sorted(ys, weights, order, inverse, dtype)
    return out, (ys, weights, order, inverse)


def _combine_sorted_bwd(dtype, residuals, g):
    ys, weights, order, inverse = residuals
    n, k = weights.shape
    g_sorted = g[order // k].astype(jnp.float32)  # [n*k, d]
    d_ys = (weights.reshape(-1)[order][:, None] * g_sorted).astype(ys.dtype)
    dots = jnp.sum(g_sorted * ys.astype(jnp.float32), axis=-1)
    return d_ys, dots[inverse].reshape(n, k), None, None


_combine_sorted.defvjp(_combine_sorted_fwd, _combine_sorted_bwd)


def combine_sorted_fits(dtype, backend: str) -> bool:
    """Whether :func:`unsort_combine` runs as ``_combine_sorted``: bf16 rows
    on a ``tpu`` backend, where it was measured; every other call keeps the
    form (and the lowered text) it had."""
    return backend == "tpu" and jnp.dtype(dtype) == jnp.bfloat16


def unsort_combine(
    ys: jax.Array, plan: DroplessPlan, dtype=jnp.float32
) -> jax.Array:
    """[n*k, d] sorted expert outputs → [n, d] of ``dtype``: each token's k
    outputs, gate-weighted and summed in float32."""
    n, k = plan.weights.shape
    if combine_sorted_fits(ys.dtype, jax.default_backend()):
        return _combine_sorted(ys, plan.weights, plan.order, plan.inverse, dtype)
    picked = _rows_from_sorted(ys, plan.order, plan.inverse)
    return sum_rows_plain(picked, plan.weights, n, k, dtype)
