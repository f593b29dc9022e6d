"""Single-traversal Adafactor: the optimizer as ONE fused per-leaf chain.

``optax.adafactor(lr)`` is a 5-stage ``optax.chain`` (factored-rms scaling,
block-RMS clipping, lr scaling, param-scale multiply, sign flip) followed by
a separate ``optax.apply_updates`` — six full traversals of the parameter
tree, each materializing a param-sized intermediate to HBM.  At the
single-chip 256-expert flagship (2.15 B params, bf16) the optimizer chain
measured ~42 ms of a 288 ms step on the v5e (device trace 2026-07-29:
``apply_updates`` 18.5 ms + four ~5.7 ms param-tree passes in
clipping/numerics/factorized) — pure HBM bandwidth, zero MXU work.

This module implements the SAME update rule as one per-leaf function inside
a single ``jax.tree.map``, so XLA fuses each leaf's entire chain into the
minimum number of HBM passes (the data dependencies require three reads of
the gradient — stats EMA, clip-RMS reduction, final apply — instead of the
chain's eleven+ param-sized reads/writes).

Deviations from optax (both strictly tighten numerics; parity is asserted
to tolerance in tests/test_ops.py):

- per-leaf math runs in float32 regardless of storage dtype (optax computes
  in the gradient's dtype, so bf16 params get bf16 statistics EMAs and a
  bf16-squared clip reduction);
- state layout is the same (count, v_row, v_col, v) with stats stored in
  the param dtype, so ``parallel.mesh.opt_state_shardings`` and the orbax
  checkpoint path treat it exactly like ``optax.adafactor`` state.

Reference contract: the reference trains its DMoE experts with vanilla
torch optimizers per expert (SURVEY.md §2 ExpertBackend); the factored
optimizer and its fusion are TPU-side choices (single-chip HBM is the
scarce resource — see BASELINE.md round-2 incident notes).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax


class FusedAdafactorState(NamedTuple):
    count: jax.Array  # int32 scalar
    v_row: optax.Params  # factored row stats ([1] sentinel when unfactored)
    v_col: optax.Params
    v: optax.Params  # full second moment ([1] sentinel when factored)


class FusedOptimizer(NamedTuple):
    """``optax.GradientTransformation`` plus an ``apply_fused`` fast path.

    ``update``/``init`` keep full optax compatibility (chaining aside);
    ``apply_fused(params, grads, state) -> (new_params, new_state)`` folds
    the parameter update into the optimizer's final per-leaf pass, so the
    update tree is never materialized to HBM and ``optax.apply_updates``'
    read-update/read-param/write-param traversal disappears (~19 ms/step
    at the 2.15 B-param flagship).  ``make_train_step`` uses it when
    present."""

    init: callable
    update: callable
    apply_fused: callable


def _factored_dims(
    shape: tuple[int, ...], factored: bool, min_dim: int
) -> Optional[tuple[int, int]]:
    """Two largest axes to reduce over, or None (mirrors optax's rule)."""
    if not factored or len(shape) < 2:
        return None
    sorted_dims = np.argsort(shape)
    if shape[sorted_dims[-2]] < min_dim:
        return None
    return int(sorted_dims[-2]), int(sorted_dims[-1])


def fused_adafactor(
    learning_rate: float,
    min_dim_size_to_factor: int = 128,
    decay_rate: float = 0.8,
    decay_offset: int = 0,
    multiply_by_parameter_scale: bool = True,
    clipping_threshold: Optional[float] = 1.0,
    weight_decay_rate: Optional[float] = None,
    eps: float = 1e-30,
    factored: bool = True,
) -> optax.GradientTransformation:
    """Adafactor with the whole per-leaf update in one traversal.

    Returns a :class:`FusedOptimizer`: ``init``/``update`` behave like a
    standard ``optax.GradientTransformation`` (``update`` emits the final
    additive delta, ``optax.apply_updates`` compatible), and
    ``apply_fused`` additionally folds the parameter add into the same
    traversal.  Drops into ``make_train_step``/checkpointing unchanged.
    """

    def init_fn(params):
        def _init(p):
            dims = _factored_dims(p.shape, factored, min_dim_size_to_factor)
            if dims is not None:
                d1, d0 = dims
                vr = jnp.zeros(np.delete(p.shape, d0), dtype=p.dtype)
                vc = jnp.zeros(np.delete(p.shape, d1), dtype=p.dtype)
                return vr, vc, jnp.zeros((1,), dtype=p.dtype)
            z = jnp.zeros((1,), dtype=p.dtype)
            return z, z, jnp.zeros(p.shape, dtype=p.dtype)

        trip = jax.tree.map(_init, params)
        return FusedAdafactorState(
            count=jnp.zeros([], jnp.int32),
            v_row=jax.tree.map(lambda _, t: t[0], params, trip),
            v_col=jax.tree.map(lambda _, t: t[1], params, trip),
            v=jax.tree.map(lambda _, t: t[2], params, trip),
        )

    def _transform(grads, state, params, apply: bool):
        if params is None:
            # literal message: optax 0.2.6 exposes no NO_PARAMS_MSG symbol
            raise ValueError(
                "You are using a transformation that requires the current "
                "value of parameters, but you are not passing `params` when "
                "calling `update`."
            )
        step = state.count
        # optax's _decay_rate_pow(step - offset): 1 - (t+1)^-decay_rate
        t = (step - decay_offset + 1).astype(jnp.float32)
        decay_t = 1.0 - t ** (-decay_rate)

        def _leaf(g, vr, vc, v, p):
            g32 = g.astype(jnp.float32)
            g_sqr = g32 * g32 + eps
            dims = _factored_dims(p.shape, factored, min_dim_size_to_factor)
            if dims is not None:
                d1, d0 = dims
                new_vr32 = decay_t * vr.astype(jnp.float32) + (
                    1.0 - decay_t
                ) * jnp.mean(g_sqr, axis=d0)
                new_vc32 = decay_t * vc.astype(jnp.float32) + (
                    1.0 - decay_t
                ) * jnp.mean(g_sqr, axis=d1)
                reduced_d1 = d1 - 1 if d1 > d0 else d1
                row_mean = jnp.mean(new_vr32, axis=reduced_d1, keepdims=True)
                row_factor = (new_vr32 / row_mean) ** -0.5
                col_factor = new_vc32**-0.5
                u = (
                    g32
                    * jnp.expand_dims(row_factor, axis=d0)
                    * jnp.expand_dims(col_factor, axis=d1)
                )
                new_vr, new_vc = new_vr32.astype(p.dtype), new_vc32.astype(p.dtype)
                new_v = v  # [1] sentinel unchanged
            else:
                new_v32 = decay_t * v.astype(jnp.float32) + (1.0 - decay_t) * g_sqr
                u = g32 * new_v32**-0.5
                new_v = new_v32.astype(p.dtype)
                new_vr, new_vc = vr, vc  # [1] sentinels unchanged
            if clipping_threshold is not None:
                clip_denom = jnp.maximum(
                    1.0, jnp.sqrt(jnp.mean(u * u)) / clipping_threshold
                )
                u = u / clip_denom
            scale = jnp.float32(learning_rate)
            if multiply_by_parameter_scale:
                p32 = p.astype(jnp.float32)
                p_rms = jnp.sqrt(jnp.mean(p32 * p32))
                scale = scale * jnp.maximum(p_rms, 1e-3)
            u = u * scale
            if weight_decay_rate is not None:
                u = u + weight_decay_rate * p.astype(jnp.float32)
            if apply:  # fold p+delta into this pass: no update tree in HBM
                first = (p.astype(jnp.float32) - u).astype(p.dtype)
            else:
                first = (-u).astype(p.dtype)
            return first, new_vr, new_vc, new_v

        out = jax.tree.map(_leaf, grads, state.v_row, state.v_col, state.v, params)
        first = jax.tree.map(lambda _, o: o[0], params, out)
        new_state = FusedAdafactorState(
            count=optax.safe_increment(step),
            v_row=jax.tree.map(lambda _, o: o[1], params, out),
            v_col=jax.tree.map(lambda _, o: o[2], params, out),
            v=jax.tree.map(lambda _, o: o[3], params, out),
        )
        return first, new_state

    def update_fn(grads, state, params):
        return _transform(grads, state, params, apply=False)

    def apply_fused(params, grads, state):
        new_params, new_state = _transform(grads, state, params, apply=True)
        return new_params, new_state

    return FusedOptimizer(init_fn, update_fn, apply_fused)
