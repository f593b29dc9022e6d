"""Unit tests for the token-dispatch math (ops/moe_dispatch.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import (
    combine_outputs,
    compute_capacity,
    dispatch_tokens,
    top_k_gating,
)


def test_compute_capacity():
    assert compute_capacity(128, 8, 2, 1.0) == 32
    assert compute_capacity(128, 8, 2, 1.25) == 40
    assert compute_capacity(1, 64, 1, 1.0) == 1  # floor of 1


@pytest.mark.parametrize(
    "n_tokens, n_slots, impl",
    [
        # the three points measured on a v5e (the rule's own docstring)
        (4096, 10240, "onehot"),
        (8192, 20480, "gather"),
        (16384, 40960, "gather"),
        # dmoe256's cell: 176 x 256 tokens, 256 experts x capacity 440
        (45056, 112640, "gather"),
    ],
)
def test_choose_dispatch_impl_classifies_the_measured_points(
    n_tokens, n_slots, impl
):
    from learning_at_home_tpu.ops.moe_dispatch import choose_dispatch_impl

    assert choose_dispatch_impl(n_tokens, n_slots) == impl


def test_topk_full_capacity_equals_softmax_topk():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(16, 4).astype(np.float32))
    plan = top_k_gating(logits, k=2, capacity=16)
    assert float(plan.dropped_fraction) == 0.0

    gates = np.asarray(jax.nn.softmax(logits, axis=-1))
    weights = np.asarray(plan.combine.sum(axis=2))  # [n, E]
    for b in range(16):
        top2 = np.argsort(-gates[b])[:2]
        expected = gates[b, top2] / gates[b, top2].sum()
        np.testing.assert_allclose(
            np.sort(weights[b][weights[b] > 0]), np.sort(expected), atol=1e-6
        )
        assert set(np.nonzero(weights[b])[0]) == set(top2)


def test_each_slot_used_once():
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(64, 8).astype(np.float32))
    plan = top_k_gating(logits, k=2, capacity=8)
    # no expert slot is claimed by two tokens
    slot_usage = np.asarray(plan.dispatch.sum(axis=0))  # [E, C]
    assert slot_usage.max() <= 1


def test_capacity_dropping():
    # all tokens want expert 0 → only C of them fit
    logits = jnp.full((10, 4), -10.0).at[:, 0].set(10.0)
    plan = top_k_gating(logits, k=1, capacity=3)
    kept = np.asarray(plan.dispatch[:, 0].sum(axis=1))  # per-token kept flag
    assert kept.sum() == 3
    # earliest tokens win slots (deterministic token order)
    np.testing.assert_array_equal(kept[:3], 1)
    assert float(plan.dropped_fraction) == pytest.approx(0.7)


def test_dispatch_combine_roundtrip():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(32, 8).astype(np.float32))
    logits = jnp.asarray(rs.randn(32, 4).astype(np.float32))
    plan = top_k_gating(logits, k=2, capacity=32)
    buckets = dispatch_tokens(x, plan)  # [E, C, d]
    assert buckets.shape == (4, 32, 8)
    # identity expert: combine(dispatch(x)) == x for weight-1 routing
    plan1 = top_k_gating(logits, k=1, capacity=32)
    y = combine_outputs(dispatch_tokens(x, plan1), plan1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)


def test_indexed_plan_matches_onehot():
    """Index-form routing must agree with the one-hot reference exactly."""
    from learning_at_home_tpu.ops import (
        combine_outputs_indexed,
        dispatch_tokens_indexed,
        top_k_gating_indices,
    )

    rs = np.random.RandomState(7)
    for n, E, k, cap in [(32, 8, 2, 6), (16, 4, 1, 2), (64, 8, 4, 16)]:
        logits = jnp.asarray(rs.randn(n, E).astype(np.float32))
        x = jnp.asarray(rs.randn(n, 8).astype(np.float32))
        ref = top_k_gating(logits, k, cap)
        idxp = top_k_gating_indices(logits, k, cap)
        np.testing.assert_allclose(
            float(idxp.dropped_fraction), float(ref.dropped_fraction), atol=1e-6
        )
        np.testing.assert_allclose(
            float(idxp.aux_loss), float(ref.aux_loss), atol=1e-5
        )
        buckets_ref = dispatch_tokens(x, ref)
        buckets_idx = dispatch_tokens_indexed(x, idxp)
        np.testing.assert_allclose(
            np.asarray(buckets_idx), np.asarray(buckets_ref), atol=1e-6
        )
        y = jnp.asarray(rs.randn(E, cap, 8).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(combine_outputs_indexed(y, idxp)),
            np.asarray(combine_outputs(y, ref)),
            atol=1e-5,
        )


def test_indexed_gating_is_differentiable():
    from learning_at_home_tpu.ops import (
        combine_outputs_indexed,
        dispatch_tokens_indexed,
        top_k_gating_indices,
    )

    rs = np.random.RandomState(8)
    x = jnp.asarray(rs.randn(16, 8).astype(np.float32))
    w = jnp.asarray(rs.randn(8, 4).astype(np.float32) * 0.1)

    def loss(w):
        plan = top_k_gating_indices(x @ w, k=2, capacity=8)
        return combine_outputs_indexed(
            dispatch_tokens_indexed(x, plan), plan
        ).sum() + plan.aux_loss

    g = jax.grad(loss)(w)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_gating_is_differentiable():
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(16, 8).astype(np.float32))
    w = jnp.asarray(rs.randn(8, 4).astype(np.float32) * 0.1)

    def loss(w):
        plan = top_k_gating(x @ w, k=2, capacity=8)
        return combine_outputs(dispatch_tokens(x, plan), plan).sum() + plan.aux_loss

    g = jax.grad(loss)(w)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_router_jitter_selection_only():
    """Jitter may change WHICH experts are picked, but the combine weights
    must always be the clean (unjittered) gate values at the selected
    indices — the fixed noise pattern must never bias the output mixture.
    And duplicate rows must stop routing identically."""
    from learning_at_home_tpu.ops.moe_dispatch import top_k_gating_indices

    rs = np.random.RandomState(0)
    # 64 IDENTICAL rows: without jitter they all pick the same experts
    row = rs.randn(1, 16).astype(np.float32) * 0.01
    logits = jnp.asarray(np.repeat(row, 64, axis=0))
    plan_clean = top_k_gating_indices(logits, 2, 8)
    plan_jit = top_k_gating_indices(logits, 2, 8, jitter=0.5)
    # clean: identical rows route identically -> heavy capacity dropping
    assert float(plan_clean.dropped_fraction) > 0.5
    # jittered: selection decorrelates, drop falls sharply
    assert float(plan_jit.dropped_fraction) < ONE_THIRD * float(
        plan_clean.dropped_fraction
    ) + 0.2
    # weights are renormalized CLEAN gate values at the selected experts
    gates = jax.nn.softmax(logits, axis=-1)
    slot = np.asarray(plan_jit.slot_for_token)  # [n, k] flat slots
    w = np.asarray(plan_jit.weights)
    expert_of_slot = slot // 8
    checked = 0
    for i in range(64):
        if (slot[i] < 0).any():
            continue  # dropped choices hide the selected expert id
        sel = expert_of_slot[i]
        gvals = np.asarray(gates[i])[sel]
        expect = gvals / gvals.sum()
        np.testing.assert_allclose(w[i], expect, rtol=1e-5, atol=1e-6)
        checked += 1
    assert checked > 0


ONE_THIRD = 1.0 / 3.0


class TestFusedAdafactor:
    """Parity of ops.fused_adafactor vs optax.adafactor (the 6-traversal
    chain it replaces — see the module docstring for the measured cost)."""

    def _tree(self, dtype):
        rs = np.random.RandomState(0)
        mk = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32)).astype(dtype)
        return {
            "w_big": mk(256, 512),      # factored (both dims >= 128)
            "w_small": mk(64, 32),      # 2-D but unfactored (dims < 128)
            "bias": mk(512),            # 1-D: unfactored
            "stack": mk(3, 256, 512),   # 3-D: factors the two largest dims
            "scalar": jnp.asarray(0.5, dtype),
        }

    def _grads(self, dtype, seed):
        rs = np.random.RandomState(seed)
        mk = lambda *s: jnp.asarray(0.1 * rs.randn(*s).astype(np.float32)).astype(dtype)
        return {
            "w_big": mk(256, 512),
            "w_small": mk(64, 32),
            "bias": mk(512),
            "stack": mk(3, 256, 512),
            "scalar": jnp.asarray(0.01, dtype),
        }

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
    def test_matches_optax_adafactor(self, dtype, tol):
        import optax

        from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

        params_ref = self._tree(dtype)
        params_fused = self._tree(dtype)
        ref = optax.adafactor(1e-2)
        fused = fused_adafactor(1e-2)
        s_ref = ref.init(params_ref)
        s_fused = fused.init(params_fused)

        for step in range(5):
            grads = self._grads(dtype, seed=step + 1)
            u_ref, s_ref = ref.update(grads, s_ref, params_ref)
            u_fused, s_fused = fused.update(grads, s_fused, params_fused)
            params_ref = optax.apply_updates(params_ref, u_ref)
            params_fused = optax.apply_updates(params_fused, u_fused)
            for k in params_ref:
                np.testing.assert_allclose(
                    np.asarray(params_fused[k], np.float32),
                    np.asarray(params_ref[k], np.float32),
                    rtol=tol, atol=tol, err_msg=f"step {step} leaf {k}",
                )

    def test_state_layout_matches_for_sharding_and_checkpoint(self):
        """v_row/v_col/v mirror the param tree with the same reduced
        shapes as optax, so opt_state_shardings and orbax treat it alike."""
        import optax

        from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

        params = self._tree(jnp.float32)
        s_ref = optax.adafactor(1e-2).init(params)
        s_fused = fused_adafactor(1e-2).init(params)
        # optax wraps in a chain tuple; ours is the bare factored state
        ref_f = s_ref[0]
        for field in ("v_row", "v_col", "v"):
            a = jax.tree.map(jnp.shape, getattr(ref_f, field))
            b = jax.tree.map(jnp.shape, getattr(s_fused, field))
            assert a == b, (field, a, b)

    def test_weight_decay_and_no_clip_variants(self):
        import optax

        from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

        params = self._tree(jnp.float32)
        grads = self._grads(jnp.float32, seed=7)
        for kwargs in (
            {"weight_decay_rate": 1e-3},
            {"clipping_threshold": None},
            {"multiply_by_parameter_scale": False},
        ):
            ref = optax.adafactor(1e-2, **kwargs)
            fused = fused_adafactor(1e-2, **kwargs)
            u_ref, _ = ref.update(grads, ref.init(params), params)
            u_fused, _ = fused.update(grads, fused.init(params), params)
            for k in params:
                np.testing.assert_allclose(
                    np.asarray(u_fused[k]), np.asarray(u_ref[k]),
                    rtol=2e-5, atol=1e-7, err_msg=str(kwargs),
                )


def test_router_jitter_salt_decorrelates_layers():
    """The round-2 advisor finding: one fixed key gave every layer the
    identical row-to-noise map.  Folding the layer index in must produce a
    different selection-noise pattern per salt while staying deterministic."""
    from learning_at_home_tpu.ops.moe_dispatch import router_jitter

    rs = np.random.RandomState(3)
    gates = jnp.asarray(rs.rand(64, 8).astype(np.float32))
    a0 = router_jitter(gates, 0.3, salt=0)
    a0_again = router_jitter(gates, 0.3, salt=0)
    a1 = router_jitter(gates, 0.3, salt=1)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a0_again))
    assert not np.allclose(np.asarray(a0), np.asarray(a1))
    # traced salt (the scan-over-layers case) matches the static pattern
    a1_traced = jax.jit(lambda s: router_jitter(gates, 0.3, salt=s))(
        jnp.int32(1)
    )
    np.testing.assert_allclose(np.asarray(a1_traced), np.asarray(a1), rtol=1e-6)


def test_small_top_k_matches_lax_top_k():
    from learning_at_home_tpu.ops.moe_dispatch import _small_top_k

    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(128, 16).astype(np.float32))
    for k in (1, 2, 4):
        w_ref, i_ref = jax.lax.top_k(x, k)
        w, i = _small_top_k(x, k)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref))
    # ties break toward the lower index, like lax.top_k
    t = jnp.asarray([[1.0, 2.0, 2.0, 0.5]])
    _, i = _small_top_k(t, 2)
    np.testing.assert_array_equal(np.asarray(i), [[1, 2]])
    with pytest.raises(ValueError):
        _small_top_k(t, 5)


class TestTokenMask:
    """Padding tokens masked out of routing (round-3 advisor: batched
    decode padding must not exhaust expert capacity ahead of real
    tokens)."""

    def test_masked_tokens_claim_no_capacity(self):
        # every token wants expert 0; capacity 2.  Unmasked, tokens 0-1
        # fill the slots and token 3 is dropped; with tokens 1-2 masked
        # as padding, token 3 (real) must get a slot instead.
        logits = jnp.asarray(
            np.tile([5.0, 0.0, 0.0, 0.0], (4, 1)), jnp.float32
        )
        mask = jnp.asarray([True, False, False, True])
        unmasked = top_k_gating(logits, k=1, capacity=2)
        assert float(unmasked.combine[3].sum()) == 0.0  # dropped
        masked = top_k_gating(logits, k=1, capacity=2, token_mask=mask)
        assert float(masked.combine[3].sum()) > 0.0  # real token fits
        # padding rows contribute nothing and occupy nothing
        assert float(masked.combine[1].sum()) == 0.0
        assert float(masked.combine[2].sum()) == 0.0
        assert not bool(masked.dispatch[1].any())
        assert not bool(masked.dispatch[2].any())
        # dropped_fraction counts only real tokens: both fit -> 0
        assert float(masked.dropped_fraction) == 0.0

    def test_indexed_plan_matches_onehot_with_mask(self):
        from learning_at_home_tpu.ops import (
            combine_outputs_indexed,
            dispatch_tokens_indexed,
            top_k_gating_indices,
        )

        rs = np.random.RandomState(3)
        logits = jnp.asarray(rs.randn(24, 6).astype(np.float32))
        mask = jnp.asarray(rs.rand(24) > 0.3)
        x = jnp.asarray(rs.randn(24, 8).astype(np.float32))
        p1 = top_k_gating(logits, k=2, capacity=4, token_mask=mask)
        p2 = top_k_gating_indices(logits, k=2, capacity=4, token_mask=mask)
        y1 = combine_outputs(dispatch_tokens(x, p1), p1)
        y2 = combine_outputs_indexed(dispatch_tokens_indexed(x, p2), p2)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
        np.testing.assert_allclose(
            float(p1.dropped_fraction), float(p2.dropped_fraction), atol=1e-6
        )
        np.testing.assert_allclose(
            float(p1.aux_loss), float(p2.aux_loss), atol=1e-5
        )
