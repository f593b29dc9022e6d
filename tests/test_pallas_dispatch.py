"""Pallas dispatch kernel vs the reference gather (interpret mode on CPU;
the same kernel compiles natively on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import (
    dispatch_tokens_indexed,
    top_k_gating_indices,
)
from learning_at_home_tpu.ops.pallas_dispatch import dispatch_tokens_pallas


@pytest.mark.parametrize("n,E,k,cap", [(32, 8, 2, 6), (16, 4, 1, 2), (64, 16, 4, 8)])
def test_pallas_dispatch_matches_reference(n, E, k, cap):
    rs = np.random.RandomState(n + E)
    x = jnp.asarray(rs.randn(n, 128).astype(np.float32))
    logits = jnp.asarray(rs.randn(n, E).astype(np.float32))
    plan = top_k_gating_indices(logits, k=k, capacity=cap)
    ref = dispatch_tokens_indexed(x, plan)
    out = dispatch_tokens_pallas(x, plan, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # empty slots are zero rows
    empty = np.asarray(plan.token_for_slot) < 0
    assert (np.asarray(out)[empty] == 0).all()


def test_pallas_dispatch_rejects_unaligned_d():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(8, 100).astype(np.float32))  # 100 % 128 != 0
    plan = top_k_gating_indices(jnp.asarray(rs.randn(8, 4).astype(np.float32)), 1, 4)
    with pytest.raises(ValueError, match="128"):
        dispatch_tokens_pallas(x, plan, interpret=True)
