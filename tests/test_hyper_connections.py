"""The residual path of several streams (``trunk.hc_coefficients``,
``hc_pre``, ``hc_post``: manifold-constrained hyper-connections) against
the plain reference's equations
(``benchmarks/configs/xing4_0_29b_a4b_reference.py``), forward and through
the Sinkhorn iterations backward; the mixing matrix's two marginals; the
clamp; what a wrong program reads; and ``hc_streams=None``: the parent's
step to the bit.

Tiny sizes on the CPU, float32.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

from __graft_entry__ import (  # noqa: E402
    glm_4_7_flash_one_chip,
    xing4_0_29b_a4b_one_chip,
)
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "xing4_0_29b_a4b_reference.py"))
N, C, B, S = 4, 48, 2, 16
SIZES = dict(reference.SIZES, hc_mult=N, norm_eps=1e-6)
HOW = (SIZES["hc_sinkhorn_iters"], SIZES["hc_eps"], SIZES["hc_clamp"],
       SIZES["norm_eps"])


def _part(seed=0, spread=1.0):
    """One part's parameters and a stream, the logits' spread a trained
    model's (``spread`` widens it)."""
    rs = np.random.RandomState(seed)
    hp = {
        "phi": jnp.asarray(rs.normal(0, 50.0 / np.sqrt(N * C), (N * C, 2 * N + N * N)),
                           jnp.float32),
        "b": jnp.asarray(np.concatenate([
            rs.normal(0, 1, 2 * N),
            (np.eye(N) + 0.3 * rs.normal(0, 1, (N, N))).ravel()]) * spread,
            jnp.float32),
        "alpha": jnp.full((3,), 0.01 * spread, jnp.float32),
    }
    x = jnp.asarray(rs.normal(0, 1, (B, S, N, C)) * rs.uniform(0.5, 2, (1, 1, N, 1)),
                    jnp.float32)
    return hp, x


def _program(hp, x, y):
    """``(h, X')`` of the three functions."""
    pre, post, res, _ = trunk.hc_coefficients(hp, x, *HOW)
    return trunk.hc_pre(x, pre), trunk.hc_post(x, y, post, res)


def _reference(hp, x, y):
    out, h, _ = reference.hc_part(hp, x, lambda h: y, SIZES)
    return h, out


def test_the_three_functions_match_the_reference():
    hp, x = _part()
    y = jnp.asarray(np.random.RandomState(1).normal(0, 1, (B, S, C)), jnp.float32)
    pre, post, res, error = jax.jit(
        lambda hp, x: trunk.hc_coefficients(hp, x, *HOW))(hp, x)
    want_pre, want_post, want_res = reference.hc_coefficients(hp, x, SIZES)
    # the program's coefficients lead with the streams' axes
    np.testing.assert_allclose(np.moveaxis(pre, 0, -1), want_pre, atol=2e-6)
    np.testing.assert_allclose(np.moveaxis(post, 0, -1), want_post, atol=4e-6)
    np.testing.assert_allclose(
        np.moveaxis(res, (0, 1), (-2, -1)), want_res, atol=2e-6)
    assert 0 <= float(error) < 1e-5
    got, want = jax.jit(_program)(hp, x, y), _reference(hp, x, y)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * np.abs(w).max())
    # visibly neither the identity nor uniform, and the input moves it
    diagonal = np.mean([np.asarray(want_res)[..., i, i] for i in range(N)])
    assert 0.3 < diagonal < 0.6
    assert np.asarray(want_res).std(axis=(0, 1)).mean() > 0.02


def test_gradients_through_the_twenty_iterations_match_the_reference():
    hp, x = _part(2)
    rs = np.random.RandomState(3)
    y = jnp.asarray(rs.normal(0, 1, (B, S, C)), jnp.float32)
    ch = jnp.asarray(rs.normal(0, 1, (B, S, C)), jnp.float32)
    cx = jnp.asarray(rs.normal(0, 1, (B, S, N, C)), jnp.float32)

    def scalar(both):
        def f(hp, x, y):
            h, out = both(hp, x, y)
            return jnp.sum(h * ch) + jnp.sum(out * cx)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    got, want = scalar(_program)(hp, x, y), scalar(_reference)(hp, x, y)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(want)[0]]
    for name, g, w in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, name  # every leaf is reached, alpha and b too
        np.testing.assert_allclose(g, w, atol=5e-5 * scale, err_msg=name)


@pytest.mark.parametrize("seed", range(4))
def test_the_mixing_matrix_is_doubly_stochastic(seed):
    hp, x = _part(10 + seed)
    _, _, res, error = trunk.hc_coefficients(hp, x, *HOW)
    res = np.asarray(res)
    assert (res > 0).all()
    assert np.abs(res.sum(axis=0) - 1).max() < 1e-5  # columns
    assert np.abs(res.sum(axis=1) - 1).max() < 1e-5  # rows
    np.testing.assert_allclose(
        float(error), max(np.abs(res.sum(axis=0) - 1).max(),
                          np.abs(res.sum(axis=1) - 1).max()), rtol=1e-5)
    # one iteration is not enough: what the counter would read of it
    _, _, once, once_error = trunk.hc_coefficients(hp, x, 1, *HOW[1:])
    assert float(once_error) > 1e-3


def test_the_clamp_holds_logits_that_would_overflow():
    hp, x = _part(5, spread=400.0)  # logits of hundreds: exp overflows float32
    for clamp, finite in (((-30.0, 30.0), True), ((-1e9, 1e9), False)):
        pre, post, res, error = trunk.hc_coefficients(
            hp, x, HOW[0], HOW[1], clamp, HOW[3])
        assert bool(jnp.isfinite(res).all()) is finite
    want = reference.hc_coefficients(hp, x, SIZES)[2]
    pre, post, res, _ = trunk.hc_coefficients(hp, x, *HOW)
    np.testing.assert_allclose(
        np.moveaxis(res, (0, 1), (-2, -1)), want, atol=1e-5)
    narrow = trunk.hc_coefficients(hp, x, HOW[0], HOW[1], (-1.0, 1.0), HOW[3])[2]
    # clipped to -1..1 no entry of the start is over e^2 times another
    assert float(jnp.abs(narrow - res).max()) > 0.1


@pytest.mark.parametrize(
    "variant", ["one_iteration", "identity_res", "static", "post_1",
                "unnormalised"])
def test_a_wrong_residual_path_is_told_from_the_right_one(variant):
    """Each wrong program the runner names moves the written streams by
    percent, not by rounding: the comparison can tell."""
    hp, x = _part(7)
    y = jnp.asarray(np.random.RandomState(8).normal(0, 1, (B, S, C)), jnp.float32)
    right = _reference(hp, x, y)[1]
    wrong = reference.hc_part(hp, x, lambda h: y, SIZES, variant)[0]
    rel = float(jnp.sqrt(jnp.sum((wrong - right) ** 2) / jnp.sum(right ** 2)))
    assert rel > 2e-2, (variant, rel)


def test_sinkhorn_in_bfloat16_is_told_from_float32():
    hp, x = _part(9)
    _, _, res, _ = trunk.hc_coefficients(hp, x, *HOW)
    logits = jnp.log(res)  # any positive start
    low = trunk.sinkhorn(jnp.exp(logits).astype(jnp.bfloat16), HOW[0], HOW[1])
    marginal = float(jnp.abs(low.astype(jnp.float32).sum(axis=0) - 1).max())
    assert marginal > 1e-3  # float32 reads under 1e-5


def _tiny_step_text(recipe, **replace):
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    model, cfg, optimizer, batch = recipe(mesh, tiny=True)
    if replace:
        model = DMoETransformerLM(dataclasses.replace(cfg, **replace), mesh)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    ids = jax.ShapeDtypeStruct((batch, cfg.seq_len), jnp.int32)
    return model.make_train_step(optimizer).lower(
        params, opt_state, ids, ids).as_text(debug_info=True)


def test_one_stream_is_the_parents_step_to_the_bit():
    """With ``hc_streams``, ``rope_scaling`` and ``v_head_dim`` at None the
    recipe's step is GLM-4.7-Flash's block at this model's sizes, letter
    for letter: the wrapper is not on the path (the parent's own programs
    are held by ``tests/test_olmoe.py``'s hashes)."""
    from test_olmoe import GLM_4_7_FLASH_TINY_STEP_SHA256, lowered_tiny_step
    import hashlib

    text = lowered_tiny_step(glm_4_7_flash_one_chip, {"expert": 1}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GLM_4_7_FLASH_TINY_STEP_SHA256
    plain = _tiny_step_text(
        xing4_0_29b_a4b_one_chip, hc_streams=None, rope_scaling=None,
        v_head_dim=None)
    assert "hc/" not in plain and "sinkhorn" not in plain
    assert "hc/sinkhorn" in _tiny_step_text(xing4_0_29b_a4b_one_chip)
