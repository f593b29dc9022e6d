"""The gated delta rule (``ops/delta_rule.py``): the chunked form against
the rule a position at a time, outputs, final state and every gradient;
the three ways of its triangular solve; the negative eigenvalue that a
write strength of 2 gives.  Tiny sizes on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops.delta_rule import (
    SOLVE_BLOCK,
    gated_delta_chunked,
    gated_delta_recurrent,
    solve_unit_lower,
)


def _inputs(seed=0, b=2, s=64, h=3, dk=8, dv=12, g_scale=0.5, dtype=jnp.float32):
    """Unit-length q (scaled) and k, values, decays' logarithms down to
    ``-g_scale`` a position and write strengths drawn up to 2; the key size
    is not the value size."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -g_scale * jax.random.uniform(ks[3], (b, s, h))
    beta = 2.0 * jax.random.uniform(ks[4], (b, s, h))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _chunked(chunk, **how):
    """The chunked rule as ONE compiled program (operation by operation the
    CPU takes seconds a call and half a minute a gradient)."""
    return jax.jit(lambda *a: gated_delta_chunked(*a, chunk, **how))


_recurrent = jax.jit(gated_delta_recurrent)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("chunk, s", [
    (16, 16), (16, 80), (32, 32), (32, 128), (64, 64), (64, 192)])
def test_the_chunked_rule_is_the_rule_a_position_at_a_time(chunk, s):
    """Output and final state, a sequence of one chunk and of many."""
    args = _inputs(seed=chunk + s, s=s)
    assert args[4].max() > 1.9  # strengths beyond 1 are in the draw
    want_o, want_state = _recurrent(*args)
    got_o, got_state = _chunked(chunk)(*args)
    assert got_o.shape == want_o.shape and got_state.shape == (2, 3, 8, 12)
    assert _rel(got_o, want_o) < 1e-5
    assert _rel(got_state, want_state) < 1e-5


@pytest.mark.parametrize("chunk, segment", [(16, 128), (32, 128), (64, 128), (16, 32)])
def test_every_gradient_of_the_chunked_rule_is_the_recurrent_ones(chunk, segment):
    """Through the output AND the final state, back to q, k, v, the decays
    and the write strengths; also where the sequence is taken in four
    segments, each made again in the backward pass from the state that
    entered it."""
    args = _inputs(seed=chunk, s=128)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 3, 12))

    def loss(rule):
        def of(*a):
            o, state = rule(*a)
            return jnp.sum(o * weights) + jnp.sum(jnp.sin(state))
        return of

    got = jax.jit(jax.grad(loss(_chunked(chunk, segment=segment)),
                           argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(loss(gated_delta_recurrent), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert _rel(a, b) < 2e-5, name


@pytest.mark.parametrize("chunk", [16, 64])
def test_decays_down_to_exp_minus_twenty_a_chunk_stay_finite(chunk):
    """The difference-of-sums rule: no quotient of exponentials, so neither
    an inf nor a nan in the outputs or in any gradient, and the values
    are still the recurrence's."""
    args = _inputs(seed=3, s=128, g_scale=2 * 20.0 / chunk)
    assert float(jnp.min(jnp.sum(args[3][:, :chunk], axis=1))) < -15.0
    want_o, want_state = _recurrent(*args)
    got_o, got_state = _chunked(chunk)(*args)
    assert _rel(got_o, want_o) < 1e-5 and _rel(got_state, want_state) < 1e-5
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(_chunked(chunk)(*a)[0] ** 2),
        argnums=(0, 1, 2, 3, 4)))(*args)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_a_write_strength_of_two_flips_the_state_along_the_key():
    """With unit ``k`` the transition ``alpha (I - beta k k^T)`` has the
    eigenvalue ``alpha (1 - beta)``: -1 at ``beta = 2`` and no decay.  What
    was written under ``k`` comes back with its sign turned, in both forms;
    at ``beta = 1`` it is erased."""
    k = jnp.zeros((1, 32, 1, 4)).at[:, :, 0, 1].set(1.0)  # one key throughout
    v = jnp.zeros((1, 32, 1, 6)).at[:, 0, 0, :].set(jnp.arange(1.0, 7.0))
    g = jnp.zeros((1, 32, 1))
    written = np.arange(1.0, 7.0)
    for second, sign in ((2.0, -1.0), (1.0, 0.0)):
        beta = jnp.zeros((1, 32, 1)).at[:, 0].set(1.0).at[:, 17].set(second)
        for rule in (_recurrent, _chunked(16)):
            o, state = rule(k, k, v, g, beta)
            np.testing.assert_allclose(o[0, 16, 0], written, atol=1e-6)
            np.testing.assert_allclose(o[0, 17, 0], sign * written, atol=1e-6)
            np.testing.assert_allclose(state[0, 0, 1], sign * written, atol=1e-6)


@pytest.mark.parametrize("how", ["blocks", "product", "triangular"])
def test_the_three_solves_agree_where_keys_differ(how):
    rs = np.random.RandomState(0)
    a = jnp.asarray(np.tril(rs.uniform(-0.2, 0.2, (3, 64, 64)), -1), jnp.float32)
    rhs = jnp.asarray(rs.normal(size=(3, 64, 5)), jnp.float32)
    got = solve_unit_lower(a, rhs, how)
    np.testing.assert_allclose(
        got + a @ got, rhs, atol=1e-5)  # (I + a) X = rhs


def test_the_blocks_inverses_hold_where_every_entry_nears_two():
    """Keys much the same throughout at write strengths near 2 and little
    decay make every entry under the diagonal near 2: forward substitution gives the inverse to
    float32's last digits, the nilpotent product inside a block of 16 has
    lost four of them."""
    from learning_at_home_tpu.ops import delta_rule

    rs = np.random.RandomState(1)  # 1.8 to 2: no entry a whole number
    a = jnp.asarray(np.tril(rs.uniform(1.8, 2.0, (64, 64)), -1), jnp.float32)
    rhs = jnp.asarray(rs.normal(size=(64, 7)), jnp.float32)
    want = np.linalg.solve(np.eye(64) + np.asarray(a, np.float64),
                           np.asarray(rhs, np.float64))
    assert _rel(solve_unit_lower(a, rhs), want) < 1e-5
    assert _rel(solve_unit_lower(a, rhs, "triangular"), want) < 1e-5
    block = a[:16, :16]
    exact = np.linalg.inv(np.eye(16) + np.asarray(block, np.float64))
    assert _rel(delta_rule._substituted_inverse(block), exact) < 1e-6
    assert _rel(delta_rule._product_inverse(block), exact) > 1e-4


def test_the_product_over_a_whole_chunk_loses_to_the_blocks_where_keys_are_alike():
    """Keys that share a large mean make ``A``'s entries of order 1: the
    powers of the product over 64 positions cancel to nothing float32
    holds; the blocks' forward substitution keeps them."""
    assert SOLVE_BLOCK == 16
    q, k, v, g, beta = _inputs(seed=2, s=128)
    k = k + 0.6
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    args = (q, k, v, 0.01 * g, beta)
    want, _ = _recurrent(*args)
    blocks, _ = _chunked(64, solve="blocks")(*args)
    product, _ = _chunked(64, solve="product")(*args)
    assert _rel(blocks, want) < 1e-5
    assert not _rel(product, want) < 1e-1  # inf or nan among them


def test_bf16_inputs_give_a_bf16_output_and_a_float32_state():
    args = _inputs(seed=5, s=64, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    want_o, want_state = _recurrent(*exact)
    got_o, got_state = _chunked(32)(*args)
    assert got_o.dtype == jnp.bfloat16 and got_state.dtype == jnp.float32
    assert _rel(got_o, want_o) < 3e-2 and _rel(got_state, want_state) < 3e-2


def test_decays_summed_in_bf16_read_worse_than_in_float32():
    args = _inputs(seed=6, s=128, g_scale=0.3)
    want, _ = _recurrent(*args)
    exact, _ = _chunked(64)(*args)
    rough, _ = _chunked(64, decay_dtype=jnp.bfloat16)(*args)
    assert _rel(rough, want) > 100 * _rel(exact, want)


def test_segments_hand_the_state_on_and_give_the_same_numbers():
    args = _inputs(seed=8, s=192)
    whole_o, whole_state = _chunked(16)(*args)
    o, state = _chunked(16, segment=48)(*args)
    np.testing.assert_allclose(o, whole_o, atol=1e-6)
    np.testing.assert_allclose(state, whole_state, atol=1e-6)
    with pytest.raises(ValueError, match="segments of 40"):
        _chunked(16, segment=40)(*args)


def test_a_chunk_that_does_not_divide_the_sequence_is_refused():
    with pytest.raises(ValueError, match="divide"):
        _chunked(32)(*_inputs(s=48))
    with pytest.raises(ValueError, match="'blocks'"):
        solve_unit_lower(jnp.zeros((24, 24)), jnp.zeros((24, 2)))
