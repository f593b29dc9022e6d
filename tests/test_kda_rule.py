"""The delta rule under a decay a KEY CHANNEL (Kimi Delta Attention;
``ops/delta_rule.py``, ``g`` of rank 4): the chunked plain form against the
rule a position at a time, outputs, final state and every gradient; with
the decay constant over a head's channels it IS the rule the head-decayed
path runs; the bounded gate's range and what the chunked form refuses.
Tiny sizes on the CPU.  The kernels under ``interpret`` are
``tests/test_kda_rule_kernels.py``'s (a file is one worker's under ``--dist
loadfile``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops.delta_rule import (
    EXPONENT_MOST,
    SOLVE_BLOCK,
    channel_decay_fits,
    gated_delta_chunked,
    gated_delta_plain,
    gated_delta_recurrent,
    kernel_fits,
)

FLOOR = -5.0  # kda_lower_bound


def _inputs(seed=0, b=2, s=64, h=3, dk=8, dv=12, floor=FLOOR, spread=2.0,
            dtype=jnp.float32):
    """Unit-length q (scaled) and k, values, a log-decay a key channel in
    ``(floor, 0)`` through the bounded gate, write strengths in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = floor * jax.nn.sigmoid(spread * jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.random.uniform(ks[4], (b, s, h))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _chunked(chunk, **how):
    how.setdefault("decay_floor", FLOOR)
    return jax.jit(lambda *a: gated_delta_chunked(*a, chunk, **how))


_recurrent = jax.jit(gated_delta_recurrent)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("chunk, s", [
    (16, 16), (16, 80), (32, 32), (32, 128), (64, 64), (64, 192)])
def test_the_chunked_rule_is_the_rule_a_position_at_a_time(chunk, s):
    """Output and final state, a sequence of one chunk and of many, decays
    down to e^-5 a position a channel (a chunk's sum down to -300)."""
    args = _inputs(seed=chunk + s, s=s)
    assert args[3].min() < 0.9 * FLOOR and args[3].max() > 0.1 * FLOOR
    want_o, want_state = _recurrent(*args)
    got_o, got_state = _chunked(chunk)(*args)
    assert got_o.shape == want_o.shape and got_state.shape == (2, 3, 8, 12)
    assert _rel(got_o, want_o) < 1e-5
    assert _rel(got_state, want_state) < 1e-5


@pytest.mark.parametrize("segment", [32, 64, 128])
def test_segments_hand_the_state_on(segment):
    """A sequence of several segments (each under ``jax.checkpoint``) is the
    sequence whole."""
    args = _inputs(seed=3, s=128)
    want_o, want_state = _recurrent(*args)
    got_o, got_state = _chunked(32, segment=segment)(*args)
    assert _rel(got_o, want_o) < 1e-5 and _rel(got_state, want_state) < 1e-5


@pytest.mark.parametrize("name, at", [
    ("q", 0), ("k", 1), ("v", 2), ("g", 3), ("beta", 4)])
def test_every_gradient_of_the_chunked_rule_is_the_recurrences(name, at):
    """Through outputs and the final state, the decays' gradient a CHANNEL;
    finite everywhere (a masked infinity's gradient would not be)."""
    args = _inputs(seed=11, s=96)

    def loss(rule):
        def of(*args):
            o, state = rule(*args)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(state * state)
        return jax.jit(jax.grad(of, argnums=at))

    want = loss(gated_delta_recurrent)(*args)
    got = loss(lambda *a: gated_delta_chunked(
        *a, 32, decay_floor=FLOOR, segment=32))(*args)
    assert got.shape == args[at].shape and bool(jnp.isfinite(got).all())
    assert _rel(got, want) < 2e-5, name


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_a_decay_constant_over_a_heads_channels_is_the_head_decayed_rule(chunk):
    """What ties KDA to the rule ``qwen3-next`` and ``olmo-hybrid`` run: with
    ``alpha`` the same in every channel of a head the channel path gives the
    head path's output, state and gradients."""
    q, k, v, g, beta = _inputs(seed=chunk, s=128, floor=-0.5)
    head = g[..., 0]
    channel = jnp.broadcast_to(head[..., None], g.shape)
    want_o, want_state = jax.jit(
        lambda *a: gated_delta_chunked(*a, chunk))(q, k, v, head, beta)
    got_o, got_state = _chunked(chunk)(q, k, v, channel, beta)
    assert _rel(got_o, want_o) < 1e-5 and _rel(got_state, want_state) < 1e-5

    def loss(g, rule_floor):
        o, state = gated_delta_chunked(
            q, k, v, g, beta, chunk, decay_floor=rule_floor)
        return jnp.sum(o * o) + jnp.sum(state)

    want = jax.jit(jax.grad(lambda g: loss(g, None)))(head)
    got = jax.jit(jax.grad(lambda g: loss(g, FLOOR)))(channel).sum(axis=-1)
    assert _rel(got, want) < 2e-5


def test_the_recurrence_scales_the_states_rows():
    """One position from a state of ones: ``S_1 = (I - beta k k^T)
    Diag(alpha) 1 + beta k v^T``, written out."""
    q, k, v, g, beta = (a[:1, :1, :1] for a in _inputs(seed=5, s=16))
    alpha = np.exp(np.asarray(g[0, 0, 0], np.float64))
    kk, vv = np.asarray(k[0, 0, 0], np.float64), np.asarray(v[0, 0, 0], np.float64)
    b0 = float(beta[0, 0, 0])
    # from S_0 = 0 the decay has nothing to scale: two positions
    first = b0 * np.outer(kk, vv)
    q2, k2, v2, g2, beta2 = (a[:1, :2, :1] for a in _inputs(seed=5, s=16))
    _, state = _recurrent(q2, k2, v2, g2, beta2)
    alpha2 = np.exp(np.asarray(g2[0, 1, 0], np.float64))
    k1, v1 = np.asarray(k2[0, 1, 0], np.float64), np.asarray(v2[0, 1, 0], np.float64)
    b1 = float(beta2[0, 1, 0])
    decayed = alpha2[:, None] * first
    want = decayed + b1 * np.outer(k1, v1 - decayed.T @ k1)
    np.testing.assert_allclose(np.asarray(state[0, 0]), want, rtol=0, atol=1e-6)
    assert alpha.shape == (8,)


def test_the_bounded_gate_keeps_a_blocks_sum_within_float32():
    """``floor * sigmoid(.)`` lies in ``(floor, 0)`` whatever its argument,
    so SOLVE_BLOCK positions' sum stays above -EXPONENT_MOST and its
    exponential is finite in float32 and in bf16."""
    x = jnp.asarray([-1e30, -50.0, -1.0, 0.0, 1.0, 50.0, 1e30], jnp.float32)
    g = FLOOR * jax.nn.sigmoid(x)
    assert bool((g >= FLOOR).all()) and bool((g <= 0.0).all())
    assert -FLOOR * SOLVE_BLOCK <= EXPONENT_MOST
    for dtype in (jnp.float32, jnp.bfloat16):
        assert bool(jnp.isfinite(jnp.exp(jnp.asarray(EXPONENT_MOST, dtype))))
    assert not bool(jnp.isfinite(jnp.exp(jnp.float32(2 * EXPONENT_MOST))))


@pytest.mark.parametrize("floor, dtype, fits", [
    (-5.0, jnp.float32, True), (-5.0, jnp.bfloat16, True), (-0.1, jnp.float32, True),
    (-5.5, jnp.float32, False), (None, jnp.float32, False),
    (1.0, jnp.float32, False), (-5.0, jnp.float16, False)])
def test_what_a_channel_decay_needs_is_said_by_one_function(floor, dtype, fits):
    assert channel_decay_fits(floor, dtype) is fits


@pytest.mark.parametrize("floor", [None, -6.0])
def test_an_unbounded_or_too_deep_gate_is_refused_not_clamped(floor):
    args = _inputs(seed=2, s=32)
    with pytest.raises(ValueError, match="a decay a key channel of decay_floor"):
        gated_delta_chunked(*args, 16, decay_floor=floor)


def test_a_chunk_of_no_whole_blocks_is_refused():
    args = _inputs(seed=2, s=48)
    with pytest.raises(ValueError, match="chunks of whole blocks"):
        gated_delta_plain(*args, 24)


def test_the_decays_rank_says_which_form_runs_and_no_flag():
    """The head-decayed call's shapes answer as they did; the channel form's
    answer is one more argument of the same pure function."""
    shape = ((1, 16384, 32, 128), (1, 16384, 32, 128))
    assert kernel_fits(*shape, 64, "tpu")
    assert not kernel_fits(*shape, 64, "cpu")
    assert kernel_fits(*shape, 64, "tpu", channel=True)
    assert not kernel_fits((1, 16384, 30, 96), (1, 16384, 30, 192), 64, "tpu", channel=True)
    assert not kernel_fits(*shape, 64, "cpu", channel=True)


def test_bf16_operands_round_where_the_head_decayed_form_rounds():
    """bf16 q, k, v against the float32 recurrence on the same rounded
    inputs: the chunked form's products take bf16 operands scaled by the
    decays, and the error stays a few bf16 roundings."""
    args = _inputs(seed=7, s=128, dtype=jnp.bfloat16)
    want_o, want_state = _recurrent(*args)
    got_o, got_state = _chunked(64)(*args)
    assert got_o.dtype == jnp.bfloat16 and got_state.dtype == jnp.float32
    assert _rel(got_o, want_o) < 3e-2 and _rel(got_state, want_state) < 3e-2
