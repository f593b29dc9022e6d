"""The delta rule under a decay a KEY CHANNEL as kernels
(``ops/delta_rule.py``: ``delta_channel_fwd``, ``delta_channel_bwd``) under
``interpret`` on the CPU, against the recurrence and the plain form: a
module apart from ``tests/test_kda_rule.py`` (whose helpers it uses) so that
``--dist loadfile`` can give the interpreter's compiles a worker of their
own."""

import jax
import jax.numpy as jnp
import pytest

from learning_at_home_tpu.ops import delta_rule
from learning_at_home_tpu.ops.delta_rule import (
    gated_delta_plain,
    gated_delta_recurrent,
    kernel_fits,
)
from test_kda_rule import FLOOR, _recurrent, _rel


def _kernel_inputs(seed=0, s=256, h=2, dtype=jnp.float32):
    """As the mixer hands them over: q and k as the convolution left them
    (the kernel makes them unit-length), keys and values of a lane tile."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, s, h, 128)).astype(dtype)
    k = jax.random.normal(ks[1], (1, s, h, 128)).astype(dtype)
    v = jax.random.normal(ks[2], (1, s, h, 128)).astype(dtype)
    g = FLOOR * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (1, s, h, 128)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, s, h)))
    return q, k, v, g, beta


def _kernel(chunk):
    return jax.jit(lambda *a: delta_rule.gated_delta_kernel(
        *a, chunk, interpret=True, unit=True))


def _plain_of_units(chunk):
    def rule(q, k, v, g, beta):
        q, k = delta_rule.unit_length(q, k)
        return gated_delta_plain(q, k, v, g, beta, chunk)
    return jax.jit(rule)


@pytest.mark.parametrize("chunk, heads", [(64, 2), (32, 2), (16, 1), (64, 3)])
def test_the_channel_kernels_are_the_rule_a_position_at_a_time(chunk, heads):
    """``delta_channel_fwd`` against the recurrence and the plain form: a
    chunk of two spans of references (64: the second span's pairs with the
    first take its first row), of one (32, 16), two heads abreast and one."""
    args = _kernel_inputs(seed=chunk, h=heads)
    with jax.default_matmul_precision("highest"):
        q, k = delta_rule.unit_length(args[0], args[1])
        want_o, want_state = _recurrent(q, k, *args[2:])
        plain_o, plain_state = _plain_of_units(chunk)(*args)
        got_o, got_state = _kernel(chunk)(*args)
    assert got_o.shape == (1, 256, heads, 128)
    assert _rel(got_o, want_o) < 2e-5 and _rel(got_state, want_state) < 2e-5
    assert _rel(got_o, plain_o) < 2e-5 and _rel(got_state, plain_state) < 2e-5


@pytest.mark.parametrize("chunk", [64, 32])
def test_every_gradient_of_the_channel_kernels_is_the_recurrences(chunk):
    """``delta_channel_bwd`` through outputs and the final state: q, k, v,
    the log-decays a CHANNEL and the strengths, over two grid steps (the
    state's cotangent carried back, the states rebuilt from the step's)."""
    args = _kernel_inputs(seed=3 + chunk, s=512)

    def loss(rule):
        def of(*args):
            o, state = rule(*args)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(state * state)
        return jax.jit(jax.grad(of, argnums=(0, 1, 2, 3, 4)))

    with jax.default_matmul_precision("highest"):
        want = loss(lambda q, k, v, g, b: gated_delta_recurrent(
            *delta_rule.unit_length(q, k), v, g, b))(*args)
        got = loss(lambda *a: delta_rule.gated_delta_kernel(
            *a, chunk, interpret=True, unit=True))(*args)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        assert _rel(g, w) < 5e-5, name


@pytest.mark.parametrize("chunk", [64, 32])
def test_the_channel_kernels_take_gates_saturated_at_the_floor(chunk):
    """Log-decays of EXACTLY ``decay_floor`` in runs of whole spans beside
    open ones (a sigmoid saturated at 1 and at 0, half the channels each
    way a run of 48 positions): a span's middle-row reference then meets
    exponents of 16 x 5 = EXPONENT_MOST exactly, the bound the plain form's
    first-row reference was set for.  Outputs, state and every gradient are
    finite and the recurrence's; the outputs and the queries' gradient to
    5e-4 of their largest and not 5e-5: a key's element under 6.5e-4 times
    ``e^-80`` falls under float32's least normal number (1.2e-38) and its
    term of a span's first row's score with itself is lost (one element in
    some rows here, up to 2e-3 of that row's score; under bf16's rounding
    of the other 127 terms)."""
    q, k, v, g, beta = _kernel_inputs(seed=21 + chunk, s=256)
    run = (jnp.arange(256) // 48)[None, :, None, None]
    channel = jnp.arange(128)[None, None, None, :]
    g = jnp.where((run + channel) % 2 == 0, FLOOR, 0.0) * jnp.ones_like(g)
    assert float(g.min()) == FLOOR and float(g.max()) == 0.0
    args = (q, k, v, g, beta)

    def both(rule):
        def of(*args):
            o, state = rule(*args)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(state * state), (o, state)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1, 2, 3, 4), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = both(lambda q, k, v, g, b: gated_delta_recurrent(
            *delta_rule.unit_length(q, k), v, g, b))(*args)
        (_, got), got_grads = both(lambda *a: delta_rule.gated_delta_kernel(
            *a, chunk, interpret=True, unit=True))(*args)
    for name, g_, w in zip(("o", "state", "q", "k", "v", "g", "beta"),
                           (*got, *got_grads), (*want, *want_grads)):
        assert bool(jnp.isfinite(g_).all()), name
        assert _rel(g_, w) < (5e-4 if name in ("o", "q") else 5e-5), name


def test_the_channel_kernels_at_bf16_round_where_the_plain_form_rounds():
    """bf16 q, k, v: kernel and plain form against the float32 recurrence on
    the same inputs, each a few bf16 roundings off and no more than twice
    the other."""
    args = _kernel_inputs(seed=9, dtype=jnp.bfloat16)
    q, k = delta_rule.unit_length(args[0], args[1])
    want_o, want_state = _recurrent(q, k, *args[2:])
    kernel_o, kernel_state = _kernel(64)(*args)
    plain_o, plain_state = _plain_of_units(64)(*args)
    assert kernel_o.dtype == jnp.bfloat16 and kernel_state.dtype == jnp.float32
    for got, plain, want in ((kernel_o, plain_o, want_o),
                             (kernel_state, plain_state, want_state)):
        assert _rel(got, want) < 3e-2
        assert _rel(got, want) < 2 * _rel(plain, want) + 1e-3


def test_a_decay_constant_over_the_channels_gives_the_head_kernels_result():
    """The channel kernels with every channel of a head at the head's decay
    against ``delta_chunk_fwd`` / ``delta_chunk_bwd`` on that decay: the rule
    ``qwen3-next`` runs."""
    q, k, v, g, beta = _kernel_inputs(seed=4)
    head = 0.1 * g[..., 0]
    channel = jnp.broadcast_to(head[..., None], g.shape)

    def loss(g):
        o, state = delta_rule.gated_delta_kernel(
            q, k, v, g, beta, 64, interpret=True, unit=True)
        return jnp.sum(o * o) + jnp.sum(state), (o, state)

    with jax.default_matmul_precision("highest"):
        (_, want), want_g = jax.jit(jax.value_and_grad(loss, has_aux=True))(head)
        (_, got), got_g = jax.jit(jax.value_and_grad(loss, has_aux=True))(channel)
    assert _rel(got[0], want[0]) < 2e-5 and _rel(got[1], want[1]) < 2e-5
    assert _rel(got_g.sum(axis=-1), want_g) < 5e-5


def test_the_channel_kernels_refuse_a_chunk_of_three_spans():
    args = _kernel_inputs(seed=1)
    with pytest.raises(ValueError, match="chunks of one or two spans"):
        delta_rule.gated_delta_kernel(*args, 128, interpret=True)
    assert not kernel_fits((1, 256, 2, 128), (1, 256, 2, 128), 128, "tpu", channel=True)
    assert kernel_fits((1, 256, 2, 128), (1, 256, 2, 128), 128, "tpu")


def test_the_channel_kernels_keep_their_residuals_by_name():
    """Under a checkpoint that saves ``DELTA_RESIDUALS`` the gradient's
    program holds ONE forward call (the recompute holds none) and one
    backward call, as the head-decayed kernels' does."""
    args = _kernel_inputs(seed=6)

    def loss(*a):
        o, state = jax.checkpoint(
            lambda *a: delta_rule.gated_delta_kernel(*a, 64, interpret=True, unit=True),
            policy=jax.checkpoint_policies.save_only_these_names(
                delta_rule.DELTA_RESIDUALS))(*a)
        return jnp.sum(o) + jnp.sum(state)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 3)))(*args))
    assert jaxpr.count("delta_channel_fwd") == 1
    assert jaxpr.count("delta_channel_bwd") == 1
