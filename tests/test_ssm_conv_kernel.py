"""The mixer's causal convolution with its SiLU as one pass
(``ops/ssm_conv.py``: ``ssm_conv_fwd``, ``ssm_conv_bwd``) against the plain
form it replaces on a TPU: under ``interpret`` on the CPU, at sizes its
tiles admit (channels a multiple of 128, a row block a multiple of 16).
What Mosaic makes of it at the cell's shape is
``tests/test_nemotron_hybrid.py``'s (an AOT compile for a described chip)
and the chip's (``tools/smallthinker_probe.py conv``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import ssm_conv

TAPS = 4


@pytest.fixture
def rows(request, monkeypatch):
    """The kernel's row block for a test (the module's is 1,024: a block
    shorter than S makes both halos cross a block's edge)."""
    monkeypatch.setattr(ssm_conv, "_ROWS", request.param)
    return request.param


def _inputs(bsz, s, c, dtype, seed=0, taps=TAPS):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(bsz, s, c), dtype),
            jnp.asarray(0.5 * rs.randn(c, taps), jnp.float32),
            jnp.asarray(0.1 * rs.randn(c), jnp.float32))


def _kernel(*args, first=0):
    return ssm_conv.causal_conv_silu_kernel(*args, first, interpret=True)


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))


# (row block, B, S, C, taps): one block, blocks shorter than S, two and
# three channel blocks, two rows of a batch, two and three taps
SHAPES = [(32, 1, 32, 128, 4), (32, 1, 128, 256, 4), (16, 2, 64, 128, 4),
          (64, 2, 192, 1536, 4), (32, 1, 96, 128, 2), (16, 2, 48, 256, 3)]
CASES = pytest.mark.parametrize("rows, bsz, s, c, taps, dtype", [
    pytest.param(r, b, s, c, k, dtype, id=f"{r}-{b}x{s}x{c}-{k}-{jnp.dtype(dtype).name}")
    for r, b, s, c, k in SHAPES for dtype in (jnp.float32, jnp.bfloat16)
], indirect=["rows"])


@CASES
def test_the_kernel_matches_the_plain_form(rows, bsz, s, c, taps, dtype):
    """The same float32 arithmetic between the same roundings: float32
    inputs to the order of a fused multiply-add, bf16 to one bf16 ulp of
    the result at most, and that rarely (a result near zero, where the
    taps cancel, to the float32 tolerance)."""
    args = _inputs(bsz, s, c, dtype, taps=taps)
    want = ssm_conv.causal_conv_silu_plain(*args)
    got = _kernel(*args)
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    else:
        ulp = np.maximum(np.abs(want) * 2.0 ** -7, 2e-6)
        assert np.all(np.abs(got - want) <= ulp)
        assert np.mean(got != want) < 0.01


@CASES
def test_the_kernels_gradients_match_autodiff_of_the_plain_form(
        rows, bsz, s, c, taps, dtype):
    """``dx``, ``dw`` and ``db`` of a weighed sum of the output against
    ``jax.grad`` of the plain form: float32 sums over all the rows in
    both (bf16: ``dx`` is rounded once in each, from float32 values that
    differ in the last bit)."""
    args = _inputs(bsz, s, c, dtype, seed=1, taps=taps)
    weigh = jnp.asarray(np.random.RandomState(2).randn(bsz, s, c), jnp.float32)

    def loss(form):
        return lambda *a: jnp.sum(form(*a).astype(jnp.float32) * weigh)

    want = jax.grad(loss(ssm_conv.causal_conv_silu_plain), argnums=(0, 1, 2))(*args)
    got = jax.grad(loss(_kernel), argnums=(0, 1, 2))(*args)
    for name, g, w in zip(("dx", "dw", "db"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        tol = 2e-3 if (name, dtype) == ("dx", jnp.bfloat16) else 2e-6
        assert _rms(g, w) < tol, (name, _rms(g, w))


@pytest.mark.parametrize("rows", [32], indirect=True)
@pytest.mark.parametrize("at", [31, 63, 64, 0])
def test_an_impulse_crosses_a_blocks_edge_forward_and_no_earlier(rows, at):
    """One nonzero input row: the output differs from the bias's alone at
    that row and the three after it, in the next block too, and at no
    earlier row."""
    s, c = 96, 128
    _, w, b = _inputs(1, s, c, jnp.float32, seed=3)
    x = jnp.zeros((1, s, c), jnp.float32).at[0, at].set(1.0)
    got = np.asarray(_kernel(x, w, b))
    still = np.asarray(jax.nn.silu(b))
    moved = np.any(got[0] != still, axis=-1)
    assert moved.tolist() == [at <= t < at + TAPS for t in range(s)]
    np.testing.assert_allclose(
        got, np.asarray(ssm_conv.causal_conv_silu_plain(x, w, b)), atol=1e-6)


@pytest.mark.parametrize("rows", [32], indirect=True)
@pytest.mark.parametrize("at", [32, 64, 66, 95])
def test_a_cotangent_crosses_a_blocks_edge_backward_and_no_later(rows, at):
    """One nonzero cotangent row: ``dx`` is nonzero at that row and the
    three before it, in the block before too, and at no later row."""
    s, c = 96, 128
    x, w, b = _inputs(1, s, c, jnp.float32, seed=4)
    dy = jnp.zeros((1, s, c), jnp.float32).at[0, at].set(1.0)
    dx, dw, db = jax.vjp(_kernel, x, w, b)[1](dy)
    moved = np.any(np.asarray(dx)[0] != 0.0, axis=-1)
    assert moved.tolist() == [at - TAPS < t <= at for t in range(s)]
    want = jax.vjp(ssm_conv.causal_conv_silu_plain, x, w, b)[1](dy)
    for g, wnt in zip((dx, dw, db), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt), atol=1e-6)


@pytest.mark.parametrize("rows", [16, 32], indirect=True)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_batch_rows_tail_does_not_leak_into_the_next_rows_head(rows, dtype):
    """Zeros stand before the first position of EVERY row of the batch:
    the second row's output is what it is alone, whatever the first row
    ends with; and its cotangent's head moves nothing in the first row."""
    x, w, b = _inputs(2, 32, 128, dtype, seed=5)
    x = x.at[0, -3:].set(100.0)
    both = _kernel(x, w, b)
    np.testing.assert_array_equal(
        np.asarray(both[1], np.float32),
        np.asarray(_kernel(x[1:], w, b)[0], np.float32))
    dy = jnp.zeros(x.shape, dtype).at[1, :3].set(1.0)
    dx = jax.vjp(_kernel, x, w, b)[1](dy)[0]
    assert not np.any(np.asarray(dx[0], np.float32))


@pytest.mark.parametrize("rows", [32], indirect=True)
@pytest.mark.parametrize("first, width", [(0, 384), (128, 384), (256, 400), (128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_reads_its_channels_where_a_wider_array_holds_them(
        rows, first, width, dtype):
    """``first``: the C channels convolved start there in an array of
    ``width`` (any width, a lane multiple or not); the result and ``dw``,
    ``db`` are the slice's, and ``dx`` is the slice's between zeros."""
    c = 128
    x, w, b = _inputs(2, 64, width, dtype, seed=6)
    w, b = w[:c], b[:c]
    weigh = jnp.asarray(np.random.RandomState(7).randn(2, 64, c), jnp.float32)

    def loss(form):
        return lambda *a: jnp.sum(form(*a).astype(jnp.float32) * weigh)

    def plain(x, w, b):
        return ssm_conv.causal_conv_silu_plain(x[..., first:first + c], w, b)

    def kernel(x, w, b):
        return _kernel(x, w, b, first=first)

    got, want = kernel(x, w, b), plain(x, w, b)
    assert got.shape == want.shape == (2, 64, c) and got.dtype == want.dtype
    tol = 2e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    dx, dw, db = jax.grad(loss(kernel), argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(x, w, b)
    assert dx.shape == x.shape and dx.dtype == x.dtype
    outside = np.ones(width, bool)
    outside[first:first + c] = False
    assert not np.any(np.asarray(dx, np.float32)[..., outside])
    for g, wnt in zip((dx, dw, db), want):
        assert _rms(g, wnt) < (2e-6 if dtype == jnp.float32 else 2e-3)


@pytest.mark.parametrize("shape, taps, backend, fits", [
    ((1, 16384, 6144), 4, "tpu", True),
    ((1, 16384, 6144), 4, "cpu", False),
    ((1, 16384, 6144), 4, "gpu", False),
    ((2, 4096, 128), 4, "tpu", True),
    ((1, 16384, 6100), 4, "tpu", False),  # channels off the lanes
    ((1, 16384 + 256, 6144), 4, "tpu", False),  # the row block does not divide
    ((1, 256, 6144), 4, "tpu", True),  # one block of the whole length
    ((1, 200, 6144), 4, "tpu", False),  # a block off the halo's tile
    ((1, 16384, 6144), 9, "tpu", True),  # eight rows back: a sublane tile
    ((1, 16384, 6144), 10, "tpu", False),
    ((1, 16384, 6144), 1, "tpu", False),  # no convolution
])
def test_the_path_rule_reads_the_backend_the_channels_and_the_rows(
        shape, taps, backend, fits):
    assert ssm_conv.conv_kernel_fits(shape, taps, backend) is fits


@pytest.mark.parametrize("first, fits", [
    (0, True), (4096, True), (512, True), (256, False), (4000, False)])
def test_the_path_rule_wants_the_channels_to_start_on_a_blocks_edge(first, fits):
    """6,144 channels are 12 blocks of 512: blocks 8-19 of the mixer's
    in-projection ``[z 4096 | x B C 6144 | dt 64]``."""
    assert ssm_conv.conv_kernel_fits((1, 16384, 6144), 4, "tpu", first) is fits


def test_a_call_the_kernel_cannot_take_returns_the_plain_forms_bits(monkeypatch):
    """``causal_conv_silu`` is the plain form on the CPU and at a shape
    the tiles refuse; where the rule admits the call it hands it to the
    kernel."""
    fits = _inputs(1, 64, 128, jnp.bfloat16)
    narrow = _inputs(1, 64, 96, jnp.bfloat16)
    wide = _inputs(1, 64, 256, jnp.bfloat16)
    wide = (wide[0], wide[1][:128], wide[2][:128])

    def same(got, want):
        return np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))

    calls = []
    monkeypatch.setattr(ssm_conv, "causal_conv_silu_kernel",
                        lambda *a: calls.append(a) or "the kernel")
    assert same(ssm_conv.causal_conv_silu(*fits),
                ssm_conv.causal_conv_silu_plain(*fits))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert same(ssm_conv.causal_conv_silu(*narrow),
                ssm_conv.causal_conv_silu_plain(*narrow))
    assert same(ssm_conv.causal_conv_silu(*wide, first=64),  # off a block's edge
                ssm_conv.causal_conv_silu_plain(wide[0][..., 64:192], *wide[1:]))
    assert not calls
    assert ssm_conv.causal_conv_silu(*fits) == "the kernel" and len(calls) == 1


@pytest.mark.parametrize("rows", [32], indirect=True)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_convolution_without_a_bias_is_the_one_with_zeros(rows, dtype, monkeypatch):
    """``b=None`` (the delta mixer's convolutions): zeros that are no
    parameter.  The plain form and the kernel (under ``interpret``, through
    the path rule as on a TPU) agree with each other and with explicit
    zeros, forward and in the gradients of ``x`` and ``w``; [q | k] at
    column 0 and v at a later block of a wider array, as the mixer calls."""
    c = 128
    x, w, _ = _inputs(2, 64, 2 * c + 40, dtype, seed=8)
    zeros = jnp.zeros((c,), jnp.float32)
    weigh = jnp.asarray(np.random.RandomState(9).randn(2, 64, c), jnp.float32)
    tol = 2e-6 if dtype == jnp.float32 else 2.0 ** -7

    def loss(form, first):
        return lambda x, w: jnp.sum(
            form(x, w, None, first=first).astype(jnp.float32) * weigh)

    for first in (0, c):
        filters = w[first:first + c]
        plain = ssm_conv.causal_conv_silu(x, filters, None, first=first)  # the CPU's
        assert np.array_equal(
            np.asarray(plain, np.float32),
            np.asarray(ssm_conv.causal_conv_silu_plain(
                x[..., first:first + c], filters, zeros), np.float32))
        want = jax.grad(loss(ssm_conv.causal_conv_silu, first), argnums=(0, 1))(
            x, filters)
        with monkeypatch.context() as on_a_tpu:
            on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
            on_a_tpu.setattr(
                ssm_conv, "causal_conv_silu_kernel",
                lambda *a, kernel=ssm_conv.causal_conv_silu_kernel: kernel(
                    *a, interpret=True))
            assert ssm_conv.conv_kernel_fits((2, 64, c), TAPS, "tpu", first)
            got = ssm_conv.causal_conv_silu(x, filters, None, first=first)
            grads = jax.grad(loss(ssm_conv.causal_conv_silu, first), argnums=(0, 1))(
                x, filters)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(plain, np.float32), atol=tol, rtol=tol)
        for g, wnt in zip(grads, want):
            assert g.shape == wnt.shape
            assert _rms(g, wnt) < (2e-6 if dtype == jnp.float32 else 2e-3)
