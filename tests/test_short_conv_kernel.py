"""The gated short convolution that is LFM2's conv mixer as one pass each
way (``ops/short_conv.py``: ``short_conv_fwd``, ``short_conv_bwd``) against
the plain form it replaces on a TPU: under ``interpret`` on the CPU, at
sizes its tiles admit (channels a multiple of 128, a row block a multiple
of 16).  What Mosaic makes of it at the cell's shape is the chip's
(``tools/smallthinker_probe.py conv gated``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import short_conv


@pytest.fixture
def rows(request, monkeypatch):
    """The kernel's row block for a test (a block shorter than S makes both
    halos cross a block's edge; the module's own is longer than any S
    here, so a sequence ends inside it: one block of the whole length)."""
    if request.param is not None:
        monkeypatch.setattr(short_conv, "_ROWS", request.param)
    return request.param


def _inputs(bsz, s, c, dtype, taps, seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(bsz, s, 3 * c), dtype),
            jnp.asarray(0.5 * rs.randn(c, taps), jnp.float32))


def _kernel(bcu, w):
    """Jitted anew a call: the blocks are read when the kernel is traced."""
    return jax.jit(lambda bcu, w: short_conv.gated_short_conv_kernel(
        bcu, w, interpret=True))(bcu, w)


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))


# (row block, B, S, C, taps): blocks shorter than S with two rows a batch,
# two channel blocks and four taps, and the module's own block, inside
# which the sequence ends
SHAPES = [(32, 2, 96, 128, 3), (16, 2, 48, 256, 4), (None, 2, 64, 128, 3)]
CASES = pytest.mark.parametrize("rows, bsz, s, c, taps, dtype", [
    pytest.param(r, b, s, c, k, dtype,
                 id=f"{r}-{b}x{s}x{c}-{k}-{jnp.dtype(dtype).name}")
    for r, b, s, c, k in SHAPES for dtype in (jnp.float32, jnp.bfloat16)
], indirect=["rows"])


@CASES
def test_the_kernel_matches_the_plain_form(rows, bsz, s, c, taps, dtype):
    """The same float32 arithmetic between the same roundings: float32
    inputs to the order of a fused multiply-add, bf16 to one bf16 ulp of
    the result at most, and that rarely."""
    args = _inputs(bsz, s, c, dtype, taps)
    want = short_conv.gated_short_conv_plain(*args)
    got = _kernel(*args)
    assert got.dtype == want.dtype and got.shape == want.shape == (bsz, s, c)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    else:
        assert np.all(np.abs(got - want) <= np.maximum(
            np.abs(want) * 2.0 ** -7, 2e-6))
        assert np.mean(got != want) < 0.01


@CASES
def test_the_kernels_gradients_match_autodiff_of_the_plain_form(
        rows, bsz, s, c, taps, dtype):
    """``dB``, ``dC``, ``du`` (the three thirds of the one array the
    backward writes) and ``dw`` of a weighed sum of the output against
    ``jax.grad`` of the plain form: float32 sums over all the rows in both
    (bf16: a third is rounded once in each, from float32 values that
    differ in the last bit)."""
    args = _inputs(bsz, s, c, dtype, taps, seed=1)
    weigh = jnp.asarray(np.random.RandomState(2).randn(bsz, s, c), jnp.float32)

    def grads(form):
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            form(*a).astype(jnp.float32) * weigh), argnums=(0, 1)))(*args)

    (got_x, got_w), (want_x, want_w) = grads(_kernel), grads(
        short_conv.gated_short_conv_plain)
    assert got_x.dtype == want_x.dtype and got_x.shape == want_x.shape
    assert got_w.dtype == want_w.dtype and got_w.shape == want_w.shape
    tol = 2e-3 if dtype == jnp.bfloat16 else 2e-6
    for third, name in enumerate(("dB", "dC", "du")):
        part = slice(third * c, (third + 1) * c)
        assert _rms(got_x[..., part], want_x[..., part]) < tol, name
    assert _rms(got_w, want_w) < 2e-6


@pytest.mark.parametrize("rows", [16], indirect=True)
def test_an_impulse_at_the_head_of_row_1_leaves_row_0_untouched(rows):
    """Zeros stand before the first position of EVERY row of the batch, and
    nothing runs from one row into the next, forward or backward: an
    impulse at position 0 of row 1 moves row 1's first ``taps`` positions
    alone, and its cotangent's head moves nothing in row 0."""
    taps, s, c = 3, 48, 128
    _, w = _inputs(2, s, c, jnp.float32, taps, seed=3)
    bcu = jnp.zeros((2, s, 3 * c), jnp.float32).at[:, :, c:2 * c].set(1.0)
    bcu = bcu.at[1, 0, :c].set(1.0).at[1, 0, 2 * c:].set(1.0)  # B u = 1 there
    bcu = bcu.at[0, -taps:, :c].set(5.0)  # row 0's tail: B alone, u 0
    got = np.asarray(_kernel(bcu, w))
    assert not np.any(got[0])
    moved = np.any(got[1] != 0.0, axis=-1)
    assert moved.tolist() == [t < taps for t in range(s)]
    np.testing.assert_allclose(
        got, np.asarray(short_conv.gated_short_conv_plain(bcu, w)), atol=1e-6)
    bcu = _inputs(2, s, c, jnp.float32, taps, seed=4)[0]
    dy = jnp.zeros((2, s, c), jnp.float32).at[1, :taps].set(1.0)
    d_bcu, _ = jax.vjp(_kernel, bcu, w)[1](dy)
    assert not np.any(np.asarray(d_bcu[0]))


@pytest.mark.parametrize("shape, taps, backend, fits", [
    ((1, 16384, 2048), 3, "tpu", True),
    ((1, 16384, 2048), 3, "cpu", False),
    ((1, 16384, 2048), 3, "gpu", False),
    ((2, 4096, 128), 3, "tpu", True),
    ((1, 16384, 2000), 3, "tpu", False),  # channels off the lanes
    ((1, 16384 + 256, 2048), 3, "tpu", False),  # the row block does not divide
    ((1, 256, 2048), 3, "tpu", True),  # one block of the whole length
    ((1, 200, 2048), 3, "tpu", False),  # a block off the halo's tile
    ((1, 16384, 2048), 9, "tpu", True),  # eight rows back: a sublane tile
    ((1, 16384, 2048), 10, "tpu", False),
    ((1, 16384, 2048), 1, "tpu", False),  # no convolution
])
def test_the_path_rule_reads_the_backend_the_channels_and_the_rows(
        shape, taps, backend, fits):
    assert short_conv.short_conv_fits(shape, taps, backend) is fits


def test_off_the_chip_the_one_rule_takes_the_plain_form():
    bcu, w = _inputs(1, 32, 128, jnp.float32, 3, seed=5)
    np.testing.assert_array_equal(
        np.asarray(short_conv.gated_short_conv(bcu, w)),
        np.asarray(short_conv.gated_short_conv_plain(bcu, w)))
