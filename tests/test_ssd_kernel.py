"""The Mamba-2 chunked scan as a kernel (``ops/ssd.py``:
``ssd_chunk_fwd``, ``ssd_chunk_bwd``) against the plain form it replaces
on a TPU: under ``interpret`` on the CPU, at sizes its tiles admit (chunks
and a state of 128, a group's channels a multiple of 128).  What Mosaic
makes of it at the cell's shape is ``tests/test_nemotron_hybrid.py``'s
(an AOT compile for a described chip) and the chip's
(``tools/smallthinker_probe.py ssd``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import ssd

Q = N = 128
# (heads a group, channels a head): a head that is a whole lane tile, two
# heads a tile (the cell's), four heads a tile
HEADS = [(1, 128), (4, 64), (4, 32)]


def _inputs(chunks, groups, heads, dtype, bsz=2, seed=0):
    hg, p = heads
    s, h = chunks * Q, groups * hg
    rs = np.random.RandomState(seed)
    return (
        jnp.asarray(rs.randn(bsz, s, h, p), dtype),
        jnp.asarray(np.log1p(np.exp(rs.randn(bsz, s, h) - 1.0)), jnp.float32),
        -jnp.asarray(rs.uniform(0.5, 4.0, h), jnp.float32),
        jnp.asarray(0.3 * rs.randn(bsz, s, groups, N), dtype),
        jnp.asarray(0.3 * rs.randn(bsz, s, groups, N), dtype),
    )


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))


# chunks x groups x (a head a tile, two heads a tile) x dtype, and four
# heads a tile twice
CASES = pytest.mark.parametrize("chunks, groups, heads, dtype", [
    pytest.param(chunks, groups, heads, dtype,
                 id=f"{chunks}-{groups}-{heads[0]}x{heads[1]}-{jnp.dtype(dtype).name}")
    for chunks, groups, heads in (
        [(c, g, hd) for c in (1, 2, 4) for g in (1, 2) for hd in HEADS[:2]]
        + [(2, 2, HEADS[2])])
    for dtype in (jnp.float32, jnp.bfloat16)
])


@CASES
def test_the_kernel_matches_the_plain_form(chunks, groups, heads, dtype):
    """``y`` and the state after the last position.  The kernel rounds
    where the plain form rounds (operands to ``x``'s dtype, float32
    decays, state and accumulation), so bf16 reads as float32 does: the
    order of a sum's terms apart."""
    args = _inputs(chunks, groups, heads, dtype)
    want_y, want_state = jax.jit(lambda *a: ssd.ssd_chunked_plain(*a, Q))(*args)
    y, state = jax.jit(
        lambda *a: ssd.ssd_chunked_kernel(*a, Q, interpret=True))(*args)
    assert y.dtype == want_y.dtype and y.shape == want_y.shape
    assert state.dtype == jnp.float32 and state.shape == want_state.shape
    tol = 1e-5 if dtype == jnp.float32 else 4e-3
    assert _rms(y, want_y) < tol
    assert _rms(state, want_state) < tol


@CASES
def test_the_kernels_gradients_match_autodiff_of_the_plain_form(
        chunks, groups, heads, dtype):
    """The five gradients of a loss that reads the output AND the last
    state (the backward kernel's seed), against ``jax.grad`` of the plain
    form.  In bf16 both round their operands, each where it multiplies:
    they differ by what each differs from float32 (0.3 %)."""
    args = _inputs(chunks, groups, heads, dtype, seed=1)
    bsz, _, h, p = args[0].shape
    rs = np.random.RandomState(2)
    weigh_y = jnp.asarray(rs.randn(*args[0].shape), jnp.float32)
    weigh_state = jnp.asarray(rs.randn(bsz, h, p, N), jnp.float32)

    def loss(form):
        def of(*a):
            y, state = form(*a)
            return (jnp.sum(y.astype(jnp.float32) * weigh_y)
                    + jnp.sum(state * weigh_state))
        return of

    # each side ONE compiled program: operation by operation the plain
    # form's autodiff takes the CPU 8 s a case
    want = jax.jit(jax.grad(loss(lambda *a: ssd.ssd_chunked_plain(*a, Q)),
                            argnums=(0, 1, 2, 3, 4)))(*args)
    got = jax.jit(jax.grad(
        loss(lambda *a: ssd.ssd_chunked_kernel(*a, Q, interpret=True)),
        argnums=(0, 1, 2, 3, 4)))(*args)
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-2
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rms(g, w) < tol, (name, _rms(g, w))


def test_a_call_the_kernel_cannot_take_returns_the_plain_forms_bits(monkeypatch):
    """``ssd_chunked`` is the plain form on the CPU, under a
    ``decay_dtype`` other than float32, and at a shape the tiles refuse;
    where all three admit the call it hands it to the kernel."""
    fits = _inputs(2, 2, (4, 64), jnp.bfloat16, bsz=1)
    narrow = _inputs(2, 2, (2, 32), jnp.bfloat16, bsz=1)  # 64 channels a group

    def same(got, want):
        return all(np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
                   for g, w in zip(got, want))

    calls = []
    monkeypatch.setattr(
        ssd, "ssd_chunked_kernel", lambda *a: calls.append(a) or "the kernel")
    assert same(ssd.ssd_chunked(*fits, Q), ssd.ssd_chunked_plain(*fits, Q))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert same(ssd.ssd_chunked(*fits, Q, jnp.bfloat16),
                ssd.ssd_chunked_plain(*fits, Q, jnp.bfloat16))
    assert same(ssd.ssd_chunked(*narrow, Q), ssd.ssd_chunked_plain(*narrow, Q))
    assert same(ssd.ssd_chunked(*fits, 64), ssd.ssd_chunked_plain(*fits, 64))
    assert not calls
    assert ssd.ssd_chunked(*fits, Q) == "the kernel" and len(calls) == 1


@pytest.mark.parametrize("x, b, chunk, backend, decay, fits", [
    ((1, 16384, 64, 64), (1, 16384, 8, 128), 128, "tpu", jnp.float32, True),
    ((1, 16384, 64, 64), (1, 16384, 8, 128), 128, "cpu", jnp.float32, False),
    ((1, 16384, 64, 64), (1, 16384, 8, 128), 128, "tpu", jnp.bfloat16, False),
    ((1, 16384, 64, 64), (1, 16384, 8, 128), 64, "tpu", jnp.float32, False),
    ((1, 16384, 64, 64), (1, 16384, 8, 64), 128, "tpu", jnp.float32, False),
    ((1, 16384, 64, 64), (1, 16384, 64, 128), 128, "tpu", jnp.float32, False),
    ((1, 16384, 8, 96), (1, 16384, 2, 128), 128, "tpu", jnp.float32, False),
    ((2, 256, 4, 256), (2, 256, 2, 128), 128, "tpu", jnp.float32, True),
    ((2, 64, 8, 64), (2, 64, 1, 128), 128, "tpu", jnp.float32, False),
    # what the chip's compiler refused: a group of one head, and a step's
    # temporaries beyond the scoped VMEM (chunks of 512; 16 heads of 128)
    ((2, 256, 2, 256), (2, 256, 2, 128), 128, "tpu", jnp.float32, False),
    ((1, 16384, 64, 64), (1, 16384, 8, 128), 256, "tpu", jnp.float32, True),
    ((1, 16384, 64, 64), (1, 16384, 8, 128), 512, "tpu", jnp.float32, False),
    ((1, 16384, 128, 64), (1, 16384, 8, 128), 128, "tpu", jnp.float32, True),
    ((1, 16384, 128, 128), (1, 16384, 8, 128), 128, "tpu", jnp.float32, False),
])
def test_the_path_rule_reads_the_backend_the_decays_and_the_tiles(
        x, b, chunk, backend, decay, fits):
    assert ssd.kernel_fits(x, b, chunk, backend, decay) is fits
