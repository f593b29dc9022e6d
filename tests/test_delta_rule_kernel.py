"""The chunked gated delta rule as a kernel (``ops/delta_rule.py``:
``delta_chunk_fwd``, ``delta_chunk_bwd``) against the plain form it
replaces on a TPU and against the rule a position at a time: under
``interpret`` on the CPU, at small sizes its tiles admit (positions in
frames of 128, keys that are no lane multiple, values of half a lane tile).  What Mosaic
makes of it at the cell's shape is ``tests/test_olmo_hybrid.py``'s (an AOT
compile for a described chip) and the chip's
(``tools/smallthinker_probe.py delta``).  What the forward keeps for the
backward, and what a checkpoint makes of it, is
``tests/test_delta_rule_kernel_residuals.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import delta_rule

NAMES = ("q", "k", "v", "g", "beta")
# (batch rows, positions, chunk, heads, a head's values): two chunks in one
# frame, two heads abreast (the cell's), two batch rows; six over three grid
# steps (the state crosses them), a head alone; four of 32 in a frame (a
# chunk that is a frame is the chip's: tools/smallthinker_probe.py delta);
# eight over two grid steps of two frames, as the cells' steps are (the
# backward rebuilds three entering states a step from the one kept; two
# heads abreast in such a step: the case of the rebuilt states below)
SHAPES = {"2-chunks": (2, 128, 64, 2, 64), "6-chunks": (1, 384, 64, 1, 128),
          "4-chunks": (1, 128, 32, 1, 128), "8-chunks": (1, 512, 64, 1, 128)}
DK = 24  # no multiple of the 128 lanes


def _inputs(s, h, dtype, seed=0, g_scale=0.5, dk=DK, dv=64, bsz=1, raw=False):
    """Unit-length q (scaled) and k (or, ``raw``, as a SiLU leaves them:
    the rule's to make unit-length), values, decays' logarithms down to
    ``-g_scale`` a position and write strengths drawn up to 2."""
    rs = np.random.RandomState(seed)

    def unit(a):
        if raw:
            return a / (1.0 + np.exp(-a))
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    return (jnp.asarray(
                unit(rs.randn(bsz, s, h, dk)) / (1.0 if raw else np.sqrt(dk)), dtype),
            jnp.asarray(unit(rs.randn(bsz, s, h, dk)), dtype),
            jnp.asarray(rs.randn(bsz, s, h, dv), dtype),
            jnp.asarray(-g_scale * rs.uniform(size=(bsz, s, h)), jnp.float32),
            jnp.asarray(2.0 * rs.uniform(size=(bsz, s, h)), jnp.float32))


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))


def _everything(form, args):
    """``(o, the last state, the five gradients)`` of ``form`` under a loss
    that reads the output AND the last state (the backward kernel's seed)."""
    rs = np.random.RandomState(2)
    bsz, s, h, dk = args[0].shape
    weigh_o = jnp.asarray(rs.randn(bsz, s, h, args[2].shape[-1]), jnp.float32)
    weigh_state = jnp.asarray(rs.randn(bsz, h, dk, args[2].shape[-1]), jnp.float32)

    def loss(*a):
        o, state = form(*a)
        return (jnp.sum(o.astype(jnp.float32) * weigh_o)
                + jnp.sum(state * weigh_state)), (o, state)

    (_, (o, state)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return o, state, grads


@functools.lru_cache(maxsize=None)
def _read(shape, dtype, raw=False):
    """The kernel's and the plain form's everything on one case's inputs;
    ``raw``: the rule makes q and k unit-length itself."""
    bsz, s, chunk, h, dv = SHAPES[shape]
    args = _inputs(s, h, dtype, dv=dv, bsz=bsz, raw=raw)
    assert delta_rule.kernel_fits(args[0].shape, args[2].shape, chunk, "tpu")

    def plain(q, k, *rest):
        if raw:
            q, k = delta_rule.unit_length(q, k)
        return delta_rule.gated_delta_plain(q, k, *rest, chunk)

    return args, chunk, _everything(
        lambda *a: delta_rule.gated_delta_kernel(
            *a, chunk, interpret=True, unit=raw), args), _everything(plain, args)


CASES = pytest.mark.parametrize("shape, dtype", [
    pytest.param(shape, dtype, id=f"{shape}-{jnp.dtype(dtype).name}")
    for shape, dtype in [
        ("2-chunks", jnp.float32), ("2-chunks", jnp.bfloat16),
        ("6-chunks", jnp.bfloat16), ("4-chunks", jnp.bfloat16),
        ("8-chunks", jnp.bfloat16)]])


@CASES
def test_the_kernel_matches_the_plain_form_and_the_recurrence(shape, dtype):
    """``o`` and the state after the last position.  The kernel rounds
    where the plain form rounds (operands to ``v``'s dtype, float32
    decays, solve, state and accumulation), so bf16 reads as float32 does:
    the order of a sum's terms apart."""
    args, _, (o, state, _), (want_o, want_state, _) = _read(shape, dtype)
    assert o.dtype == want_o.dtype and o.shape == want_o.shape
    assert state.dtype == jnp.float32 and state.shape == want_state.shape
    tol = 1e-5 if dtype == jnp.float32 else 4e-3
    assert _rms(o, want_o) < tol and _rms(state, want_state) < tol
    exact_o, exact_state = jax.jit(delta_rule.gated_delta_recurrent)(*args)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert _rms(o, exact_o) < tol and _rms(state, exact_state) < tol


@CASES
def test_the_kernels_gradients_match_autodiff_of_the_plain_form(shape, dtype):
    """The five gradients, the last state's cotangent not zero.  In bf16
    both round their operands, each where it multiplies: they differ by
    what each differs from float32 (0.3-0.5 %)."""
    _, _, (_, _, got), (_, _, want) = _read(shape, dtype)
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-2
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rms(g, w) < tol, (name, _rms(g, w))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_makes_q_and_k_unit_length_as_the_plain_form_does(dtype):
    """``unit``: q and k as the mixer's convolution left them; the kernel
    scales them in VMEM, forward, and carries the gradients through the
    scaling, backward (``unit_length`` and autodiff in the plain form)."""
    _, _, (o, state, got), (want_o, want_state, want) = _read("2-chunks", dtype, True)
    tol = 1e-5 if dtype == jnp.float32 else 4e-3
    assert _rms(o, want_o) < tol and _rms(state, want_state) < tol
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-2
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rms(g, w) < tol, (name, _rms(g, w))


def test_decays_down_to_exp_of_minus_50_a_position_stay_finite():
    """Sums of -3,200 inside a chunk: every decay is the exp of a masked
    DIFFERENCE, forward and backward; no quotient, no 0 times inf."""
    args = _inputs(128, 1, jnp.bfloat16, seed=3, g_scale=50.0, dv=128)
    o, state, grads = _everything(
        lambda *a: delta_rule.gated_delta_kernel(*a, 64, interpret=True), args)
    want_o, want_state, want = _everything(
        lambda *a: delta_rule.gated_delta_plain(*a, 64), args)
    for got in (o, state, *grads):
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    assert _rms(o, want_o) < 4e-3 and _rms(state, want_state) < 4e-3
    for name, g, w in zip(NAMES, grads, want):
        assert _rms(g, w) < 1.5e-2, (name, _rms(g, w))


def test_the_kernels_inverse_holds_where_every_entry_nears_two():
    """``tests/test_delta_rule.py``'s hard case (keys much the same, write
    strengths near 2, little decay: every entry under the diagonal near 2)
    in both chunks of a frame: the kernel's forward substitution reads no
    worse than the plain ``blocks`` solve, to float32's last digits."""
    rs = np.random.RandomState(1)  # 1.8 to 2: no entry a whole number
    a = np.zeros((128, 128), np.float32)
    a[:64, :64] = np.tril(rs.uniform(1.8, 2.0, (64, 64)), -1)
    a[64:, 64:] = np.tril(rs.uniform(1.8, 2.0, (64, 64)), -1)
    rhs = rs.normal(size=(128, 7)).astype(np.float32)
    want = np.linalg.solve(np.eye(128) + a.astype(np.float64), rhs.astype(np.float64))
    i, j = np.indices((128, 128))
    inverse = delta_rule._unit_lower_inverse(
        jnp.asarray(a), jnp.asarray(i, jnp.int32), jnp.asarray(j, jnp.int32), 64)
    got = jnp.matmul(inverse, rhs, precision=jax.lax.Precision.HIGHEST)
    plain = jnp.concatenate([
        delta_rule.solve_unit_lower(jnp.asarray(a[n:n + 64, n:n + 64]), rhs[n:n + 64])
        for n in (0, 64)])
    assert _rms(plain, want) < 1e-5
    assert _rms(got, want) < max(2 * _rms(plain, want), 1e-6)
    assert np.array_equal(np.asarray(inverse)[64:, :64], np.zeros((64, 64)))


def _parents_inverse(a, i, j, c):
    """``_unit_lower_inverse`` as PR 61 had it (d38e7a2), the reference of
    the case below: the identity as 16 tiles of [8, 128], a block's rows
    in its own 16 lanes and zeros in the other 112, and joins that
    multiply all 128 rows."""
    frame = a.shape[0]
    block = min(delta_rule.SOLVE_BLOCK, c)
    blocks = range(frame // block)
    diag = jnp.concatenate(
        [a[b * block:(b + 1) * block, b * block:(b + 1) * block] for b in blocks],
        axis=0)
    eye = jnp.where(i == j, 1.0, 0.0).astype(jnp.float32)
    tiles = [eye[r:r + 8, :] for r in range(0, frame, 8)]
    per = block // 8
    for step in range(block - 1):
        for b in blocks:
            row = jnp.broadcast_to(
                tiles[b * per + step // 8][step % 8:step % 8 + 1, :], (8, frame))
            for tile in range(b * per + (step + 1) // 8, (b + 1) * per):
                tiles[tile] = tiles[tile] - diag[
                    tile * 8:(tile + 1) * 8, step:step + 1] * row
    x = jnp.concatenate(tiles, axis=0)
    width = block
    highest = jax.lax.Precision.HIGHEST
    while width < c:
        below = jnp.where(
            (i // width == j // width + 1) & (i // (2 * width) == j // (2 * width)),
            a, 0.0)
        x = x - delta_rule._dot(
            delta_rule._dot(x, below, precision=highest), x, precision=highest)
        width *= 2
    return x


@pytest.mark.parametrize("entries", ["as-the-rule-makes-them", "every-entry-near-two"])
@pytest.mark.parametrize("c", [16, 32, 64, 128])
def test_the_packed_inverse_is_the_parents_to_the_bit(c, entries):
    """The frame's inverse from operands without their structural zeros
    (the 16-blocks side by side on full lanes, the joins over the rows a
    join changes) against the parent's body: BIT FOR BIT, at every chunk
    the grid admits, where ``A`` is the rule's own (unit keys, strengths
    up to 2, decays) and where every entry under the diagonal is 1.8 to 2.
    The multipliers are made by gathers along the lanes, which move
    entries and compute nothing, so the CPU's bits are held to as the
    chip's are."""
    rs = np.random.RandomState(c)
    frame = delta_rule.FRAME
    i, j = np.indices((frame, frame))
    if entries == "every-entry-near-two":
        a = rs.uniform(1.8, 2.0, (frame, frame))
    else:
        k = rs.randn(frame, DK)
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        gamma = np.cumsum(-0.5 * rs.uniform(size=(frame // c, c)), axis=1).reshape(-1, 1)
        a = 2.0 * rs.uniform(size=(frame, 1)) * (k @ k.T) * np.exp(
            np.minimum(gamma - gamma.T, 0.0))
    a = jnp.asarray(np.where((i // c == j // c) & (i > j), a, 0.0), jnp.float32)
    i, j = jnp.asarray(i, jnp.int32), jnp.asarray(j, jnp.int32)
    got = np.asarray(delta_rule._unit_lower_inverse(a, i, j, c))
    want = np.asarray(_parents_inverse(a, i, j, c))
    assert np.abs(want - np.eye(frame)).max() > 0.1 and np.all(np.isfinite(want))
    assert np.array_equal(got, want)
    assert np.array_equal(got[(i // c != j // c) | (i < j)], np.zeros(
        int(((i // c != j // c) | (i < j)).sum()), np.float32))


def test_a_call_the_kernel_cannot_take_returns_the_plain_forms_bits(monkeypatch):
    """``gated_delta_chunked`` is the plain form on the CPU (its bits),
    and on a TPU under a ``decay_dtype`` other than float32, under another
    solve than ``blocks`` and at a chunk the tiles refuse (the plain form's
    own result, whatever it is); where all four admit the call it hands it
    to the kernel."""
    args = _inputs(128, 2, jnp.bfloat16)
    chunked = delta_rule.gated_delta_chunked
    for got, want in zip(chunked(*args, 64), delta_rule.gated_delta_plain(*args, 64)):
        assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    with pytest.raises(ValueError, match="no tiles"):
        delta_rule.gated_delta_kernel(*args, 8, interpret=True)

    calls = []
    monkeypatch.setattr(
        delta_rule, "gated_delta_kernel",
        lambda *a, **kw: calls.append((a, kw)) or "the kernel")
    monkeypatch.setattr(
        delta_rule, "gated_delta_plain", lambda *a: ("the plain form", a[5:]))
    assert chunked(*args, 64)[0] == "the plain form"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert chunked(*args, 64, jnp.bfloat16) == (
        "the plain form", (64, jnp.bfloat16, "blocks", delta_rule.SEGMENT))
    assert chunked(*args, 64, solve="product") == (
        "the plain form", (64, jnp.float32, "product", delta_rule.SEGMENT))
    assert chunked(*args, 8) == (
        "the plain form", (8, jnp.float32, "blocks", delta_rule.SEGMENT))
    assert not calls
    assert chunked(*args, 64, unit=True) == "the kernel"
    assert calls == [((*args, 64), {"unit": True})]


@pytest.mark.parametrize("q, dv, chunk, backend, decay, solve, fits", [
    ((1, 16384, 30, 96), 192, 64, "tpu", jnp.float32, "blocks", True),
    ((1, 16384, 30, 96), 192, 64, "cpu", jnp.float32, "blocks", False),
    ((1, 16384, 30, 96), 192, 64, "tpu", jnp.bfloat16, "blocks", False),
    ((1, 16384, 30, 96), 192, 64, "tpu", jnp.float32, "product", False),
    ((1, 16384, 30, 96), 192, 64, "tpu", jnp.float32, "triangular", False),
    ((1, 16384, 30, 96), 192, 32, "tpu", jnp.float32, "blocks", True),
    ((1, 16384, 30, 96), 192, 128, "tpu", jnp.float32, "blocks", True),
    # a chunk the frame does not hold, one that is no whole block of the
    # solve, positions that are no whole frames, a chunk that does not divide
    ((1, 16384, 30, 96), 192, 256, "tpu", jnp.float32, "blocks", False),
    ((1, 16384, 30, 96), 192, 8, "tpu", jnp.float32, "blocks", False),
    ((1, 192, 30, 96), 192, 64, "tpu", jnp.float32, "blocks", False),
    ((1, 64, 30, 96), 192, 64, "tpu", jnp.float32, "blocks", False),
    ((1, 16384, 30, 96), 192, 48, "tpu", jnp.float32, "blocks", False),
    # keys that are no whole sublane tile; values that make no lane tiles,
    # two heads side by side or one; a state beyond the scoped VMEM
    ((1, 16384, 30, 100), 192, 64, "tpu", jnp.float32, "blocks", False),
    ((1, 16384, 30, 96), 160, 64, "tpu", jnp.float32, "blocks", False),
    ((1, 16384, 15, 96), 192, 64, "tpu", jnp.float32, "blocks", False),
    ((1, 16384, 30, 256), 512, 64, "tpu", jnp.float32, "blocks", False),
    ((2, 1024, 7, 128), 128, 64, "tpu", jnp.float32, "blocks", True),
])
def test_the_path_rule_reads_the_backend_the_decays_the_solve_and_the_tiles(
        q, dv, chunk, backend, decay, solve, fits):
    got = delta_rule.kernel_fits(q, (*q[:3], dv), chunk, backend, decay, solve)
    assert got is fits


@pytest.mark.parametrize("h, s, c, dk, dv, grid", [
    (30, 16384, 64, 96, 192, (2, 256)),  # the cell's
    (30, 384, 64, 96, 192, (2, 128)),
    (7, 256, 64, 128, 128, (1, 256)),
    (30, 16384, 64, 128, 256, (2, 256)),
    (30, 16384, 64, 256, 256, (1, 256)),
    (30, 16384, 64, 256, 384, None),
])
def test_the_grid_takes_two_heads_abreast_where_the_states_leave_room(
        h, s, c, dk, dv, grid):
    assert delta_rule._grid(h, s, c, dk, dv) == grid
