"""The recurrent mixers' gate and grouped RMSNorm as one pass each way
(``ops/gate_norm.py``: ``gate_norm_fwd``, ``gate_norm_bwd``) against the
plain form it replaces on a TPU: under ``interpret`` on the CPU, at sizes its
tiles admit, in both orders (the gate first under a scale a channel and a
skip: Mamba-2; the norm first under one scale shared by the heads: Gated
DeltaNet), and under a gate that is ONE float32 number a group behind a
sigmoid (Kimi Delta Attention as Ling-3.0 gates it: a block of all the
channels, a group at a time).  What Mosaic makes of it at the cells' shapes is
``tests/test_nemotron_hybrid.py``'s (AOT compiles for a described chip) and
the chip's (``tools/smallthinker_probe.py gate_norm``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.ops import gate_norm

EPS = 1e-5


@pytest.fixture
def rows(request, monkeypatch):
    """The kernel's row block for a test (the module's is 512: a block
    shorter than S makes the partial sums of several blocks add up), in
    strips of 16 (the module's is 64: a block of 32 is two)."""
    monkeypatch.setattr(gate_norm, "_ROWS", request.param)
    monkeypatch.setattr(gate_norm, "_STRIP", 16)
    return request.param


def _inputs(bsz, s, c, wide, n_scale, heads, dtype, seed=0):
    """``(y [B, S, C], z [B, S, wide], scale [n_scale], skip)``: ``skip``
    ``(x [B, S, C], D [heads])``, or None where ``heads`` is 0."""
    rs = np.random.RandomState(seed)
    y, z = (jnp.asarray(rs.randn(bsz, s, n), dtype) for n in (c, wide))
    scale = jnp.asarray(1.0 + 0.1 * rs.randn(n_scale), jnp.float32)
    skip = (jnp.asarray(rs.randn(bsz, s, c), dtype),
            jnp.asarray(rs.randn(heads), jnp.float32)) if heads else None
    return y, z, scale, skip


def _forms(group, gate_first, first, c):
    def plain(y, z, scale, skip):
        return gate_norm.gated_rms_norm_plain(
            y, z[..., first:first + c], scale, group, EPS, gate_first, skip)

    def kernel(y, z, scale, skip):
        return gate_norm.gated_rms_norm_kernel(
            y, z, scale, group, EPS, gate_first, first, skip, interpret=True)

    return plain, kernel


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))


# (name, C, group, the scale's length, heads of the skip): Mamba-2's groups
# of four lane tiles under a scale a channel with the skip (two groups: two
# channel blocks), Gated DeltaNet's heads of one and a half under a shared
# scale (four heads: two blocks of 384), and each under the other's order
LAYOUTS = {"512-a-channel-skip": (1024, 512, 1024, 16),
           "192-shared": (768, 192, 192, 0),
           "192-shared-skip": (768, 192, 192, 4),
           "512-a-channel": (512, 512, 512, 0),
           "64-in-a-lane-tile": (256, 64, 256, 0)}
# (row block, B, S): one block of the whole length, blocks shorter than S,
# two rows of a batch
LENGTHS = [(32, 1, 32), (16, 2, 48)]
# z's column in a wider array: 0, and a block's edge (the array's width is
# no multiple of the block, as the cells' in-projections')
CASES = pytest.mark.parametrize("rows, bsz, s, layout, gate_first, at_edge, dtype", [
    pytest.param(r, b, s, layout, gate_first, at_edge, dtype,
                 id=f"{r}-{b}x{s}-{layout}-{'gate' if gate_first else 'norm'}-first"
                    f"-{'edge' if at_edge else '0'}-{jnp.dtype(dtype).name}")
    for r, b, s in LENGTHS for layout in LAYOUTS for gate_first in (True, False)
    for at_edge in (False, True) for dtype in (jnp.float32, jnp.bfloat16)
], indirect=["rows"])


def _case(bsz, s, layout, at_edge, dtype, seed):
    c, group, n_scale, heads = LAYOUTS[layout]
    block = gate_norm._blocks((bsz, s, c), group)[1]
    first = 2 * block if at_edge else 0
    args = _inputs(bsz, s, c, first + c + (40 if at_edge else 0), n_scale, heads,
                   dtype, seed)
    return c, group, first, args


@CASES
def test_the_kernel_matches_the_plain_form(
        rows, bsz, s, layout, gate_first, at_edge, dtype):
    """The same float32 arithmetic between the same roundings: float32
    inputs to the order of a sum taken in another order, bf16 to one bf16
    ulp of the result at most, and that rarely."""
    c, group, first, args = _case(bsz, s, layout, at_edge, dtype, 0)
    plain, kernel = _forms(group, gate_first, first, c)
    want, got = plain(*args), kernel(*args)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape == (bsz, s, c)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    else:
        assert np.all(np.abs(got - want) <= np.maximum(np.abs(want) * 2.0 ** -7, 2e-6))
        assert np.mean(got != want) < 0.01


@CASES
def test_the_kernels_gradients_match_autodiff_of_the_plain_form(
        rows, bsz, s, layout, gate_first, at_edge, dtype):
    """``dy``, ``dz``, ``dscale`` (and ``dx``, ``dD``) of a weighed sum of
    the output against ``jax.grad`` of the plain form: float32 sums over
    all the rows in both (bf16: a cotangent is rounded once in each, from
    float32 values that differ in the last bits); ``dz`` has the wider
    array's shape and is zero beside the gate's channels, the slice's
    transpose."""
    c, group, first, args = _case(bsz, s, layout, at_edge, dtype, 1)
    plain, kernel = _forms(group, gate_first, first, c)
    weigh = jnp.asarray(np.random.RandomState(2).randn(bsz, s, c), jnp.float32)

    def loss(form):
        return lambda *a: jnp.sum(form(*a).astype(jnp.float32) * weigh)

    argnums = (0, 1, 2, 3) if args[3] is not None else (0, 1, 2)
    # each side one compiled program (80 cases: operation by operation the
    # two gradients are most of this file's time)
    want = jax.tree_util.tree_leaves(jax.jit(jax.grad(loss(plain), argnums))(*args))
    got = jax.tree_util.tree_leaves(jax.jit(jax.grad(loss(kernel), argnums))(*args))
    assert len(got) == len(want) == (5 if args[3] is not None else 3)
    for name, g, w in zip(("dy", "dz", "dscale", "dx", "dD"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        rounded = dtype == jnp.bfloat16 and name in ("dy", "dz", "dx")
        assert _rms(g, w) < (3e-3 if rounded else 3e-6), (name, _rms(g, w))
    outside = np.ones(args[1].shape[-1], bool)
    outside[first:first + c] = False
    assert not np.any(np.asarray(got[1], np.float32)[..., outside])


@pytest.mark.parametrize("rows", [16], indirect=True)
@pytest.mark.parametrize("gate_first", [True, False])
def test_a_groups_mean_reads_its_own_channels_and_no_others(rows, gate_first):
    """One row with one group's channels scaled a thousandfold: the other
    groups' outputs do not move (a masked sum that let a neighbour's lanes
    in, in a block of two heads of 192, would)."""
    c, group = 768, 192
    y, z, scale, _ = _inputs(1, 32, c, c, group, 0, jnp.float32, seed=3)
    _, kernel = _forms(group, gate_first, 0, c)
    still = np.asarray(kernel(y, z, scale, None))
    for k in range(c // group):
        lanes = slice(k * group, (k + 1) * group)
        moved = np.asarray(kernel(
            y.at[0, 5, lanes].multiply(1000.0), z, scale, None))
        differs = np.any(moved != still, axis=(0, 1))
        assert differs[lanes].all() and not np.delete(differs, lanes).any()


@pytest.mark.parametrize("shape, group, backend, first, fits", [
    ((1, 16384, 4096), 512, "tpu", 0, True),  # nemotron: z at column 0 of 10,304
    ((1, 16384, 5760), 192, "tpu", 11520, True),  # olmo-hybrid: z at 11,520 of 17,340
    ((2, 4096, 128), 64, "tpu", 0, True),
    ((1, 256, 4096), 512, "tpu", 0, True),  # one block of the whole length
    ((1, 16384, 4096), 512, "cpu", 0, False),  # the backend
    ((1, 16384, 4096), 512, "gpu", 0, False),
    ((1, 16384, 4000), 500, "tpu", 0, False),  # channels off the lanes
    ((1, 16384, 5760), 320, "tpu", 0, False),  # whole groups on whole lane tiles: 640 > 512
    ((1, 16384, 2880), 192, "tpu", 0, False),  # 7.5 blocks of 384: a block would split a head
    ((1, 16384, 5760), 192, "tpu", 11520 + 192, False),  # first off a block's edge
    ((1, 16384, 4096), 512, "tpu", 256, False),
    ((1, 16384 + 256, 4096), 512, "tpu", 0, False),  # the row block does not divide
    ((1, 200, 4096), 512, "tpu", 0, False),  # a block off a 16-bit sublane tile
    ((1, 96, 4096), 512, "tpu", 0, False),  # a block its strips of 64 do not divide
])
def test_the_path_rule_reads_the_backend_the_channels_the_groups_and_the_rows(
        shape, group, backend, first, fits):
    assert gate_norm.gate_norm_fits(shape, group, backend, first) is fits


@pytest.mark.parametrize("shape, group, channels", [
    ((1, 16384, 4096), 512, 512), ((1, 16384, 5760), 192, 384),
    ((1, 64, 1024), 128, 512), ((1, 64, 384), 64, 384), ((1, 64, 5760), 320, None)])
def test_a_channel_block_holds_whole_groups_on_whole_lane_tiles(shape, group, channels):
    assert gate_norm._blocks(shape, group)[1] == channels


def test_a_call_the_kernel_cannot_take_returns_the_plain_forms_bits(monkeypatch):
    """``gated_rms_norm`` is the plain form on the CPU and at a shape the
    tiles refuse; where the rule admits the call it hands it to the kernel
    with everything the call was given."""
    y, z, scale, skip = _inputs(1, 32, 128, 296, 128, 2, jnp.bfloat16)
    narrow = _inputs(1, 32, 96, 96, 96, 0, jnp.bfloat16)

    def same(got, want):
        return np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))

    calls = []
    monkeypatch.setattr(gate_norm, "gated_rms_norm_kernel",
                        lambda *a: calls.append(a) or "the kernel")
    want = gate_norm.gated_rms_norm_plain(y, z[..., 128:256], scale, 64, EPS, True, skip)
    assert same(gate_norm.gated_rms_norm(y, z, scale, 64, EPS, True, 128, skip), want)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert same(  # channels off the lanes
        gate_norm.gated_rms_norm(*narrow[:3], 32, EPS, False),
        gate_norm.gated_rms_norm_plain(*narrow[:3], 32, EPS, False))
    assert same(  # off a block's edge
        gate_norm.gated_rms_norm(y, z, scale, 64, EPS, True, 64, skip),
        gate_norm.gated_rms_norm_plain(y, z[..., 64:192], scale, 64, EPS, True, skip))
    assert not calls
    assert gate_norm.gated_rms_norm(y, z, scale, 64, EPS, True, 128, skip) == "the kernel"
    assert calls == [(y, z, scale, 64, EPS, True, 128, skip, "silu")]


@pytest.mark.parametrize("gate_first", [True, False, "a head's sigmoid"])
def test_the_plain_form_is_the_mixers_arithmetic_of_before(gate_first):
    """The mixers' lines as ``models/trunk.py`` had them before PR 47 (and
    ``channel_delta_mixer``'s before PR 67), written out: the plain form
    returns their bits (float32 in, so that nothing hides in a rounding)."""
    f32 = jnp.float32
    b, s, heads, per = 2, 16, 4, 32
    c = heads * per
    y, z, scale, (x, d) = _inputs(
        b, s, c, c, c if gate_first is True else per, heads, f32, seed=4)
    if gate_first == "a head's sigmoid":  # channel_delta_mixer: ONE number a head
        o32 = y.reshape(b, s, heads, per).astype(f32)
        head_gate = z[..., :heads, None]
        was = (o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + EPS)
               * scale.astype(f32) * jax.nn.sigmoid(head_gate)).astype(y.dtype)
        was = was.reshape(b, s, c)
        got = gate_norm.gated_rms_norm_plain(
            y, head_gate[..., 0], scale, per, EPS, False, None, "sigmoid")
    elif gate_first:  # ssm_mixer: groups of two heads' channels, a scale a channel
        groups = 2
        was = y.reshape(b, s, heads, per).astype(f32) + d.astype(f32)[:, None] * x.reshape(
            b, s, heads, per).astype(f32)
        was = was.reshape(b, s, c) * jax.nn.silu(z.astype(f32))
        grouped = was.reshape(b, s, groups, c // groups)
        ms = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        was = (grouped * jax.lax.rsqrt(ms + EPS)).reshape(b, s, c) * scale
        got = gate_norm.gated_rms_norm_plain(y, z, scale, c // groups, EPS, True, (x, d))
    else:  # delta_mixer: a head a group, one scale shared by the heads
        o = y.reshape(b, s, heads, per).astype(f32)
        ms = jnp.mean(o * o, axis=-1, keepdims=True)
        was = (o * jax.lax.rsqrt(ms + EPS) * scale).reshape(b, s, c) * jax.nn.silu(
            z.astype(f32))
        got = gate_norm.gated_rms_norm_plain(y, z, scale, per, EPS, False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(was))


# ---- a gate that is one number a group (PR 67) ----


@pytest.fixture
def group_rows(request, monkeypatch):
    """The row block under a gate a group (the module's is 256: a block
    shorter than S makes the partial sums of several blocks add up)."""
    monkeypatch.setattr(gate_norm, "_GROUP_ROWS", request.param)
    return request.param


def _group_inputs(bsz, s, c, group, dtype, seed=0):
    """``(y [B, S, C], the gate [B, S, C / group] float32, scale [group])``."""
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(bsz, s, c), dtype),
            jnp.asarray(2.0 * rs.randn(bsz, s, c // group), jnp.float32),
            jnp.asarray(1.0 + 0.1 * rs.randn(group), jnp.float32))


def _group_forms(group, gate="sigmoid"):
    def plain(y, z, scale):
        return gate_norm.gated_rms_norm_plain(y, z, scale, group, EPS, False, None, gate)

    def kernel(y, z, scale):
        return gate_norm.gated_rms_norm_kernel(
            y, z, scale, group, EPS, False, 0, None, gate, interpret=True)

    return plain, kernel


# (row block, B, S, C, group, the gate's function): Ling-3.0's heads of one
# lane tile in blocks shorter than S over two rows of a batch; a block of
# the whole length whose groups are two lane tiles; the other function
GROUP_CASES = pytest.mark.parametrize("group_rows, bsz, s, c, group, gate, dtype", [
    pytest.param(r, b, s, c, group, gate, dtype,
                 id=f"{r}-{b}x{s}x{c}-{group}-{gate}-{jnp.dtype(dtype).name}")
    for r, b, s, c, group, gate in [
        (64, 2, 128, 512, 128, "sigmoid"), (32, 1, 32, 1024, 256, "sigmoid"),
        (32, 1, 32, 256, 128, "silu")]
    for dtype in (jnp.float32, jnp.bfloat16)
], indirect=["group_rows"])


@GROUP_CASES
def test_under_a_gate_a_group_the_kernel_and_its_gradients_match_the_plain_form(
        group_rows, bsz, s, c, group, gate, dtype):
    """The output as above; ``dy``, the GATE's gradient (float32 [B, S, C /
    group] whatever ``y``'s dtype: one group sum serves it and ``dy``) and
    ``dscale`` against ``jax.grad`` of the plain form."""
    args = _group_inputs(bsz, s, c, group, dtype)
    plain, kernel = _group_forms(group, gate)
    weigh = jnp.asarray(np.random.RandomState(2).randn(bsz, s, c), jnp.float32)

    def both(form):
        def fn(*a):
            out, back = jax.vjp(form, *a)
            return (out, *back((weigh.astype(dtype))))
        return jax.jit(fn)

    want, got = both(plain)(*args), both(kernel)(*args)
    for name, g, w in zip(("out", "dy", "dz", "dscale"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
    assert got[2].dtype == jnp.float32 and got[2].shape == (bsz, s, c // group)
    out, out_want = (np.asarray(a[0], np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(out, out_want, atol=2e-6, rtol=2e-6)
    else:
        assert np.all(np.abs(out - out_want) <= np.maximum(
            np.abs(out_want) * 2.0 ** -7, 2e-6))
        assert np.mean(out != out_want) < 0.01
    for name, g, w in zip(("dy", "dz", "dscale"), got[1:], want[1:]):
        rounded = dtype == jnp.bfloat16 and name == "dy"
        assert _rms(g, w) < (3e-3 if rounded else 3e-6), (name, _rms(g, w))


@pytest.mark.parametrize("group_rows", [16], indirect=True)
def test_a_heads_mean_and_gate_read_their_own_channels_and_no_others(group_rows):
    """One row with one head's channels scaled a thousandfold, then with
    that head's gate moved: the other heads' outputs stay as they were."""
    c, group = 512, 128
    y, z, scale = _group_inputs(1, 32, c, group, jnp.float32, seed=3)
    _, kernel = _group_forms(group)
    still = np.asarray(kernel(y, z, scale))
    for k in range(c // group):
        lanes = slice(k * group, (k + 1) * group)
        for moved in (kernel(y.at[0, 5, lanes].multiply(1000.0), z, scale),
                      kernel(y, z.at[0, 5, k].add(1.0), scale)):
            differs = np.any(np.asarray(moved) != still, axis=(0, 1))
            assert differs[lanes].all() and not np.delete(differs, lanes).any()


@pytest.mark.parametrize("shape, group, backend, fits", [
    ((1, 16384, 4096), 128, "tpu", True),  # ling-3.0: 32 heads of 128
    ((2, 4096, 1024), 256, "tpu", True),
    ((1, 64, 512), 128, "tpu", True),  # one block of the whole length
    ((1, 16384, 4096), 128, "cpu", False),  # the backend
    ((1, 16384, 4096), 64, "tpu", False),  # a group off the lane tiles
    ((1, 16384, 5760), 192, "tpu", False),
    ((1, 16384, 8192), 128, "tpu", False),  # wider than the one block
    ((1, 16384 + 64, 4096), 128, "tpu", False),  # the row block does not divide
    ((1, 200, 4096), 128, "tpu", False),  # a block off a 16-bit sublane tile
    ((1, 96, 4096), 128, "tpu", True),  # no strips here: the block is walked whole
])
def test_the_path_rule_under_a_gate_a_group(shape, group, backend, fits):
    assert gate_norm.gate_norm_fits(shape, group, backend, 0, True) is fits
    assert gate_norm.gate_a_group(shape, (*shape[:2], shape[2] // group), group)
    assert not gate_norm.gate_a_group(shape, shape, group)


def test_a_gate_a_group_the_kernel_cannot_take_returns_the_plain_forms_bits(
        monkeypatch):
    """On the CPU and at heads off the lane tiles ``gated_rms_norm`` is the
    plain form; where the rule admits the call the kernel gets all of it.
    The gate a group stands after the norm, at column 0, without a skip:
    anything else is refused, not built."""
    y, z, scale = _group_inputs(1, 32, 256, 128, jnp.bfloat16)
    narrow = _group_inputs(1, 32, 256, 64, jnp.bfloat16)
    calls = []
    monkeypatch.setattr(gate_norm, "gated_rms_norm_kernel",
                        lambda *a: calls.append(a) or "the kernel")

    def same(got, want):
        return np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))

    want = gate_norm.gated_rms_norm_plain(y, z, scale, 128, EPS, False, None, "sigmoid")
    assert same(gate_norm.gated_rms_norm(
        y, z, scale, 128, EPS, False, gate="sigmoid"), want)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert same(
        gate_norm.gated_rms_norm(*narrow, 64, EPS, False, gate="sigmoid"),
        gate_norm.gated_rms_norm_plain(*narrow, 64, EPS, False, None, "sigmoid"))
    assert not calls
    assert gate_norm.gated_rms_norm(
        y, z, scale, 128, EPS, False, gate="sigmoid") == "the kernel"
    assert calls == [(y, z, scale, 128, EPS, False, 0, None, "sigmoid")]
    for refused in ({"gate_first": True}, {"gate_first": False, "first": 128},
                    {"gate_first": False, "skip": (y, jnp.ones(2))},
                    {"gate_first": False, "gate": "tanh"}):
        with pytest.raises(ValueError):
            gate_norm.gated_rms_norm(y, z, scale, 128, EPS, **refused)
    assert len(calls) == 1
