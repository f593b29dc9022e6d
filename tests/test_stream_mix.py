"""The residual streams' three passes as kernels (``ops/stream_mix.py``:
``stream_stats_fwd`` / ``_bwd``, ``stream_read_fwd`` / ``_bwd``,
``stream_write_fwd`` / ``_bwd``) against the plain forms they replace on a
TPU: under ``interpret`` on the CPU at a small shape with the cell's ratios
(four streams of a few lane tiles, rows two blocks), float32 and bf16
streams, outputs and every gradient through a part's chain; the rule
``stream_mix_fits`` by what it takes and what it refuses; and the plain
forms against the arithmetic ``trunk.hc_coefficients`` / ``hc_pre`` /
``hc_post`` held before this module, to the bit.  What Mosaic makes of the
kernels at the cell's shape is ``tests/test_xing4_chip.py``'s (AOT compiles
for a described chip) and the chip's (``tools/smallthinker_probe.py
streams``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.ops import stream_mix

B, S, N, C = 2, 32, 4, 256
O = 2 * N + N * N
F32 = jnp.float32
DTYPES = [pytest.param(jnp.float32, id="float32"), pytest.param(jnp.bfloat16, id="bf16")]
# the relative rms a kernel may stand from the plain form: the sums' order
# (float32), one rounding of the output more or less (bf16)
CLOSE = {jnp.float32: 2e-6, jnp.bfloat16: 4e-3}


@pytest.fixture(autouse=True)
def two_blocks(monkeypatch):
    """Row blocks of 32 of the 64 tokens (the module's are 64 and 256 of
    the cell's 16,384) and chunks of 128 of a stream's 256 lanes (the
    module's 1,792 of 3,584): the grid walks two blocks, ``dphi`` adds up
    over them, and a strip's per-token sums add up over two chunks."""
    monkeypatch.setattr(stream_mix, "_ROWS", 32)
    monkeypatch.setattr(stream_mix, "_STATS_ROWS", 32)
    monkeypatch.setattr(stream_mix, "_CHUNK", 128)


def _inputs(dtype):
    """Streams of unlike sizes, what a part gave, its parameters' ``phi``
    (as large as a trained model's: the coefficients spread) and the
    cotangents of the part's two results."""
    rs = np.random.RandomState(0)
    x = rs.normal(0, 1, (B, S, N, C)) * rs.uniform(0.5, 2, (1, 1, N, 1))
    return {
        "x": jnp.asarray(x, dtype),
        "y": jnp.asarray(rs.normal(0, 1, (B, S, C)), dtype),
        "phi": jnp.asarray(rs.normal(0, 1.0 / np.sqrt(N * C), (N * C, O)), F32),
        "dh": jnp.asarray(rs.normal(0, 1, (B, S, C)), dtype),
        "dout": jnp.asarray(rs.normal(0, 1, (B, S, N, C)), dtype),
    }


def _coefficients():
    rs = np.random.RandomState(1)
    return {
        "pre": jnp.asarray(rs.uniform(0, 1, (N, B, S)), F32),
        "post": jnp.asarray(rs.uniform(0, 2, (N, B, S)), F32),
        "res": jnp.asarray(rs.uniform(0, 1, (N, N, B, S)), F32),
    }


PLAIN = {
    "stats": stream_mix.token_stats_plain,
    "read": stream_mix.stream_read_plain,
    "write": stream_mix.stream_write_plain,
}
KERNEL = {
    "stats": functools.partial(stream_mix.token_stats_kernel, interpret=True),
    "read": functools.partial(stream_mix.stream_read_kernel, interpret=True),
    "write": functools.partial(stream_mix.stream_write_kernel, interpret=True),
}


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))


def _arguments(stage, dtype):
    a, k = _inputs(dtype), _coefficients()
    return {
        "stats": (a["x"], a["phi"]),
        "read": (a["x"], k["pre"]),
        "write": (a["x"], a["y"], k["post"], k["res"]),
    }[stage]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stage", ["stats", "read", "write"])
def test_a_kernels_output_is_the_plain_forms(stage, dtype):
    args = _arguments(stage, dtype)
    got, want = KERNEL[stage](*args), PLAIN[stage](*args)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        # the per-token numbers are float32 sums on both sides
        limit = CLOSE[jnp.float32] if w.dtype == F32 else CLOSE[dtype]
        assert _rms(g, w) < limit, stage


def _part(forms, norm_eps=1e-6):
    """A scalar of a part as ``Transformer._hc_read`` / ``_hc_write`` chain
    the three: the statistics, coefficients of them (the sigmoids, and a
    softmax in the Sinkhorn's place: rows that sum to one), the read, what a
    part makes of it, the write.  ``offsets`` [3 n + n n, B, S] is added to
    the coefficients, so its gradient is THEIRS; ``y_offset`` likewise."""

    def scalar(x, phi, offsets, y_offset, w, dout):
        ms, m = forms["stats"](x, phi)
        m = m * jax.lax.rsqrt(ms + norm_eps)
        pre = jax.nn.sigmoid(m[:N]) + offsets[:N]
        post = 2.0 * jax.nn.sigmoid(m[N:2 * N]) + offsets[N:2 * N]
        res = jax.nn.softmax(m[2 * N:].reshape(N, N, B, S), axis=1) + (
            offsets[2 * N:].reshape(N, N, B, S))
        h = forms["read"](x, pre)
        y = jnp.tanh(h.astype(F32) * w).astype(x.dtype) + y_offset
        out = forms["write"](x, y, post, res)
        return jnp.sum(out.astype(F32) * dout.astype(F32))

    return scalar


@pytest.mark.parametrize("dtype", DTYPES)
def test_every_gradient_of_a_parts_chain_is_the_plain_forms(dtype):
    """x (its three gradients added), phi, pre, post, res and y."""
    a = _inputs(dtype)
    args = (a["x"], a["phi"], jnp.zeros((O, B, S), F32),
            jnp.zeros((B, S, C), dtype), jnp.asarray(0.7, F32), a["dout"])
    names = ("x", "phi", "pre | post | res", "y", "w")
    got = jax.grad(_part(KERNEL), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(_part(PLAIN), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(names, got, want):
        assert g.dtype == w.dtype
        assert float(jnp.max(jnp.abs(w.astype(F32)))) > 0, name
        # bf16: each of x's three gradients is rounded before the add, on
        # both sides, and y's rounding moves what follows
        assert _rms(g, w) < (2e-5 if dtype == jnp.float32 else 1.5e-2), name
    offsets = np.asarray(got[2])
    for part in (offsets[:N], offsets[N:2 * N], offsets[2 * N:]):
        assert np.abs(part).max() > 0  # pre, post and res each


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stage", ["stats", "read", "write"])
def test_a_kernels_own_gradients_are_the_plain_forms(stage, dtype):
    """Each stage's ``jax.vjp`` under given cotangents, a stage at a time:
    what the chain's sums cannot tell apart."""
    a = _inputs(dtype)
    args = _arguments(stage, dtype)
    rs = np.random.RandomState(5)
    cotangent = {
        "stats": (jnp.asarray(rs.normal(0, 1, (B, S)), F32),
                  jnp.asarray(rs.normal(0, 1, (O, B, S)), F32)),
        "read": a["dh"], "write": a["dout"],
    }[stage]
    got = jax.vjp(KERNEL[stage], *args)[1](cotangent)
    want = jax.vjp(PLAIN[stage], *args)[1](cotangent)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        limit = 2e-5 if dtype == jnp.float32 else (
            CLOSE[dtype] if w.dtype != F32 else 1e-5)
        if stage == "stats" and dtype == jnp.bfloat16:
            limit = 6e-3  # dm is rounded to the stream's dtype for the MXU
        assert _rms(g, w) < limit, stage


FITS = [
    ("the_cells_streams", (1, 16384, 4, 3584), jnp.bfloat16, "tpu", True),
    ("float32_streams", (1, 16384, 4, 1792), jnp.float32, "tpu", True),
    ("a_batch_of_rows", (4, 4096, 4, 3584), jnp.bfloat16, "tpu", True),
    ("two_streams", (1, 16384, 2, 1024), jnp.bfloat16, "tpu", True),
    ("fewer_tokens_than_a_block", (1, 32, 4, 256), jnp.bfloat16, "tpu", True),
    ("a_cpu_backend", (1, 16384, 4, 3584), jnp.bfloat16, "cpu", False),
    ("a_gpu_backend", (1, 16384, 4, 3584), jnp.bfloat16, "gpu", False),
    ("rows_no_block_divides", (1, 16384 + 64, 4, 3584), jnp.bfloat16, "tpu", False),
    ("rows_off_the_sublane_tile", (1, 24, 4, 256), jnp.bfloat16, "tpu", False),
    ("channels_off_the_lane_tile", (1, 16384, 4, 3584 + 64), jnp.bfloat16, "tpu", False),
    ("one_stream", (1, 16384, 1, 3584), jnp.bfloat16, "tpu", False),
    ("float16_streams", (1, 16384, 4, 3584), jnp.float16, "tpu", False),
    # stream_stats_bwd's blocks would ask more VMEM than a call may have
    ("a_row_too_wide_for_vmem", (1, 16384, 4, 16384), jnp.bfloat16, "tpu", False),
    ("float32_streams_too_wide_for_vmem", (1, 16384, 4, 3584), jnp.float32,
     "tpu", False),
]


@pytest.mark.parametrize(
    "shape, dtype, backend, fits", [pytest.param(*f[1:], id=f[0]) for f in FITS])
def test_the_rule_takes_and_refuses_by_what_the_call_can_see(
        monkeypatch, shape, dtype, backend, fits):
    monkeypatch.undo()  # the module's own blocks
    assert stream_mix.stream_mix_fits(shape, dtype, backend) is fits


def _six_calls(shape, dtype):
    """The six kernels' calls traced over streams of ``shape`` (nothing is
    lowered or run): what each asks of the compiler, ``_params`` sees."""
    b, s, n, c = shape

    def chain(x, phi, pre, post, res, y):
        ms, m = stream_mix.token_stats_kernel(x, phi)
        h = stream_mix.stream_read_kernel(x, pre)
        out = stream_mix.stream_write_kernel(x, y + h, post, res)
        return jnp.sum(ms) + jnp.sum(m) + jnp.sum(out.astype(F32))

    like = jax.ShapeDtypeStruct
    return jax.eval_shape(
        jax.grad(chain, argnums=(0, 1, 2, 3, 4, 5)),
        like(shape, dtype), like((n * c, 2 * n + n * n), F32),
        like((n, b, s), F32), like((n, b, s), F32), like((n, n, b, s), F32),
        like((b, s, c), dtype))


@pytest.mark.parametrize(
    "shape, dtype",
    [pytest.param(*f[1:3], id=f[0]) for f in FITS if f[3] == "tpu" and f[4]])
def test_what_the_rule_takes_every_call_may_ask_for(monkeypatch, shape, dtype):
    """The rule counts ``stream_stats_bwd``'s blocks, the widest: no other
    call of a shape it takes asks more VMEM than a call may have."""
    monkeypatch.undo()
    assert [g.shape for g in _six_calls(shape, dtype)][0] == shape


def test_a_kernel_called_by_hand_on_a_row_too_wide_is_refused_by_name(monkeypatch):
    monkeypatch.undo()
    with pytest.raises(ValueError, match="stream_mix_fits refuses"):
        _six_calls((1, 16384, 4, 16384), jnp.bfloat16)


def test_on_the_cpu_the_rule_answers_with_the_plain_forms(monkeypatch):
    """The three stages through the rule trace no kernel here."""
    a, k = _inputs(jnp.bfloat16), _coefficients()

    def three(x, y, phi, pre, post, res):
        return (stream_mix.token_stats(x, phi), stream_mix.stream_read(x, pre),
                stream_mix.stream_write(x, y, post, res))

    args = (a["x"], a["y"], a["phi"], k["pre"], k["post"], k["res"])
    assert "pallas_call" not in str(jax.make_jaxpr(three)(*args))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a new function: the trace of ``three`` is cached by its arguments
    assert str(jax.make_jaxpr(lambda *a: three(*a))(*args)).count("pallas_call") == 3


# ---- the plain forms are the trunk's arithmetic of before this module ----


def _coefficients_before(p, x, sinkhorn_iters, eps, res_clamp, norm_eps):
    """``trunk.hc_coefficients`` as PR 64 left it (scopes aside)."""
    n = x.shape[2]
    f32 = jnp.float32
    x32 = x.astype(f32)
    inv_rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=(2, 3)) + norm_eps)
    m = jnp.einsum(
        "bsnc,nco->obs", x, p["phi"].astype(x.dtype).reshape(n, x.shape[3], -1),
        preferred_element_type=f32) * inv_rms
    alpha, b = p["alpha"].astype(f32), p["b"].astype(f32)[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
    logits = (alpha[2] * m[2 * n:] + b[2 * n:]).reshape(n, n, *m.shape[1:])
    res = trunk.sinkhorn(jnp.exp(jnp.clip(logits, *res_clamp)), sinkhorn_iters, eps)
    error = jnp.maximum(
        jnp.max(jnp.abs(res.sum(axis=0) - 1.0)),
        jnp.max(jnp.abs(res.sum(axis=1) - 1.0)))
    return pre, post, res, error


def _pre_before(x, pre):
    weights = jnp.moveaxis(pre, 0, -1)[..., None]
    return jnp.sum(weights * x.astype(jnp.float32), axis=2).astype(x.dtype)


def _post_before(x, y, post, res):
    mix = jnp.moveaxis(res, (0, 1), (2, 3))[..., None]
    write = jnp.moveaxis(post, 0, -1)[..., None]
    mixed = jnp.sum(mix * x.astype(jnp.float32)[:, :, None], axis=3)
    return (mixed + write * y.astype(jnp.float32)[:, :, None]).astype(x.dtype)


HOW = (20, 1e-6, (-10.0, 10.0), 1e-6)


def _hc_part(coefficients, read, write):
    def both(hp, x, y):
        pre, post, res, error = coefficients(hp, x, *HOW)
        return read(x, pre), write(x, y, post, res), res, error

    def scalar(hp, x, y):
        h, out, _, _ = both(hp, x, y)
        return jnp.sum(h.astype(F32)) + jnp.sum(out.astype(F32) ** 2)

    return jax.jit(both), jax.jit(jax.grad(scalar, argnums=(0, 1, 2)))


def _hc_parameters(a):
    rs = np.random.RandomState(3)
    return {"phi": a["phi"] * 50.0, "alpha": jnp.full((3,), 0.01, F32),
            "b": jnp.asarray(rs.normal(0, 1, O), F32)}


@pytest.mark.parametrize("dtype", DTYPES)
def test_on_the_cpu_the_trunk_is_to_the_bit_what_it_was(dtype):
    """``trunk.hc_coefficients``, ``hc_pre`` and ``hc_post`` through this
    module's rule, results and gradients, against the functions PR 64 left:
    the plain forms are those, operation for operation, so
    ``tests/test_xing4.py``, ``tests/test_hyper_connections.py`` and the
    runner's wrong programs read what they read."""
    a = _inputs(dtype)
    hp = _hc_parameters(a)
    now = _hc_part(trunk.hc_coefficients, trunk.hc_pre, trunk.hc_post)
    before = _hc_part(_coefficients_before, _pre_before, _post_before)
    for fn_now, fn_before in zip(now, before):
        got = jax.tree_util.tree_leaves(fn_now(hp, a["x"], a["y"]))
        want = jax.tree_util.tree_leaves(fn_before(hp, a["x"], a["y"]))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


@pytest.mark.parametrize("dtype", DTYPES)
def test_the_trunk_on_the_kernels_reads_what_the_plain_trunk_reads(
        monkeypatch, dtype):
    """The three trunk functions with the kernel forms in them (as on a
    TPU: the rule's names replaced by the kernels under ``interpret``)
    against themselves on the plain forms: the coefficients through twenty
    Sinkhorn rounds, the read, the write, and the gradients of the
    parameters, the streams and ``y``."""
    a = _inputs(dtype)
    hp = _hc_parameters(a)
    want = _hc_part(trunk.hc_coefficients, trunk.hc_pre, trunk.hc_post)
    want = [fn(hp, a["x"], a["y"]) for fn in want]
    monkeypatch.setattr(trunk, "token_stats", KERNEL["stats"])
    monkeypatch.setattr(trunk, "stream_read", KERNEL["read"])
    monkeypatch.setattr(trunk, "stream_write", KERNEL["write"])
    got = _hc_part(trunk.hc_coefficients, trunk.hc_pre, trunk.hc_post)
    got = [fn(hp, a["x"], a["y"]) for fn in got]
    limit = 1e-4 if dtype == jnp.float32 else 1.5e-2
    for g, w in zip(jax.tree_util.tree_leaves(got[:2]),
                    jax.tree_util.tree_leaves(want[:2])):
        if g.ndim:
            assert _rms(g, w) < limit
    assert abs(float(got[0][3]) - float(want[0][3])) < 1e-5  # the marginals
