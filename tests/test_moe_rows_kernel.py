"""``ops/moe_rows.py``: the sum over a token's ``k`` sorted rows as a
Pallas kernel, under ``interpret`` against the plain form, and the sorted
layer's two passes that call it against the forms they replace."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from learning_at_home_tpu.ops import moe_dispatch, moe_rows  # noqa: E402

# the five sorted cells' widths and choices (olmoe and glm-4.7-flash 2,048,
# smallthinker 2,560, nemotron 2,688, k-exaone 6,144; top-4, 6, 8)
WIDTHS = (2048, 2560, 2688, 6144)
CHOICES = (4, 6, 8)
N = 32  # two strips of tokens


def _rows(n, k, d, seed=0):
    rs = np.random.RandomState(seed)
    rows = jnp.asarray(rs.randn(n * k, d), jnp.bfloat16)
    weights = jnp.asarray(rs.rand(n, k), jnp.float32)
    return rows, weights


def _kernel(rows, weights, n, k, dtype, masked=False):
    return moe_rows.sum_rows_kernel(
        rows, weights, n, k, dtype, interpret=True, masked=masked)


def chip_form(monkeypatch):
    """The sorted layer's passes as on the chip, off it: every rule is asked
    as on a ``tpu`` backend, and a kernel runs under ``interpret``.  Returns
    the kernels' names as they are called."""
    called = []

    def sum_rows(rows, weights, n, k, dtype, masked=False):
        if moe_rows.sum_rows_fits(n, k, rows.shape[-1], rows.dtype, "tpu"):
            called.append("moe_rows_sum")
            return _kernel(rows, weights, n, k, dtype, masked)
        return moe_rows.sum_rows_plain(rows, weights, n, k, dtype, masked)

    on_chip = moe_dispatch.combine_sorted_fits
    monkeypatch.setattr(
        moe_dispatch, "combine_sorted_fits", lambda dtype, backend: on_chip(dtype, "tpu"))
    monkeypatch.setattr(moe_dispatch, "sum_rows", sum_rows)
    return called


@pytest.fixture
def kernel_form(monkeypatch):
    return chip_form(monkeypatch)


@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "plain-sum"])
@pytest.mark.parametrize("k", CHOICES)
@pytest.mark.parametrize("d", WIDTHS)
def test_the_kernels_sum_is_the_plain_forms(d, k, weighted):
    """float32: within what two orders of the same k float32 products and
    additions can differ by (the plain form's order is its compiler's);
    without weights bit for bit.  bf16: the float32 sums cast once."""
    rows, weights = _rows(N, k, d)
    weights = weights if weighted else None
    got = _kernel(rows, weights, N, k, jnp.float32)
    want = moe_rows.sum_rows_plain(rows, weights, N, k, jnp.float32)
    if weighted:
        terms = jnp.abs(weights[:, :, None] * rows.reshape(N, k, d).astype(jnp.float32))
        bound = np.asarray(terms.sum(axis=1)) * k * 2.0 ** -24
        assert (np.abs(np.asarray(got) - np.asarray(want)) <= bound).all()
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cast = _kernel(rows, weights, N, k, jnp.bfloat16)
    assert cast.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(cast.astype(jnp.float32)),
        np.asarray(got.astype(jnp.bfloat16).astype(jnp.float32)))


@pytest.mark.parametrize("form", ["kernel", "plain"])
@pytest.mark.parametrize("k", CHOICES)
def test_a_masked_sum_reads_no_row_of_weight_zero(k, form):
    """A share's rows (PR 60): an assignment with no row reads any row, NaN
    among them, under a weight of 0; the other rows add as they did."""
    rows, weights = _rows(N, k, 256, seed=5)
    dead = np.random.RandomState(6).rand(N, k) < 0.4
    weights = jnp.where(dead, 0.0, weights)
    clean = jnp.where(dead.reshape(-1)[:, None], 0, rows)
    rotten = jnp.where(dead.reshape(-1)[:, None], jnp.nan, rows)
    sum_rows = _kernel if form == "kernel" else moe_rows.sum_rows_plain
    got = sum_rows(rotten, weights, N, k, jnp.float32, masked=True)
    want = sum_rows(clean, weights, N, k, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    assert np.isnan(np.asarray(sum_rows(rotten, weights, N, k, jnp.float32))).any()


def test_more_tokens_than_a_block_take_a_grid_of_blocks(monkeypatch):
    """Every block's rows land on its own tokens."""
    monkeypatch.setattr(moe_rows, "_TOKENS", 32)  # three blocks at a small n
    n, k, d = 96, 6, 256
    rows, _ = _rows(n, k, d, seed=3)
    np.testing.assert_array_equal(
        np.asarray(_kernel(rows, None, n, k, jnp.float32)),
        np.asarray(moe_rows.sum_rows_plain(rows, None, n, k, jnp.float32)))


@pytest.mark.parametrize("why, n, k, d, dtype, backend", [
    ("not the chip", 16384, 6, 2048, jnp.bfloat16, "cpu"),
    ("float32 rows have no halves", 16384, 6, 2048, jnp.float32, "tpu"),
    ("an odd k splits a word between tokens", 16384, 7, 2048, jnp.bfloat16, "tpu"),
    ("a k of whole sublane tiles is the compiler's", 16384, 8, 2048, jnp.bfloat16, "tpu"),
    ("half a lane tile", 16384, 6, 2112, jnp.bfloat16, "tpu"),
    ("tokens no block divides", 16384 + 16, 6, 2048, jnp.bfloat16, "tpu"),
    ("half a strip of tokens", 24, 6, 2048, jnp.bfloat16, "tpu"),
])
def test_the_rule_refuses(why, n, k, d, dtype, backend):
    assert not moe_rows.sum_rows_fits(n, k, d, dtype, backend), why


@pytest.mark.parametrize("n, k, d", [
    (16384, 6, 2560),   # smallthinker-21b-a3b-train-zipf16k
    (16384, 4, 2048), (4096, 6, 2048), (16, 4, 128),
])
def test_the_rule_admits_the_cells(n, k, d):
    assert moe_rows.sum_rows_fits(n, k, d, jnp.bfloat16, "tpu")


def test_sum_rows_takes_the_plain_form_off_the_chip():
    rows, weights = _rows(N, 8, 256)
    got = moe_rows.sum_rows(rows, weights, N, 8, jnp.float32)
    want = moe_rows.sum_rows_plain(rows, weights, N, 8, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _plan(n, k, experts, seed):
    logits = jnp.asarray(np.random.RandomState(seed).randn(n, experts) * 3, jnp.float32)
    return moe_dispatch.dropless_routing(logits, k, renormalize=True)


def _passes(plan, x, ys, g_sorted, g_tokens):
    """What a train step takes from the two entry points: both results and
    every gradient."""
    sorted_rows, sort_back = jax.vjp(lambda x: moe_dispatch.sort_tokens(x, plan), x)

    def combine(ys, weights):
        return moe_dispatch.unsort_combine(
            ys, plan._replace(weights=weights), jnp.bfloat16)

    combined, combine_back = jax.vjp(combine, ys, plan.weights)
    d_ys, d_weights = combine_back(g_tokens)
    return {"sorted": sorted_rows, "d_x": sort_back(g_sorted)[0],
            "combined": combined, "d_ys": d_ys, "d_weights": d_weights}


@pytest.fixture(scope="module", params=[(64, 8, 16), (128, 6, 8), (64, 4, 8)],
                ids=["top8", "top6", "top4"])
def routed(request):
    n, k, experts = request.param
    d = 256
    rs = np.random.RandomState(7)
    plan = _plan(n, k, experts, seed=11)
    arrays = [jnp.asarray(rs.randn(*s), jnp.bfloat16)
              for s in ((n, d), (n * k, d), (n * k, d), (n, d))]
    return plan, arrays, _passes(plan, *arrays)


@pytest.mark.parametrize("what", ["sorted", "d_x", "combined", "d_ys", "d_weights"])
def test_the_sorted_layers_passes_keep_their_numbers(routed, kernel_form, what):
    """Both entry points under the kernel form against the plain form:
    the bf16 results and row gradients after one cast from the same
    float32 numbers, the weights' gradient the same float32 dot products
    added in another order."""
    plan, arrays, want = routed
    got = _passes(plan, *arrays)[what]
    k = plan.weights.shape[1]
    # the sort's backward and the combine's forward
    assert kernel_form == ["moe_rows_sum"] * (2 * (k != 8))
    assert got.dtype == want[what].dtype and got.shape == want[what].shape
    got, exact = np.asarray(got.astype(jnp.float32)), np.asarray(want[what].astype(jnp.float32))
    if what == "d_weights":
        np.testing.assert_allclose(got, exact, rtol=1e-5, atol=1e-5)
    else:
        # an ulp of a float32 sum can move its bf16 rounding
        differ = got != exact
        assert differ.mean() < 1e-3
        np.testing.assert_allclose(got, exact, rtol=2.0 ** -7, atol=0)


def test_the_kernel_form_keeps_no_gathered_rows(kernel_form):
    """The combine's backward reads ``ys`` where the grouped matmul left
    it: ``ys[inverse]`` is no residual, so under remat nothing gathers it
    again."""
    plan = _plan(64, 4, 8, seed=2)
    ys = jnp.ones((64 * 4, 256), jnp.bfloat16)
    _, back = jax.vjp(
        lambda ys: moe_dispatch.unsort_combine(ys, plan, jnp.bfloat16), ys)
    residuals = [r for r in jax.tree_util.tree_leaves(back)
                 if getattr(r, "shape", None) == ys.shape]
    assert len(residuals) == 1  # ys itself
    jaxpr = str(jax.make_jaxpr(back)(jnp.ones((64, 256), jnp.bfloat16)))
    assert jaxpr.count("gather") == 3  # the cotangents, their weights, the dots
    assert "pallas_call" not in jaxpr
