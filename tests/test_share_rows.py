"""The share path's row movements as gathers (``ops/moe_dispatch.py``:
``SharePlan.slot``, ``share_gather_fits``, ``_share_rows_to_buffer``,
``_share_combine``): the plan's second order, the rule's answers at the
five share cells' shapes, and the gather forms against the scatter-adds
they replace, value and every gradient."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from learning_at_home_tpu.ops import moe_dispatch  # noqa: E402
from test_moe_rows_kernel import chip_form  # noqa: E402

N, EXPERTS, K, FIRST, HELD, D = 64, 16, 4, 4, 4, 128
ROWS = moe_dispatch.share_buffer_rows(N, K, HELD, EXPERTS)  # 128 of 256 assignments


def _logits(scenario):
    logits = np.random.RandomState(3).randn(N, EXPERTS).astype(np.float32)
    if scenario == "held-experts-with-no-row":
        logits[:, FIRST + 1:FIRST + 3] -= 20.0
    if scenario == "overflow":  # every assignment of the first tokens falls here
        logits[:, FIRST:FIRST + HELD] += 20.0
    return jnp.asarray(logits)


SCENARIOS = ("level", "held-experts-with-no-row", "overflow")


def _plan(scenario):
    return moe_dispatch.share_routing(
        _logits(scenario), K, FIRST, HELD, ROWS, score="sigmoid")


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_slot_is_the_buffers_order_read_by_assignment(scenario):
    plan = jax.jit(lambda: _plan(scenario))()
    slot, weight = np.asarray(plan.slot), np.asarray(plan.slot_weight)
    kept = slot < ROWS
    assert slot.shape == (N, K) and slot.dtype == np.int32
    assert (slot[~kept] == ROWS).all() and not weight[~kept].any()
    assert kept.sum() == int(plan.valid.sum()) == int(plan.group_sizes.sum())
    # every buffer row that holds an assignment is the slot of that one alone
    tokens, choices = np.nonzero(kept)
    rows = slot[kept]
    assert sorted(rows) == list(range(kept.sum()))
    np.testing.assert_array_equal(np.asarray(plan.token)[rows], tokens)
    np.testing.assert_array_equal(np.asarray(plan.weight)[rows], weight[kept])
    dropped = int(plan.routed_here) - kept.sum()
    assert (dropped > 0) == (scenario == "overflow")
    if scenario == "held-experts-with-no-row":
        assert list(np.asarray(plan.group_sizes)[1:3]) == [0, 0]


# (n, k, rows, d) of the five share cells (sdar's n is its doubled row)
CELLS = {
    "glm-4.7-flash": (16384, 4, 65536, 2048),
    "sdar-30b-a3b": (16384, 8, 65536, 2048),
    "nemotron-labs-twotower": (16384, 6, 49152, 2688),
    "qwen3-next": (16384, 10, 40960, 2048),
    "k-exaone": (16384, 8, 16384, 6144),
}


@pytest.mark.parametrize("cell, dtype, backend, fits", [
    ("glm-4.7-flash", jnp.bfloat16, "tpu", True),
    ("sdar-30b-a3b", jnp.bfloat16, "tpu", True),
    ("nemotron-labs-twotower", jnp.bfloat16, "tpu", True),
    ("qwen3-next", jnp.bfloat16, "tpu", False),
    ("k-exaone", jnp.bfloat16, "tpu", False),
    ("glm-4.7-flash", jnp.float32, "tpu", False),
    ("glm-4.7-flash", jnp.bfloat16, "cpu", False),
])
def test_the_rule_at_the_share_cells(cell, dtype, backend, fits):
    n, k, rows, d = CELLS[cell]
    assert moe_dispatch.share_gather_fits(n, k, rows, d, dtype, backend) is fits


def _forced(monkeypatch):
    """Both rules answer yes whatever the rows' dtype; the sum is the
    kernel's under ``interpret`` where its own rule admits the shape."""
    called = chip_form(monkeypatch)
    monkeypatch.setattr(moe_dispatch, "combine_sorted_fits", lambda *a: True)
    monkeypatch.setattr(moe_dispatch, "share_gather_fits", lambda *a: True)
    return called


def _layer(scenario, dtype):
    """A share's layer with the experts cut out: sort, a row-wise product,
    combine; the value and the gradients to the tokens, to the buffer's rows
    and to the router's logits.  NaN stands in every buffer row outside the
    groups, in what the combine reads and in what the sort's backward is
    handed."""
    rs = np.random.RandomState(9)
    x = jnp.asarray(rs.randn(N, D), dtype)
    into = jnp.asarray(rs.randn(ROWS, D), dtype)
    g = jnp.asarray(rs.randn(N, D), jnp.float32)
    logits = _logits(scenario)
    valid = np.asarray(_plan(scenario).valid)
    assert valid.all() == (scenario == "overflow")
    empty = jnp.asarray(~valid)[:, None]
    junk = jnp.where(empty, jnp.nan, 0.5).astype(dtype)

    def loss(x, into, logits):
        plan = moe_dispatch.share_routing(
            logits, K, FIRST, HELD, ROWS, score="sigmoid")
        ys = moe_dispatch.share_sort_tokens(x, plan) * junk + jnp.where(
            empty, jnp.nan, into)
        y = moe_dispatch.share_combine(ys, plan, N, dtype)
        return jnp.sum(y.astype(jnp.float32) * g), y

    (_, y), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(x, into, logits)
    return dict(zip(("y", "d_x", "d_rows", "d_logits"), (y, *grads)))


@pytest.fixture(scope="module", params=[
    (s, d) for s in SCENARIOS for d in ("float32", "bfloat16")], ids="-".join)
def both_forms(request):
    scenario, dtype = request.param
    want = _layer(scenario, jnp.dtype(dtype))
    with pytest.MonkeyPatch.context() as patch:
        called = _forced(patch)
        got = _layer(scenario, jnp.dtype(dtype))
    return dtype, called, got, want


@pytest.mark.parametrize("what", ["y", "d_x", "d_rows", "d_logits"])
def test_the_gather_forms_keep_value_and_gradients(both_forms, what):
    """float32 rows: the same float32 products, added in another order.
    bf16 rows: those sums cast once, where the scatter-add of the sort's
    backward added in bf16 (k roundings a token)."""
    dtype, called, got, want = both_forms
    # the combine's forward and the sort's backward: bf16 rows take the kernel
    assert called == ["moe_rows_sum"] * (2 * (dtype == "bfloat16"))
    got, want = got[what], want[what]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(want).all() and np.isfinite(got).all()
    # no assignment, no sum: the same rows of zeros in both forms
    np.testing.assert_array_equal((got == 0).all(axis=-1), (want == 0).all(axis=-1))
    if dtype == "float32" or what == "d_logits":
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    else:
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=2.0 ** -6, atol=scale * 2.0 ** -7)


def test_dropped_assignments_add_nothing_and_get_no_gradient(monkeypatch):
    _forced(monkeypatch)
    plan = _plan("overflow")
    kept = np.asarray(plan.slot) < ROWS
    # the buffer ends with the second held expert's rows: half of every token's
    assert (kept.sum(axis=1) == K // 2).all()
    ys = jnp.ones((ROWS, D), jnp.float32)
    y = moe_dispatch.share_combine(ys, plan, N)
    np.testing.assert_allclose(
        np.asarray(y)[:, 0], np.asarray(plan.weight).reshape(2, N).sum(axis=0),
        rtol=1e-6)
    x = jnp.ones((N, D), jnp.float32)
    _, sort_back = jax.vjp(lambda x: moe_dispatch.share_sort_tokens(x, plan), x)
    (d_x,) = sort_back(jnp.ones((ROWS, D), jnp.float32))
    np.testing.assert_array_equal(np.asarray(d_x)[:, 0], kept.sum(axis=1))


def test_the_gather_form_keeps_no_gathered_rows(monkeypatch):
    """The combine's backward reads ``ys`` where the grouped matmul left
    it and gathers the token cotangents once, in their own dtype."""
    _forced(monkeypatch)
    plan = _plan("level")
    ys = jnp.ones((ROWS, D), jnp.bfloat16)
    _, back = jax.vjp(
        lambda ys: moe_dispatch.share_combine(ys, plan, N, jnp.bfloat16), ys)
    shapes = [getattr(r, "shape", None) for r in jax.tree_util.tree_leaves(back)]
    assert (N * K, D) not in shapes and shapes.count((ROWS, D)) == 1  # ys itself
    jaxpr = jax.make_jaxpr(back)(jnp.ones((N, D), jnp.bfloat16))
    gathers = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "gather"]
    assert [e.outvars[0].aval.dtype for e in gathers] == [jnp.bfloat16]
    assert "scatter" not in str(jaxpr) and "pallas_call" not in str(jaxpr)


def test_off_the_chip_the_forms_are_the_scatter_adds():
    plan = _plan("level")
    x = jnp.ones((N, D), jnp.bfloat16)
    ys = jnp.ones((ROWS, D), jnp.bfloat16)
    sort = str(jax.make_jaxpr(jax.grad(
        lambda x: moe_dispatch.share_sort_tokens(x, plan).astype(jnp.float32).sum()))(x))
    combine = str(jax.make_jaxpr(
        lambda ys: moe_dispatch.share_combine(ys, plan, N, jnp.bfloat16))(ys))
    assert "scatter-add" in sort and "custom_vjp" not in sort
    assert "scatter-add" in combine and "custom_vjp" not in combine
