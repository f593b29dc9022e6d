"""The expert layers' row movements (the sort and the combine of the sorted
layer and of a share) forward and backward at each cell's shape,
AOT-compiled for a described (not attached) ``v5e`` chip: nothing runs.  A
module apart from ``tests/test_olmoe_chip.py`` (whose helper it uses) so that
``--dist loadfile`` can give the compiles a worker of their own."""

import re

import jax
import jax.numpy as jnp
import pytest

from test_olmoe import probe
from test_olmoe_chip import _no_compile_cache

# the ``v5e_chip`` fixture is tests/conftest.py's


@pytest.mark.parametrize("n, k, d, sums", [
    (16384, 8, 2048, 0),   # olmoe-1b-7b-train-zipf4k: the compiler's sum of 8 rows
    (16384, 6, 2560, 2),   # smallthinker-21b-a3b-train-zipf16k: ``moe_rows_sum``
], ids=["olmoe", "smallthinker"])
def test_the_sorted_layers_row_movements_compile_for_v5e(
        v5e_chip, monkeypatch, n, k, d, sums):
    """A layer's sort and combine, forward and backward at a cell's shape,
    for a described chip: Mosaic accepts the kernel at its blocks where the
    rule admits ``k``; four row gathers (the sort's, the combine's, one
    each way: a fifth under remat, the sort's forward again) where the
    parent's five held the combine's twice, and no scatter."""
    from learning_at_home_tpu.ops import moe_dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(v5e_chip)

    def through(x, weights, order, inverse):
        plan = moe_dispatch.DroplessPlan(order, inverse, None, weights, None)
        with jax.named_scope("moe_sort"):
            xs = moe_dispatch.sort_tokens(x, plan)
        with jax.named_scope("moe_combine"):
            y = moe_dispatch.unsort_combine(xs * 2, plan, x.dtype)
        return (y.astype(jnp.float32) ** 2).sum()

    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
        ((n, d), jnp.bfloat16), ((n, k), jnp.float32),
        ((n * k,), jnp.int32), ((n * k,), jnp.int32))]
    with _no_compile_cache():
        text = jax.jit(jax.grad(through, argnums=(0, 1))).lower(*shapes).compile().as_text()
    found = probe.moe_rows_kernel_calls(text)
    assert found["moe_rows_sum"] == {
        "calls": sums, "under_moe_sort": sums // 2, "under_moe_combine": sums // 2}
    assert (found["row_gathers"], found["row_scatters"]) == (4, 0)


@pytest.mark.parametrize("n, k, scored, held, d, sums, scatters", [
    (16384, 4, 64, 32, 2048, 2, False),    # glm-4.7-flash: n k = R, ``moe_rows_sum``
    (16384, 8, 128, 32, 2048, 0, False),   # sdar-30b-a3b: n k = 2 R, the compiler's sum of 8
    (16384, 6, 128, 32, 2688, 2, False),   # nemotron-labs-twotower: n k = 2 R
    (16384, 10, 512, 64, 2048, 0, True),   # qwen3-next: n k = 4 R keeps the scatter-adds
    (16384, 8, 128, 8, 6144, 0, True),     # k-exaone: n k = 8 R
], ids=["glm-4.7-flash", "sdar", "nemotron", "qwen3-next", "k-exaone"])
def test_a_shares_row_movements_compile_for_v5e(
        v5e_chip, monkeypatch, n, k, scored, held, d, sums, scatters):
    """A share's sort and combine, forward and backward at each share
    cell's shape, for a described chip (PR 60): where ``share_gather_fits``
    says so no scatter is left and the masked sum is Mosaic's where ``k``
    admits the kernel; everywhere the combine's backward gathers the token
    cotangents as bf16, never as float32."""
    from learning_at_home_tpu.ops import moe_dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    rows = moe_dispatch.share_buffer_rows(n, k, held, scored)
    assert moe_dispatch.share_gather_fits(
        n, k, rows, d, jnp.bfloat16, "tpu") is not scatters

    def through(x, logits):
        with jax.named_scope("router"):
            plan = moe_dispatch.share_routing(logits, k, 0, held, rows, score="sigmoid")
        with jax.named_scope("moe_sort"):
            xs = moe_dispatch.share_sort_tokens(x, plan)
        with jax.named_scope("moe_combine"):
            y = moe_dispatch.share_combine(xs * 2, plan, n, x.dtype)
        return (y.astype(jnp.float32) ** 2).sum()

    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
        ((n, d), jnp.bfloat16), ((n, scored), jnp.float32))]
    with _no_compile_cache():
        text = jax.jit(jax.grad(through, argnums=(0, 1))).lower(*shapes).compile().as_text()
    found = probe.moe_rows_kernel_calls(text)
    assert found["moe_rows_sum"] == {
        "calls": sums, "under_moe_sort": sums // 2, "under_moe_combine": sums // 2}
    assert (found["row_scatters"] > 0) is scatters
    # the sort's forward and the combine's backward, and the two sums' where they are gathers
    assert found["row_gathers"] == (2 if scatters else 4)
    assert not re.search(
        r'= f32\[\d+,%d\][^\n]* gather\([^\n]*op_name="[^"\n]*/gather"' % d, text)
