"""K-EXAONE's train step at published widths, AOT-compiled for a described
(not attached) ``v5e`` chip: nothing runs.  A module apart from
``tests/test_kexaone.py``'s CPU cases, so that ``--dist loadfile`` can give
the compile a worker of its own.
"""

import jax
import jax.numpy as jnp

from test_kexaone import probe
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.ops import moe_dispatch


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The 5-layer train step at published widths, compiled for a
    described chip (nothing runs): 2.504 B parameters, the compiler's own
    count of what is live in the step between a quarter of the chip's
    memory (the benchmark's floor for a cell) and all of it, and every
    grouped matmul of the four mixture layers at the tile rule's answers
    for 6144 x 2048 over a buffer of 16,384 rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "k_exaone_one_chip")
    assert memory["parameters"] == 2_504_068_864
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    assert memory["grouped_matmul_tilings"] == {
        "256,2048,1024": 4 * 9, "256,1024,1024": 4 * 3}
    # the share's row movements by ``share_gather_fits`` (PR 60): n k = 8 R keeps
    # the two scatter-adds a mixture layer (two instructions each) beside
    # three gathers: the sort's forward twice under remat, the combine's
    # backward once, in bf16
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 0, "under_moe_sort": 0, "under_moe_combine": 0},
        "row_gathers": 4 * 3, "row_scatters": 4 * 2 * 2}
    assert memory["loss_layer_products"] == 3  # of the head's; four before PR 34
    assert moe_dispatch.grouped_matmul_tiles(16384, 6144, 2048, jnp.bfloat16) == (
        256, 2048, 1024)
    # the four window layers run the band kernel (PR 63: a window of 128
    # is shorter than the blocked kernel's key block), one forward and ONE
    # backward call a layer and no dQ kernel anywhere in the step; the
    # global layer keeps the blocked kernel's fused backward.  One forward
    # a kernel layer (10 before PR 38): remat keeps the kernels' output and
    # row sums, 273 MB a layer, so the recompute holds no forward call.
    # ``attention_kernel_calls`` reads the compiled step's instructions,
    # ``attention_kernel_tilings`` the traced step's equations: the policy
    # takes the call out before the compiler sees it
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1,
        "band_attention_fwd": 4, "band_attention_bwd": 4}
    assert memory["kept_residual_bytes"] == 5 * 64 * 16384 * (128 * 2 + 4)
    # and the results of the attention part's products (PR 53): q, k, v and
    # the output projection's, bf16 [16384, 8192 + 1024 + 1024 + 6144] a
    # layer, 2.68 GB (13.79 GB live, 81.6 %, from 12.18: the band above
    # holds it), and the backward pass runs none of the four a second time
    assert memory["kept_product_bytes"] == 5 * 16384 * (8192 + 2 * 1024 + 6144) * 2
    assert memory["recomputed_attention_products"] == 0
    # the optimized HLO's instructions carry the attention part's stages
    # (PR 52), and the kernel's calls sit under ``flash``, not its ``layout``
    stages = memory["attention_stages"]
    assert stages["stages"] == [
        "flash", "flash/layout", "norm", "out_proj", "proj", "qk_norm", "rope"]
    assert stages["kernel_scopes"] == [
        "attention/flash/band_attention_bwd", "attention/flash/band_attention_fwd",
        *(f"attention/flash/vmap(jit(_splash_attention))/{name}/{name}"
          for name in ("splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"))]
    tilings = memory["attention_kernel_tilings"]
    assert {kind: {name: call["calls"] for name, call in calls.items()}
            for kind, calls in tilings.items()} == {
        "global": {"splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1},
        "window": {"band_attention_fwd": 4, "band_attention_bwd": 4}}
    assert trunk.band_kernel_fits((1, 16384, 64, 128), 8, 128, "tpu")
    # a window layer's grid is (batch row, key/value head, block of 512
    # positions): a step holds the eight query heads of its key/value head
    # and the whole softmax of its 512 queries; the backward's one step
    # more writes the last key block's gradients
    assert [(call["block_q"], call["block_kv"], call["grid"])
            for call in tilings["window"].values()] == [
        (512, 512, [1, 8, 32]), (512, 512, [1, 8, 33])]
    # the queries' gradient once a key block of 1024, [16, 64, 16384, 128]
    # bf16, is the fused backward's: the global layer keeps it, and no
    # kernel of a window layer writes anything larger than the queries'
    # gradient itself
    partials = 16 * 64 * 16384 * 128 * 2
    assert tilings["global"]["splash_mha_dkv_no_residuals"]["largest_result_bytes"] == partials
    assert all(call["largest_result_bytes"] == partials // 16
               for call in tilings["window"].values())
