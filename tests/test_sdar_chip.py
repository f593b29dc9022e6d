"""SDAR's block-diffusion train step at published widths, AOT-compiled for a
described (not attached) ``v5e`` chip: nothing runs.  A module apart from
``tests/test_sdar.py``'s CPU cases, so that ``--dist loadfile`` can give the
compile a worker of its own.
"""

import jax

from test_kexaone import probe
from learning_at_home_tpu.models import trunk


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The 8-layer step over the doubled row of 16,384 positions, compiled
    for a described chip (nothing runs): Mosaic takes the computable mask;
    1.517 B parameters; the compiler's own count of what is live in the
    step inside the memory band (a quarter of the chip, the benchmark's
    floor for a cell, to nine tenths); one forward and one fused backward
    kernel call a layer at the causal rule's blocks, the forward's grid
    shrunk to the 9 key blocks a row of the mask's table needs at most."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "sdar_one_chip")
    assert memory["parameters"] == 1_516_670_976
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    assert memory["loss_layer_products"] == 3
    # gate and up 2048 x 768 and down 768 x 2048 over a buffer of 65,536 rows
    assert set(memory["grouped_matmul_tilings"]) == {
        "256,2048,768", "256,768,2048", "256,768,1024", "256,1024,768"}
    assert sum(memory["grouped_matmul_tilings"].values()) == 8 * 12
    # the share's row movements by ``share_gather_fits`` (PR 60): n k = 2 R, so
    # every sum over a token's assignments is a gather and the compiler's
    # sum of 8 rows (``k`` fills a sublane tile); no scatter-add
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 0, "under_moe_sort": 0, "under_moe_combine": 0},
        "row_gathers": 8 * 5, "row_scatters": 0}
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 8, "splash_mha_dkv_no_residuals": 8}
    # remat keeps the kernel's output and row sums, and the four products
    assert memory["kept_residual_bytes"] == 8 * 32 * 16384 * (128 * 2 + 4)
    assert memory["kept_product_bytes"] == 8 * 16384 * (4096 + 2 * 512 + 2048) * 2
    assert memory["recomputed_attention_products"] == 0
    tilings = memory["attention_kernel_tilings"]["attention"]
    want = trunk.flash_block_sizes((1, 16384, 32, 128), "tpu")
    forward = tilings["splash_mha_fwd_residuals"]
    assert (forward["block_q"], forward["block_kv"]) == (want.block_q, want.block_kv)
    # 16 query blocks; a row of the table holds 9 key blocks at most (a
    # noised query block: its own diagonal block and up to 8 clean ones)
    assert forward["grid"] == [32, 16, 9]
    assert tilings["splash_mha_dkv_no_residuals"]["grid"] == [16, 32, 16]
