"""GLM-4.7-Flash's train step at published widths, AOT-compiled for a
described (not attached) ``v5e`` chip: nothing runs.  A module apart from
``tests/test_glm47.py``'s CPU cases, so that ``--dist loadfile`` can give the
compile a worker of its own.
"""

import jax

from test_glm47 import probe


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The 5-layer train step with the prediction block at published
    widths, compiled for a described chip (nothing runs): 1.839 B
    parameters, the compiler's own count of what is live in the step
    between a quarter of the chip's memory (the benchmark's floor for a
    cell) and 0.9 of it (8.50 GB, 50.3 %, when this was written: ISSUE.md
    expected 59-74 %), every grouped matmul of the five mixture layers at
    the tile rule's answers for 2048 x 1536 over a buffer of 65,536 rows,
    the blocked kernel at heads of 256 in all six layers, and the head's
    three products a pass, two passes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "glm_4_7_flash_one_chip")
    assert memory["parameters"] == 1_838_980_928
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    assert memory["grouped_matmul_tilings"] == {
        "256,2048,768": 5 * 5, "256,1536,1024": 5 * 4,
        "256,1024,768": 5 * 2, "256,768,1024": 5}
    # the share's row movements by ``share_gather_fits`` (PR 60): n k = R, so
    # every sum over a token's assignments is a gather (five a mixture layer:
    # the sort's forward twice under remat, the combine's, and the two
    # backward) and the masked kernel ``moe_rows_sum``; no scatter-add
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 10, "under_moe_sort": 5, "under_moe_combine": 5},
        "row_gathers": 5 * 5, "row_scatters": 0}
    assert memory["loss_layer_products"] == 2 * 3
    # one forward a kernel layer (12 before PR 38): remat keeps the
    # kernel's output and row sums, 169 MB a layer, so the recompute holds
    # no forward call; read off the compiled step, and below off the
    # traced one, where the policy has already taken the call out
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 6,
        "splash_mha_dkv_no_residuals": 6}  # fused: no dQ kernel of its own
    assert memory["kept_residual_bytes"] == 6 * 20 * 16384 * (256 * 2 + 4)
    # and the results of three of the attention part's six products (PR
    # 53): the two down to the latents (768, and 512 with the 64 rotated)
    # and the output projection's, bf16 [16384, 768 + 576 + 2048] a layer,
    # 0.67 GB; the three products UP from the latents run a second time in
    # all six layers (kept, their 2.77 GB cost the cell 0.23 % on the chip)
    assert memory["kept_product_bytes"] == 6 * 16384 * (768 + 576 + 2048) * 2
    assert memory["recomputed_attention_products"] == 6 * 3
    calls = memory["attention_kernel_tilings"]["attention"]
    assert {name: (c["calls"], c["block_q"], c["block_kv"]) for name, c in calls.items()} == {
        "splash_mha_fwd_residuals": (6, 1024, 1024),
        "splash_mha_dkv_no_residuals": (6, 1024, 1024)}
    # the queries' gradient once a key block, [16, 20, 16384, 256] bf16
    assert calls["splash_mha_dkv_no_residuals"]["largest_result_bytes"] == (
        16 * 20 * 16384 * 256 * 2)
