"""Qwen3-Next's train step at published widths, AOT-compiled for a described
(not attached) ``v5e`` chip: nothing runs.  A module apart from
``tests/test_qwen3next.py``'s CPU cases, so that ``--dist loadfile`` can give
the compile a worker of its own.
"""

import os

import jax

from test_qwen3next import REPO, harness

probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))


def test_the_whole_step_fits_the_chip_and_runs_the_rule_once_a_layer(
        v5e_chip, monkeypatch):
    """The eight-layer train step at published widths, compiled for a
    described chip (nothing runs): 1,978,847,360 parameters; the
    compiler's own count of what is live in the step 10.37 GB, 61.3 % of
    the chip, when this was written (PR 58; 8.67 GB before it); a delta
    layer's forward kernel once and its backward kernel once, every call
    under ``delta/core`` (32 value heads of 128/128, two a grid row); kept
    across the backward pass under ``delta_rule.DELTA_RESIDUALS``: each
    delta layer's output (bf16 [16384, 4096]) and the float32 state
    entering each of the kernel's 64 grid steps, 134 MB each."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "qwen3_next_one_chip")
    assert memory["parameters"] == 1_978_847_360
    assert 0.25 < memory["share_of_chip"] and memory["live_bytes"] < 11.0e9, memory
    # the share's row movements by ``share_gather_fits`` (PR 60): n k = 4 R keeps
    # the two scatter-adds a mixture layer (two instructions each: the sorted
    # updates' gather and the segment sum) beside three gathers: the sort's
    # forward twice under remat, the combine's backward once, in bf16
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 0, "under_moe_sort": 0, "under_moe_combine": 0},
        "row_gathers": 8 * 3, "row_scatters": 8 * 2 * 2}
    assert memory["delta_kernel_calls"] == {
        "delta_chunk_fwd": {"calls": 6, "under_delta_core": 6},
        "delta_chunk_bwd": {"calls": 6, "under_delta_core": 6}}
    assert memory["loops_under_delta_core"] == 0
    assert memory["kept_delta_bytes"] == 6 * (
        16384 * 4096 * 2 + 64 * 32 * 128 * 128 * 4)
    assert memory["gate_norm_kernel_calls"] == {
        "gate_norm_fwd": {"calls": 2 * 6, "under_delta_gate_norm": 2 * 6},
        "gate_norm_bwd": {"calls": 6, "under_delta_gate_norm": 6}}
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 2, "splash_mha_dkv_no_residuals": 2}
    assert memory["recomputed_attention_products"] == 0
