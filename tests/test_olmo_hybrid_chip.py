"""Olmo-Hybrid's train step and the delta rule's kernels at published widths,
AOT-compiled for a described (not attached) ``v5e`` chip: nothing runs.  A
module apart from ``tests/test_olmo_hybrid.py``'s CPU cases, so that ``--dist
loadfile`` can give the compiles a worker of their own.
"""

import jax
import jax.numpy as jnp

from test_olmo_hybrid import CELL_FILE, probe


def test_the_rules_kernels_compile_for_the_chip_at_the_cells_shape(v5e_chip):
    """``delta_chunk_fwd`` and ``delta_chunk_bwd`` at ``[1, 16384, 30, 96 /
    192]`` bf16 in chunks of 64, compiled for a described chip (nothing
    runs): Mosaic takes keys of 96 and values of 192 as blocks that span
    the arrays' last axis, three heads abreast, the frames' transposes,
    the products at the highest precision and the VMEM the kernels ask
    for."""
    from learning_at_home_tpu.ops import delta_rule

    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, h, dk, dv = (CELL_FILE[k] for k in (
        "seq_len", "linear_num_key_heads", "linear_key_head_dim",
        "linear_value_head_dim"))
    assert (s, h, dk, dv) == (16384, 30, 96, 192)
    assert delta_rule.kernel_fits((1, s, h, dk), (1, s, h, dv), 64, "tpu")

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (shaped((1, s, h, dk), jnp.bfloat16), shaped((1, s, h, dk), jnp.bfloat16),
            shaped((1, s, h, dv), jnp.bfloat16), shaped((1, s, h), jnp.float32),
            shaped((1, s, h), jnp.float32))

    def loss(*a):
        o, state = delta_rule.gated_delta_kernel(*a, 64)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(state)

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    calls = probe.scan_kernel_calls(text, "delta_chunk", "delta/core")
    assert {name: c["calls"] for name, c in calls.items()} == {
        "delta_chunk_fwd": 1, "delta_chunk_bwd": 1}


def test_the_whole_step_fits_the_chip_and_runs_the_rule_as_kernels(
        v5e_chip, monkeypatch):
    """The eight-layer train step at published widths, compiled for a
    described chip (nothing runs): 1,857,720,552 parameters; the
    compiler's own count of what is live in the step 14.32 GB, 84.7 % of
    the chip, when this was written (PR 58; 12.18 GB before it): it now
    holds, across the backward pass, each delta layer's output (bf16 [16384,
    5760], 189 MB) and the float32 state entering each of the kernel's 64
    grid steps of 256 positions (142 MB; the parent's state a CHUNK, 566
    MB, lived for one layer's backward), 1.98 GB over the six layers, named
    ``delta_rule.DELTA_RESIDUALS`` for the layer's checkpoint; so a delta
    layer's forward kernel ONCE (the recompute holds no call: the backward
    kernel rebuilds the chunks' entering states in VMEM from the step's)
    and its backward kernel once, every call under ``delta/core``, and no
    loop over chunks or segments left there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "olmo_hybrid_7b_one_chip")
    assert memory["parameters"] == 1_857_720_552
    assert 0.25 < memory["share_of_chip"] and memory["live_bytes"] < 14.4e9, memory
    assert memory["delta_kernel_calls"] == {
        "delta_chunk_fwd": {"calls": 6, "under_delta_core": 6},
        "delta_chunk_bwd": {"calls": 6, "under_delta_core": 6}}
    assert memory["kept_delta_bytes"] == 6 * (
        16384 * 5760 * 2 + 64 * 30 * 96 * 192 * 4)
    assert memory["loops_under_delta_core"] == 0
    # the norm and the gate as one pass: forward, recomputed (remat keeps
    # nothing of it: its own recompute stays, reading the kept ``o``) and
    # backward a delta layer, every call under
    # ``delta/gate_norm``, and no float32 ``[1, 16384, 5760]`` written there
    # (PR 47; the parent's live count read 11,423,113,216)
    assert memory["gate_norm_kernel_calls"] == {
        "gate_norm_fwd": {"calls": 2 * 6, "under_delta_gate_norm": 2 * 6},
        "gate_norm_bwd": {"calls": 6, "under_delta_gate_norm": 6}}
    assert memory["float32_arrays_beside_gate_norm"] == []
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 2, "splash_mha_dkv_no_residuals": 2}
    # the results of the two attention layers' products are kept across the
    # backward pass (PR 53): q, k, v and the output projection's, bf16
    # [16384, 4 x 3840] a layer, 1.01 GB, under the band above; the backward
    # pass runs none of the four a second time
    assert memory["kept_product_bytes"] == 2 * 16384 * (4 * 3840) * 2
    assert memory["recomputed_attention_products"] == 0
    assert memory["loss_layer_products"] == 3
