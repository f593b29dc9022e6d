"""Nemotron-Labs-TwoTower's train step and its kernels at published widths,
AOT-compiled for a described (not attached) ``v5e`` chip: nothing runs.  A
module apart from ``tests/test_nemotron_hybrid.py``'s CPU cases, so that
``--dist loadfile`` can give the compiles a worker of their own.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from test_nemotron_hybrid import CELL_FILE, REPO, harness, probe
from learning_at_home_tpu.ops import gate_norm, ssd, ssm_conv


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The nine-layer train step at published widths, compiled for a
    described chip (nothing runs): 1,624,837,632 parameters, the
    compiler's own count of what is live in the step between a quarter of
    the chip's memory (the benchmark's floor for a cell) and 0.9 of it
    (9.92 GB, 58.7 %, when this was written: ISSUE.md expected 47-65 %;
    10.31 GB, 61.0 %, with the scan's kernels and what remat keeps of them: PR 40),
    the blocked kernel at heads of 128 in the one attention layer, once
    forward (remat keeps its residuals) and once fused backward, and the
    head's three products a pass."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "nemotron_labs_twotower_one_chip")
    assert memory["parameters"] == 1_624_837_632
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    # four mixture layers x (2 forward + 2 recomputed + 4 backward) calls,
    # each at the tile rule's answer for a width of 1,856 at its cover
    assert memory["grouped_matmul_tilings"] == {
        "256,896,1920": 4 * 3, "256,1920,896": 4 * 3,
        "256,896,640": 4, "256,640,896": 4}
    # the share's row movements by ``share_gather_fits`` (PR 60): n k = 2 R, so
    # every sum over a token's assignments is a gather and the masked kernel
    # ``moe_rows_sum`` (k = 6); no scatter-add
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 8, "under_moe_sort": 4, "under_moe_combine": 4},
        "row_gathers": 4 * 5, "row_scatters": 0}
    assert memory["loss_layer_products"] == 3
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1}
    assert memory["kept_residual_bytes"] == 32 * 16384 * (128 * 2 + 4)
    # and the results of the attention layer's products (PR 53): q, k, v
    # and the output projection's, bf16 [16384, 4096 + 256 + 256 + 2688],
    # 0.24 GB NAMED; the backward pass runs none of the four a second time.
    # Of the output projection's 88 MB nothing is held: in a layer of one
    # mixer no backward equation reads it, so the checkpoint drops it from
    # its residuals and the compiled step has no ``reduce_precision`` of it
    assert memory["kept_product_bytes"] == 16384 * (4096 + 2 * 256 + 2688) * 2
    assert memory["recomputed_attention_products"] == 0
    calls = memory["attention_kernel_tilings"]["global"]
    assert {name: (c["calls"], c["block_q"], c["block_kv"]) for name, c in calls.items()} == {
        "splash_mha_fwd_residuals": (1, 1024, 1024),
        "splash_mha_dkv_no_residuals": (1, 1024, 1024)}
    # 32 query heads over 2 key/value heads, as they come
    assert calls["splash_mha_fwd_residuals"]["grid"][0] == 32
    # the scan's kernels, once forward (remat keeps the output and the
    # entering states: the recompute holds no scan) and once backward a
    # state-space layer, every call under ``ssm/scan``
    assert memory["scan_kernel_calls"] == {
        "ssd_chunk_fwd": {"calls": 4, "under_ssm_scan": 4},
        "ssd_chunk_bwd": {"calls": 4, "under_ssm_scan": 4}}
    assert memory["kept_scan_bytes"] == 4 * (
        16384 * 4096 * 2 + 128 * 64 * 64 * 128 * 4)
    # the convolution's one pass forward, recomputed (remat keeps nothing
    # of it) and backward, for each of ``x``, ``B`` and ``C`` of a
    # state-space layer, every call under ``ssm/conv``, and no float32 copy
    # of ``x B C`` or of a part written there (PR 41)
    assert memory["conv_kernel_calls"] == {
        "ssm_conv_fwd": {"calls": 24, "under_ssm_conv": 24},
        "ssm_conv_bwd": {"calls": 12, "under_ssm_conv": 12}}
    assert memory["float32_arrays_under_ssm_conv"] == []
    # the skip, the gate and the norm as one pass: forward, recomputed
    # (remat keeps nothing of it) and backward a state-space layer, every
    # call under ``ssm/gate_norm``, and no float32 ``[1, 16384, 4096]``
    # written there or under ``ssm/scan`` on their behalf (PR 47; the
    # parent's live count read 9,878,984,192)
    assert memory["gate_norm_kernel_calls"] == {
        "gate_norm_fwd": {"calls": 2 * 4, "under_ssm_gate_norm": 2 * 4},
        "gate_norm_bwd": {"calls": 4, "under_ssm_gate_norm": 4}}
    assert memory["float32_arrays_beside_gate_norm"] == []


def test_the_scan_kernels_compile_for_the_chip_at_the_cells_shape(v5e_chip):
    """``ssd_chunk_fwd`` and ``ssd_chunk_bwd`` at ``[1, 16384, 64, 64]``,
    state 128, 8 groups, chunks of 128, bf16, compiled for a described
    chip (nothing runs): Mosaic takes the tiles, the transposes and the
    VMEM the kernels ask for."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, h, p, g, n = (CELL_FILE[k] for k in (
        "seq_len", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size"))
    assert (s, h, p, g, n, CELL_FILE["chunk_size"]) == (16384, 64, 64, 8, 128, 128)
    assert ssd.kernel_fits((1, s, h, p), (1, s, g, n), 128, "tpu")

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (shaped((1, s, h, p), jnp.bfloat16), shaped((1, s, h), jnp.float32),
            shaped((h,), jnp.float32), shaped((1, s, g, n), jnp.bfloat16),
            shaped((1, s, g, n), jnp.bfloat16))

    def loss(*a):
        y, state = ssd.ssd_chunked_kernel(*a, 128)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(state)

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    assert {name: c["calls"] for name, c in probe.scan_kernel_calls(text).items()} == {
        "ssd_chunk_fwd": 1, "ssd_chunk_bwd": 1}


def test_the_convolutions_kernels_compile_for_the_chip_at_the_cells_shape(v5e_chip):
    """``ssm_conv_fwd`` and ``ssm_conv_bwd`` as the cell's mixer calls them,
    compiled for a described chip (nothing runs): ``x`` (4,096 channels),
    ``B`` and ``C`` (1,024 each) read out of the in-projection's
    ``[1, 16384, 10304]`` bf16 where they lie, four taps: Mosaic takes the
    blocks at their offsets, the halos' tiles, the rolls along the sublanes
    and the VMEM the two scratches ask for."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, taps = CELL_FILE["seq_len"], CELL_FILE["conv_kernel"]
    d_inner = CELL_FILE["mamba_num_heads"] * CELL_FILE["mamba_head_dim"]
    group = CELL_FILE["n_groups"] * CELL_FILE["ssm_state_size"]
    wide = 2 * d_inner + 2 * group + CELL_FILE["mamba_num_heads"]
    parts = [(d_inner, d_inner), (2 * d_inner, group), (2 * d_inner + group, group)]
    assert (s, taps, wide, parts) == (
        16384, 4, 10304, [(4096, 4096), (8192, 1024), (9216, 1024)])
    assert all(ssm_conv.conv_kernel_fits((1, s, c), taps, "tpu", first)
               for first, c in parts)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(zxbcdt, w, b):  # the square: its cotangent reads the forward's result
        lo = d_inner
        return sum(jnp.sum(ssm_conv.causal_conv_silu_kernel(
            zxbcdt, w[first - lo:first - lo + c], b[first - lo:first - lo + c],
            first).astype(jnp.float32) ** 2) for first, c in parts)

    c = d_inner + 2 * group
    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shaped((1, s, wide), jnp.bfloat16), shaped((c, taps), jnp.bfloat16),
            shaped((c,), jnp.bfloat16)).compile().as_text()
    calls = probe.scan_kernel_calls(text, "ssm_conv", "ssm/conv")
    assert {name: entry["calls"] for name, entry in calls.items()} == {
        "ssm_conv_fwd": 3, "ssm_conv_bwd": 3}


@pytest.mark.parametrize("cell", ["nemotron", "olmo-hybrid"])
def test_the_gate_norm_kernels_compile_for_the_chip_at_the_cells_shapes(v5e_chip, cell):
    """``gate_norm_fwd`` and ``gate_norm_bwd`` as the two hybrid cells'
    mixers call them, compiled for a described chip (nothing runs; here
    because the described chip's library is one file's to load).  Nemotron:
    4,096 channels in groups of 512 under a scale a channel, the gate
    first, ``z`` at column 0 of the in-projection's ``[1, 16384, 10304]``
    bf16, the skip ``y + D x`` inside.  Olmo-Hybrid: 5,760 channels, heads
    of 192 two to a block of 384 lanes under one shared scale, the norm
    first, ``z`` at column 11,520 of ``[1, 16384, 17340]``.  Mosaic takes
    the blocks at their offsets, the masked sums along the lanes and the
    partial sums' blocks."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, bf16 = CELL_FILE["seq_len"], jnp.bfloat16
    c, wide, first, group, n_scale, gate_first, heads = {
        "nemotron": (4096, 10304, 0, 512, 4096, True, 64),
        "olmo-hybrid": (5760, 17340, 11520, 192, 192, False, 0)}[cell]
    assert gate_norm.gate_norm_fits((1, s, c), group, "tpu", first)

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, bf16, sharding=one)

    def loss(y, z, scale, skip):  # the square: its cotangent reads the result
        return jnp.sum(gate_norm.gated_rms_norm_kernel(
            y, z, scale, group, 1e-5, gate_first, first, skip).astype(jnp.float32) ** 2)

    skip = (shaped(1, s, c), shaped(heads)) if heads else None
    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3) if heads else (0, 1, 2))).lower(
            shaped(1, s, c), shaped(1, s, wide), shaped(n_scale), skip).compile().as_text()
    calls = probe.scan_kernel_calls(text, "gate_norm", "gate_norm")
    assert {name: entry["calls"] for name, entry in calls.items()} == {
        "gate_norm_fwd": 1, "gate_norm_bwd": 1}


def test_the_convolutions_kernels_compile_at_the_delta_mixers_shape(v5e_chip):
    """The same two kernels as Olmo-Hybrid's delta mixer calls them
    (``trunk.delta_mixer``; here because the described chip's library is
    one file's to load): ``[q | k]`` as ONE call of 5,760 channels at
    column 0 and ``v`` as one at column 5,760 of the in-projection's ``[1,
    16384, 17340]`` bf16, four taps, no bias: blocks of 384 channels, the
    last of a wide array that is no multiple of the lanes."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    olmo = harness.load_json(os.path.join(
        REPO, "benchmarks", "configs", "olmo-hybrid-7b.json"))
    s, taps = olmo["seq_len"], olmo["linear_conv_kernel_dim"]
    heads = olmo["linear_num_key_heads"]
    d_qk = 2 * heads * olmo["linear_key_head_dim"]
    d_v = heads * olmo["linear_value_head_dim"]
    wide = d_qk + 2 * d_v + 2 * heads
    assert (s, taps, d_qk, d_v, wide) == (16384, 4, 5760, 5760, 17340)
    assert all(ssm_conv.conv_kernel_fits((1, s, 5760), taps, "tpu", first)
               for first in (0, d_qk))

    def loss(proj, w):
        return sum(jnp.sum(ssm_conv.causal_conv_silu_kernel(
            proj, w[first:first + 5760], jnp.zeros((5760,), jnp.float32),
            first).astype(jnp.float32) ** 2) for first in (0, d_qk))

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.ShapeDtypeStruct((1, s, wide), jnp.bfloat16, sharding=one),
            jax.ShapeDtypeStruct((d_qk + d_v, taps), jnp.bfloat16, sharding=one),
        ).compile().as_text()
    calls = probe.scan_kernel_calls(text, "ssm_conv", "ssm/conv")
    assert {name: entry["calls"] for name, entry in calls.items()} == {
        "ssm_conv_fwd": 2, "ssm_conv_bwd": 2}
