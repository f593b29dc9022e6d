"""Ling-3.0-flash-VL's channel-decayed rule's kernels and its gate-and-norm
under a head-wise gate at published widths, AOT-compiled for a described (not attached) ``v5e`` chip:
nothing runs.  A module apart from ``tests/test_ling3.py``'s CPU cases, so
that ``--dist loadfile`` can give the compiles a worker of their own.
"""

import re

import jax
import jax.numpy as jnp

from test_olmo_hybrid import probe


def test_the_channel_rules_kernels_compile_for_the_chip_at_the_cells_shape(v5e_chip):
    """``delta_channel_fwd`` and ``delta_channel_bwd`` at ``[1, 16384, 32,
    128 / 128]`` bf16 with float32 log-decays ``[1, 16384, 32, 128]`` in
    chunks of 64, compiled for a described chip (nothing runs): Mosaic takes
    the sums as a block of ``q``'s kind, two heads abreast, the stacked
    score products, the rows turned into the state's columns and the VMEM
    the kernels ask for."""
    from learning_at_home_tpu.ops import delta_rule

    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, h, dk, dv = 16384, 32, 128, 128
    assert delta_rule.kernel_fits(
        (1, s, h, dk), (1, s, h, dv), 64, "tpu", channel=True)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (shaped((1, s, h, dk), jnp.bfloat16), shaped((1, s, h, dk), jnp.bfloat16),
            shaped((1, s, h, dv), jnp.bfloat16), shaped((1, s, h, dk), jnp.float32),
            shaped((1, s, h), jnp.float32))

    def loss(*a):
        o, state = delta_rule.gated_delta_kernel(*a, 64, unit=True)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(state)

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    calls = probe.scan_kernel_calls(text, "delta_channel", "delta/core")
    assert {name: c["calls"] for name, c in calls.items()} == {
        "delta_channel_fwd": 1, "delta_channel_bwd": 1}


def test_the_gate_and_norm_under_a_heads_gate_compiles_for_the_chip_as_two_kernels(
        v5e_chip):
    """``RMSNorm(o)`` a head times ``sigmoid`` of ONE float32 number a head
    (``ops/gate_norm.py`` under a gate a group, PR 67) at ``[1, 16384,
    4096]`` bf16, gate ``[1, 16384, 32]`` float32, heads of 128, forward and
    gradients compiled for a described chip (nothing runs): Mosaic takes a
    block of all 4,096 channels beside the rows' whole gate, a head's column
    of it under the head's lanes and the gate's gradient as a masked
    select a head; one call each way, and no float32 ``[1, 16384, 4096]``
    written beside them."""
    from learning_at_home_tpu.ops import gate_norm

    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, c, group = 16384, 4096, 128
    assert gate_norm.gate_norm_fits((1, s, c), group, "tpu", 0, True)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
        ((1, s, c), jnp.bfloat16), ((1, s, c // group), jnp.float32),
        ((group,), jnp.float32), ((1, s, c), jnp.bfloat16))]

    def both(y, gate, scale, dout):
        with jax.named_scope("delta"), jax.named_scope("gate_norm"):
            out, back = jax.vjp(
                lambda *a: gate_norm.gated_rms_norm_kernel(
                    *a, group, 1e-6, False, 0, None, "sigmoid"), y, gate, scale)
            return (out, *back(dout))

    with probe.no_compile_cache():
        text = jax.jit(both).lower(*args).compile().as_text()
    assert probe.scan_kernel_calls(text, "gate_norm", "delta/gate_norm") == {
        "gate_norm_fwd": {"calls": 1, "under_delta_gate_norm": 1},
        "gate_norm_bwd": {"calls": 1, "under_delta_gate_norm": 1}}
    assert not re.findall(r"^\s*%\S+ = f32\[1,16384,4096\]", text, re.M)
    _, dy, dgate, dscale = jax.eval_shape(both, *args)
    assert (dgate.shape, dgate.dtype) == ((1, s, c // group), jnp.float32)
    assert (dy.dtype, dscale.shape) == (jnp.bfloat16, (group,))
