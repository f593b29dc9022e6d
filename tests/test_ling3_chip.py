"""Ling-3.0-flash-VL's train step and the channel-decayed rule's kernels at
published widths, AOT-compiled for a described (not attached) ``v5e`` chip:
nothing runs.  A module apart from ``tests/test_ling3.py``'s CPU cases, so
that ``--dist loadfile`` can give the compiles a worker of their own.
"""

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
