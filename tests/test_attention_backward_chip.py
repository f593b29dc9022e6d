"""The blocked attention kernel's forward and its ONE backward call
(``ops/attention_backward.py``) at the ``xing4.0-29b-a4b`` cell's shape,
AOT-compiled for a described (not attached) ``v5e`` chip: nothing runs.  A
module apart from ``tests/test_attention_backward.py``'s CPU cases, so that
``--dist loadfile`` can give the compile a worker of its own.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.ops import attention_backward as ab  # noqa: E402

probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))
B, S, H, HD, HDV = 1, 16384, 32, 192, 128
KERNEL = re.compile(r"%(splash_mha[\w.\-]*) = ")


def test_the_core_compiles_for_the_chip_at_the_cells_shape(v5e_chip, monkeypatch):
    """``attention_core`` under ``flash`` and YaRN's scale at ``[1, 16384,
    32, 192 | 128]`` bf16, forward and backward, compiled for a described
    chip: Mosaic takes the blocks and the VMEM the call asks for (a head's
    ``dq`` resident, 29.4 MB of 96); the compiled text holds the library's
    forward once and ONE backward custom call, whose name the benchmark's
    reader files as backward; no array of ``S / block_kv`` partials of the
    queries' gradient, so no sum over them by XLA."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trunk.resident_backward_fits((B, S, H, HD), H, HDV, None, None, "tpu")
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    q, k, v = (jax.ShapeDtypeStruct((B, S, H, width), jnp.bfloat16, sharding=one)
               for width in (HD, HD, HDV))

    def loss(q, k, v):
        out = trunk.attention_core(q, k, v, "flash", scale=0.1)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile().as_text()
    kernels = KERNEL.findall(text)
    forward = [name for name in kernels if "_fwd" in name]
    backward = [name for name in kernels if "_fwd" not in name]
    assert len(forward) == 1 and len(backward) == 1, kernels
    assert backward[0].startswith(ab.NAME)
    blocks = S // min(ab._BLOCKS[1], S)
    assert blocks == 16 and f"[{blocks},{H},{S},{HD}]" not in text
    # the three gradients leave the call in the operands' dtype
    assert re.search(
        rf"%{re.escape(backward[0])} = \(bf16\[{B},{H},{S},{HD}\]\S* bf16\[{B},{H},{S},{HD}\]"
        rf"\S* bf16\[{B},{H},{S},{HDV}\]", text)
