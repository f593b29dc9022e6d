"""The residual streams' kernels (``ops/stream_mix.py``) at the
``xing4.0-29b-a4b`` cell's shape, AOT-compiled for a described (not
attached) ``v5e`` chip: nothing runs.  A module apart from
``tests/test_stream_mix.py``'s CPU cases, so that ``--dist loadfile`` can
give the compiles a worker of their own.  (The whole step compiles in a
minute and a half here: ``tools/smallthinker_probe.py memory
xing4_0_29b_a4b_one_chip`` is that reading, by hand.)
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

import __graft_entry__  # noqa: E402
from learning_at_home_tpu.ops import stream_mix  # noqa: E402

probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))
B, S, N, C = 1, 16384, 4, 3584


def test_a_parts_kernels_compile_for_the_chip_at_the_cells_shape(
        v5e_chip, monkeypatch):
    """A part's chain, the cell's model's own ``_hc_read`` and
    ``_hc_write`` on the fold [1, 16384, 14336] bf16 that its stack hands
    on, forward and backward, compiled for a described chip: Mosaic
    takes the blocks (64 and 256 rows of 14,336 lanes), the per-token
    columns, the statistics' two products and the VMEM the calls ask for;
    each of the six kernels is called once under its scope, and NO array
    of the streams' size is copied, reshaped to another tiling or written
    by XLA but the ONE add of the fold's three gradients, which carries
    scope ``hc/pre`` (the unfolds' name)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert stream_mix.stream_mix_fits((B, S, N, C), jnp.bfloat16, "tpu")
    one = jax.sharding.SingleDeviceSharding(v5e_chip)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    model, cfg, _, _ = __graft_entry__.xing4_0_29b_a4b_one_chip(
        jax.sharding.Mesh(np.array([v5e_chip]), ("expert",)))
    assert (cfg.hc_streams, cfg.d_model) == (N, C)

    def part(hp, fold, w):
        streams, h, write = model._hc_read(hp, fold)
        out = model._hc_fold(model._hc_write(streams, h @ w, write))
        return jnp.sum(out.astype(jnp.float32) ** 2)

    hp = {"phi": shaped((N * C, 2 * N + N * N), jnp.float32),
          "b": shaped((2 * N + N * N,), jnp.float32),
          "alpha": shaped((3,), jnp.float32)}
    args = (hp, shaped((B, S, N * C), jnp.bfloat16), shaped((C, C), jnp.bfloat16))
    with probe.no_compile_cache():
        traced = jax.jit(jax.grad(part, argnums=(0, 1, 2))).trace(*args)
        text = traced.lower().compile().as_text()
    found = probe.stream_kernel_calls(text, traced.jaxpr.jaxpr)
    assert found["outside_the_scopes"] == 0
    calls = {(scope, name): {k: v for k, v in entry.items() if k != "grid"}
             for scope, names in found["calls"].items()
             for name, entry in names.items()}
    assert calls == {
        ("hc/coeff", "stream_stats_fwd"): {"forward": 1, "rows": 256},
        ("hc/coeff", "stream_stats_bwd"): {"backward": 1, "rows": 256},
        ("hc/pre", "stream_read_fwd"): {"forward": 1, "rows": 64},
        ("hc/pre", "stream_read_bwd"): {"backward": 1, "rows": 64},
        ("hc/post", "stream_write_fwd"): {"forward": 1, "rows": 64},
        ("hc/post", "stream_write_bwd"): {"backward": 1, "rows": 64},
    }
    # what XLA itself writes of the streams' size: the add alone, the
    # read's ``pre[j] dh`` fused into it, filed with the mixing (the
    # loss's own cotangent of the write is the other, outside scope hc)
    assert found["stream_sized_results_of_xla"] == {"hc/pre": 1}
    entry = text[text.find("\nENTRY"):]
    copied = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", entry)
    assert not [dims for dims in copied if probe._stream_sized(dims)]
