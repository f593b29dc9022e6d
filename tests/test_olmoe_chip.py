"""OLMoE's layer, its kernels and its whole train step at published widths,
AOT-compiled for a described (not attached) ``v5e`` chip: nothing runs.  A
module apart from ``tests/test_olmoe.py``'s CPU cases, so that ``--dist
loadfile`` can give the compiles a worker of their own; what remat keeps, at
the smallest shape the kernel takes, is ``tests/test_olmoe_chip_remat.py``'s,
and the expert layers' row movements at each cell's shape
``tests/test_olmoe_chip_rows.py``'s.
"""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from test_olmoe import _call_and_gradients, _compiled_tilings, probe
from __graft_entry__ import olmoe_one_chip
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.ops.moe_dispatch import grouped_matmul, grouped_matmul_tiles


# the ``v5e_chip`` fixture is tests/conftest.py's

@contextlib.contextmanager
def _no_compile_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_one_olmoe_layer_compiles_for_v5e_at_published_widths(v5e_chip, monkeypatch):
    """Forward and backward of ONE layer of the recipe (2048 wide, 16
    heads of 128, 64 gated experts of 1024, top-8 dropless, 4 x 4,096
    tokens) for a described chip: the grouped matmul, the sort, the
    gathers and the blocked attention kernel at its tiles are accepted at
    the sizes the cell runs, and no [B, H, S, S] scores are left."""
    # the chip is described, not attached: this process's backend is the
    # CPU, and the recipe would resolve as it does there
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array([v5e_chip]), ("expert",))
    model, cfg, _, batch = olmoe_one_chip(mesh)
    assert model.attn_impl == "flash"  # what a user on the chip gets
    assert (cfg.d_model, cfg.n_heads, cfg.num_experts, cfg.k,
            model.moe.ffn_dim, cfg.seq_len, batch) == (2048, 16, 64, 8, 1024, 4096, 4)
    one = NamedSharding(mesh, P())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        shapes["layers"][0], model.param_shardings(shapes)["layers"][0],
    )
    x = jax.ShapeDtypeStruct((batch, cfg.seq_len, cfg.d_model), cfg.dtype, sharding=one)

    def layer_loss(lp, x):
        y, aux = model._layer(lp, x, 0, None, cfg.attention_layer(0))
        return (y.astype(jnp.float32) ** 2).mean() + aux["aux_loss"]

    with _no_compile_cache():
        compiled = jax.jit(jax.grad(layer_loss, argnums=(0, 1))).lower(lp, x).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 9  # 3 forward, 6 backward
    # the compiler took the tiles it was handed (its own 512,512,512 is
    # nowhere), and they fit VMEM: a refused setting fails the compile
    assert _compiled_tilings(text) == {
        "256,2048,1024", "256,1024,2048", "256,1024,1024"}
    # forward and the fused backward, under the scope (the kernel writes a
    # newline into its call's attributes, so the instruction's name and
    # its op_name sit on different lines of the text)
    kernels = set(re.findall(
        r'op_name="[^"]*[/(]attention[/)]+flash/[^"]*/(\w+)/pallas_call"', text))
    assert len(kernels) == 2 and all(k.startswith("splash_mha") for k in kernels), kernels
    assert "[4,16,4096,4096]" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 12e9


@pytest.mark.parametrize("shape", [
    (4, 256, 8, 64),     # dmoe256's, were it asked for the kernel
    (16, 512, 8, 64),    # auto's threshold
    (8, 1024, 8, 64),
    (2, 4096, 16, 128),
    (1, 8192, 16, 128),
])
def test_blocked_attention_compiles_for_v5e_at_its_tiles(v5e_chip, monkeypatch, shape):
    """Mosaic takes the forward kernel and the fused backward kernel at the
    tiles ``flash_block_sizes`` gives for lengths on both sides of the
    sweep's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def both(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: trunk.attention_core(q, k, v, "flash"), q, k, v)
        return out, vjp(do)

    with _no_compile_cache():
        text = jax.jit(both).lower(x, x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # forward, the fused backward


def test_the_whole_step_holds_one_forward_kernel_call_a_layer(v5e_chip, monkeypatch):
    """The 4-layer train step at published widths, compiled for a
    described chip (nothing runs): 1.884 B parameters, the compiler's own
    count of what is live in the step between a quarter of the chip's
    memory and 0.9 of it (8.93 GB, 52.8 %, when this was written), and the
    blocked kernel called once forward and once backward a layer (8 and 4
    before PR 38: remat keeps the kernel's output and row sums, 68 MB a
    layer, and the recompute holds no forward call)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "olmoe_one_chip")
    assert memory["parameters"] == 1_884_325_888
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    assert memory["loss_layer_products"] == 3
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 4, "splash_mha_dkv_no_residuals": 4}
    assert memory["kept_residual_bytes"] == 4 * 4 * 16 * 4096 * (128 * 2 + 4)
    # and the results of the attention part's products (PR 53): q, k, v and
    # the output projection's, bf16 [4, 4096, 4 x 2048] a layer, 1.07 GB;
    # the backward pass runs none of the four a second time
    assert memory["kept_product_bytes"] == 4 * 4 * 4096 * (4 * 2048) * 2
    assert memory["recomputed_attention_products"] == 0
    assert {name: (c["calls"], c["block_q"], c["block_kv"])
            for name, c in memory["attention_kernel_tilings"]["attention"].items()} == {
        "splash_mha_fwd_residuals": (4, 1024, 1024),
        "splash_mha_dkv_no_residuals": (4, 1024, 1024)}
    # five row gathers a mixture layer (six before PR 50: the combine's
    # gathered rows are no residual, so remat gathers them no second time),
    # no scatter, and the sum of 8 rows left to the compiler
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 0, "under_moe_sort": 0, "under_moe_combine": 0},
        "row_gathers": 4 * 5, "row_scatters": 0}


@pytest.mark.parametrize("m, a, b", [
    (2048, 512, 256),     # the fewest rows that get tiles, narrower than one
    (8192, 4096, 4096),   # wider than one: an accumulator beside the tiles
    (4096, 16384, 512),
    (4096, 512, 16384),
    (4096, 2560, 768),    # SmallThinker's: the largest whole-matrix tile,
    (4096, 768, 2560),    # and 1280 = 2560 / 2 in the weights' gradients
])
def test_grouped_matmul_compiles_for_v5e_at_its_tiles(v5e_chip, m, a, b):
    """The chip's compiler takes the call and both gradients at the tiles
    ``grouped_matmul_tiles`` gives beyond the cell's widths: a setting
    that does not fit VMEM fails the compile."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    x = jax.ShapeDtypeStruct((m, a), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((8, a, b), jnp.bfloat16, sharding=one)
    g = jax.ShapeDtypeStruct((m, b), jnp.bfloat16, sharding=one)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)

    with _no_compile_cache():
        text = jax.jit(
            functools.partial(_call_and_gradients, grouped_matmul)
        ).lower(x, w, g, sizes).compile().as_text()
    assert _compiled_tilings(text) == {
        ",".join(map(str, grouped_matmul_tiles(*call)))
        for call in ((m, a, b, jnp.bfloat16), (m, b, a, jnp.bfloat16),
                     (m, a, b, jnp.bfloat16, True))
    }
