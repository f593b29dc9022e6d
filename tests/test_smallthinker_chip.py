"""SmallThinker's train step and its two kinds of layer at published widths,
AOT-compiled for a described (not attached) ``v5e`` chip: nothing runs.  A
module apart from ``tests/test_smallthinker.py``'s CPU cases, so that ``--dist
loadfile`` can give the compiles a worker of their own.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from test_smallthinker import probe
from __graft_entry__ import smallthinker_one_chip
from learning_at_home_tpu.models.transformer import AttentionLayer


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The 4-layer train step at published widths, compiled for a
    described chip (nothing runs): 2.372 B parameters, the compiler's
    own count of what is live in the step is between a quarter of the
    chip's memory (the benchmark's floor for a cell) and all of it, and
    every one of the step's 48 grouped-matmul instructions runs at
    ``grouped_matmul_tiles``'s answer for its shape (16 did before PR 32)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip)
    assert memory["parameters"] == 2_372_426_240
    assert memory["argument_bytes"] > 2 * memory["parameters"]  # bf16, state
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    # a layer's 12 grouped matmuls (remat runs the 3 forward ones twice),
    # each compiled at the tiles the rule reads from its shape
    assert memory["grouped_matmul_tilings"] == {
        "256,2560,768": 4 * 5, "256,768,2560": 4 * 4,
        "256,1280,768": 4 * 2, "256,768,1280": 4 * 1,
    }
    # the logits and the two gradient products, in one scan of chunks: the
    # chip's compiler keeps no fourth product of the head's (PR 34)
    assert memory["loss_layer_products"] == 3
    # a window of 4,096 is no shorter than the kernel's key block: all four
    # layers keep the fused backward at 1024-wide blocks (PR 36); one
    # forward a layer (8 before PR 38): remat keeps the kernel's output and
    # row sums, 119 MB a layer, and the recompute holds no forward call,
    # in the compiled step's instructions and in the traced step's
    # equations (``attention_kernel_tilings``) alike
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 4, "splash_mha_dkv_no_residuals": 4}
    # a mixture layer's sums over a token's 6 rows are ``ops/moe_rows.py``'s
    # kernel, once behind the combine's gather and once behind the sort's
    # backward; five row gathers a layer (six before PR 50), no scatter
    assert memory["moe_rows_kernel_calls"] == {
        "moe_rows_sum": {"calls": 4 * 2, "under_moe_sort": 4, "under_moe_combine": 4},
        "row_gathers": 4 * 5, "row_scatters": 0}
    assert memory["kept_residual_bytes"] == 4 * 28 * 16384 * (128 * 2 + 4)
    # and the results of the attention part's products (PR 53): q, k, v and
    # the output projection's, bf16 [16384, 3584 + 512 + 512 + 2560] a
    # layer, 0.94 GB; the backward pass runs none of the four a second time
    assert memory["kept_product_bytes"] == 4 * 16384 * (3584 + 2 * 512 + 2560) * 2
    assert memory["recomputed_attention_products"] == 0
    assert {kind: {name: call["calls"] for name, call in calls.items()}
            for kind, calls in memory["attention_kernel_tilings"].items()} == {
        "global": {"splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1},
        "window": {"splash_mha_fwd_residuals": 3, "splash_mha_dkv_no_residuals": 3}}
    assert {(call["block_q"], call["block_kv"])
            for calls in memory["attention_kernel_tilings"].values()
            for call in calls.values()} == {(1024, 1024)}


@pytest.mark.parametrize("layer", [0, 1], ids=["global", "window"])
def test_one_layer_compiles_for_v5e_at_published_widths(v5e_chip, monkeypatch, layer):
    """Forward and backward of one global and one window layer of the
    recipe (2560 wide, 28 heads over 4 key/value heads of 128, 64 ReGLU
    experts of 768 top-6, 1 x 16,384 tokens) for a described chip: the
    blocked kernel takes the 4 key/value heads as they are under its
    causal and its local mask, and no [.., 16384, 16384] array is left."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array([v5e_chip]), ("expert",))
    model, cfg, _, batch = smallthinker_one_chip(mesh)
    assert model.attn_impl == "flash"  # what a user on the chip gets
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.num_experts,
            cfg.k, model.moe.ffn_dim, cfg.seq_len, cfg.vocab_size, batch) == (
        2560, 28, 4, 128, 64, 6, 768, 16384, 151936, 1)
    kind = cfg.attention_layer(layer)
    assert kind == (AttentionLayer(None, False), AttentionLayer(4096, True))[layer]
    one = NamedSharding(mesh, P())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        shapes["layers"][layer], model.param_shardings(shapes)["layers"][layer],
    )
    x = jax.ShapeDtypeStruct((batch, cfg.seq_len, cfg.d_model), cfg.dtype, sharding=one)

    def layer_loss(lp, x):
        y, aux = model._layer(lp, x, layer, None, kind)
        return (y.astype(jnp.float32) ** 2).mean() + aux["aux_loss"]

    with probe.no_compile_cache():
        compiled = jax.jit(jax.grad(layer_loss, argnums=(0, 1))).lower(lp, x).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 9  # 3 forward, 6 backward
    scope = "attention/" + ("global", "window")[layer]
    kernels = set(re.findall(
        r'op_name="[^"]*[/(]%s[/)]+flash/[^"]*/(\w+)/pallas_call"' % scope, text))
    assert len(kernels) == 2 and all(k.startswith("splash_mha") for k in kernels), kernels
    # the kernel reads K and V with their 4 heads: no 28-head copy is made
    calls = re.findall(r"%splash_mha_fwd\w*(?:\.\d+)? = [^\n]*custom-call\(", text)
    assert calls and "bf16[4,16384,128]" in text
    assert ("rope" in text) == kind.rotary
    assert "16384,16384" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9
