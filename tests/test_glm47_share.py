"""GLM-4.7-Flash's share of the experts in the pod step: the two shares add
up to the uncut layer, the set-up levels the prediction block's router too;
the refusals beside that path; the kernel's tiles at heads of 256 and the
grouped matmul's at this model's shapes.  A module apart from
``tests/test_glm47.py`` (the block against its reference, and what must fail
that comparison), so that ``--dist loadfile`` can spread the two.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_glm47 import SIZES, _one_device_mesh, reference, tiny  # noqa: F401  (a fixture)
from __graft_entry__ import glm_4_7_flash_one_chip
from learning_at_home_tpu.models import transformer, trunk
from learning_at_home_tpu.models.transformer import DMoETransformerLM
from learning_at_home_tpu.ops import moe_dispatch
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts


# ---- (d) the share ----


def _layer_of_all_experts(seed=5, d=32, f=16, experts=16, k=4, n=96):
    rs = np.random.RandomState(seed)

    def w(*shape):
        return jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)

    moe = {"gate": w(d, experts) * 4, "w_gate": w(experts, d, f),
           "w_up": w(experts, d, f), "w_down": w(experts, f, d),
           "router_bias": jnp.asarray(rs.uniform(-0.1, 0.1, experts), jnp.float32)}
    lp = {"ln2": {"scale": jnp.asarray(rs.uniform(0.5, 1.5, d), jnp.float32)},
          "moe": moe,
          "shared": {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}}
    h = jnp.asarray(rs.randn(1, n, d), jnp.float32)
    sizes = dict(SIZES, experts_per_token=k, held=None, first_k_dense_replace=0)
    # loads levelled, as the set-up leaves them: no share's buffer overflows
    m = reference.rms(h, lp["ln2"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    moe["router_bias"], _ = moe_dispatch.level_bias(
        jax.nn.sigmoid(m @ moe["gate"]), moe["router_bias"], k)
    return lp, h, sizes


def test_the_two_shares_add_up_to_the_uncut_layer():
    """The routed parts both shares give (each its own half of the 16
    experts, through the program's share path), with the shared expert
    counted once, equal the uncut reference's layer; so do the
    reference's own shares."""
    lp, h, sizes = _layer_of_all_experts()
    d, experts, held, k = h.shape[-1], 16, 8, 4
    want, _, _ = reference.ffn_part(lp, h, sizes, 0)
    m = reference.rms(h, lp["ln2"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    total = trunk.gated_mlp(lp["shared"], m)  # what both chips compute alike: once
    ref_total = reference.gated(lp["shared"], m, lambda a: a)
    for first in (0, held):
        cut = {**lp["moe"], **{name: lp["moe"][name][first:first + held]
                               for name in ("w_gate", "w_up", "w_down")}}
        share = ShardedMixtureOfExperts(
            _one_device_mesh(), hidden_dim=d, num_experts=experts, k=k,
            dtype=jnp.float32, ffn_dim=16, expert_kind="gated_silu",
            routing="dropless", router_score="sigmoid", router_bias=True,
            routed_scale=1.8, held_experts=held, first_held_expert=first)
        part, aux = jax.jit(share)(cut, m)
        assert float(aux["dropped_fraction"]) == 0.0, first
        total = total + part
        ref_total = ref_total + reference.routed_part(
            cut, m, dict(sizes, held=(first, held)))
    scale = np.abs(np.asarray(want - h)).max()
    for summed in (total, ref_total):
        np.testing.assert_allclose(
            np.asarray(h + summed.reshape(h.shape)), np.asarray(want), rtol=0,
            atol=1e-5 * scale)


def test_set_up_levels_the_blocks_router_too(tiny):
    """``level_router_bias`` levels five routers, the block's the last, on
    the next ids the rows themselves give; the stack's layers' levelled
    biases are what they are without the block."""
    model, cfg, params, ids, _ = tiny
    pool = [ids, jnp.roll(ids, 5, axis=1)]
    levelled, loads = model.level_router_bias(params, pool)
    assert len(loads) == 5
    assert all(after <= before and after < 1.3 for before, after in loads)
    was = params["mtp"]["layer"]["moe"]["router_bias"]
    now = levelled["mtp"]["layer"]["moe"]["router_bias"]
    assert float(jnp.abs(now - was).max()) > 0
    stack_alone = {k: v for k, v in params.items() if k != "mtp"}
    alone, loads_alone = model.level_router_bias(stack_alone, pool)
    assert loads_alone == loads[:4]
    for a, b in zip(alone["layers"][1:], levelled["layers"][1:]):
        np.testing.assert_array_equal(a["moe"]["router_bias"], b["moe"]["router_bias"])
    # and the step's rule moves the block's bias as it moves the others
    _, _, optimizer, _ = glm_4_7_flash_one_chip(_one_device_mesh(), tiny=True)
    before = np.asarray(now)
    own = jax.tree_util.tree_map(jnp.copy, levelled)  # the step donates them
    opt_state = model.init_opt_state(optimizer, own)
    stepped, _, _, metrics = model.make_train_step(optimizer)(
        own, opt_state, ids, jnp.roll(ids, -1, axis=1))
    moved = np.asarray(stepped["mtp"]["layer"]["moe"]["router_bias"]) - before
    np.testing.assert_allclose(np.abs(moved[moved != 0]), 0.001, rtol=1e-4)
    assert (moved != 0).any()
    assert "expert_counts" not in metrics and "ce_mtp" in metrics


# ---- (e) the refusals beside the path ----


@pytest.mark.parametrize("changes, error, match", [
    ({"mtp_layers": 2}, ValueError, "0 or 1"),
    # (q_latent_dim None beside a kv_latent_dim is a FORM since PR 66: queries
    # of one plain product, tests/test_ling3.py builds and trains it)
    ({"rope_head_dim": None}, ValueError, "together"),
    ({"head_dim": None}, ValueError, "together"),
    ({"n_kv_heads": 2}, ValueError, "latent attention"),
    ({"qk_norm": "head"}, ValueError, "latent attention"),
    ({"rope_head_dim": 16}, ValueError, "rope_head_dim"),
    ({"seq_parallel": True}, NotImplementedError, "latent attention"),
])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(
        tiny, changes, error, match):
    _, cfg, _, _, _ = tiny
    with pytest.raises(error, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), _one_device_mesh())


def test_the_cached_decoder_refuses_the_block_by_name(tiny):
    model, cfg, params, ids, _ = tiny
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.generate(params, ids[:, :4], 2, use_cache=True)
    plain = dataclasses.replace(
        cfg, kv_latent_dim=None, q_latent_dim=None, rope_head_dim=None)
    with pytest.raises(NotImplementedError, match="next-but-one-token block"):
        DMoETransformerLM(plain, _one_device_mesh()).generate(
            params, ids[:, :4], 2, use_cache=True)
    out = model.generate(params, ids[:1, :4], 2)  # the full forward decodes
    assert out.shape == (1, 6)


# ---- (f) the kernel's tiles and the grouped matmul's at this model's shapes ----


def test_flash_block_sizes_at_heads_of_256():
    sizes = trunk.flash_block_sizes((1, 16384, 20, 256), "tpu")
    assert sizes.use_fused_bwd_kernel
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == (1024, 1024, 256)
    assert (sizes.block_q_dkv, sizes.block_kv_dkv, sizes.block_kv_dkv_compute) == (
        1024, 1024, 512)
    assert trunk.flash_block_sizes((1, 16384, 20, 256), "cpu") is None
    assert trunk.flash_block_sizes((1, 16384, 20, 192), "tpu") is None
    short = trunk.flash_block_sizes((1, 128, 20, 256), "tpu")
    assert (short.block_q, short.block_kv_compute) == (128, 128)
    # heads of 64 and 128 as before
    for hd in (64, 128):
        was = trunk.flash_block_sizes((4, 4096, 16, hd), "tpu")
        assert (was.block_q, was.block_kv, was.block_kv_compute,
                was.block_kv_dkv_compute, was.use_fused_bwd_kernel) == (
            1024, 1024, 512, 512, True)
    window = trunk.flash_block_sizes((1, 16384, 64, 128), "tpu", 128)
    assert (window.block_q, window.block_kv, window.use_fused_bwd_kernel) == (
        512, 512, False)
    assert transformer.auto_attn_impl("tpu", 1, 16384, 256) == "flash"
    assert transformer.auto_attn_impl("cpu", 1, 16384, 256) == "xla"
    assert transformer.auto_attn_impl("tpu", 4, 16384, 256) == "xla"


def test_grouped_matmul_tiles_at_2048_by_1536():
    tiles = moe_dispatch.grouped_matmul_tiles
    assert tiles(65536, 2048, 1536, jnp.bfloat16) == (256, 2048, 768)
    assert tiles(65536, 1536, 2048, jnp.bfloat16) == (256, 1536, 1024)
    assert tiles(65536, 2048, 1536, jnp.bfloat16, weights_gradient=True) == (
        256, 1024, 768)
    assert tiles(65536, 1536, 2048, jnp.bfloat16, weights_gradient=True) == (
        256, 768, 1024)
