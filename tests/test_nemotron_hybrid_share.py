"""Nemotron-Labs-TwoTower's share of the experts in the pod step: the four
shares add up to the uncut layer, the un-gated kind, the set-up's levelling
of the four routers; the refusals beside that path; the kernel's and the
grouped matmul's tiles at this model's shapes.  A module apart from
``tests/test_nemotron_hybrid.py`` (the stack against its reference, and what
must fail that comparison), so that ``--dist loadfile`` can spread the two.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_hybrid import (  # noqa: F401  (``tiny`` is a fixture)
    SIZES,
    _close,
    _one_device_mesh,
    _streams,
    reference,
    tiny,
)
from __graft_entry__ import nemotron_labs_twotower_one_chip
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.models.transformer import (
    DMoETransformerConfig,
    DMoETransformerLM,
)
from learning_at_home_tpu.ops import moe_dispatch
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts


# ---- (c) the share ----


def _layer_of_all_experts(seed=5, d=32, f=16, f_shared=24, experts=16, k=3, n=96):
    rs = np.random.RandomState(seed)

    def w(*shape):
        return jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)

    moe = {"gate": w(d, experts) * 4, "w_up": w(experts, d, f),
           "w_down": w(experts, f, d),
           "router_bias": jnp.asarray(rs.uniform(-0.1, 0.1, experts), jnp.float32)}
    lp = {"norm": {"scale": jnp.asarray(rs.uniform(0.5, 1.5, d), jnp.float32)},
          "moe": moe, "shared": {"w_up": w(d, f_shared), "w_down": w(f_shared, d)}}
    x = jnp.asarray(rs.randn(1, n, d), jnp.float32)
    sizes = dict(SIZES, pattern="E", experts_per_token=k, held=None)
    # loads levelled, as the set-up leaves them: no share's buffer overflows
    m = reference.rms(x, lp["norm"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    moe["router_bias"], _ = moe_dispatch.level_bias(
        jax.nn.sigmoid(m @ moe["gate"]), moe["router_bias"], k)
    return lp, x, sizes


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts the four shares give (each its own quarter of the
    16 experts, through the program's share path), with the shared expert
    counted once, equal the uncut reference's layer; so do the reference's
    own shares."""
    lp, x, sizes = _layer_of_all_experts()
    d, experts, held, k = x.shape[-1], 16, 4, 3
    want, _, _ = reference.layer(lp, x, sizes, 0)
    m = reference.rms(x, lp["norm"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    # what the four chips compute alike: once
    total = trunk.gated_mlp(lp["shared"], m, trunk.squared_relu)
    ref_total = reference.relu2_mlp(
        lp["shared"]["w_up"], lp["shared"]["w_down"], m, lambda a: a)
    _close(total, ref_total, 1e-5)
    for first in range(0, experts, held):
        cut = {**lp["moe"], **{name: lp["moe"][name][first:first + held]
                               for name in ("w_up", "w_down")}}
        share = ShardedMixtureOfExperts(
            _one_device_mesh(), hidden_dim=d, num_experts=experts, k=k,
            dtype=jnp.float32, ffn_dim=16, expert_kind="relu2",
            routing="dropless", router_score="sigmoid", router_bias=True,
            routed_scale=2.5, held_experts=held, first_held_expert=first)
        part, aux = jax.jit(share)(cut, m)
        assert float(aux["dropped_fraction"]) == 0.0, first
        total = total + part
        ref_total = ref_total + reference.routed_part(
            cut, m, dict(sizes, held=(first, held)))
    scale = np.abs(np.asarray(want - x)).max()
    for summed in (total, ref_total):
        np.testing.assert_allclose(
            np.asarray(x + summed.reshape(x.shape)), np.asarray(want), rtol=0,
            atol=1e-5 * scale)


def test_the_ungated_kind_whole_runs_two_grouped_matmuls_forward():
    """``relu2`` on the dropless path with every expert here: the result is
    the reference's, the expert stack holds two matrices, and the traced
    forward holds two ``ragged_dot`` where a gated kind holds three."""
    lp, x, sizes = _layer_of_all_experts()
    d = x.shape[-1]
    m = reference.rms(x, lp["norm"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    calls = {}
    for kind in ("relu2", "gated_silu"):
        moe = ShardedMixtureOfExperts(
            _one_device_mesh(), hidden_dim=d, num_experts=16, k=3,
            dtype=jnp.float32, ffn_dim=16, expert_kind=kind, routing="dropless",
            router_score="sigmoid", router_bias=True, routed_scale=2.5)
        p = moe.init_params(jax.random.PRNGKey(0))
        assert ("w_gate" in p) is (kind != "relu2")
        calls[kind] = str(jax.make_jaxpr(moe)(p, m)).count("ragged_dot_general[")
    assert calls == {"relu2": 2, "gated_silu": 3}
    whole = ShardedMixtureOfExperts(
        _one_device_mesh(), hidden_dim=d, num_experts=16, k=3,
        dtype=jnp.float32, ffn_dim=16, expert_kind="relu2", routing="dropless",
        router_score="sigmoid", router_bias=True, routed_scale=2.5)
    got, _ = jax.jit(whole)(lp["moe"], m)
    _close(got, reference.routed_part(lp["moe"], m, sizes), 1e-5)


def test_set_up_levels_the_four_routers_and_only_them(tiny):
    """``level_router_bias`` levels the four mixture layers' biases on the
    stream each layer's own input is (its router reads the layer's ONE
    norm), touches no other leaf, and the step's rule then moves them."""
    model, cfg, params, ids, _ = tiny
    pool = [ids, jnp.roll(ids, 5, axis=1)]
    levelled, loads = model.level_router_bias(params, pool)
    assert len(loads) == 4
    assert all(after <= before and after < 1.8 for before, after in loads)
    changed = [
        jax.tree_util.keystr(path)
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_leaves(levelled))
        if not np.array_equal(np.asarray(a), np.asarray(b))]
    assert changed == [f"['layers'][{i}]['moe']['router_bias']" for i in (1, 3, 6, 8)]
    # the first mixture layer's bias is level_bias on its own input's scores
    lp = params["layers"][1]
    streams = [x for p in pool for i, (_, x) in enumerate(_streams(model, params, p)) if i == 1]
    scores = jnp.concatenate([jax.nn.sigmoid(model.moe.router_logits(
        lp["moe"], model._norm(lp["norm"], x).reshape(-1, cfg.d_model)))
        for x in streams])
    want, _ = moe_dispatch.level_bias(scores, lp["moe"]["router_bias"], cfg.k)
    np.testing.assert_allclose(
        np.asarray(levelled["layers"][1]["moe"]["router_bias"]), np.asarray(want),
        atol=1e-6)
    _, _, optimizer, _ = nemotron_labs_twotower_one_chip(_one_device_mesh(), tiny=True)
    before = [np.asarray(levelled["layers"][i]["moe"]["router_bias"]) for i in (1, 3, 6, 8)]
    own = jax.tree_util.tree_map(jnp.copy, levelled)  # the step donates them
    opt_state = model.init_opt_state(optimizer, own)
    stepped, _, _, metrics = model.make_train_step(optimizer)(
        own, opt_state, ids, jnp.roll(ids, -1, axis=1))
    for was, i in zip(before, (1, 3, 6, 8)):
        moved = np.asarray(stepped["layers"][i]["moe"]["router_bias"]) - was
        np.testing.assert_allclose(np.abs(moved[moved != 0]), 0.001, rtol=1e-4)
        assert (moved != 0).any()
    assert "expert_counts" not in metrics and "ssm_decay_min" in metrics


# ---- (d) the refusals beside the path ----


@pytest.mark.parametrize("changes, error, match", [
    ({"seq_parallel": True}, NotImplementedError, "mixer_pattern"),
    ({"mixer_pattern": ("ssm",) * 9}, ValueError, "one 'moe'"),
    ({"mixer_pattern": ("ssm", "moe")}, ValueError, "each of the 9 layers"),
    ({"mixer_pattern": ("ssm", "dense") + ("moe",) * 7}, ValueError, "'ssm', 'attention' or 'moe'"),
    ({"ssm_state_dim": None}, ValueError, "ssm_state_dim"),
    ({"ffn_pattern": ("moe",) * 9}, ValueError, "ONE mixer"),
    ({"mtp_layers": 1}, ValueError, "ONE mixer"),
    ({"expert_kind": "gelu"}, ValueError, "must not be 'gelu'"),
    ({"expert_kind": "gelu", "shared_experts": 0}, NotImplementedError, "not 'gelu'"),
    ({"expert_kind": "relu3"}, ValueError, "'relu2'"),
])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(
        tiny, changes, error, match):
    _, cfg, _, _, _ = tiny
    with pytest.raises(error, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), _one_device_mesh())


def test_the_cached_decoder_refuses_the_stack_by_name(tiny):
    model, _, params, ids, _ = tiny
    with pytest.raises(NotImplementedError, match="recurrent state"):
        model.generate(params, ids[:, :4], 2, use_cache=True)
    out = model.generate(params, ids[:1, :4], 2)  # the full forward decodes
    assert out.shape == (1, 6)


def test_a_stack_that_describes_no_mixer_is_the_program_of_before():
    """``mixer_pattern=None``: every layer an attention block and a
    feed-forward part, its parameters under the names they had."""
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, seq_len=8,
        num_experts=4)
    assert cfg.mixer_pattern is None and cfg.mixture_layers() == 2
    params = DMoETransformerLM(cfg, _one_device_mesh()).init_params(
        jax.random.PRNGKey(0))
    assert sorted(params["layers"][0]) == [
        "ln1", "ln2", "moe", "wk", "wo", "wq", "wv"]


# ---- (e) the kernel's tiles and the grouped matmul's at this model's shapes ----


def test_flash_block_sizes_at_32_heads_of_128():
    sizes = trunk.flash_block_sizes((1, 16384, 32, 128), "tpu")
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == (1024, 1024, 512)
    assert (sizes.block_q_dkv, sizes.block_kv_dkv, sizes.block_kv_dkv_compute) == (
        1024, 1024, 512)
    assert sizes.use_fused_bwd_kernel
    # 16 query heads a key/value head: the shape rule asks for neither count
    assert trunk.flash_block_sizes((1, 16384, 2, 128), "tpu") == sizes


GROUPED_MATMUL_ANSWERS_BEFORE = [
    # (m, k, n, weights_gradient) -> tiles: every answer a cell rests on
    ((131072, 2048, 1024, False), (256, 2048, 1024)),
    ((131072, 1024, 2048, False), (256, 1024, 2048)),
    ((131072, 2048, 1024, True), (256, 1024, 1024)),
    ((98304, 2560, 768, False), (256, 2560, 768)),
    ((98304, 768, 2560, False), (256, 768, 2560)),
    ((98304, 2560, 768, True), (256, 1280, 768)),
    ((16384, 6144, 2048, False), (256, 2048, 1024)),
    ((65536, 2048, 1536, False), (256, 2048, 768)),
    ((65536, 1536, 2048, False), (256, 1536, 1024)),
    ((65536, 2048, 1536, True), (256, 1024, 768)),
    ((256, 2048, 1024, False), None),  # under GROUPED_MATMUL_MIN_ROWS
    # 1000 = 7.8 x 128: no multiple of the lanes divides, and no half lane
    ((131072, 2048, 1000, False), None),
    ((131072, 1000, 2048, True), None),
]


@pytest.mark.parametrize("shape, tiles", GROUPED_MATMUL_ANSWERS_BEFORE)
def test_grouped_matmul_tiles_answers_of_before_are_unchanged(shape, tiles):
    m, k, n, weights_gradient = shape
    assert moe_dispatch.grouped_matmul_tiles(
        m, k, n, jnp.bfloat16, weights_gradient) == tiles


@pytest.mark.parametrize("shape, tiles", [
    # this model's six calls a layer, over the share's buffer of 49,152 rows:
    # 1,856 = 14.5 x 128 is tiled at its cover, 1,920 (PERF.md section 6, PR 39)
    ((49152, 2688, 1856, False), (256, 896, 1920)),  # up; down's rows' gradient
    ((49152, 1856, 2688, False), (256, 1920, 896)),  # down; up's rows' gradient
    ((49152, 2688, 1856, True), (256, 896, 640)),
    ((49152, 1856, 2688, True), (256, 640, 896)),
    ((49152, 2688, 1856 + 1, False), None),  # any other remainder: none
    ((256, 2688, 1856, False), None),
])
def test_grouped_matmul_tiles_at_a_width_of_half_a_lane_tile(shape, tiles):
    m, k, n, weights_gradient = shape
    assert moe_dispatch.share_buffer_rows(16384, 6, 32, 128) == 49152
    assert moe_dispatch.grouped_matmul_tiles(
        m, k, n, jnp.bfloat16, weights_gradient) == tiles
    assert moe_dispatch.grouped_matmul_tiles(m, k, n, jnp.float32) is None
