"""Xing4.0-29B-A4B's language model in the pod step as one chip's share
(``__graft_entry__.xing4_0_29b_a4b_one_chip``) against its plain reference
(``benchmarks/configs/xing4_0_29b_a4b_reference.py``): four residual
streams mixed by hyper-connections round every part, latent attention at
heads of 12 over values of 8 under YaRN past the original length, the
block that predicts the next-but-one token; the share; the refusals beside
that path; the runner's limits; and the benchmark's files for it.

Tiny sizes on the CPU, float32.  The three hyper-connection functions
alone are ``tests/test_hyper_connections.py``'s.
"""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)
import xing4_flops  # noqa: E402

from __graft_entry__ import xing4_0_29b_a4b_one_chip  # noqa: E402
from benchmark_cells import layer_metric_file, readings_of_cell  # noqa: E402
from runner_limits import (  # noqa: E402,F401  (``compiled_once`` is a fixture)
    close as _close,
    compiled_once,
    decisive,
    Limits,
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "xing4_0_29b_a4b_reference.py"))
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_xing4.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "xing4-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "xing4.0-29b-a4b.json"))
CELL = "xing4.0-29b-a4b-train-zipf16k"
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference
limits = Limits(runner, reference, TINY_FILE)
pytestmark = pytest.mark.usefixtures("compiled_once")


def _decisive(params, seed=7):
    """Seeded weights under which every part of the block decides: a router
    that decides (the program's init gives near-equal scores), selection
    biases off zero, norm scales off 1."""
    return decisive(params, seed, drawn={"['router_bias']": 0.2}, scaled={"['gate']": 20.0})


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(xing4_0_29b_a4b_one_chip, _decisive)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference on the tiny weights, once a module: both heads'
    float32 logits, the streams after the copy-in, every layer and the
    block's layer, the three losses, the gradients."""
    _, _, params, ids, tgt = tiny

    def everything(p):
        streams = []
        logits, logits_mtp, _, _ = reference.forward(
            p, ids, tgt, SIZES, streams=streams)
        return logits, logits_mtp, streams

    logits, logits_mtp, streams = jax.jit(everything)(params)
    losses = jax.jit(lambda p: reference.losses(p, ids, tgt, SIZES))(params)
    _, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)
    return logits, logits_mtp, streams, losses, grads


# ---- (a) the program against the reference ----


def test_the_tiny_recipe_keeps_the_block(tiny):
    """What ``tiny`` must keep of the published block, and the rehearsal
    file's sizes are the tiny recipe's (the runner's own check)."""
    _, cfg, params, _, _ = tiny
    assert (cfg.hc_streams, cfg.hc_sinkhorn_iters) == (4, 20)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_head_dim) == (12, 8, 4)
    assert cfg.seq_len > cfg.rope_scaling.original_max_position_embeddings
    assert cfg.held_experts < cfg.num_experts and cfg.k < cfg.num_experts
    assert cfg.ffn_pattern == ("dense", "moe")
    assert (cfg.mtp_layers, cfg.mtp_loss_weight) == (1, 0.3)
    second = params["layers"][1]
    assert second["wq_b"].shape == (24, 4 * 12)
    assert second["wkv_b"].shape == (16, 4 * (8 + 8))  # [k_nope 8 | v 8] a head
    assert second["wo"].shape == (4 * 8, 64)  # the VALUES' width in
    for part in ("hc_attn", "hc_ffn"):
        assert second[part]["phi"].shape == (4 * 64, 24)
        assert second[part]["b"].shape == (24,) and second[part]["alpha"].shape == (3,)
        assert second[part]["b"].dtype == jnp.float32
    assert set(params["mtp"]["layer"]) >= {"hc_attn", "hc_ffn", "moe", "shared"}
    runner._check_sizes(TINY_FILE, cfg)
    with pytest.raises(harness.BenchError, match="hc_sinkhorn_iters"):
        runner._check_sizes(dict(TINY_FILE, hc_sinkhorn_iters=19), cfg)
    with pytest.raises(harness.BenchError, match="rope_scaling"):
        runner._check_sizes(dict(TINY_FILE, rope_scaling=dict(
            TINY_FILE["rope_scaling"], factor=32)), cfg)


def test_every_stream_of_every_layer_matches_the_reference(tiny, want):
    """A layer at a time from the program's own embedding: the four streams
    after each layer, then the block's, each against the reference's."""
    model, cfg, params, ids, tgt = tiny
    streams = want[2]

    @jax.jit
    def got_streams(params):
        x = params["embed"][ids].astype(cfg.dtype)
        out = [model._hc_copy(x)]
        for i, lp in enumerate(params["layers"]):
            x, _ = model._layer(lp, x, i, None, cfg.attention_layer(i))
            out.append(x)
        hf = model._norm(params["ln_f"], model._hc_sum(x)[0])
        z = model._mtp_input(params["mtp"], hf, tgt, params["embed"])
        z, _ = model._layer(params["mtp"]["layer"], z, cfg.n_layers, None,
                            cfg.attention_layer(cfg.n_layers))
        return out + [z]

    got = got_streams(params)
    assert len(got) == len(streams) == 1 + cfg.n_layers + 1
    for layer, (g, w) in enumerate(zip(got, streams)):
        assert g.shape == w.shape == (ids.shape[0], cfg.seq_len, 4, cfg.d_model)
        for stream in range(4):
            _close(g[:, :, stream], w[:, :, stream], 2e-5,
                   err_msg=f"layer {layer} stream {stream}")
    # the streams differ from each other: the mixing is neither a copy nor a sum
    last = np.asarray(streams[cfg.n_layers])
    assert np.abs(last[:, :, 0] - last[:, :, 1]).max() > 0.1 * np.abs(last).max()


def test_both_heads_logits_and_both_losses_match_the_reference(tiny, want):
    model, cfg, params, ids, tgt = tiny
    logits, logits_mtp, _, (loss, ce, ce_mtp), _ = want

    @jax.jit
    def got(params):
        x, x_mtp, _ = model._hidden(params, ids, next_ids=tgt)
        head = model._head(params)
        total, metrics = model.loss_fn(params, ids, tgt)
        return (model._logits(x, head), model._logits(x_mtp, head), total,
                metrics["ce"], metrics["ce_mtp"], metrics)

    got_logits, got_mtp, got_loss, got_ce, got_ce_mtp, metrics = got(params)
    _close(got_logits, logits, 2e-5)
    _close(got_mtp, logits_mtp, 2e-5)
    np.testing.assert_allclose(
        [got_loss, got_ce, got_ce_mtp], [loss, ce, ce_mtp], rtol=2e-6)
    assert 0 <= float(metrics["hc_res_marginal_error"]) < 1e-4
    assert 1.0 <= float(metrics["hc_stream_rms_spread"]) < 4.0


def test_gradients_of_every_parameter_match_the_reference(tiny, want):
    model, _, params, ids, tgt = tiny
    grads = want[4]
    got = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0]))(params)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(grads)[0]]
    assert any("hc_attn']['alpha" in n for n in names)
    for name, g, w in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(grads)):
        if name.endswith("['router_bias']"):
            continue  # no gradient reaches it on either side
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-4 * scale, err_msg=name)


# ---- (b) YaRN, the unequal head sizes ----


def test_yarn_frequencies_ramp_between_the_two_pairs():
    """The published numbers: pairs 0..10 turn as they did, pairs 23..31 64
    times slower, a linear ramp between; the softmax's scale 2.005 times
    1 / sqrt(192); the cosines' factor 1."""
    scaling = trunk.RopeScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    plain = 1.0 / 1e4 ** (np.arange(32) * 2 / 64)
    got = trunk.yarn_inv_freq(64, 1e4, scaling)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    ratio = plain[11:23] / got[11:23]
    assert (np.diff(ratio) > 0).all() and 1 < ratio[0] < ratio[-1] < 64
    want, amplitude = reference.yarn_inv_freq(64, 1e4, CELL_FILE["rope_scaling"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    cos_factor, scale = trunk.yarn_scales(scaling)
    assert cos_factor == amplitude == 1.0
    assert scale == pytest.approx((0.1 * math.log(64) + 1) ** 2) == pytest.approx(
        2.005, abs=1e-3)
    assert reference.softmax_scale(runner.reference_sizes(CELL_FILE)) == (
        pytest.approx(scale / math.sqrt(192)))
    half = trunk.yarn_scales(dataclasses.replace(scaling, mscale_all_dim=0.0))
    assert half == (pytest.approx(0.1 * math.log(64) + 1), 1.0)


def test_rotation_past_the_original_length_matches_the_reference(tiny):
    """Positions 0..31 over an original length of 8: past it a pair on the
    ramp turns by another angle than the plain frequencies give."""
    _, cfg, _, _, _ = tiny
    x = jnp.asarray(np.random.RandomState(0).normal(0, 1, (1, 32, 3, 4)), jnp.float32)
    positions = jnp.arange(32)
    got = trunk.rotary(x, positions, cfg.rope_theta, cfg.rope_scaling)
    want = reference.rope(x, SIZES["rope_theta"], SIZES["rope_scaling"])
    _close(got, want, 1e-6)
    plain = trunk.rotary(x, positions, cfg.rope_theta)
    assert float(jnp.abs(plain - got)[:, 9:].max()) > 0.1


def test_heads_of_12_over_values_of_8_match_the_reference(tiny):
    """The core at unequal sizes and YaRN's scale against the reference's
    blocked softmax; the kernel's rule answers for (192, 128) on a TPU and
    for no size it was not run at."""
    model, cfg, _, _, _ = tiny
    rs = np.random.RandomState(1)
    q, k = (jnp.asarray(rs.normal(0, 1, (2, 32, 4, 12)), jnp.float32) for _ in "qk")
    v = jnp.asarray(rs.normal(0, 1, (2, 32, 4, 8)), jnp.float32)
    got = trunk.attention_core(q, k, v, "xla", scale=model._attn_scale)
    assert got.shape == (2, 32, 4, 8)
    want = reference.attention(q, k, v, lambda a: a, reference.softmax_scale(SIZES))
    _close(got, want, 1e-5)
    assert model._attn_scale == pytest.approx(reference.softmax_scale(SIZES))
    # the forward's tiles are the library's; the backward is the repo's ONE
    # call (PR 69), so no tile of an unfused pair's dQ kernel is left
    sizes = trunk.flash_block_sizes((1, 16384, 32, 192), "tpu", value_dim=128)
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == (1024, 1024, 256)
    assert sizes.use_fused_bwd_kernel and sizes.block_q_dq is None
    assert trunk.resident_backward_fits(
        (1, 16384, 32, 192), 32, 128, None, None, "tpu")
    assert not trunk.resident_backward_fits(
        (1, 16384, 32, 192), 32, 128, None, None, "cpu")
    assert trunk.flash_block_sizes((1, 16384, 32, 192), "tpu") is None
    assert trunk.flash_block_sizes((1, 16384, 32, 128), "tpu", value_dim=64) is None
    assert trunk.flash_block_sizes((1, 16384, 32, 192), "cpu", value_dim=128) is None
    # the accepted cells' answers are what they were
    assert trunk.flash_block_sizes((1, 16384, 20, 256), "tpu").use_fused_bwd_kernel
    assert trunk.flash_block_sizes((1, 16384, 20, 256), "tpu").block_kv_compute == 256


# ---- (c) the share ----


def test_two_shares_parts_add_up_to_the_uncut_part(tiny):
    """The share test: a mixture layer's feed-forward part ``y`` from the
    two shares' experts (0..7 and 8..15), the shared expert counted once,
    adds up to the uncut reference's part, and ``X'`` follows from it."""
    model, cfg, params, ids, _ = tiny
    mesh = _one_device_mesh()
    whole = DMoETransformerLM(dataclasses.replace(
        cfg, held_experts=None, first_held_expert=0), mesh)
    lp_whole = _decisive(whole.init_params(jax.random.PRNGKey(5)))["layers"][1]
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.normal(0, 1, (2, 32, 4, 64)), jnp.float32)

    def share_of(first):
        moe = {k: (v[first:first + 8] if k in ("w_gate", "w_up", "w_down") else v)
               for k, v in lp_whole["moe"].items()}
        return {**lp_whole, "moe": moe}

    sizes = dict(SIZES, held=None)

    def part_of(lp, held):  # one compiled program a side, not op by op
        return jax.jit(lambda lp, x: reference.hc_part(
            lp["hc_ffn"], x, lambda h: reference.ffn_output(
                lp, h, dict(sizes, held=held), 1)[0], sizes))(lp, x)

    streams, h, y_whole = part_of(lp_whole, None)
    shared = reference.gated(
        reference._f32(lp_whole["shared"]),
        reference.rms(h, lp_whole["ln2"]["scale"], sizes["norm_eps"]).reshape(-1, 64),
        lambda a: a).reshape(h.shape)
    ys = []
    for first in (0, 8):
        share = DMoETransformerLM(dataclasses.replace(
            cfg, held_experts=8, first_held_expert=first), mesh)
        lp = share_of(first)
        (_, read_h, write), out = jax.jit(lambda lp, x: (
            share._hc_read(lp["hc_ffn"], x), share._ffn_block(lp, x, None, 1)[0]))(lp, x)
        _close(read_h, h, 1e-5)
        # what the share's part gave: X' = H_res X + H_post y, solved for y
        post, res, _ = write
        mixed = trunk.hc_post(x, jnp.zeros_like(h), post, res)
        y = (out - mixed)[:, :, 0] / jnp.moveaxis(post, 0, -1)[..., :1]
        ys.append(y)
        _close(out, part_of(lp, (first, 8))[0], 2e-5)
    _close(ys[0] + ys[1] - shared, y_whole, 5e-5)
    _, post_c, res_c = reference.hc_coefficients(lp_whole["hc_ffn"], x, sizes)
    follows = (jnp.einsum("bsij,bsjc->bsic", res_c, x)
               + post_c[..., None] * (ys[0] + ys[1] - shared)[:, :, None])
    _close(follows, streams, 5e-5)


# ---- (d) the refusals, by name ----


def _cfg(**replace):
    cfg = xing4_0_29b_a4b_one_chip(_one_device_mesh(), tiny=True)[1]
    return dataclasses.replace(cfg, **replace)


REFUSALS = {
    "a_mesh_of_several_chips": (
        lambda: DMoETransformerLM(_cfg(), make_mesh(
            {"expert": 2}, devices=jax.devices()[:2])),
        NotImplementedError, "hc_streams on a mesh of several chips"),
    "block_diffusion": (
        lambda: DMoETransformerLM(
            _cfg(objective="block_diffusion", mtp_layers=0, mtp_loss_weight=0.0),
            _one_device_mesh()),
        NotImplementedError, "hc_streams with objective='block_diffusion'"),
    "a_norm_on_a_parts_output": (
        lambda: DMoETransformerLM(_cfg(norm_place="output"), _one_device_mesh()),
        NotImplementedError, "hc_streams wraps an attention part"),
    "one_stream_named_as_several": (
        lambda: DMoETransformerLM(_cfg(hc_streams=1), _one_device_mesh()),
        ValueError, "hyper-connections mix two streams or more"),
    "values_narrower_without_latents": (
        lambda: DMoETransformerLM(
            _cfg(kv_latent_dim=None, q_latent_dim=None, rope_head_dim=None,
                 rope_scaling=None), _one_device_mesh()),
        NotImplementedError, "v_head_dim and rope_scaling belong to latent"),
    "scaled_frequencies_without_latents": (
        lambda: DMoETransformerLM(
            _cfg(kv_latent_dim=None, q_latent_dim=None, rope_head_dim=None,
                 v_head_dim=None), _one_device_mesh()),
        NotImplementedError, "v_head_dim and rope_scaling belong to latent"),
    "the_cached_decoder": (
        lambda: DMoETransformerLM(_cfg(), _one_device_mesh()).generate(
            None, jnp.zeros((1, 4), jnp.int32), 2, use_cache=True),
        NotImplementedError, "use_cache=True with hc_streams"),
}


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_a_combination_not_built_is_refused_by_name(name):
    build, error, words = REFUSALS[name]
    with pytest.raises(error, match=words):
        build()


def test_the_ring_refuses_scaled_frequencies_by_name():
    """``seq_parallel`` with ``rope_scaling``: the ring's own sentence, on a
    configuration that passes the ring's other refusals (plain projections
    would be refused first for the latents)."""
    from learning_at_home_tpu.models import transformer

    source = open(transformer.__file__).read()
    assert "with rope_scaling: the ring's core" in source
    with pytest.raises(NotImplementedError, match="seq_parallel=True"):
        DMoETransformerLM(
            _cfg(seq_parallel=True, hc_streams=None, mtp_layers=0,
                 mtp_loss_weight=0.0),
            make_mesh({"seq": 2}, devices=jax.devices()[:2]))


# ---- (e) the runner's limits ----


def test_the_block_as_it_is_reads_inside_the_runner_tolerances(tiny):
    read = limits.read(tiny)
    assert limits.outside(read) == []
    # the embedding, two layers, the block's combine, the block's layer
    assert len(read["embed_and_layers_rms"]) == 5
    assert len(read["stream_layers_rms"]) == len(read["hc_coeff_layers_rms"]) == 3
    assert len(read["near_tie_shares"]) == 3 and read["near_tie_shares"][0] == 0.0
    # both heads, the block's layer, its combine, two layers
    assert len(read["grad_stream_stages_rms"]) == 6
    assert read["step_read"] is True
    for name in runner.GRADIENT_READINGS:
        assert 0.0 <= read[name] < 1e-3, (name, read[name])


@pytest.mark.parametrize("name", sorted(runner.WRONG_PROGRAMS))
def test_a_wrong_program_fails_the_runner_tolerances(tiny, name):
    read = limits.read(tiny, **runner.WRONG_PROGRAMS[name])
    assert limits.outside(read), read


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    """The reference with float8 operands in the program's place reads
    outside the layer and logits limits."""
    read = limits.read(tiny, operand_dtype=jnp.float8_e4m3fn)
    assert limits.none_inside(read, "layers_rms", "stream_rms", "logits_rms",
                              "mtp_logits_rms", "grads_rms"), read
    assert limits.inside(read, "hc_coeff_rms")  # float32


def test_the_counters_limits_tell_a_wrong_residual_path():
    assert runner.hc_problems(
        {"hc_res_marginal_error": [5e-6, 2e-5], "hc_stream_rms_spread": [1.2]}) == []
    assert len(runner.hc_problems(
        {"hc_res_marginal_error": [3e-2], "hc_stream_rms_spread": [9.0]})) == 2
    assert len(runner.hc_problems({})) == 2  # a program without the counters
    assert len(runner.hc_problems({"hc_res_marginal_error": [float("nan")],
                                   "hc_stream_rms_spread": [1.0]})) == 1


# ---- (f) the benchmark's files ----


def test_flops_of_the_cell_are_the_issue_arithmetic():
    """6.52 GFLOP a token, the attention core about 46 % of it."""
    parts = xing4_flops.forward_flops_per_token(CELL_FILE)
    assert round(xing4_flops.train_flops_per_token(CELL_FILE) / 1e9, 2) == 6.52
    assert 0.45 < parts["attention_core"] / sum(parts.values()) < 0.47
    assert parts["hc_coefficients"] == 12 * 2 * 4 * 3584 * 24
    assert parts["dense_ffn"] == 6 * 3584 * 9216  # ONE dense layer is run
    assert xing4_flops.level_rows_per_token(CELL_FILE) == 2.0
    pairs = 16384 * 16385 // 2
    assert xing4_flops.attention_kernel_flops(
        CELL_FILE, 16384, "global", "forward") == 32 * pairs * 2 * (192 + 128)
    assert xing4_flops.attention_kernel_flops(
        CELL_FILE, 16384, "global", "backward") == 32 * pairs * 2 * (2 * 192 + 192)
    with pytest.raises(ValueError, match="window"):
        xing4_flops.attention_kernel_flops(CELL_FILE, 16384, "window", "forward")
    # the mixing: 43 C numbers a token a part, two bytes each; bandwidth-bound
    assert xing4_flops.hc_mix_bytes(CELL_FILE, 16384) == 12 * 16384 * 43 * 3584 * 2
    least = xing4_flops.hc_mix_least_seconds(CELL_FILE, 16384, "TPU v5 lite")
    assert least == pytest.approx(60.6e9 / 819e9, rel=1e-3)
    assert xing4_flops.hc_mix_flops(CELL_FILE, 16384) / 197e12 < 0.01 * least


def test_parameters_of_the_cell_are_the_issue_arithmetic():
    """2,234,679,428 parameters, from the recipe's shapes."""
    model, cfg, _, batch = xing4_0_29b_a4b_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    attention = (3584 * 768 + 768 + 768 * 6144 + 3584 * 576 + 512 + 512 * 8192
                 + 4096 * 3584)
    assert attention == 28_411_136
    hyper = 2 * (14336 * 24 + 24 + 3)
    dense, sparse = shapes["layers"][0], shapes["layers"][1]
    assert count(dense) == attention + 3 * 3584 * 9216 + 2 * 3584 + hyper
    outside = attention + 2 * 3584 + 3584 * 64 + 64 + 3 * 3584 * 1024 + hyper
    assert count(sparse) == outside + 32 * 3 * 3584 * 1024
    assert count(shapes["mtp"]) == count(sparse) + 7168 * 3584 + 3 * 3584
    assert count(shapes) == CELL_FILE["parameters"] == 2_234_679_428
    assert (cfg.seq_len, cfg.vocab_size, batch) == (16384, 16384, 1)
    assert 2 * count(shapes) > 0.25 * 16_909_334_528  # the guide's floor
    runner._check_sizes(CELL_FILE, cfg)


def test_configuration_file_carries_the_catalog_entry():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the two of ``reduced`` it has."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if json.loads(line)["name"] == "Xing4.0-29B-A4B")
    assert CELL_FILE["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CELL_FILE.get(k) != v}
    assert differs == {"n_routed_experts", "vocab_size"}
    assert CELL_FILE["reduced"] == ["n_layers", "n_routed_experts", "vocab_size"]
    assert (CELL_FILE["n_routed_experts_published"], CELL_FILE["vocab_size_published"],
            CELL_FILE["num_hidden_layers"], CELL_FILE["n_layers"]) == (
        64, 131072, 40, 5)
    assert CELL_FILE["n_routed_experts"] * CELL_FILE["chips_sharing_a_layers_experts"] == 64
    assert CELL_FILE["vocab_size"] * CELL_FILE["chips_sharing_the_vocabulary"] == 131072
    for words in ("NO learned scale", "COLUMNS FIRST", "SUMMED",
                  "hc_alpha_init 0.01", "mtp_loss_weight 0.3", "pair 10",
                  "[k_nope 128 | v 128]"):
        assert any(words in a for a in CELL_FILE["assumed"]), words


def test_reducers_read_this_cells_tables_and_nothing_where_there_is_none():
    """This cell's nine readings through the accepted reducers, each from
    the file of the entry the manifest reports it by; ``None`` (the metric
    is left out) where a program or a trace has nothing to read, as the
    parent commit has not."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "reducers"))
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    readings = readings_of_cell(manifest, CELL)
    assert len(readings) == 22

    obs = {"tokens_per_s_per_chip": 12000.0, "device_kind": "TPU v5 lite",
           "sizes": CELL_FILE, "tokens_per_step_per_chip": 16384,
           "local_rows_over_level": [1.0, 1.5], "intervals_s": [1.0, 2.0, 3.0],
           "trace": {"span_s": 10.0, "busy_s": 9.0},
           "scopes": {"grouped_matmul_s": 0.2, "grouped_matmul_calls": 60,
                      "total_s": 2.0, "mtp_s": 0.4, "attention_kernel_s": 1.0,
                      "by_scope": {"attention": 1.1, "latent_down": 0.1,
                                   "latent_up": 0.12, "rope": 0.03, "ce": 0.2,
                                   "hc/coeff": 0.05, "hc/sinkhorn": 0.03,
                                   "hc/pre": 0.04, "hc/post": 0.16, "hc": 0.02},
                      "attention_kernels": {
                          "global.forward": {"s": 0.4, "calls": 6},
                          "global.backward": {"s": 0.6, "calls": 12}}}}

    def read(reading, observations=obs):
        spec = layer_metric_file(manifest, readings[reading])
        reducer = harness.load_module(manifest, "reducers", spec["reducer"])
        return reducer.reduce(observations, **spec["args"])

    want = xing4_flops.train_flops_per_token(CELL_FILE, 1.25) * 12000 / 197e12
    assert read("mfu") == pytest.approx(100 * want)
    assert read("expert_matmul_roofline") == pytest.approx(
        100 * 60 * 2 * 32768 * 1.25 * 3584 * 1024 / (0.2 * 197e12))
    pairs = 32 * (16384 * 16385 // 2)
    assert read("attention_core_roofline") == pytest.approx(
        100 * (6 * pairs * 640 + 12 * pairs * 1152) / (1.0 * 197e12))
    assert read("mtp_share") == pytest.approx(20.0)
    assert read("attention_latent_share") == pytest.approx(12.5)
    assert read("hc_share") == pytest.approx(15.0)
    assert read("hc_coeff_share") == pytest.approx(4.0)
    assert read("hc_mix_share") == pytest.approx(10.0)
    step_s = 0.2 / 2.0 * 9.0 / 10.0 * 2.0
    assert read("hc_mix_roofline") == pytest.approx(
        100 * xing4_flops.hc_mix_least_seconds(CELL_FILE, 16384, "TPU v5 lite")
        / step_s)
    bare = dict(obs, scopes={"total_s": 2.0, "by_scope": {}})
    assert read("hc_mix_roofline", bare) is None
    assert read("mtp_share", bare) is None
    assert read("hc_share", bare) == 0.0
    assert read("hc_mix_roofline", dict(obs, scopes=None)) is None


def test_the_scope_table_takes_in_the_streams_scopes():
    """The runner's table over a made-up trace: the four ``hc`` scopes come
    out under their own names wherever the part lies (a layer, the
    prediction block, the backward pass), what else lies under ``hc`` (the
    copy, the sum) is ``hc``, and the attention's own time stays its."""
    import re
    import types

    latent = harness.load_path(os.path.join(
        REPO, "benchmarks", "runners", "train_recipe_latent.py"))
    base = types.SimpleNamespace(
        SCOPES=tuple((n, re.compile(r"[/(]%s[/)]" % n)) for n in (
            *runner.EXTRA_SCOPES, "experts", "attention", "ce")),
        GROUPED_MATMUL="ragged-dot", GROUPED_MATMUL_LAYOUT="ragged-dot-metadata")
    names = {"a": "jit(train_step)/layer_1/hc/coeff/dot_general",
             "b": "jit(train_step)/layer_1/checkpoint/hc/sinkhorn/div",
             "c": "jit(train_step)/transpose(jvp(layer_2))/hc/post/mul",
             "d": "jit(train_step)/mtp/layer_0/hc/pre/reduce_sum",
             "e": "jit(train_step)/hc/sum/reduce_sum",
             "f": "jit(train_step)/layer_1/attention/latent_up/dot_general",
             "g": "jit(train_step)/layer_1/attention/dot_general"}
    hlo = "\n".join(
        f'  %{n} = f32[] fusion(), metadata={{op_name="{p}"}}' for n, p in names.items())
    ops = [(n, i * 10, i * 10 + 1 + i) for i, n in enumerate(names)]
    table = latent._blocks_with_mtp().make_scope_times(base)(ops, hlo)
    by_scope = {k: round(v * 1e9) for k, v in table["by_scope"].items()}
    assert by_scope == {"hc/coeff": 1, "hc/sinkhorn": 2, "hc/post": 3,
                        "hc/pre": 4, "hc": 5, "latent_up": 6, "attention": 7}
    assert round(table["mtp_s"] * 1e9) == 4
