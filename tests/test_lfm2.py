"""LFM2-8B-A1B in the pod step (``__graft_entry__.lfm2_8b_a1b_one_chip``)
against its plain reference (``benchmarks/configs/lfm2_8b_a1b_reference.py``):
a gated short convolution as the mixer of three layers in four, grouped
softmax attention with a norm over each head in the fourth, a dense leading
layer, sigmoid-routed experts with a selection bias ALL held, a tied head;
the refusals beside that path; the cut's arithmetic; and the benchmark's
files for it.

Tiny sizes on the CPU.  The kernel alone is ``tests/test_short_conv_kernel.py``'s.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)
import lfm2_flops  # noqa: E402

from __graft_entry__ import lfm2_8b_a1b_one_chip  # noqa: E402
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import (  # noqa: E402
    AttentionLayer,
    DMoETransformerLM,
)
from learning_at_home_tpu.ops import short_conv  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402
from runner_limits import (  # noqa: E402,F401  (``compiled_once`` is a fixture)
    compiled_once,
    decisive,
    Limits,
    one_device_mesh as _one_device_mesh,
)

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "lfm2_8b_a1b_reference.py"))
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_lfm2.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "lfm2-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "lfm2-8b-a1b.json"))
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference
limits = Limits(runner, reference, TINY_FILE)
pytestmark = pytest.mark.usefixtures("compiled_once")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _decisive(params, seed=7):
    """Seeded weights under which every part of the stack decides: norm
    scales off 1, routers that choose firmly, selection biases off 0."""
    return decisive(params, seed, drawn={"['router_bias']": 0.3},
                    scaled={"['moe']['gate']": 40.0})


@pytest.fixture(scope="module")
def tiny():
    model, cfg, _, batch = lfm2_8b_a1b_one_chip(_one_device_mesh(), tiny=True)
    params = _decisive(model.init_params(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)
    return model, cfg, params, ids, tgt


@pytest.fixture(scope="module")
def both(tiny):
    """``(logits, loss, gradients)`` of the program and of the reference,
    one compiled program a side."""
    model, _, params, ids, tgt = tiny

    @jax.jit
    def got(params):
        (loss, _), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(
            params, ids, tgt)
        return model.apply(params, ids)[0], loss, grads

    @jax.jit
    def want(params):
        loss, grads = reference.loss_and_grads(params, ids, tgt, SIZES)
        return reference.forward(params, ids, SIZES)[0], loss, grads

    return got(params), want(params)


# ---- (a) the stack against the reference ----


def test_the_recipe_is_the_stack_the_issue_names(tiny):
    _, cfg, params, _, _ = tiny
    assert [cfg.attention_layer(i).mixer for i in range(cfg.n_layers)] == [
        "conv", "softmax", "conv"]  # C A C at tiny sizes, C A C C C on the chip
    assert cfg.ffn_pattern == ("dense", "moe", "moe")
    assert [sorted(set(lp) & {"conv", "wq", "ffn", "moe", "q_norm"})
            for lp in params["layers"]] == [
        ["conv", "ffn"], ["moe", "q_norm", "wq"], ["conv", "moe"]]
    _, full, _, _ = lfm2_8b_a1b_one_chip(_one_device_mesh())
    assert [full.attention_layer(i).mixer for i in range(full.n_layers)] == [
        "conv", "softmax", "conv", "conv", "conv"]
    assert full.ffn_pattern == ("dense", "moe", "moe", "moe", "moe")
    assert set(params["layers"][0]["conv"]) == {"w_in", "conv_w", "w_out"}
    assert params["layers"][0]["conv"]["conv_w"].shape == (cfg.d_model, 3)
    assert "lm_head" not in params and cfg.held_experts is None


def test_logits_and_loss_match_the_reference(both):
    (got, got_loss, _), (want, want_loss, _) = both
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert abs(float(got_loss) - float(want_loss)) < 2e-5


def test_gradients_of_every_parameter_match_the_reference(tiny, both):
    """Every leaf, relative to the leaf's own largest gradient; the
    selection biases get none on either side."""
    (_, _, got), (_, _, want) = both
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == len(
        jax.tree_util.tree_leaves(tiny[2]))
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b)), name
            continue
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        assert float(jnp.abs(a - b).max()) < 1e-3 * scale, name


def test_the_step_reports_the_conv_mixers_output_and_drops_nothing(tiny):
    model, cfg, params, ids, tgt = tiny
    _, metrics = jax.jit(model.loss_fn)(params, ids, tgt)
    assert float(metrics["dropped_fraction"]) == 0.0
    assert metrics["expert_counts"].shape == (2, cfg.num_experts)
    assert int(metrics["expert_counts"].sum()) == 2 * ids.size * cfg.k
    # the smallest over the conv layers of the rms of what the mixer gave
    @jax.jit
    def by_layer(params):
        rms, x = [], params["embed"][ids].astype(cfg.dtype)
        for i, lp in enumerate(params["layers"]):
            if "conv" in lp:
                out = trunk.short_conv_mixer(lp["conv"], model._norm(lp["ln1"], x))
                rms.append(jnp.sqrt(jnp.mean(out.astype(jnp.float32) ** 2)))
            x = model._layer(lp, x, i, None, cfg.attention_layer(i))[0]
        return jnp.stack(rms)

    rms = by_layer(params)
    assert rms.shape == (2,)
    assert abs(float(metrics["shortconv_out_rms"]) - float(rms.min())) < 1e-5


def test_the_mixer_runs_the_kernel_form_to_the_same_numbers(tiny, monkeypatch):
    """``trunk.short_conv_mixer`` with the kernel (under ``interpret``) in
    the one rule's place: the program's numbers to float32 rounding."""
    model, cfg, params, ids, _ = tiny
    p = params["layers"][0]["conv"]
    x = model._norm(params["layers"][0]["ln1"],
                    params["embed"][ids].astype(cfg.dtype))
    want = jax.jit(trunk.short_conv_mixer)(p, x)
    monkeypatch.setattr(
        trunk, "gated_short_conv",
        lambda bcu, w: short_conv.gated_short_conv_kernel(bcu, w, interpret=True))
    got = jax.jit(trunk.short_conv_mixer)(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_routers_level_with_every_expert_held(tiny):
    """``level_router_bias`` on a stack whose mixers are conv and attention
    layers and whose routers hold all their experts: both mixture layers,
    each no less level than before, the dense layer passed through."""
    model, cfg, params, ids, _ = tiny
    fresh = model.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)  # a few ids hold most of a row, as Zipf's do
    pool = [jnp.asarray(np.minimum(rng.zipf(1.5, ids.shape) - 1,
                                   cfg.vocab_size - 1), jnp.int32)
            for _ in range(4)]
    levelled, loads = model.level_router_bias(fresh, pool)
    assert len(loads) == 2
    for before, after in loads:
        assert before > 1.2 and after < 1.05, loads
    biases = [lp["moe"]["router_bias"] for lp in levelled["layers"] if "moe" in lp]
    assert len(biases) == 2 and any(np.any(np.asarray(b)) for b in biases)
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)),
        levelled["layers"][0], fresh["layers"][0])
    assert all(jax.tree_util.tree_leaves(same))


# ---- (b) refusals ----


@pytest.mark.parametrize("changes, devices, error, match", [
    ({}, 2, NotImplementedError, "'conv' layer on a mesh of several chips"),
    ({"norm_place": "output"}, 1, NotImplementedError, "'conv' layer"),
    ({"layer_pattern": (AttentionLayer(None, False, "linear"),)}, 1, ValueError,
     "'delta' or 'conv'"),
    ({"ffn_pattern": None, "mixer_pattern": ("attention", "moe", "moe")},
     1, ValueError, "'conv' layer"),
    ({"objective": "block_diffusion"}, 1, NotImplementedError, "'conv' layer"),
])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(
        tiny, changes, devices, error, match):
    _, cfg, _, _, _ = tiny
    mesh = make_mesh({"expert": devices}, devices=jax.devices()[:devices])
    with pytest.raises(error, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), mesh)


def test_the_ring_refuses_the_conv_layer_by_name(tiny):
    _, cfg, _, _, _ = tiny
    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="'conv' layer"):
        DMoETransformerLM(dataclasses.replace(cfg, seq_parallel=True), mesh)


def test_the_cached_decoder_refuses_the_conv_layer_by_name(tiny):
    model, _, params, ids, _ = tiny
    with pytest.raises(NotImplementedError, match="'conv' layer"):
        model.generate(params, ids[:, :4], 4, use_cache=True)
    out = model.generate(params, ids[:, :4], 3)  # the re-forward path runs it
    assert out.shape == (ids.shape[0], 7)


# ---- (c) the cut's arithmetic and the benchmark's files ----


def _parameters(cfg) -> int:
    shapes = jax.eval_shape(
        DMoETransformerLM(cfg, _one_device_mesh()).init_params,
        jax.random.PRNGKey(0))
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))


def test_the_cut_and_the_whole_model_count_what_the_issue_counts():
    _, cfg, _, batch = lfm2_8b_a1b_one_chip(_one_device_mesh())
    assert batch == 1 and _parameters(cfg) == 1_564_784_896 == CELL_FILE["parameters"]
    kinds = {"conv": AttentionLayer(None, False, "conv"),
             "full_attention": AttentionLayer(None, True)}
    types = CELL_FILE["layer_types"]
    whole = dataclasses.replace(
        cfg, n_layers=len(types), vocab_size=CELL_FILE["vocab_size_published"],
        layer_pattern=tuple(kinds[t] for t in types),
        ffn_pattern=("dense",) * CELL_FILE["num_dense_layers"] + ("moe",) * (
            len(types) - CELL_FILE["num_dense_layers"]))
    assert types.count("conv") == 18 and len(types) == 24
    assert _parameters(whole) == 8_339_930_560 == CELL_FILE[
        "parameters_of_the_whole_model"]


def test_the_configuration_file_keeps_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert CELL_FILE["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CELL_FILE.get(k) != v}
    assert differs == {"vocab_size"}
    assert CELL_FILE["reduced"] == ["n_layers", "vocab_size"]
    assert CELL_FILE["vocab_size"] * 4 == CELL_FILE["vocab_size_published"]
    assert runner.layers_run(CELL_FILE) == [
        ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse"),
        ("conv", "sparse"), ("conv", "sparse")]
    for word in ("tied", "1e-6", "lecun", "16,384 of max_position", "4 bytes"):
        assert any(word in line for line in CELL_FILE["assumed"]), word


def test_the_runner_fails_where_file_and_program_differ(tiny):
    _, cfg, _, _, _ = tiny
    runner._check_sizes(TINY_FILE, cfg)
    for key, value in (("parameters", 1), ("conv_L_cache", 4),
                       ("num_dense_layers", 1), ("first_layer", 0)):
        with pytest.raises(harness.BenchError, match="disagree"):
            runner._check_sizes({**TINY_FILE, key: value}, cfg)


def test_the_counted_work_is_the_issues_arithmetic():
    parts = lfm2_flops.forward_flops_per_token(CELL_FILE)
    matrices = sum(v for k, v in parts.items()
                   if k not in ("attention_core", "shortconv_core")) / 2
    assert round(matrices / 1e6, 1) == 331.6
    assert round(parts["attention_core"] / 2e6, 1) == 33.6
    assert round(parts["routed_experts"] / 2 / matrices, 2) == 0.53
    assert round(parts["shortconv_projections"] / 2 / matrices, 2) == 0.20
    # forward and backward with remat's second forward: the issue's 47.8 TFLOP
    step = lfm2_flops.train_flops_per_token(CELL_FILE) * CELL_FILE["seq_len"]
    assert round(step * 4 / 3 / 1e12, 1) == 47.9
    least = lfm2_flops.shortconv_core_least_seconds(
        CELL_FILE, CELL_FILE["seq_len"], "TPU v5 lite")
    assert round(least * 1e3, 2) == 3.61  # 2.95 GB at 819 GB/s: the bytes bound


@pytest.mark.parametrize("wrong", [
    how["wrong"] for how in runner.WRONG_PROGRAMS.values()
    if how["wrong"] != "half_loss"])  # 15 s more for the step's second fault
def test_a_wrong_program_falls_outside_a_limit(tiny, wrong):
    """Each of the runner's named faults (but the step on half the loss: the
    chip's probe runs it, and ``frozen_leaf`` takes the same path through
    the timed step here), in the program's place in the runner's own
    comparison at the tiny size, is outside at least one tolerance; the
    program itself is inside all (the rehearsal holds that)."""
    read = limits.read(tiny, wrong=wrong)
    assert limits.outside(read), read
