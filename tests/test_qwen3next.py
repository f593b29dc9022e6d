"""Qwen3-Next-80B-A3B-Instruct in the pod step as one chip's share
(``__graft_entry__.qwen3_next_one_chip``) against its plain reference
(``benchmarks/configs/qwen3_next_80b_a3b_reference.py``): gated delta-rule
layers with fewer key heads than value heads, three to every softmax layer
whose output is gated and whose heads are rotated in their first quarter,
norms of scale ``1 + w``, a mixture with a gated shared expert in every
layer, a share of the experts held; the refusals beside that path; the
cut's arithmetic; and the benchmark's files for it.

Tiny sizes on the CPU.  Each mixer alone against the reference's equations
(sections b, c, d) is ``tests/test_qwen3next_mixers.py``'s.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)
import qwen3next_flops  # noqa: E402

from __graft_entry__ import qwen3_next_one_chip  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.ops import delta_rule  # noqa: E402
from runner_limits import (  # noqa: E402
    decisive,
    one_device_mesh as _one_device_mesh,
)

REFERENCE = os.path.join(
    REPO, "benchmarks", "configs", "qwen3_next_80b_a3b_reference.py")
reference = harness.load_path(REFERENCE)
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_qwen3next.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "qwen3next-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "qwen3-next-80b-a3b.json"))
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference


def _decisive(params, seed=7):
    """Seeded weights under which every part of the stack decides: norm
    offsets off 0 and the plain scale off 1, routers that choose firmly,
    gates off one half."""
    return decisive(params, seed, drawn={"['offset']": 0.3}, scaled={
        "['moe']['gate']": 40.0, "['shared_gate']": 4.0})


@pytest.fixture(scope="module")
def tiny():
    model, cfg, _, batch = qwen3_next_one_chip(_one_device_mesh(), tiny=True)
    params = _decisive(model.init_params(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)
    return model, cfg, params, ids, tgt


def _compared(tiny, wrong=None):
    """The runner's comparison of the tiny stack, one row."""
    model, _, params, ids, tgt = tiny
    return runner.compare_with_reference(
        model, params, reference, TINY_FILE, ids[:1], tgt[:1], wrong=wrong)


@pytest.fixture(scope="module")
def sound(tiny):
    """The stack as it is, forward: the backward comparison at these sizes
    is the rehearsal's (``tests/test_benchmark_cells.py`` holds its four
    readings under 1e-4), not a second time here."""
    compare_gradients = runner.compare_gradients
    runner.compare_gradients = lambda *args: dict.fromkeys(
        runner.GRADIENT_READINGS, 0.0)
    try:
        return _compared(tiny)
    finally:
        runner.compare_gradients = compare_gradients


@pytest.fixture(scope="module")
def reference_loss_and_grads(tiny):
    _, _, params, ids, tgt = tiny
    return jax.jit(lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, atol=tol * max(np.abs(want).max(), 1e-6))


# ---- (a) the stack ----


def test_the_tiny_recipe_keeps_the_stack(tiny):
    """One period L L L F, two value heads a key head, four query heads a
    key/value head pair, a rotated quarter, both gates, offset norms but
    for the delta rule's own, a share of the experts in every layer."""
    model, cfg, params, _, _ = tiny
    kinds = [cfg.attention_layer(i).mixer for i in range(cfg.n_layers)]
    assert kinds == ["delta", "delta", "delta", "softmax"]  # one period
    assert cfg.delta_value_heads == 2 * cfg.n_heads
    assert cfg.n_heads == 2 * cfg.n_kv_heads and cfg.rotary_dim * 4 == cfg.head_dim
    assert cfg.mixture_layers() == cfg.n_layers and cfg.held_experts < cfg.num_experts
    assert not cfg.delta_neg_eigval and not cfg.router_bias
    d, hd = cfg.d_model, cfg.head_dim
    for i, lp in enumerate(params["layers"]):
        assert ("delta" in lp) == (kinds[i] == "delta")
        assert set(lp) >= {"ln1", "ln2", "moe", "shared", "shared_gate"}
        assert set(lp["ln1"]) == set(lp["ln2"]) == {"offset"}
        assert lp["shared_gate"].shape == (d, 1)
        assert lp["moe"]["gate"].shape == (d, cfg.num_experts)
        assert lp["moe"]["w_up"].shape[0] == cfg.held_experts
        assert "router_bias" not in lp["moe"]
    full = params["layers"][3]
    assert full["wq"].shape == (d, 2 * cfg.n_heads * hd)  # [query | gate] a head
    assert full["wo"].shape == (cfg.n_heads * hd, d)
    assert full["wk"].shape == (d, cfg.n_kv_heads * hd)
    assert full["q_norm"]["offset"].shape == full["k_norm"]["offset"].shape == (hd,)
    delta = params["layers"][0]["delta"]
    hk, hv = cfg.n_heads, cfg.delta_value_heads
    dk, dv = cfg.delta_key_dim, cfg.delta_value_dim
    assert delta["w_in"].shape == (d, 2 * hk * dk + 2 * hv * dv + 2 * hv)
    assert delta["conv_w"].shape == (2 * hk * dk + hv * dv, 4)
    assert delta["A_log"].shape == delta["dt_bias"].shape == (hv,)
    assert set(delta["gate_norm"]) == {"scale"}  # the plain form, scale 1
    assert set(params["ln_f"]) == {"offset"}
    fresh = model.init_params(jax.random.PRNGKey(1))
    assert not np.asarray(fresh["ln_f"]["offset"]).any()  # zero from the seed
    assert (np.asarray(fresh["layers"][0]["delta"]["gate_norm"]["scale"]) == 1).all()


def test_the_published_recipe_is_the_issue_arithmetic():
    """1,978,847,360 parameters, counted from the shapes; the in-projection
    [2048, 12352], wq [2048, 8192], decays float32, the buffer 40,960 rows."""
    from learning_at_home_tpu.ops.moe_dispatch import share_buffer_rows

    model, cfg, _, batch = qwen3_next_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert count == 1_978_847_360 and batch == 1
    delta = shapes["layers"][0]["delta"]
    assert delta["w_in"].shape == (2048, 12352) and delta["conv_w"].shape == (8192, 4)
    assert delta["A_log"].dtype == delta["dt_bias"].dtype == jnp.float32
    assert delta["w_in"].dtype == jnp.bfloat16
    assert shapes["layers"][3]["wq"].shape == (2048, 8192)
    assert shapes["layers"][3]["moe"]["w_up"].shape == (64, 2048, 512)
    assert shapes["lm_head"].shape == (2048, 18992)
    assert share_buffer_rows(16384, 10, 64, 512) == 40_960
    mixer = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(delta))
    assert mixer == 33_718_464  # the issue's count
    assert delta_rule.kernel_fits(
        (1, 16384, 32, 128), (1, 16384, 32, 128), cfg.delta_chunk, "tpu")


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
def test_the_whole_stack_matches_the_reference(
        tiny, reference_loss_and_grads, remat):
    """float32 on both sides, the tiny stack (one period): the loss and
    every leaf's gradient (relative to the leaf's own largest; the logits
    are the comparison's below, a block at a time).  What is left is the
    order of the sums (the chunked rule against the scan over positions,
    the chunked cross-entropy against the whole softmax, the sorted buffer
    against the scan over experts).  Remat changes no number."""
    model, cfg, params, ids, tgt = tiny
    model = DMoETransformerLM(dataclasses.replace(cfg, remat=remat), model.mesh)
    (loss, metrics), got = jax.jit(jax.value_and_grad(
        model.loss_fn, has_aux=True))(params, ids, tgt)
    want_loss, want = reference_loss_and_grads
    assert abs(float(loss) - float(want_loss)) < 2e-5
    assert float(metrics["dropped_fraction"]) == 0.0
    assert {"attention_gate_mean", "shared_gate_mean", "held_experts_empty",
            "delta_decay_min", "delta_beta_max"} <= set(metrics)
    assert 0.0 < float(metrics["delta_beta_max"]) <= 1.0  # no factor 2
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(path)
        _close(a, b, 1e-3)


def test_the_runners_plain_rule_is_the_optimizers_first_step():
    """``_first_step``, which the runner holds a step's change of each leaf
    to, against ``fused_adafactor`` from an empty state: a factored matrix,
    a factored stack of them, a vector, a leaf that starts at zero."""
    from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

    rs = np.random.RandomState(9)
    params = {"matrix": rs.randn(160, 256) * 0.02, "stack": rs.randn(3, 256, 160),
              "vector": rs.randn(64), "zero": np.zeros(48),
              "narrow": rs.randn(256, 8)}
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    grads = {k: jnp.asarray(rs.randn(*v.shape) * 1e-3, jnp.float32)
             for k, v in params.items()}
    optimizer = fused_adafactor(1e-4)
    want, _ = jax.jit(optimizer.apply_fused)(params, grads, optimizer.init(params))
    plain = jax.jit(lambda params, grads: {
        name: runner._first_step(p, grads[name], 1e-4)
        for name, p in params.items()})(params, grads)
    for name, p in params.items():
        got = plain[name]
        moved = np.abs(np.asarray(want[name] - p)).max()
        assert moved > 0, name
        np.testing.assert_allclose(
            np.asarray(got - p), np.asarray(want[name] - p), rtol=0,
            atol=1e-3 * moved, err_msg=name)


# ---- (e) the runner's comparison, and what must fail it ----


def _outside(read):
    return [k for k, limit in runner.TOLERANCES.items() if not read[k] <= limit]


@pytest.mark.parametrize("wrong, outside", [
    (None, []),
    ("no_attention_gate", ["attention_rms"]),
    ("no_shared_gate", ["layers_rms"]),
    ("hidden_twice_beta", ["hidden_token_median"]),
])
def test_the_runners_comparison_tells_a_missing_gate(tiny, sound, wrong, outside):
    """The stack as it is reads inside the runner's limits but for the
    guard of a comparison over 64 positions; a program without either gate
    falls outside the limit that names it, and so does a ``_hidden`` that
    composes another stack than the layers compared (write strengths of
    ``2 sigmoid(b)``).  A wrong program is read forward alone."""
    read = _compared(tiny, wrong) if wrong else sound
    assert set(outside) <= set(_outside(read)), read
    if not outside:
        assert _outside(read) in ([], ["near_tie_share"]), read
        assert len(read["delta_layers_rms"]) == 3 and len(read["attention_layers_rms"]) == 1
        assert max(read["router_logits_layers_rms"]) < 1e-5
    assert read["grads_rms"] == 0.0  # backward: the rehearsal's, not here


def test_the_configuration_file_and_the_program_must_agree(tiny):
    _, cfg, _, _, _ = tiny
    runner._check_sizes(TINY_FILE, cfg)
    for key, value in (("linear_num_value_heads", 2), ("partial_rotary_factor", 0.5),
                       ("num_experts", 8), ("full_attention_interval", 2),
                       ("shared_expert_gate", False)):
        with pytest.raises(harness.BenchError, match="disagree"):
            runner._check_sizes({**TINY_FILE, key: value}, cfg)


# ---- (f) refusals ----


def test_the_cached_decoder_refuses_the_block_by_name(tiny):
    model, cfg, params, ids, _ = tiny
    with pytest.raises(NotImplementedError, match="'delta' layer"):
        model.generate(params, ids[:, :4], 4, use_cache=True)
    no_delta = DMoETransformerLM(dataclasses.replace(
        cfg, layer_pattern=(cfg.layer_pattern[-1],)), model.mesh)
    with pytest.raises(NotImplementedError, match="attention_gate"):
        no_delta.generate(params, ids[:, :4], 4, use_cache=True)


@pytest.mark.parametrize("changes, match", [
    (dict(delta_value_heads=6), "multiple of the rule's 4 key heads"),
    (dict(qk_norm=True), "no norm over the whole queries"),
    (dict(shared_experts=0), "gates a shared expert"),
    (dict(norm="rmsnorm_plus"), "norm must be"),
])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(tiny, changes, match):
    _, cfg, _, _, _ = tiny
    with pytest.raises(ValueError, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), _one_device_mesh())


# ---- (g) the cut's arithmetic and the benchmark's files ----


def test_flops_of_the_cell_are_the_files_arithmetic():
    forward = qwen3next_flops.forward_flops_per_token(CELL_FILE)
    assert forward["delta_projections"] == 6 * 2 * 2048 * (12352 + 4096)
    assert forward["delta_recurrence"] == 6 * 6 * 32 * 128 * 128
    assert forward["projections"] == 2 * 2 * 2048 * (8192 + 4096 + 2 * 512)
    assert forward["routed_experts"] == 8 * 1.25 * 6 * 2048 * 512
    assert forward["head"] == 2 * 2048 * 18992
    assert qwen3next_flops.train_flops_per_token(CELL_FILE) == pytest.approx(3.0254e9, rel=1e-4)
    # q and k once a KEY head: 2 x 2048 channels, v and o 4096 each
    assert qwen3next_flops.delta_core_bytes(CELL_FILE, 1) == 6 * (
        2 * (4096 + 4096 + 4096) + 256 + 2 * 2 * (4096 + 4096) + 2 * 4096 + 512)
    assert qwen3next_flops.delta_core_least_seconds(
        CELL_FILE, 16384, "TPU v5 lite") == pytest.approx(7.958e-3, rel=1e-3)
    assert qwen3next_flops.rows_over_level({}) is None


def test_configuration_file_carries_the_catalog_entry():
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differ = {k for k, v in catalog.items() if CELL_FILE[k] != v}
    assert differ == {"num_experts", "vocab_size"} < set(CELL_FILE["reduced"])
    assert CELL_FILE["num_experts_published"] == catalog["num_experts"]
    assert CELL_FILE["vocab_size_published"] == catalog["vocab_size"]
    assert CELL_FILE["vocab_size"] * 8 == catalog["vocab_size"]
    assert CELL_FILE["chips_sharing_a_layers_experts"] == 8
    assert CELL_FILE["chips_sharing_the_vocabulary"] == 8
    assert CELL_FILE["not_built"] and CELL_FILE["assumed"]
    source = open(REFERENCE).read()
    assert "learning_at_home_tpu" not in source.replace(
        "imports nothing of the program", "")
    model, cfg, _, _ = qwen3_next_one_chip(_one_device_mesh())
    runner._check_sizes(CELL_FILE, cfg)
