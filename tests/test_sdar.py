"""SDAR-30B-A3B-Chat in the pod step, trained by block diffusion, as one
chip's share (``__graft_entry__.sdar_one_chip``) against its plain reference
(``benchmarks/configs/sdar_30b_a3b_reference.py``): the noising, the doubled
row, the weighted loss in place over the noised half, every leaf's gradient;
the chunked cross-entropy with weights; the four shares that add up; the
runner's comparison and the six programs that must fail it; the refusals;
the cut's arithmetic; and the benchmark's files for it.

Tiny sizes on the CPU.  The mask itself is ``tests/test_sdar_mask.py``'s.
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
import sdar_flops  # noqa: E402

from __graft_entry__ import sdar_one_chip  # noqa: E402
from learning_at_home_tpu.models import transformer  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts  # noqa: E402
from runner_limits import (  # noqa: E402,F401  (``compiled_once`` is a fixture)
    compiled_once,
    decisive,
    Limits,
    one_device_mesh as _one_device_mesh,
)

REFERENCE = os.path.join(REPO, "benchmarks", "configs", "sdar_30b_a3b_reference.py")
reference = harness.load_path(REFERENCE)
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_sdar.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "sdar-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "sdar-30b-a3b.json"))
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference
limits = Limits(runner, reference, TINY_FILE, with_targets=False)
pytestmark = pytest.mark.usefixtures("compiled_once")


def _decisive(params, seed=7):
    """Seeded weights under which every part of the stack decides: norm
    scales off 1, routers that choose firmly."""
    return decisive(params, seed, scaled={"['moe']['gate']": 40.0})


@pytest.fixture(scope="module")
def tiny():
    model, cfg, _, batch = sdar_one_chip(_one_device_mesh(), tiny=True)
    params = _decisive(model.init_params(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    ids = jnp.asarray(
        rng.integers(0, cfg.vocab_size - 1, (batch, cfg.seq_len)), jnp.int32)
    return model, cfg, params, ids, jax.random.key(3)


@pytest.fixture(scope="module")
def reference_loss_and_grads(tiny):
    """ONE compiled reference for the module: loss and every leaf's
    gradient under the draws the program's key gives."""
    model, _, params, ids, key = tiny
    u, t = model.noise_draws(key, ids.shape[0])
    return jax.jit(lambda p: reference.loss_and_grads(p, ids, u, t, SIZES))(params)


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, atol=tol * max(np.abs(want).max(), 1e-6))


# ---- (a) the stack, the noising, the loss ----


def test_the_tiny_recipe_keeps_the_block(tiny):
    model, cfg, params, _, _ = tiny
    assert cfg.objective == "block_diffusion" and cfg.diffusion_block == 4
    assert cfg.n_heads == 2 * cfg.n_kv_heads and cfg.qk_norm == "head"
    assert cfg.mixture_layers() == cfg.n_layers and cfg.held_experts < cfg.num_experts
    assert not cfg.router_bias and not cfg.shared_experts and not cfg.tie_embeddings
    for lp in params["layers"]:
        assert set(lp) == {"ln1", "ln2", "moe", "wq", "wk", "wv", "wo",
                           "q_norm", "k_norm"}
        assert lp["moe"]["gate"].shape == (cfg.d_model, cfg.num_experts)
        assert lp["moe"]["w_up"].shape[0] == cfg.held_experts
        assert lp["q_norm"]["scale"].shape == (cfg.head_dim,)
    assert "pos" not in params  # rotary positions: no table of seq_len rows


def test_the_published_recipe_is_the_issue_arithmetic():
    """1,516,670,976 parameters, counted from the shapes; a step's tokens
    are the 8,192 data tokens of its one row; the buffer twice the level
    share of 16,384 positions."""
    from learning_at_home_tpu.ops.moe_dispatch import share_buffer_rows

    model, cfg, _, batch = sdar_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert count == 1_516_670_976 and batch == 1 and cfg.seq_len == 8192
    layer = shapes["layers"][0]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(layer)) == 170_135_808
    assert layer["wq"].shape == (2048, 4096) and layer["wk"].shape == (2048, 512)
    assert layer["moe"]["w_up"].shape == (32, 2048, 768)
    assert layer["moe"]["gate"].shape == (2048, 128)
    assert shapes["lm_head"].shape == (2048, 37984)
    assert share_buffer_rows(16384, 8, 32, 128) == 65_536


def test_the_noising_is_the_references_restatement(tiny):
    """From the same draws: the same doubled row, the same weights; a block
    shares one ``p``; the mask id is the vocabulary's last."""
    model, cfg, _, ids, key = tiny
    u, t = model.noise_draws(key, ids.shape[0])
    assert u.shape == ids.shape and t.shape == (ids.shape[0], cfg.seq_len // 4)
    row, weights = jax.jit(model.noised_row)(ids, u, t)
    want_row, want_weights = reference.noised_row(ids, u, t, SIZES)
    assert (np.asarray(row) == np.asarray(want_row)).all()
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_weights), rtol=1e-6)
    row, weights = np.asarray(row), np.asarray(weights)
    s = cfg.seq_len
    assert (row[:, s:] == np.asarray(ids)).all()  # the clean copy, second
    masked = weights > 0
    assert (row[:, :s][masked] == cfg.vocab_size - 1).all()
    assert (row[:, :s][~masked] == np.asarray(ids)[~masked]).all()
    assert 0 < masked.mean() < 1
    by_block = weights.reshape(ids.shape[0], -1, 4)  # one p a block
    assert ((by_block == 0) | (by_block == by_block.max(-1, keepdims=True))).all()
    assert weights[masked].min() >= 1.0 and weights.max() <= 1.0 / transformer.DIFFUSION_P_FLOOR
    # everything masked where the draws say so, nothing where they cannot
    all_row, all_w = model.noised_row(ids, jnp.zeros_like(u), jnp.ones_like(t))
    assert (np.asarray(all_row)[:, :s] == cfg.vocab_size - 1).all()
    assert (np.asarray(all_w) == 1.0).all()


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
def test_the_whole_stack_matches_the_reference(
        tiny, reference_loss_and_grads, remat):
    """float32 on both sides: the weighted loss and every leaf's gradient
    (relative to the leaf's own largest).  What is left is the order of the
    sums (the chunked cross-entropy against the whole softmax, the sorted
    buffer against the scan over experts).  Remat changes no number."""
    model, cfg, params, ids, key = tiny
    model = DMoETransformerLM(dataclasses.replace(cfg, remat=remat), model.mesh)
    (loss, metrics), got = jax.jit(jax.value_and_grad(
        model.loss_fn, has_aux=True))(params, ids, ids, key)
    want_loss, want = reference_loss_and_grads
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    assert float(metrics["dropped_fraction"]) == 0.0
    assert {"masked_share", "loss_weight_mean", "attention_admitted_pairs",
            "attention_visited_pairs", "held_experts_empty"} <= set(metrics)
    s = cfg.seq_len
    assert float(metrics["attention_admitted_pairs"]) == 16 * (s // 4) * (s // 4 + 1)
    assert float(metrics["attention_visited_pairs"]) == (2 * s) ** 2  # the xla core
    assert float(metrics["loss_weight_mean"]) >= 1.0
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(path)
        _close(a, b, 1e-3)


def test_the_logits_of_the_noised_half_match_the_reference(tiny):
    model, cfg, params, ids, key = tiny
    u, t = model.noise_draws(key, ids.shape[0])
    row, _ = model.noised_row(ids, u, t)
    got = jax.jit(lambda p, row: model.apply(p, row)[0])(params, row)
    want, _, _, _ = jax.jit(lambda p: reference.forward(p, ids, u, t, SIZES))(params)
    _close(got[:, : cfg.seq_len], want, 1e-4)


def test_a_train_step_draws_fresh_noise_and_counts_data_tokens(tiny):
    """Two steps on one batch mask different tokens (the optimizer's count
    is folded into the key); two batches at one count differ too; the
    targets are not read; and nothing of the key comes from the host."""
    model, cfg, params, ids, _ = tiny
    _, _, optimizer, _ = sdar_one_chip(_one_device_mesh(), tiny=True)
    state = model.init_opt_state(optimizer, params)
    key0 = model.noise_key(state, ids)
    assert (jax.random.key_data(key0) != jax.random.key_data(
        model.noise_key(state, ids[::-1]))).any()
    step = model.make_train_step(optimizer)
    copy = jax.tree_util.tree_map(jnp.copy, params)
    p1, state1, loss0, m0 = step(copy, state, ids, jnp.zeros_like(ids))
    assert (jax.random.key_data(key0) != jax.random.key_data(
        model.noise_key(state1, ids))).any()
    _, _, loss1, m1 = step(p1, state1, ids, ids)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(m0["masked_share"]) != float(m1["masked_share"]) or (
        float(m0["loss_weight_mean"]) != float(m1["loss_weight_mean"]))
    with pytest.raises(ValueError, match="noise_key"):
        model.loss_fn(params, ids, ids)
    with pytest.raises(NotImplementedError, match="accum_steps"):
        model.make_train_step(optimizer, accum_steps=2)


# ---- (b) the chunked cross-entropy with weights ----


@pytest.fixture(scope="module")
def ce_case():
    rs = np.random.RandomState(2)
    n, d, v = 80, 16, 40  # chunks of 32: two and a remainder of 16
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    head = jnp.asarray(rs.randn(d, v) / 4, jnp.float32)
    targets = jnp.asarray(rs.randint(0, v, n), jnp.int32)
    weights = jnp.asarray(
        np.where(rs.rand(n) < 0.5, 1.0 / rs.uniform(0.01, 1.0, n), 0.0), jnp.float32)
    return x, head, targets, weights


def test_the_chunked_ce_with_weights_is_the_plain_weighted_ce(ce_case):
    x, head, targets, weights = ce_case

    def plain(x, head):
        logp = jax.nn.log_softmax(x @ head, axis=-1)
        ces = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(ces * weights) / x.shape[0]

    def chunked(x, head):
        return transformer._ce_of_chunks(
            x, head, targets, 32, x.shape[0], False, weights)

    want, (want_x, want_head) = jax.jit(jax.value_and_grad(plain, (0, 1)))(x, head)
    got, (got_x, got_head) = jax.jit(jax.value_and_grad(chunked, (0, 1)))(x, head)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert abs(float(jax.jit(chunked)(x, head)) - float(want)) < 1e-5 * abs(float(want))
    _close(got_x, want_x, 1e-5)
    _close(got_head, want_head, 1e-5)
    unmasked = np.asarray(weights) == 0
    assert not np.asarray(got_x)[unmasked].any()  # weight 0: no gradient at all
    _, back = jax.vjp(lambda w: transformer._ce_of_chunks(
        x, head, targets, 32, x.shape[0], False, w), weights)
    assert not np.asarray(back(jnp.float32(1.0))[0]).any()  # none to the weights


def test_weights_of_one_are_the_unweighted_path_to_the_bit(ce_case):
    x, head, targets, _ = ce_case
    ones = jnp.ones(x.shape[0], jnp.float32)

    def both(weights):
        return jax.jit(jax.value_and_grad(
            lambda x, head: transformer._ce_of_chunks(
                x, head, targets, 32, x.shape[0], False, weights), (0, 1)))(x, head)

    (a, (ax, ah)), (b, (bx, bh)) = both(None), both(ones)
    assert float(a) == float(b)
    assert (np.asarray(ax) == np.asarray(bx)).all()
    assert (np.asarray(ah) == np.asarray(bh)).all()


# ---- (c) the shares add up ----


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts all 4 shares give (each its own 8 of the 32
    experts, through the program's share path: 32 + 32 + 32 + 32 of 128 in
    the deployment) equal the uncut reference's layer; so do the
    reference's own shares.  No share's buffer overflows and none
    renormalises over the experts it holds."""
    rs = np.random.RandomState(5)
    d, f, experts, held, k, n = 32, 16, 32, 8, 8, 128

    def w(*shape):
        return jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)

    moe = {"gate": w(d, experts), "w_gate": w(experts, d, f),
           "w_up": w(experts, d, f), "w_down": w(experts, f, d)}
    lp = {"ln2": {"scale": jnp.asarray(rs.uniform(0.5, 1.5, d), jnp.float32)},
          "moe": moe}
    h = jnp.asarray(rs.randn(1, n, d), jnp.float32)
    sizes = dict(SIZES, experts_per_token=k, held=None)
    want, _, _ = reference.ffn_part(lp, h, sizes)
    m = reference.norm(h, lp["ln2"], sizes["norm_eps"]).reshape(-1, d)

    def share_of(first):
        cut = {name: moe[name][first:first + held]
               for name in ("w_gate", "w_up", "w_down")}
        return {**moe, **cut}

    total = ref_total = jnp.zeros_like(m)
    for j in range(experts // held):
        share = ShardedMixtureOfExperts(
            _one_device_mesh(), hidden_dim=d, num_experts=experts, k=k,
            dtype=jnp.float32, ffn_dim=f, expert_kind="gated_silu",
            routing="dropless", held_experts=held, first_held_expert=j * held)
        part, aux = jax.jit(share)(share_of(j * held), m)
        assert float(aux["dropped_fraction"]) == 0.0, j
        total = total + part
        ref_total = ref_total + reference.routed_part(
            share_of(j * held), m, dict(sizes, held=(j * held, held)))
    gates = reference.router(moe, m, sizes)[3]
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    scale = np.abs(np.asarray(want - h)).max()
    for got in (total, ref_total):
        np.testing.assert_allclose(
            np.asarray(h + got.reshape(h.shape)), np.asarray(want), rtol=0,
            atol=1e-5 * scale)


# ---- (d) the runner's comparison, and what must fail it ----


def test_the_runners_comparison_passes_the_program(tiny):
    read = limits.read(tiny)
    assert limits.outside(read) == [], read
    assert read["noise_mismatches"] == 0.0
    assert len(read["attention_layers_rms"]) == 2
    assert len(read["grad_stream_layers_rms"]) == 3  # embedding, two layers
    assert max(read["router_logits_layers_rms"]) < 1e-5
    for name in runner.GRADIENT_READINGS:
        assert 0.0 <= read[name] < 1e-4, (name, read[name])


@pytest.mark.parametrize("name, outside", [
    ("the program under a causal mask over the doubled row", "attention_rms"),
    ("the program whose noised blocks see their own clean block", "attention_rms"),
    ("the program with positions 0..2S-1", "attention_rms"),
    ("the loss without 1/p", "loss"),
    ("the loss on shifted targets", "loss"),
    ("the head over the clean half", "loss"),
    ("the program, its router's logits in bfloat16", "router_logits_rms"),
    ("the step at twice its learning rate", "update_total"),
])
def test_each_wrong_program_reads_outside_a_named_limit(tiny, name, outside):
    """Each of the runner's ``WRONG_PROGRAMS``, read in the program's place
    at the tiny size: outside the limit that names its fault."""
    read = limits.read(tiny, **runner.WRONG_PROGRAMS[name])
    assert outside in limits.outside(read), read
    if outside == "loss":  # its step is held to the chain too
        assert {"step_grad_norms", "update_norm"} & set(limits.outside(read)), read


def test_the_wrong_programs_are_the_issues_six_and_the_routers():
    assert len(runner.WRONG_PROGRAMS) == 8
    with pytest.raises(harness.BenchError, match="no wrong program"):
        runner._wrong_program(
            sdar_one_chip(_one_device_mesh(), tiny=True)[0], "another")


def test_the_configuration_file_and_the_program_must_agree(tiny):
    _, cfg, _, _, _ = tiny
    runner._check_sizes(TINY_FILE, cfg)
    for key, value in (("block_length", 2), ("mask_token_id", 0), ("p_floor", 0.01),
                       ("num_experts", 8), ("objective", "next_token"),
                       ("positions_run", 32)):
        with pytest.raises(harness.BenchError, match="disagree"):
            runner._check_sizes({**TINY_FILE, key: value}, cfg)


def test_the_set_up_takes_out_the_shared_component_and_places_the_experts(tiny):
    """``route_like_a_trained_model``: each gate is a column permutation of
    the gate less its component along one direction, nothing else of the
    tree moves, and the chip's rows come to the level share."""
    model, cfg, params, ids, _ = tiny
    pool = [ids, ids[::-1], (ids + 1) % (cfg.vocab_size - 1)]
    new, loads = runner.route_like_a_trained_model(model, params, pool)
    assert len(loads) == cfg.n_layers
    assert all(abs(after - 1.0) <= max(abs(before - 1.0), 0.05) + 1e-9
               for before, after, _, _ in loads), loads
    assert all(0.8 < after < 1.2 for _, after, _, _ in loads), loads
    for old_lp, new_lp in zip(params["layers"], new["layers"]):
        old_gate, new_gate = np.asarray(old_lp["moe"]["gate"]), np.asarray(new_lp["moe"]["gate"])
        change = old_gate[:, None, :] - new_gate[:, :, None]  # [d, new, old]
        # every new column is an old column less its part along a few directions
        matched = np.abs(change).sum(0).argmin(axis=1)
        assert sorted(matched) == list(range(cfg.num_experts))  # a permutation
        rest = old_gate[:, matched] - new_gate
        assert np.linalg.matrix_rank(
            rest, tol=1e-5 * np.abs(old_gate).max()) == runner.SHARED_DIRECTIONS
        for name in ("w_gate", "w_up", "w_down"):
            assert new_lp["moe"][name] is old_lp["moe"][name]
        assert new_lp["wq"] is old_lp["wq"]
    assert new["embed"] is params["embed"] and new["lm_head"] is params["lm_head"]


def test_the_window_checks_hold_every_step():
    fine = {"dropped_fraction": [0.0, 0.0], "masked_share": [0.47, 0.52]}
    assert runner.share_problems(fine) == []
    assert "masked_share" in runner.share_problems(
        {**fine, "masked_share": [0.5, 0.56]})[0]
    assert "overflowed" in runner.share_problems(
        {**fine, "dropped_fraction": [0.0, 1e-3]})[0]


# ---- (e) refusals ----


def test_generate_refuses_the_objective_by_name(tiny):
    model, _, params, ids, _ = tiny
    for use_cache in (False, True):
        with pytest.raises(NotImplementedError, match="block_diffusion"):
            model.generate(params, ids[:, :4], 4, use_cache=use_cache)


@pytest.mark.parametrize("changes, error, match", [
    (dict(positions="learned"), NotImplementedError, "no learned positions"),
    (dict(mtp_layers=1, mtp_loss_weight=0.3), NotImplementedError, "next-but-one"),
    (dict(diffusion_block=5), ValueError, "must divide"),
    (dict(seq_parallel=True), NotImplementedError, "no ring"),
    (dict(objective="masked_lm"), ValueError, "objective must be"),
])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(
        tiny, changes, error, match):
    _, cfg, _, _, _ = tiny
    with pytest.raises(error, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), _one_device_mesh())


# ---- (f) the cut's arithmetic and the benchmark's files ----


def test_flops_of_the_cell_are_the_files_arithmetic():
    forward = sdar_flops.forward_flops_per_token(CELL_FILE)
    pairs = 16 * 2048 * 2049  # L'^2 nb (nb + 1)
    assert sdar_flops.admitted_pairs(CELL_FILE) == pairs == 67_141_632
    assert 4 * pairs == pytest.approx(16384 ** 2, rel=1e-3)  # a quarter of (2 S)^2
    # the last layer's clean queries feed nothing: 7.5 of 8 layers' pairs
    assert sdar_flops.loss_pairs_per_layer(CELL_FILE) == pairs * 7.5 / 8
    assert forward["attention_core"] == 7.5 * 4 * 128 * 32 * pairs / 8192
    assert forward["projections"] == 15 * 4 * 2048 * 4096 + 16 * 4 * 2048 * 512
    assert forward["routed_experts"] == 15 * 2 * 6 * 2048 * 768
    assert forward["head"] == 2 * 2048 * 37984  # the noised half alone
    assert sdar_flops.train_flops_per_token(CELL_FILE) == pytest.approx(6.0723e9, rel=1e-4)
    # a call of the kernel, forward: 4 x 128 x the pairs, 32 heads
    assert sdar_flops.attention_kernel_flops(
        CELL_FILE, 8192, "global", "forward") == 32 * pairs * 7.5 / 8 * 2 * 128 * 2
    assert sdar_flops.counted_rows(CELL_FILE, 8192, 1.0) == 32768
    assert sdar_flops.rows_over_level({}) is None


def test_configuration_file_carries_the_catalog_entry():
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differ = {k for k, v in catalog.items() if CELL_FILE[k] != v}
    assert differ == {"num_experts", "vocab_size"} < set(CELL_FILE["reduced"])
    assert CELL_FILE["num_experts_published"] == catalog["num_experts"]
    assert CELL_FILE["vocab_size_published"] == catalog["vocab_size"]
    assert CELL_FILE["vocab_size"] * 4 == catalog["vocab_size"]
    assert CELL_FILE["chips_sharing_a_layers_experts"] == 4
    assert CELL_FILE["chips_sharing_the_vocabulary"] == 4
    assert CELL_FILE["seq_len"] * 2 == CELL_FILE["positions_run"] == 16384
    assert CELL_FILE["mask_token_id"] == CELL_FILE["vocab_size"] - 1
    assert any("generation" in line for line in CELL_FILE["not_built"])
    for word in ("block_length 4", "noise schedule", "in-place", "mask_token_id",
                 "router_aux_loss_coef"):
        assert any(word in line for line in CELL_FILE["assumed"]), word
    source = open(REFERENCE).read()
    assert "learning_at_home_tpu" not in source
    model, cfg, _, _ = sdar_one_chip(_one_device_mesh())
    runner._check_sizes(CELL_FILE, cfg)
    assert runner.data_vocab(cfg) == 37983
