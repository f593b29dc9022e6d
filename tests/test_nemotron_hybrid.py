"""Nemotron-Labs-TwoTower's hybrid tower in the pod step as one chip's share
(``__graft_entry__.nemotron_labs_twotower_one_chip``) against its plain
reference (``benchmarks/configs/nemotron_labs_twotower_30b_a3b_reference.py``):
the Mamba-2 state-space mixer in its chunked form against the recurrence a
position at a time, layers that are ONE mixer each, un-gated squared-ReLU
experts on the share; the refusals beside that path; the cut's arithmetic;
and the benchmark's files for it.

Tiny sizes on the CPU.  Here: the stack against its reference, the cut's
arithmetic and the benchmark's files; what must fail the runner's comparison
is ``tests/test_nemotron_hybrid_comparison.py``'s, the share, the refusals and the tiles are ``tests/test_nemotron_hybrid_share.py``'s,
the AOT compiles at published widths for a described (not attached) ``v5e``
chip ``tests/test_nemotron_hybrid_chip.py``'s.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)
import nemotron_flops  # noqa: E402

from __graft_entry__ import nemotron_labs_twotower_one_chip  # noqa: E402
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.ops import gate_norm  # noqa: E402
from learning_at_home_tpu.ops.ssd import ssd_chunked  # noqa: E402
from runner_limits import (  # noqa: E402
    close as _close,
    decisive,
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)

REFERENCE = os.path.join(
    REPO, "benchmarks", "configs", "nemotron_labs_twotower_30b_a3b_reference.py")
reference = harness.load_path(REFERENCE)
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_hybrid.py"))
probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "nemotron-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "nemotron-labs-twotower-30b-a3b.json"))
CELL = "nemotron-labs-twotower-30b-a3b-train-zipf16k"
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference


def _decisive(params, seed=7):
    """Seeded weights under which every part of the stack decides: a router
    that decides (the program's init gives near-equal scores), selection
    biases off zero, norm scales, ``D`` and the convolution's bias off
    their initial values."""
    return decisive(
        params, seed, spread=("['scale']", "['D']"),
        drawn={"['router_bias']": 0.2, "['conv_b']": 0.3}, scaled={"['gate']": 20.0})


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(nemotron_labs_twotower_one_chip, _decisive)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's float32 logits, loss and gradients on the tiny
    weights, each one compiled program, once a module."""
    _, _, params, ids, tgt = tiny
    logits = jax.jit(lambda p: reference.forward(p, ids, SIZES)[0])(params)
    loss, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)
    return np.asarray(logits), float(loss), grads


def _mixer(cfg, lp, x, chunk=None):
    """The program's mixer on the stream ``x``: output, state, least decay."""
    return trunk.ssm_mixer(
        lp["ssm"], trunk.rms_norm(lp["norm"], x, cfg.norm_eps), cfg.ssm_heads,
        cfg.ssm_groups, chunk or cfg.ssm_chunk, cfg.norm_eps)


# ---- (a) the program against the reference ----


def test_the_tiny_recipe_keeps_the_stack(tiny):
    """What ``tiny`` must keep of the published stack, and the rehearsal
    file's sizes are the tiny recipe's (the runner's own check)."""
    _, cfg, params, _, _ = tiny
    assert cfg.mixer_pattern == (
        "ssm", "moe", "ssm", "moe", "ssm", "attention", "moe", "ssm", "moe")
    assert cfg.mixture_layers() == 4
    assert cfg.held_experts < cfg.num_experts and cfg.k < cfg.num_experts
    assert cfg.ssm_heads > cfg.ssm_groups > 1  # several heads a group
    assert cfg.seq_len > cfg.ssm_chunk  # several chunks a row
    assert cfg.shared_expert_dim != cfg.expert_ffn_dim  # its own width
    assert not any(cfg.attention_layer(i).rotary for i in range(cfg.n_layers))
    assert "pos" not in params  # no table either
    ssm, moe, attention = (params["layers"][i] for i in (0, 1, 5))
    # ONE norm and ONE mixer a layer
    assert sorted(ssm) == ["norm", "ssm"]
    assert sorted(moe) == ["moe", "norm", "shared"]
    assert sorted(attention) == ["norm", "wk", "wo", "wq", "wv"]
    assert ssm["ssm"]["w_in"].shape == (48, 64 + (64 + 2 * 2 * 16) + 8)
    assert ssm["ssm"]["conv_w"].shape == (128, 4)
    assert ssm["ssm"]["w_out"].shape == (64, 48)
    assert ssm["ssm"]["gate_norm"]["scale"].shape == (64,)
    for name in ("dt_bias", "A_log", "D"):
        assert ssm["ssm"][name].shape == (8,)
    # un-gated: two matrices an expert, the router as wide as published
    assert sorted(moe["moe"]) == ["gate", "router_bias", "w_down", "w_up"]
    assert moe["moe"]["w_up"].shape == (4, 48, 24)
    assert moe["moe"]["gate"].shape == (48, 16)
    assert sorted(moe["shared"]) == ["w_down", "w_up"]
    assert moe["shared"]["w_up"].shape == (48, 40)
    assert attention["wq"].shape == (48, 64) and attention["wk"].shape == (48, 16)
    runner._check_sizes(TINY_FILE, cfg)
    for key, value in (("hybrid_override_pattern", "MEMEM*MEE"),
                       ("n_routed_experts", 16), ("chunk_size", 16),
                       ("mamba_head_dim", 16), ("time_step_max", 0.2),
                       ("moe_shared_expert_intermediate_size", 24),
                       ("mlp_hidden_act", "silu"), ("rotated_layers", [5])):
        with pytest.raises(harness.BenchError, match=key):
            runner._check_sizes(dict(TINY_FILE, **{key: value}), cfg)


def test_seeded_decays_are_in_a_trained_models_range():
    """``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1] and at least the
    floor, ``A = -exp(A_log)`` in [-16, -1], ``D`` 1, the three float32
    whatever the parameters' dtype."""
    model, cfg, _, _ = nemotron_labs_twotower_one_chip(_one_device_mesh())
    cut = dataclasses.replace(  # the published mixer over a narrow stream
        cfg, d_model=64, vocab_size=64, num_experts=8, held_experts=2,
        expert_ffn_dim=8, shared_expert_dim=8, n_heads=2, head_dim=8)
    shapes = DMoETransformerLM(cut, _one_device_mesh())
    ssm = shapes.init_params(jax.random.PRNGKey(5))["layers"][0]["ssm"]
    dt = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert dt.shape == (64,) and 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1001
    assert np.log(dt).std() > 1.0  # spread over the decades, not one value
    a = np.asarray(jnp.exp(ssm["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 2.0
    assert np.asarray(ssm["D"]).tolist() == [1.0] * 64
    assert {ssm[k].dtype for k in ("dt_bias", "A_log", "D")} == {jnp.dtype("float32")}
    assert ssm["w_in"].dtype == jnp.bfloat16


@pytest.mark.parametrize("chunks", [1, 4, 8])
def test_the_state_space_mixer_matches_the_recurrence_as_written(tiny, chunks):
    """The mixer alone, float32: the output at every position and the
    state after the last one, whatever the chunk (one chunk of S, four of
    S/4, eight of S/8)."""
    _, cfg, params, ids, _ = tiny
    lp = params["layers"][0]
    x = reference.embed(params, ids)
    want, want_state = reference.ssm_part(lp, x, SIZES)
    got, state, decay_min = jax.jit(
        lambda lp, x: _mixer(cfg, lp, x, cfg.seq_len // chunks))(lp, x)
    assert got.shape == x.shape and state.shape == (2, 8, 8, 16)
    _close(got, want, 1e-5)
    _close(state, want_state, 1e-5)
    assert 0.0 < float(decay_min) < 1.0
    # the chunk is how the program gets there, not what it computes
    one, one_state, _ = jax.jit(
        lambda lp, x: _mixer(cfg, lp, x, cfg.seq_len))(lp, x)
    _close(got, one, 2e-6)
    _close(state, one_state, 2e-6)


def _kernel_under_interpret(monkeypatch, calls):
    """``trunk``'s gate and norm go through the kernel form, interpreted."""
    def through_the_kernel(y, z, scale, group, eps, gate_first, first=0, skip=None):
        assert gate_norm.gate_norm_fits(y.shape, group, "tpu", first)
        calls.append((y.shape, group, gate_first, first, skip is not None))
        return gate_norm.gated_rms_norm_kernel(
            y, z, scale, group, eps, gate_first, first, skip, interpret=True)

    monkeypatch.setattr(trunk, "gated_rms_norm", through_the_kernel)


@pytest.mark.parametrize("groups", [2, 1])
def test_the_mixer_through_the_gate_norm_kernel_matches_the_reference(
        monkeypatch, groups):
    """The mixer alone at widths the kernel's tiles admit (4 heads of 64:
    256 channels in groups of 128 or one of 256), float32, with its gate,
    skip and norm as ``gate_norm_fwd`` under ``interpret``: the output and
    the state against the recurrence as written, within what the plain
    form is held to above; and every gradient of the mixer against the
    plain form's."""
    d, h, hp, n, s = 32, 4, 64, 16, 32
    sizes = dict(SIZES, mamba_num_heads=h, mamba_head_dim=hp, ssm_state_size=n,
                 n_groups=groups)
    d_inner, conv_dim = h * hp, h * hp + 2 * groups * n
    rs = np.random.RandomState(5)

    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rs.randn(*shape), jnp.float32)

    lp = {"norm": {"scale": 1.0 + normal(d, scale=0.1)}, "ssm": {
        "w_in": normal(d, d_inner + conv_dim + h, scale=d ** -0.5),
        "conv_w": normal(conv_dim, 4, scale=0.5), "conv_b": normal(conv_dim, scale=0.1),
        "dt_bias": normal(h), "A_log": jnp.log(jnp.asarray(rs.uniform(1, 16, h), jnp.float32)),
        "D": jnp.asarray(rs.uniform(0.5, 1.5, h), jnp.float32),
        "gate_norm": {"scale": 1.0 + normal(d_inner, scale=0.2)},
        "w_out": normal(d_inner, d, scale=d_inner ** -0.5)}}
    x = normal(2, s, d)

    def mixer(lp, x):
        return trunk.ssm_mixer(
            lp["ssm"], trunk.rms_norm(lp["norm"], x, 1e-5), h, groups, 16, 1e-5)

    def loss(lp, x):
        out, state, _ = mixer(lp, x)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(state)

    plain = jax.jit(jax.grad(loss, argnums=(0, 1)))(lp, x)
    calls = []
    _kernel_under_interpret(monkeypatch, calls)
    want, want_state = jax.jit(lambda lp, x: reference.ssm_part(lp, x, sizes))(lp, x)
    got, state, _ = jax.jit(mixer)(lp, x)  # traced once: one call of the kernel
    assert calls == [((2, s, d_inner), d_inner // groups, True, 0, True)]
    _close(got, want, 1e-5)
    _close(state, want_state, 1e-5)
    through = jax.jit(jax.grad(loss, argnums=(0, 1)))(lp, x)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(through),
                            jax.tree_util.tree_leaves(plain)):
        _close(g, w, 1e-5, err_msg=jax.tree_util.keystr(path))


def test_the_convolution_reads_zeros_before_the_sequence(tiny):
    """Positions 0, 1 and 2 see 1, 2 and 3 of the 4 taps: by hand against
    the reference's, and the program's mixer at those positions."""
    _, cfg, params, ids, _ = tiny
    lp = params["layers"][0]
    p = lp["ssm"]
    x = reference.embed(params, ids)
    u = reference.rms(x, lp["norm"]["scale"], SIZES["norm_eps"])
    raw = np.asarray(u @ p["w_in"])[..., 64:64 + 128]  # xBC before the conv
    w, bias = np.asarray(p["conv_w"]), np.asarray(p["conv_b"])
    by_hand = np.stack([
        bias + sum(w[:, 3 - back] * raw[:, t - back] for back in range(t + 1))
        for t in range(3)], axis=1)
    by_hand = by_hand / (1.0 + np.exp(-by_hand))  # silu
    _, xh, bmat, cmat, _, _ = reference.ssm_inputs(reference._f32(p), u, SIZES)
    got = np.concatenate([np.asarray(a)[:, :3].reshape(2, 3, -1)
                          for a in (xh, bmat, cmat)], axis=-1)
    np.testing.assert_allclose(got, by_hand, atol=1e-5)
    want, _ = reference.ssm_part(lp, x, SIZES)
    _close(_mixer(cfg, lp, x)[0][:, :3], want[:, :3], 1e-5)
    # causal: a later input moves no earlier output
    later = x.at[:, 10:].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(_mixer(cfg, lp, later)[0][:, :10]),
        np.asarray(_mixer(cfg, lp, x)[0][:, :10]))


@pytest.mark.parametrize("which", ["program", "reference"])
def test_heads_read_their_groups_b_and_c(which):
    """8 heads over 2 groups: heads 0..3 read group 0's ``B`` and ``C``,
    heads 4..7 group 1's; changing one group's moves only its heads."""
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(1, 16, 8, 4), jnp.float32)
    dt = jnp.asarray(rs.uniform(0.01, 0.5, (1, 16, 8)), jnp.float32)
    a = -jnp.asarray(rs.uniform(1, 4, 8), jnp.float32)
    b, c = (jnp.asarray(rs.randn(1, 16, 2, 6), jnp.float32) for _ in range(2))

    def scan(b, c):
        if which == "program":
            return ssd_chunked(x, dt, a, b, c, 4)
        return reference.recurrence(x, dt, a, b, c)

    base, base_state = scan(b, c)
    for moved in (scan(b.at[:, :, 1].mul(2.0), c), scan(b, c.at[:, :, 1].add(1.0))):
        y, state = moved
        np.testing.assert_array_equal(np.asarray(y[:, :, :4]), np.asarray(base[:, :, :4]))
        assert np.abs(np.asarray(y[:, :, 4:] - base[:, :, 4:])).min() > 0
        np.testing.assert_array_equal(
            np.asarray(state[:, :4]), np.asarray(base_state[:, :4]))


def test_the_chunked_scans_gradients_match_the_sequential_references(tiny):
    """Autodiff through the chunked form (cumulative sums, masked decays,
    the scan over the chunks) against ``jax.grad`` of the recurrence a
    position at a time: of the mixer's parameters and of its input, from
    a loss that reads the output AND the last state."""
    _, cfg, params, ids, _ = tiny
    lp = params["layers"][2]
    x = reference.embed(params, ids)
    rs = np.random.RandomState(4)
    w_out = jnp.asarray(rs.randn(*x.shape), jnp.float32)
    w_state = jnp.asarray(rs.randn(2, 8, 8, 16), jnp.float32)

    def got_loss(lp, x):
        out, state, _ = _mixer(cfg, lp, x)
        return jnp.sum(out * w_out) + jnp.sum(state * w_state)

    def want_loss(lp, x):
        out, state = reference.ssm_part(lp, x, SIZES)
        return jnp.sum(out * w_out) + jnp.sum(state * w_state)

    got = jax.jit(jax.grad(got_loss, (0, 1)))(lp, x)
    want = jax.grad(want_loss, (0, 1))(lp, x)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(w)).max() > 0, path
        _close(g, w, err_msg=jax.tree_util.keystr(path))


def test_logits_and_loss_of_the_whole_stack_match_the_reference(tiny, want):
    model, cfg, params, ids, tgt = tiny
    want_logits, want_loss, _ = want
    logits, aux = jax.jit(model.apply)(params, ids)
    _close(logits, want_logits)
    loss, metrics = jax.jit(model.loss_fn)(params, ids, tgt)
    assert abs(float(loss) - want_loss) <= 1e-5 * abs(want_loss)
    # the step's counters: four routers' rows, and the least decay
    assert metrics["expert_counts"].shape == (4, 16)
    assert int(metrics["expert_counts"].sum()) == 4 * ids.size * cfg.k
    assert float(metrics["dropped_fraction"]) == 0.0
    assert 0.0 < float(metrics["ssm_decay_min"]) < 1.0
    decay_min = jax.jit(lambda lp, x: _mixer(cfg, lp, x)[2])
    least = min(float(decay_min(lp, x)) for lp, x in _streams(model, params, ids)
                if "ssm" in lp)
    assert float(metrics["ssm_decay_min"]) == pytest.approx(least, rel=1e-5)


def _streams(model, params, ids):
    """(layer's parameters, the stream that enters it), the program's: a
    layer a compiled call (one program a kind of layer), as
    ``level_router_bias`` runs them."""
    layer = jax.jit(model._layer, static_argnums=(4,))
    x = params["embed"][ids].astype(model.cfg.dtype)
    for index, lp in enumerate(params["layers"]):
        yield lp, x
        x, _ = layer(lp, x, index, None, model.cfg.attention_layer(index))


def test_gradients_of_every_parameter_match_the_reference(tiny, want):
    """The gradient of EVERY leaf of the nine layers, the table and the
    head to 1e-4 of the reference's largest entry of that leaf; the
    selection biases' are exactly zero on both sides."""
    model, _, params, ids, tgt = tiny
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0]))(params)
    names = []
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want[2]),
    ):
        name, w = jax.tree_util.keystr(path), np.asarray(w)
        names.append(name)
        if name.endswith("['router_bias']"):
            assert not np.asarray(g).any() and not w.any(), name
            continue
        assert np.abs(w).max() > 0, name
        _close(g, w, err_msg=name)
    assert "['layers'][0]['ssm']['A_log']" in names
    assert "['layers'][7]['ssm']['conv_w']" in names
    assert "['layers'][5]['wk']" in names and "['layers'][8]['shared']['w_up']" in names


def test_flops_of_the_cell_are_the_issue_arithmetic():
    """2.608 GFLOP a token trained, by part; the recurrence is 4 H P N
    whatever the chunk; the scan's least time is its bytes."""
    parts = nemotron_flops.forward_flops_per_token(CELL_FILE)
    assert parts["ssm_projections"] == 4 * (2 * 2688 * 10304 + 2 * 4096 * 2688)
    assert parts["ssm_recurrence"] == 4 * 4 * 64 * 64 * 128
    assert parts["projections"] == 2 * 2688 * 128 * (2 * 32 + 2 * 2)
    assert parts["attention_core"] == 4 * 128 * 32 * 16385 / 2
    assert parts["shared_expert"] == 4 * 4 * 2688 * 3712
    assert parts["routed_experts"] == 4 * 1.5 * 4 * 2688 * 1856
    assert parts["router"] == 4 * 2 * 2688 * 128
    assert parts["head"] == 2 * 2688 * 16384
    total = nemotron_flops.train_flops_per_token(CELL_FILE)
    assert total == pytest.approx(2.608e9, rel=1e-3)
    assert dict(CELL_FILE, chunk_size=256) != CELL_FILE
    assert nemotron_flops.train_flops_per_token(dict(CELL_FILE, chunk_size=256)) == total
    assert nemotron_flops.grouped_matmul_flops(CELL_FILE, 16384) == (
        2.0 * 24576 * 2688 * 1856)
    # x, B, C, dt read and y written once forward: 0.34 GB a layer
    forward_bytes = 16384 * 2 * (4096 + 2 * 1024 + 64 + 4096)
    assert nemotron_flops.ssd_scan_bytes(CELL_FILE, 16384) == 3 * 4 * forward_bytes
    assert nemotron_flops.ssd_scan_flops(CELL_FILE, 16384) == (
        3 * 4 * 16384 * 4 * 64 * 64 * 128)
    least = nemotron_flops.ssd_scan_least_seconds(CELL_FILE, 16384, "TPU v5 lite")
    assert least == pytest.approx(3 * 4 * forward_bytes / 819e9) and least > (
        nemotron_flops.ssd_scan_flops(CELL_FILE, 16384) / 197e12)
    assert nemotron_flops.attention_kernel_flops(
        CELL_FILE, 16384, "global", "forward") == 32 * (16384 * 16385 // 2) * 2 * 128 * 2


def test_parameters_of_the_cell_are_the_issue_arithmetic():
    """1,624,837,632 parameters from the recipe's ``eval_shape``: a
    state-space layer 38,744,896, the attention layer 23,399,040, a mixture
    layer with its 32 held experts 339,593,984."""
    model, cfg, _, batch = nemotron_labs_twotower_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == 1_624_837_632
    assert [count(lp) for lp in shapes["layers"]] == [
        {"ssm": 38_744_896, "attention": 23_399_040, "moe": 339_593_984}[m]
        for m in cfg.mixer_pattern]
    assert count(shapes["layers"][1]["moe"]) - 2688 * 128 - 128 == 32 * 9_977_856
    assert shapes["embed"].shape == (16384, 2688) == shapes["lm_head"].shape[::-1]
    assert batch == 1 and cfg.seq_len == 16384
    assert {a.dtype for a in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype("bfloat16"), jnp.dtype("float32")}
    runner._check_sizes(CELL_FILE, cfg)


def test_configuration_file_carries_the_catalog_entry():
    """Every key of the catalog row's ``config`` unchanged but the two the
    share cuts; depth under a key of its own."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in open(catalog)
               if "Nemotron-Labs-TwoTower-30B-A3B" in line)
    assert CELL_FILE["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CELL_FILE.get(k) != v}
    assert differs == {"n_routed_experts", "vocab_size"}
    assert CELL_FILE["reduced"] == ["n_layers", "n_routed_experts", "vocab_size"]
    assert (CELL_FILE["n_layers"], CELL_FILE["n_routed_experts"],
            CELL_FILE["vocab_size"]) == (9, 32, 16384)
    assert (CELL_FILE["n_routed_experts_published"],
            CELL_FILE["vocab_size_published"]) == (128, 131072)
    assert CELL_FILE["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert "denoiser" in CELL_FILE["not_built"][0]


def test_the_scope_roofline_reducer_reads_a_scope_and_nothing_where_there_is_none():
    reducer = harness.load_path(os.path.join(
        REPO, "benchmarks", "reducers", "scope_roofline.py"))
    spec = harness.load_json(os.path.join(
        REPO, "benchmarks", "layer_metrics", "nemotron.ssm_scan_roofline.json"))
    obs = {
        "device_kind": "TPU v5 lite", "sizes": CELL_FILE,
        "tokens_per_step_per_chip": 16384, "intervals_s": [0.8, 0.81, 0.79],
        "trace": {"busy_s": 2.97, "span_s": 3.0},
        "scopes": {"total_s": 3.0, "by_scope": {"ssm/scan": 0.75, "ssm": 0.1}},
    }
    least = nemotron_flops.ssd_scan_least_seconds(CELL_FILE, 16384, "TPU v5 lite")
    want = 100.0 * least / (0.75 / 3.0 * 0.99 * 0.8)
    assert reducer.reduce(obs, **spec["args"]) == pytest.approx(want)
    assert 0 < want < 100
    # a program without the scope (the parent), a CPU: nothing, no error
    bare = dict(obs, scopes={"total_s": 3.0, "by_scope": {"attention": 1.0}})
    assert reducer.reduce(bare, **spec["args"]) is None
    assert reducer.reduce(dict(obs, scopes=None), **spec["args"]) is None
    assert reducer.reduce(dict(obs, device_kind="cpu"), **spec["args"]) is None
    share = harness.load_path(os.path.join(
        REPO, "benchmarks", "reducers", "scope_share.py"))
    whole = harness.load_json(os.path.join(
        REPO, "benchmarks", "layer_metrics", "nemotron.ssm_share.json"))
    assert share.reduce(obs, **whole["args"]) == pytest.approx(100 * 0.85 / 3.0)


def test_a_program_without_the_recipe_fails_at_once_with_no_result(tmp_path):
    """The new runner on a program from before this configuration (no
    ``nemotron_labs_twotower_one_chip`` in ``__graft_entry__``): ``no
    recipe``, exit code 2, no result line: what the parent commit does on
    the new cell."""
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    tiny_file = dict(TINY_FILE, recipe="a_recipe_from_the_future")
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "nemotron-tiny.json"
    path.write_text(json.dumps(tiny_file))
    manifest = harness.load_json(os.path.join(
        REPO, "benchmarks", "rehearsal", "manifest_nemotron.json"))
    manifest["configs"][0]["file"] = os.path.relpath(path, REPO)
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    env = clean_jax_subprocess_env(REPO, platform="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    run = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest",
         os.path.relpath(tmp_path / "manifest.json", REPO), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert run.returncode == 2 and "no recipe" in run.stderr
    assert not run.stdout.strip()
