"""Nemotron-Labs-TwoTower's hybrid tower in the pod step as one chip's share
(``__graft_entry__.nemotron_labs_twotower_one_chip``) against its plain
reference (``benchmarks/configs/nemotron_labs_twotower_30b_a3b_reference.py``):
the Mamba-2 state-space mixer in its chunked form against the recurrence a
position at a time, layers that are ONE mixer each, un-gated squared-ReLU
experts on the share; the refusals beside that path; the cut's arithmetic;
and the benchmark's files for it.

Tiny sizes on the CPU, except the AOT compile at published widths for a
described (not attached) ``v5e`` chip.
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
from learning_at_home_tpu.models.transformer import (  # noqa: E402
    DMoETransformerConfig,
    DMoETransformerLM,
)
from learning_at_home_tpu.ops import gate_norm  # noqa: E402
from learning_at_home_tpu.ops import moe_dispatch  # noqa: E402
from learning_at_home_tpu.ops import ssd  # noqa: E402
from learning_at_home_tpu.ops import ssm_conv  # noqa: E402
from learning_at_home_tpu.ops.ssd import ssd_chunked  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts  # noqa: E402

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


def _one_device_mesh():
    return make_mesh({"expert": 1}, devices=jax.devices()[:1])


def _decisive(params, seed=7):
    """Seeded weights under which every part of the stack decides: a router
    that decides (the program's init gives near-equal scores), selection
    biases off zero, norm scales, ``D`` and the convolution's bias off
    their initial values."""
    rs = np.random.RandomState(seed)

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith(("['scale']", "['D']")):
            return a * jnp.asarray(rs.uniform(0.5, 1.5, a.shape), a.dtype)
        if name.endswith("['router_bias']"):
            return jnp.asarray(rs.uniform(-0.2, 0.2, a.shape), a.dtype)
        if name.endswith("['conv_b']"):
            return jnp.asarray(rs.uniform(-0.3, 0.3, a.shape), a.dtype)
        return a * (20.0 if name.endswith("['gate']") else 1.0)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    model, cfg, _, batch = nemotron_labs_twotower_one_chip(
        _one_device_mesh(), tiny=True)
    params = _decisive(model.init_params(jax.random.PRNGKey(11)))
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len + 1)))
    return model, cfg, params, ids[:, :-1], ids[:, 1:]


def _close(got, want, tol=1e-4, **kw):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=0, atol=tol * np.abs(want).max(), **kw)


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
    one, one_state, _ = _mixer(cfg, lp, x, cfg.seq_len)
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

    plain = jax.grad(loss, argnums=(0, 1))(lp, x)
    calls = []
    _kernel_under_interpret(monkeypatch, calls)
    want, want_state = reference.ssm_part(lp, x, sizes)
    got, state, _ = mixer(lp, x)
    assert calls == [((2, s, d_inner), d_inner // groups, True, 0, True)]
    _close(got, want, 1e-5)
    _close(state, want_state, 1e-5)
    through = jax.grad(loss, argnums=(0, 1))(lp, x)
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


def test_logits_and_loss_of_the_whole_stack_match_the_reference(tiny):
    model, cfg, params, ids, tgt = tiny
    want, _, _ = reference.forward(params, ids, SIZES)
    logits, aux = jax.jit(model.apply)(params, ids)
    _close(logits, want)
    want_loss = reference.loss(params, ids, tgt, SIZES)
    loss, metrics = jax.jit(model.loss_fn)(params, ids, tgt)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    # the step's counters: four routers' rows, and the least decay
    assert metrics["expert_counts"].shape == (4, 16)
    assert int(metrics["expert_counts"].sum()) == 4 * ids.size * cfg.k
    assert float(metrics["dropped_fraction"]) == 0.0
    assert 0.0 < float(metrics["ssm_decay_min"]) < 1.0
    least = min(float(_mixer(cfg, lp, x)[2]) for lp, x in _streams(model, params, ids)
                if "ssm" in lp)
    assert float(metrics["ssm_decay_min"]) == pytest.approx(least, rel=1e-5)


def _streams(model, params, ids):
    """(layer's parameters, the stream that enters it), the program's."""
    x = params["embed"][ids].astype(model.cfg.dtype)
    for index, lp in enumerate(params["layers"]):
        yield lp, x
        x, _ = model._layer(lp, x, index, None, model.cfg.attention_layer(index))


def test_gradients_of_every_parameter_match_the_reference(tiny):
    """The gradient of EVERY leaf of the nine layers, the table and the
    head to 1e-4 of the reference's largest entry of that leaf; the
    selection biases' are exactly zero on both sides."""
    model, _, params, ids, tgt = tiny
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0]))(params)
    _, want = reference.loss_and_grads(params, ids, tgt, SIZES)
    names = []
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want),
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


# ---- (b) the negatives: the comparison can fail ----


def _reference_with(**changes):
    """A copy of the reference module with functions replaced."""
    broken = harness.load_path(REFERENCE)
    for name, value in changes.items():
        setattr(broken, name, value)
    return broken


def _norm_then_gate(y, z, scale, groups, eps):
    b, s, d_inner = y.shape
    normed = reference.rms(
        y.reshape(b, s, groups, d_inner // groups), 1.0, eps)
    return normed.reshape(b, s, d_inner) * scale * jax.nn.silu(z)


NEGATIVES = {
    "a_scan_without_the_d_x_term": (
        dict(skip_term=lambda d, x: 0.0 * x), ("ssm_rms", "layers_rms")),
    "a_gate_applied_after_the_norm": (
        dict(gated_norm=_norm_then_gate), ("ssm_rms", "layers_rms")),
    "a_decay_taken_from_dt_without_softplus": (
        dict(step_sizes=lambda dt, dt_bias: jnp.abs(dt + dt_bias)),
        ("ssm_rms", "ssm_state_rms")),
    "an_expert_with_relu_in_place_of_its_square": (
        dict(activation=jax.nn.relu), ("layers_rms",)),
}


def test_the_stack_as_it_is_reads_inside_the_runner_tolerances(tiny):
    model, _, params, ids, tgt = tiny
    read = runner.compare_with_reference(
        model, params, reference, TINY_FILE, ids[:1], tgt[:1])
    limits = {**runner.TOLERANCES, "near_tie_share": 1.0}  # 32 positions
    assert [k for k, lim in limits.items() if not read[k] <= lim] == []
    assert len(read["embed_and_layers_rms"]) == 10  # the embedding, nine layers
    assert len(read["ssm_layers_rms"]) == len(read["ssm_states_rms"]) == 4
    assert len(read["near_tie_shares"]) == 4


@pytest.mark.parametrize("name", sorted(NEGATIVES))
def test_a_wrong_stack_fails_the_runner_tolerances(tiny, name):
    """Each read OUTSIDE the tolerance: the comparison can fail.  (The
    wrong side is the reference's copy; the program is as it is.)"""
    model, _, params, ids, tgt = tiny
    changes, outside = NEGATIVES[name]
    read = runner.compare_with_reference(
        model, params, _reference_with(**changes), TINY_FILE, ids[:1], tgt[:1])
    for key in outside:
        assert not read[key] <= runner.TOLERANCES[key], (key, read[key])


def test_a_hidden_that_composes_another_stack_fails_the_runner_tolerances(tiny):
    """``_hidden`` over a stack whose state-space layers are skipped (the
    layers themselves as they are) reads outside ``hidden_token_median``."""
    _, cfg, params, ids, tgt = tiny
    model = DMoETransformerLM(cfg, _one_device_mesh())
    layer = model._layer
    model._layer = lambda lp, x, *rest: (
        (x, None) if "ssm" in lp and x.shape[0] != 1 else layer(lp, x, *rest))
    read = runner.compare_with_reference(
        model, params, reference, TINY_FILE, ids[:1], tgt[:1])
    assert read["hidden_token_median"] <= runner.TOLERANCES["hidden_token_median"]
    model._layer = lambda lp, x, *rest: (
        (x, None) if "ssm" in lp else layer(lp, x, *rest))
    whole = model._hidden(params, ids[:1])[0]
    right = DMoETransformerLM(cfg, _one_device_mesh())._hidden(params, ids[:1])[0]
    rel = np.median(np.linalg.norm(np.asarray(whole - right), axis=-1)
                    / np.linalg.norm(np.asarray(right), axis=-1))
    assert rel > runner.TOLERANCES["hidden_token_median"]


def test_lower_precisions_fail_the_runner_tolerances(tiny):
    """The reference with float8 operands in the program's place reads
    outside the layer and logits limits, with bf16 operands inside; the
    program's scan with bf16 decays reads worse than with float32 ones."""
    model, _, params, ids, tgt = tiny
    for dtype, inside in ((jnp.float8_e4m3fn, False), (jnp.bfloat16, True)):
        read = runner.compare_with_reference(
            model, params, reference, TINY_FILE, ids[:1], tgt[:1],
            operand_dtype=dtype)
        for key in ("layers_rms", "ssm_rms", "logits_rms"):
            assert (read[key] <= runner.TOLERANCES[key]) is inside, (dtype, key)
    exact = runner.compare_with_reference(
        model, params, reference, TINY_FILE, ids[:1], tgt[:1])
    rough = runner.compare_with_reference(
        model, params, reference, TINY_FILE, ids[:1], tgt[:1],
        decay_dtype=jnp.bfloat16)
    assert rough["ssm_rms"] > 100 * exact["ssm_rms"]
    assert rough["ssm_state_rms"] > 100 * exact["ssm_state_rms"]


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


# ---- (f) the chip's compiler accepts the step at published widths ----


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The nine-layer train step at published widths, compiled for a
    described chip (nothing runs): 1,624,837,632 parameters, the
    compiler's own count of what is live in the step between a quarter of
    the chip's memory (the benchmark's floor for a cell) and 0.9 of it
    (9.92 GB, 58.7 %, when this was written: ISSUE.md expected 47-65 %;
    10.31 GB, 61.0 %, with the scan's kernels and what remat keeps of them: PR 40),
    the blocked kernel at heads of 128 in the one attention layer, once
    forward (remat keeps its residuals) and once fused backward, and the
    head's three products a pass."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "nemotron_labs_twotower_one_chip")
    assert memory["parameters"] == 1_624_837_632
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    # four mixture layers x (2 forward + 2 recomputed + 4 backward) calls,
    # each at the tile rule's answer for a width of 1,856 at its cover
    assert memory["grouped_matmul_tilings"] == {
        "256,896,1920": 4 * 3, "256,1920,896": 4 * 3,
        "256,896,640": 4, "256,640,896": 4}
    assert memory["loss_layer_products"] == 3
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1}
    assert memory["kept_residual_bytes"] == 32 * 16384 * (128 * 2 + 4)
    # and the results of the attention layer's products (PR 53): q, k, v
    # and the output projection's, bf16 [16384, 4096 + 256 + 256 + 2688],
    # 0.24 GB NAMED; the backward pass runs none of the four a second time.
    # Of the output projection's 88 MB nothing is held: in a layer of one
    # mixer no backward equation reads it, so the checkpoint drops it from
    # its residuals and the compiled step has no ``reduce_precision`` of it
    assert memory["kept_product_bytes"] == 16384 * (4096 + 2 * 256 + 2688) * 2
    assert memory["recomputed_attention_products"] == 0
    calls = memory["attention_kernel_tilings"]["global"]
    assert {name: (c["calls"], c["block_q"], c["block_kv"]) for name, c in calls.items()} == {
        "splash_mha_fwd_residuals": (1, 1024, 1024),
        "splash_mha_dkv_no_residuals": (1, 1024, 1024)}
    # 32 query heads over 2 key/value heads, as they come
    assert calls["splash_mha_fwd_residuals"]["grid"][0] == 32
    # the scan's kernels, once forward (remat keeps the output and the
    # entering states: the recompute holds no scan) and once backward a
    # state-space layer, every call under ``ssm/scan``
    assert memory["scan_kernel_calls"] == {
        "ssd_chunk_fwd": {"calls": 4, "under_ssm_scan": 4},
        "ssd_chunk_bwd": {"calls": 4, "under_ssm_scan": 4}}
    assert memory["kept_scan_bytes"] == 4 * (
        16384 * 4096 * 2 + 128 * 64 * 64 * 128 * 4)
    # the convolution's one pass forward, recomputed (remat keeps nothing
    # of it) and backward, for each of ``x``, ``B`` and ``C`` of a
    # state-space layer, every call under ``ssm/conv``, and no float32 copy
    # of ``x B C`` or of a part written there (PR 41)
    assert memory["conv_kernel_calls"] == {
        "ssm_conv_fwd": {"calls": 24, "under_ssm_conv": 24},
        "ssm_conv_bwd": {"calls": 12, "under_ssm_conv": 12}}
    assert memory["float32_arrays_under_ssm_conv"] == []
    # the skip, the gate and the norm as one pass: forward, recomputed
    # (remat keeps nothing of it) and backward a state-space layer, every
    # call under ``ssm/gate_norm``, and no float32 ``[1, 16384, 4096]``
    # written there or under ``ssm/scan`` on their behalf (PR 47; the
    # parent's live count read 9,878,984,192)
    assert memory["gate_norm_kernel_calls"] == {
        "gate_norm_fwd": {"calls": 2 * 4, "under_ssm_gate_norm": 2 * 4},
        "gate_norm_bwd": {"calls": 4, "under_ssm_gate_norm": 4}}
    assert memory["float32_arrays_beside_gate_norm"] == []


def test_the_scan_kernels_compile_for_the_chip_at_the_cells_shape(v5e_chip):
    """``ssd_chunk_fwd`` and ``ssd_chunk_bwd`` at ``[1, 16384, 64, 64]``,
    state 128, 8 groups, chunks of 128, bf16, compiled for a described
    chip (nothing runs): Mosaic takes the tiles, the transposes and the
    VMEM the kernels ask for."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, h, p, g, n = (CELL_FILE[k] for k in (
        "seq_len", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size"))
    assert (s, h, p, g, n, CELL_FILE["chunk_size"]) == (16384, 64, 64, 8, 128, 128)
    assert ssd.kernel_fits((1, s, h, p), (1, s, g, n), 128, "tpu")

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (shaped((1, s, h, p), jnp.bfloat16), shaped((1, s, h), jnp.float32),
            shaped((h,), jnp.float32), shaped((1, s, g, n), jnp.bfloat16),
            shaped((1, s, g, n), jnp.bfloat16))

    def loss(*a):
        y, state = ssd.ssd_chunked_kernel(*a, 128)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(state)

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    assert {name: c["calls"] for name, c in probe.scan_kernel_calls(text).items()} == {
        "ssd_chunk_fwd": 1, "ssd_chunk_bwd": 1}


def test_the_convolutions_kernels_compile_for_the_chip_at_the_cells_shape(v5e_chip):
    """``ssm_conv_fwd`` and ``ssm_conv_bwd`` as the cell's mixer calls them,
    compiled for a described chip (nothing runs): ``x`` (4,096 channels),
    ``B`` and ``C`` (1,024 each) read out of the in-projection's
    ``[1, 16384, 10304]`` bf16 where they lie, four taps: Mosaic takes the
    blocks at their offsets, the halos' tiles, the rolls along the sublanes
    and the VMEM the two scratches ask for."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, taps = CELL_FILE["seq_len"], CELL_FILE["conv_kernel"]
    d_inner = CELL_FILE["mamba_num_heads"] * CELL_FILE["mamba_head_dim"]
    group = CELL_FILE["n_groups"] * CELL_FILE["ssm_state_size"]
    wide = 2 * d_inner + 2 * group + CELL_FILE["mamba_num_heads"]
    parts = [(d_inner, d_inner), (2 * d_inner, group), (2 * d_inner + group, group)]
    assert (s, taps, wide, parts) == (
        16384, 4, 10304, [(4096, 4096), (8192, 1024), (9216, 1024)])
    assert all(ssm_conv.conv_kernel_fits((1, s, c), taps, "tpu", first)
               for first, c in parts)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(zxbcdt, w, b):  # the square: its cotangent reads the forward's result
        lo = d_inner
        return sum(jnp.sum(ssm_conv.causal_conv_silu_kernel(
            zxbcdt, w[first - lo:first - lo + c], b[first - lo:first - lo + c],
            first).astype(jnp.float32) ** 2) for first, c in parts)

    c = d_inner + 2 * group
    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shaped((1, s, wide), jnp.bfloat16), shaped((c, taps), jnp.bfloat16),
            shaped((c,), jnp.bfloat16)).compile().as_text()
    calls = probe.scan_kernel_calls(text, "ssm_conv", "ssm/conv")
    assert {name: entry["calls"] for name, entry in calls.items()} == {
        "ssm_conv_fwd": 3, "ssm_conv_bwd": 3}


@pytest.mark.parametrize("cell", ["nemotron", "olmo-hybrid"])
def test_the_gate_norm_kernels_compile_for_the_chip_at_the_cells_shapes(v5e_chip, cell):
    """``gate_norm_fwd`` and ``gate_norm_bwd`` as the two hybrid cells'
    mixers call them, compiled for a described chip (nothing runs; here
    because the described chip's library is one file's to load).  Nemotron:
    4,096 channels in groups of 512 under a scale a channel, the gate
    first, ``z`` at column 0 of the in-projection's ``[1, 16384, 10304]``
    bf16, the skip ``y + D x`` inside.  Olmo-Hybrid: 5,760 channels, heads
    of 192 two to a block of 384 lanes under one shared scale, the norm
    first, ``z`` at column 11,520 of ``[1, 16384, 17340]``.  Mosaic takes
    the blocks at their offsets, the masked sums along the lanes and the
    partial sums' blocks."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    s, bf16 = CELL_FILE["seq_len"], jnp.bfloat16
    c, wide, first, group, n_scale, gate_first, heads = {
        "nemotron": (4096, 10304, 0, 512, 4096, True, 64),
        "olmo-hybrid": (5760, 17340, 11520, 192, 192, False, 0)}[cell]
    assert gate_norm.gate_norm_fits((1, s, c), group, "tpu", first)

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, bf16, sharding=one)

    def loss(y, z, scale, skip):  # the square: its cotangent reads the result
        return jnp.sum(gate_norm.gated_rms_norm_kernel(
            y, z, scale, group, 1e-5, gate_first, first, skip).astype(jnp.float32) ** 2)

    skip = (shaped(1, s, c), shaped(heads)) if heads else None
    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3) if heads else (0, 1, 2))).lower(
            shaped(1, s, c), shaped(1, s, wide), shaped(n_scale), skip).compile().as_text()
    calls = probe.scan_kernel_calls(text, "gate_norm", "gate_norm")
    assert {name: entry["calls"] for name, entry in calls.items()} == {
        "gate_norm_fwd": 1, "gate_norm_bwd": 1}


def test_the_convolutions_kernels_compile_at_the_delta_mixers_shape(v5e_chip):
    """The same two kernels as Olmo-Hybrid's delta mixer calls them
    (``trunk.delta_mixer``; here because the described chip's library is
    one file's to load): ``[q | k]`` as ONE call of 5,760 channels at
    column 0 and ``v`` as one at column 5,760 of the in-projection's ``[1,
    16384, 17340]`` bf16, four taps, no bias: blocks of 384 channels, the
    last of a wide array that is no multiple of the lanes."""
    one = jax.sharding.SingleDeviceSharding(v5e_chip)
    olmo = harness.load_json(os.path.join(
        REPO, "benchmarks", "configs", "olmo-hybrid-7b.json"))
    s, taps = olmo["seq_len"], olmo["linear_conv_kernel_dim"]
    heads = olmo["linear_num_key_heads"]
    d_qk = 2 * heads * olmo["linear_key_head_dim"]
    d_v = heads * olmo["linear_value_head_dim"]
    wide = d_qk + 2 * d_v + 2 * heads
    assert (s, taps, d_qk, d_v, wide) == (16384, 4, 5760, 5760, 17340)
    assert all(ssm_conv.conv_kernel_fits((1, s, 5760), taps, "tpu", first)
               for first in (0, d_qk))

    def loss(proj, w):
        return sum(jnp.sum(ssm_conv.causal_conv_silu_kernel(
            proj, w[first:first + 5760], jnp.zeros((5760,), jnp.float32),
            first).astype(jnp.float32) ** 2) for first in (0, d_qk))

    with probe.no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.ShapeDtypeStruct((1, s, wide), jnp.bfloat16, sharding=one),
            jax.ShapeDtypeStruct((d_qk + d_v, taps), jnp.bfloat16, sharding=one),
        ).compile().as_text()
    calls = probe.scan_kernel_calls(text, "ssm_conv", "ssm/conv")
    assert {name: entry["calls"] for name, entry in calls.items()} == {
        "ssm_conv_fwd": 2, "ssm_conv_bwd": 2}
