"""K-EXAONE's language model in the pod step as one chip's share
(``__graft_entry__.k_exaone_one_chip``) against its plain reference
(``benchmarks/configs/k_exaone_236b_a23b_reference.py``): a share of
sigmoid-routed experts beside a shared expert, a dense first layer, window
and global layers with a norm over each head's queries and keys; the
selection bias and its balancing rule; the sorted-row buffer; the refusals
beside that path; and the benchmark's files for it.

Tiny sizes on the CPU, except the AOT compile at published widths for a
described (not attached) ``v5e`` chip.
"""

import ast
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
import kexaone_flops  # noqa: E402

from __graft_entry__ import k_exaone_one_chip  # noqa: E402
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import (  # noqa: E402
    AttentionLayer,
    DMoETransformerLM,
)
from learning_at_home_tpu.ops import moe_dispatch  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts  # noqa: E402

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "k_exaone_236b_a23b_reference.py"))
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_share.py"))
probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "kexaone-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "k-exaone-236b-a23b.json"))
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference


def _one_device_mesh():
    return make_mesh({"expert": 1}, devices=jax.devices()[:1])


def _decisive(params, seed=7):
    """Seeded weights under which every part of the block decides and
    bf16 still reads the block as it is: a router that decides (the
    program's init gives near-equal scores), selection biases off zero,
    embeddings whose mean square is near the norm's eps, feed-forward
    outputs small enough that the few tokens whose 4th and 5th scores swap
    under bf16 do not swamp the rest of 32, norm scales off 1."""
    rs = np.random.RandomState(seed)
    scale = {"['gate']": 20.0, "['embed']": 0.3, "['w_down']": 0.3}

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            return a * jnp.asarray(rs.uniform(0.5, 1.5, a.shape), a.dtype)
        if name.endswith("['router_bias']"):
            return jnp.asarray(rs.uniform(-0.2, 0.2, a.shape), a.dtype)
        return a * next((v for k, v in scale.items() if name.endswith(k)), 1.0)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    model, cfg, _, batch = k_exaone_one_chip(_one_device_mesh(), tiny=True)
    params = _decisive(model.init_params(jax.random.PRNGKey(11)))
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len + 1)))
    return model, cfg, params, ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's logits, loss and gradients on the tiny weights."""
    _, _, params, ids, tgt = tiny
    logits, _, _ = reference.forward(params, ids, SIZES)
    loss, grads = reference.loss_and_grads(params, ids, tgt, SIZES)
    return np.asarray(logits), float(loss), grads


# ---- (a) the program against the reference ----


def test_the_tiny_recipe_keeps_the_block(tiny):
    """What ``tiny`` must keep of the published block, and the rehearsal
    file's sizes are the tiny recipe's (the runner's own check)."""
    _, cfg, params, _, _ = tiny
    assert cfg.held_experts < cfg.num_experts and cfg.k < cfg.num_experts
    assert cfg.n_kv_heads < cfg.n_heads and cfg.qk_norm == "head"
    kinds = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    local = AttentionLayer(8, True)
    assert kinds == [local] * 3 + [AttentionLayer(None, False), local]
    assert local.window < cfg.seq_len
    assert cfg.ffn_pattern == ("dense", "moe", "moe", "moe", "moe")
    first, second = params["layers"][:2]
    assert "ffn" in first and "moe" not in first and "shared" not in first
    assert "moe" in second and "shared" in second and "ffn" not in second
    assert first["ffn"]["w_gate"].shape == (64, 96)
    assert second["moe"]["gate"].shape == (64, 16)  # the router's width
    assert second["moe"]["w_gate"].shape == (4, 64, 24)  # the experts held
    assert second["moe"]["router_bias"].dtype == jnp.float32
    assert second["q_norm"]["scale"].shape == (16,)  # one head's
    runner._check_sizes(TINY_FILE, cfg)
    with pytest.raises(harness.BenchError, match="mlp_layer_types"):
        runner._check_sizes(dict(TINY_FILE, mlp_layer_types=["sparse"] * 48), cfg)
    with pytest.raises(harness.BenchError, match="num_experts"):
        runner._check_sizes(dict(TINY_FILE, num_experts=16), cfg)


def test_logits_and_loss_match_the_reference_in_float32(tiny, want):
    model, _, params, ids, tgt = tiny
    want_logits, want_loss, _ = want
    logits, _ = jax.jit(model.apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits), want_logits, rtol=0,
        atol=1e-4 * np.abs(want_logits).max(),
    )
    loss, metrics = jax.jit(model.loss_fn)(params, ids, tgt)
    assert abs(float(loss) - want_loss) <= 1e-4 * abs(want_loss)
    assert float(metrics["dropped_fraction"]) == 0.0
    assert metrics["expert_counts"].shape == (4, 16)  # a mixture layer's own
    assert int(metrics["expert_counts"].sum()) == 4 * ids.size * 4


def test_gradients_match_the_reference_in_float32(tiny, want):
    """The gradient of EVERY leaf to 1e-4 of the reference's largest entry
    of that leaf; the selection biases' are exactly zero on both sides."""
    model, _, params, ids, tgt = tiny
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0]))(params)
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want[2]),
    ):
        name, w = jax.tree_util.keystr(path), np.asarray(w)
        if name.endswith("['router_bias']"):
            assert not np.asarray(g).any() and not w.any(), name
            continue
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=0, atol=1e-4 * np.abs(w).max(), err_msg=name)


def _bf16_model(cfg, mesh, **changes):
    return DMoETransformerLM(
        dataclasses.replace(cfg, dtype=jnp.bfloat16, **changes), mesh)


def test_block_in_bf16_is_inside_the_runner_tolerances(tiny):
    model, cfg, params, ids, tgt = tiny
    read = runner.compare_with_reference(
        _bf16_model(cfg, model.mesh), params, reference, TINY_FILE, ids[:1], tgt[:1])
    assert not runner.over_tolerance(read), read
    assert 0.0 < read["near_tie_share"] and read["near_tie_shares"][0] == 0.0
    assert len(read["embed_and_layers_rms"]) == 1 + cfg.n_layers


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    """The reference itself with every matmul operand rounded to
    float8_e4m3, the nearest precision below the configuration's bf16, is
    outside the runner's limits; rounded to bf16 it is inside."""
    model, _, params, ids, tgt = tiny
    for dtype, outside in ((jnp.float8_e4m3fn, True), (jnp.bfloat16, False)):
        read = runner.compare_with_reference(
            model, params, reference, TINY_FILE, ids[:1], tgt[:1],
            operand_dtype=dtype)
        assert bool(runner.over_tolerance(read)) is outside, (dtype, read)


def test_a_token_between_two_experts_is_left_out_where_one_of_them_is_held(
        tiny, monkeypatch):
    """A position whose 4th and 5th ``score + bias`` lie within ``MARGIN``
    in the reference is not compared in that layer, if one of the two is
    an expert held here: a swap between two absent experts leaves this
    share's part as it was.  A margin that leaves no position to compare
    is itself outside the limits."""
    model, cfg, params, ids, tgt = tiny
    lp = params["layers"][1]
    h = jnp.asarray(np.random.RandomState(5).randn(1, 32, 64), jnp.float32)
    scores = np.asarray(reference.router_scores(lp, h, SIZES))
    order = np.argsort(scores, axis=-1)
    gap = np.take_along_axis(scores, order[:, -4:-3], -1) - np.take_along_axis(
        scores, order[:, -5:-4], -1)
    held = (order[:, -5:-3] < 4).any(axis=-1)  # experts 0..3 are here
    assert 0 < held.sum() < len(held)
    margin = np.asarray(reference.router_margin(lp, h, SIZES))
    np.testing.assert_allclose(margin[held], gap[held, 0], rtol=1e-6)
    assert np.isinf(margin[~held]).all()
    every = np.asarray(reference.router_margin(lp, h, dict(SIZES, held=None)))
    np.testing.assert_allclose(every, gap[:, 0], rtol=1e-6)
    monkeypatch.setattr(runner, "MARGIN", np.inf)
    with np.errstate(invalid="ignore"):
        read = runner.compare_with_reference(
            _bf16_model(cfg, model.mesh), params, reference, TINY_FILE,
            ids[:1], tgt[:1])
    assert "near_tie_share" in [p.split()[0] for p in runner.over_tolerance(read)]
    assert read["near_tie_shares"][0] == 0.0  # the dense layer leaves out none


# ---- (e) a stack without a part of the block fails (a) ----


def _no_window(cfg):
    return {"layer_pattern": tuple(
        dataclasses.replace(a, window=None) for a in cfg.layer_pattern)}


class _Without:
    """What the runner's comparison calls of a model, with a part of every
    layer's PROGRAM-side parameters taken away first (the reference keeps
    the given ones): the shared expert dropped, or the dense block's
    output matrix zeroed (the layer then adds nothing after its
    attention)."""

    def __init__(self, model, part):
        self.cfg, self.moe = model.cfg, model.moe
        self._head, self._logits, self._norm = model._head, model._logits, model._norm
        self._attention_block = model._attention_block

        def cut(lp):
            if part == "shared":
                return {k: v for k, v in lp.items() if k != "shared"}
            if part == "ffn" and "ffn" in lp:
                return {**lp, "ffn": {**lp["ffn"], "w_down": lp["ffn"]["w_down"] * 0}}
            return lp

        def whole(p):
            return {**p, "layers": tuple(map(cut, p["layers"]))}

        self._layer = lambda lp, *rest: model._layer(cut(lp), *rest)
        self._hidden = lambda p, i: model._hidden(whole(p), i)
        self.loss_fn = lambda p, i, t: model.loss_fn(whole(p), i, t)


MUTATIONS = {
    "window_ignored": lambda cfg, mesh: _bf16_model(cfg, mesh, **_no_window(cfg)),
    "rotary_on_the_global_layer": lambda cfg, mesh: _bf16_model(
        cfg, mesh, layer_pattern=tuple(
            dataclasses.replace(a, rotary=True) for a in cfg.layer_pattern)),
    "dense_layer_left_out": lambda cfg, mesh: _Without(_bf16_model(cfg, mesh), "ffn"),
    "shared_expert_left_out": lambda cfg, mesh: _Without(
        _bf16_model(cfg, mesh), "shared"),
    "gates_not_scaled": lambda cfg, mesh: _bf16_model(cfg, mesh, routed_scale=1.0),
    "gates_not_renormalised": lambda cfg, mesh: _bf16_model(
        cfg, mesh, renormalize=False),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_block_fails_the_runner_tolerances(tiny, name):
    """The limits are tight: a stack without its window, its dense layer
    or its shared expert, and each other plausible misreading of the
    block, computed in bf16 like the program, reads outside them."""
    model, cfg, params, ids, tgt = tiny
    read = runner.compare_with_reference(
        MUTATIONS[name](cfg, model.mesh), params, reference, TINY_FILE,
        ids[:1], tgt[:1])
    assert runner.over_tolerance(read), read


def test_a_whole_that_composes_other_layers_fails_the_runner_tolerances(tiny):
    """The layers are compared one at a time; ``hidden_token_median`` holds
    ``_hidden`` (what ``apply`` and ``loss_fn`` run) to the same layers."""
    model, cfg, params, ids, tgt = tiny
    mixed = _Without(_bf16_model(cfg, model.mesh), None)
    wrong = _bf16_model(cfg, model.mesh, **_no_window(cfg))
    mixed._hidden, mixed.loss_fn = wrong._hidden, wrong.loss_fn
    read = runner.compare_with_reference(
        mixed, params, reference, TINY_FILE, ids[:1], tgt[:1])
    assert "hidden_token_median" in [
        p.split()[0] for p in runner.over_tolerance(read)], read


def test_norm_over_each_head_is_not_the_norm_over_the_projection():
    """``qkv_projections`` reads which norm it is off the scale's width:
    one head's ``hd`` against the projection's ``H * hd``."""
    rs = np.random.RandomState(0)
    d, heads, kv, hd = 32, 4, 2, 8
    lp = {w: jnp.asarray(rs.randn(d, n * hd), jnp.float32) / 6
          for w, n in (("wq", heads), ("wk", kv), ("wv", kv))}
    x = jnp.asarray(rs.randn(2, 5, d), jnp.float32)
    gq, gk = rs.uniform(0.5, 1.5, hd), rs.uniform(0.5, 1.5, hd)
    per_head = {**lp, "q_norm": {"scale": jnp.asarray(gq, jnp.float32)},
                "k_norm": {"scale": jnp.asarray(gk, jnp.float32)}}
    q, k, v = trunk.qkv_projections(per_head, x, heads)
    raw_q = np.asarray(x @ lp["wq"]).reshape(2, 5, heads, hd)
    want_q = raw_q / np.sqrt((raw_q ** 2).mean(-1, keepdims=True) + 1e-5) * gq
    np.testing.assert_allclose(np.asarray(q), want_q, rtol=1e-5, atol=1e-6)
    raw_k = np.asarray(x @ lp["wk"]).reshape(2, 5, kv, hd)
    want_k = raw_k / np.sqrt((raw_k ** 2).mean(-1, keepdims=True) + 1e-5) * gk
    np.testing.assert_allclose(np.asarray(k), want_k, rtol=1e-5, atol=1e-6)
    whole = {**lp, "q_norm": {"scale": jnp.tile(per_head["q_norm"]["scale"], heads)},
             "k_norm": {"scale": jnp.tile(per_head["k_norm"]["scale"], kv)}}
    q_whole, _, _ = trunk.qkv_projections(whole, x, heads)
    assert np.abs(np.asarray(q_whole) - want_q).max() > 0.05


# ---- (b) the shares add up ----


def _layer_of_all_experts(seed=5, d=32, f=16, experts=32, k=4, n=96):
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
    sizes = dict(SIZES, experts_per_token=k, held=None,
                 mlp_layer_types=["sparse"], layer_types=["full_attention"])
    # loads levelled, as the set-up leaves them: no share's buffer overflows
    m = reference.rms(h, lp["ln2"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    moe["router_bias"], _ = moe_dispatch.level_bias(
        jax.nn.sigmoid(m @ moe["gate"]), moe["router_bias"], k)
    return lp, h, sizes


def _share_of(moe, first, held):
    cut = {k: moe[k][first:first + held] for k in ("w_gate", "w_up", "w_down")}
    return {**moe, **cut}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts all 16 shares give (each its own 2 of the 32
    experts, through the program's share path), with the shared expert
    counted once, equal the uncut reference's layer; so do the
    reference's own shares."""
    lp, h, sizes = _layer_of_all_experts()
    d, experts, held, k = h.shape[-1], 32, 2, 4
    want, _, _ = reference.ffn_part(lp, h, sizes, 0)
    m = reference.rms(h, lp["ln2"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    total = trunk.gated_mlp(lp["shared"], m)  # what every chip computes alike: once
    ref_total = reference.gated(lp["shared"], m, lambda a: a)
    for j in range(experts // held):
        share = ShardedMixtureOfExperts(
            _one_device_mesh(), hidden_dim=d, num_experts=experts, k=k,
            dtype=jnp.float32, ffn_dim=16, expert_kind="gated_silu",
            routing="dropless", router_score="sigmoid", router_bias=True,
            routed_scale=2.5, held_experts=held, first_held_expert=j * held)
        part, aux = jax.jit(share)(_share_of(lp["moe"], j * held, held), m)
        assert float(aux["dropped_fraction"]) == 0.0, j
        total = total + part
        ref_total = ref_total + reference.routed_part(
            _share_of(lp["moe"], j * held, held), m,
            dict(sizes, held=(j * held, held)))
    scale = np.abs(np.asarray(want - h)).max()
    np.testing.assert_allclose(
        np.asarray(h + total.reshape(h.shape)), np.asarray(want), rtol=0,
        atol=1e-5 * scale)
    np.testing.assert_allclose(
        np.asarray(h + ref_total.reshape(h.shape)), np.asarray(want), rtol=0,
        atol=1e-5 * scale)


def test_a_share_leaves_out_the_absent_experts_and_does_not_renormalise():
    """One share's part is the uncut layer's routed sum restricted to the
    held experts, gates as normalised over all k chosen: a share that
    renormalised over the experts it holds would read otherwise."""
    lp, h, sizes = _layer_of_all_experts()
    d = h.shape[-1]
    m = reference.rms(h, lp["ln2"]["scale"], sizes["norm_eps"]).reshape(-1, d)
    _, _, chosen, gates = reference.router(lp["moe"], m, sizes)
    held = slice(8, 16)
    share = ShardedMixtureOfExperts(
        _one_device_mesh(), hidden_dim=d, num_experts=32, k=4,
        dtype=jnp.float32, ffn_dim=16, expert_kind="gated_silu",
        routing="dropless", router_score="sigmoid", router_bias=True,
        routed_scale=2.5, held_experts=8, first_held_expert=8)
    part, aux = jax.jit(share)(_share_of(lp["moe"], 8, 8), m)
    want = sum(
        gates[:, e:e + 1] * reference.gated(
            {k: lp["moe"][k][e] for k in ("w_gate", "w_up", "w_down")}, m,
            lambda a: a)
        for e in range(held.start, held.stop))
    np.testing.assert_allclose(np.asarray(part), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
    here = np.asarray(chosen[:, held].sum())
    assert float(aux["local_rows_over_level"]) == pytest.approx(
        here / (m.shape[0] * 4 * 8 / 32))
    tokens_with_none_here = int((np.asarray(chosen[:, held]).sum(-1) == 0).sum())
    assert tokens_with_none_here > 0
    assert not np.asarray(part)[np.asarray(chosen[:, held]).sum(-1) == 0].any()


# ---- (c) the selection bias ----


def test_the_bias_selects_and_does_not_weigh():
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(64, 16), jnp.float32)
    bias = jnp.zeros(16).at[3].set(10.0).at[5].set(-10.0)
    _, w0, i0 = moe_dispatch.router_choice(logits, 4, True, "sigmoid", None, 2.5)
    gates, w, i = moe_dispatch.router_choice(logits, 4, True, "sigmoid", bias, 2.5)
    assert (np.asarray(i) == 3).any(axis=1).all()  # followed in the choice
    assert not (np.asarray(i) == 5).any()
    assert (np.asarray(i0) == 5).any()
    s = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(s, np.asarray(i), axis=1)
    np.testing.assert_allclose(  # ignored in the gates
        np.asarray(w), 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-6)
    zero = moe_dispatch.router_choice(logits, 4, True, "sigmoid", jnp.zeros(16), 2.5)
    np.testing.assert_array_equal(np.asarray(zero[2]), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(zero[1]), np.asarray(w0))
    with pytest.raises(ValueError, match="sigmoid"):
        moe_dispatch.router_choice(logits, 4, True, "softmax", bias)


def test_softmax_choice_is_the_routing_it_was():
    rs = np.random.RandomState(2)
    logits = jnp.asarray(rs.randn(40, 8), jnp.float32)
    gates, w, i = moe_dispatch.router_choice(logits, 2, False)
    want_w, want_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 2)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)
    plan = moe_dispatch.dropless_routing(logits, 2, False)
    assert int(plan.group_sizes.sum()) == 80


@pytest.mark.parametrize("rate", [0.0, 1e-3])
def test_the_step_moves_the_bias_by_the_rule_alone(tiny, rate):
    """No gradient reaches the bias and what the optimizer makes of a zero
    gradient is discarded: after a step it is where the balancing rule
    puts it, ``rate * sign(mean - count)`` from where it was."""
    import optax

    model, cfg, params, ids, tgt = tiny
    model = DMoETransformerLM(
        dataclasses.replace(cfg, router_bias_rate=rate), model.mesh)
    optimizer = optax.adamw(1e-2, weight_decay=0.1)  # decays EVERY leaf it is given
    before = [np.asarray(lp["moe"]["router_bias"]) for lp in params["layers"][1:]]
    counts = np.asarray(jax.jit(model.loss_fn)(params, ids, tgt)[1]["expert_counts"])
    step = jax.jit(
        model.make_train_step(optimizer).__wrapped__)  # no donation: params is shared
    new, _, _, metrics = step(params, optimizer.init(params), ids, tgt)
    assert "expert_counts" not in metrics and "router_bias_abs_max" in metrics
    for j, (b, lp) in enumerate(zip(before, new["layers"][1:])):
        moved = np.asarray(lp["moe"]["router_bias"]) - b
        np.testing.assert_allclose(
            moved, rate * np.sign(counts[j].mean() - counts[j]), atol=1e-7)
    gate_moved = np.asarray(new["layers"][1]["moe"]["gate"]
                            - params["layers"][1]["moe"]["gate"])
    assert np.abs(gate_moved).max() > 0


def test_the_rule_levels_uneven_loads():
    """``level_bias`` on scores that send everything to a few experts:
    the largest load over the mean falls to near 1, the bias it returns is
    the one that reads it, and experts that drew too much were moved down."""
    rs = np.random.RandomState(4)
    favour = np.linspace(1.0, -1.0, 16)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(2048, 16) * 0.5 + favour, jnp.float32))
    bias, (before, after) = moe_dispatch.level_bias(scores, jnp.zeros(16), 4)
    assert before > 2.5 and after < 1.2, (before, after)
    _, top = jax.lax.top_k(scores + bias, 4)
    counts = np.bincount(np.asarray(top).ravel(), minlength=16)
    assert counts.max() * 16 / (2048 * 4) == pytest.approx(after)
    assert bias[0] < 0 < bias[-1]
    moved = moe_dispatch.balanced_bias(jnp.zeros(4), jnp.asarray([5, 1, 3, 3]), 0.5)
    np.testing.assert_array_equal(np.asarray(moved), [-0.5, 0.5, 0.0, 0.0])


def test_set_up_levels_every_mixture_layer_on_the_pool(tiny):
    model, cfg, params, ids, _ = tiny
    levelled, loads = model.level_router_bias(params, [ids, ids[::-1]])
    assert len(loads) == 4 and all(after <= before for before, after in loads)
    assert max(after for _, after in loads) < 1.3, loads
    metrics = jax.jit(model.loss_fn)(levelled, ids, ids)[1]
    assert float(metrics["expert_load_max_over_mean"]) < 1.5
    assert 0.5 < float(metrics["local_rows_over_level"]) < 1.5
    for old, new in zip(params["layers"], levelled["layers"]):
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(old)[0],
                                jax.tree_util.tree_leaves(new)):
            same = np.array_equal(np.asarray(a), np.asarray(b))
            assert same != jax.tree_util.keystr(path).endswith("['router_bias']")


# ---- (d) the buffer ----


@pytest.mark.parametrize("n, k, held, experts, rows", [
    (16384, 8, 8, 128, 16384),  # the cell: twice the level share is S
    (64, 4, 4, 16, 128), (96, 4, 2, 32, 48), (1000, 8, 8, 128, 1024),
    (40, 2, 3, 4, 80),  # never more than every assignment
])
def test_the_buffer_is_twice_the_level_share(n, k, held, experts, rows):
    assert moe_dispatch.share_buffer_rows(n, k, held, experts) == rows


def test_overflow_is_counted_and_a_level_batch_drops_nothing():
    rs = np.random.RandomState(6)
    n, experts, k, first, held = 64, 16, 4, 4, 4
    rows = moe_dispatch.share_buffer_rows(n, k, held, experts)
    level = jnp.asarray(rs.randn(n, experts), jnp.float32)
    plan = moe_dispatch.share_routing(level, k, first, held, rows, score="sigmoid")
    here = int(plan.routed_here)
    assert 0 < here <= rows and int(plan.group_sizes.sum()) == here
    assert int(plan.valid.sum()) == here and int(plan.counts.sum()) == n * k
    assert not np.asarray(plan.weight)[here:].any()
    # every token to the held experts: 256 assignments, a buffer of 128
    crowded = level.at[:, first:first + held].add(20.0)
    plan = moe_dispatch.share_routing(crowded, k, first, held, rows, score="sigmoid")
    assert int(plan.routed_here) == n * k and int(plan.group_sizes.sum()) == rows
    assert np.asarray(plan.valid).all()
    np.testing.assert_array_equal(np.asarray(plan.group_sizes), [64, 64, 0, 0])
    moe = ShardedMixtureOfExperts(
        _one_device_mesh(), hidden_dim=8, num_experts=experts, k=k,
        dtype=jnp.float32, ffn_dim=8, expert_kind="gated_silu",
        routing="dropless", router_score="sigmoid", held_experts=held,
        first_held_expert=first)
    params = moe.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(rs.randn(n, 8), jnp.float32)
    gate = np.zeros((8, experts), np.float32)
    gate[:, first:first + held] = 1.0  # crowds whatever has a positive sum
    _, aux = jax.jit(moe)({**params, "gate": jnp.abs(jnp.asarray(gate))}, jnp.abs(x))
    assert float(aux["dropped_fraction"]) == 0.5
    assert float(aux["local_rows_over_level"]) == 4.0
    _, aux = jax.jit(moe)(params, x)
    assert float(aux["dropped_fraction"]) == 0.0


def test_the_share_path_equals_masked_dense_experts_and_its_gradients():
    """Sort, grouped matmul and scatter-add against a loop over the held
    experts with a mask, forward and gradients, empty buffer rows and all."""
    rs = np.random.RandomState(8)
    n, d, f, experts, k, first, held = 48, 16, 8, 16, 4, 8, 4
    moe = ShardedMixtureOfExperts(
        _one_device_mesh(), hidden_dim=d, num_experts=experts, k=k,
        dtype=jnp.float32, ffn_dim=f, expert_kind="gated_silu",
        routing="dropless", router_score="sigmoid", router_bias=True,
        routed_scale=2.5, held_experts=held, first_held_expert=first)
    params = moe.init_params(jax.random.PRNGKey(1))
    params = {**params, "gate": params["gate"] * 50}
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    sizes = dict(SIZES, experts_per_token=k, held=(first, held))

    @jax.jit
    def program(p, x):
        return (moe(p, x)[0] ** 2).sum()

    def plain(p, x):
        return (reference.routed_part(p, x, sizes) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(jax.jit(moe)(params, x)[0]),
        np.asarray(reference.routed_part(params, x, sizes)), atol=1e-5)
    got = jax.jit(jax.grad(program, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.grad(plain, argnums=(0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4 * max(np.abs(b).max(), 1e-6))


# ---- refusals, the other paths ----


@pytest.mark.parametrize("changes, error, match", [
    ({"ffn_pattern": ("dense",) * 4}, ValueError, "each of the 5"),
    ({"ffn_pattern": ("dense",) * 5}, ValueError, "has no router"),
    ({"dense_ffn_dim": None}, ValueError, "dense_ffn_dim"),
    ({"qk_norm": "heads"}, ValueError, "qk_norm"),
    ({"routing": "capacity"}, NotImplementedError, "sigmoid"),
    ({"router_score": "softmax"}, ValueError, "selection bias"),
    ({"held_experts": 17}, ValueError, "not among"),
    ({"first_held_expert": 13}, ValueError, "not among"),
    ({"expert_kind": "gelu"}, ValueError, "gated"),
])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(
        tiny, changes, error, match):
    _, cfg, _, _, _ = tiny
    with pytest.raises(error, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), _one_device_mesh())


def test_a_stack_of_dense_layers_alone_builds_and_steps(tiny):
    """No 'moe' layer at all (the refusal went with PR 45): the same block
    with a dense feed-forward part in every layer and no selection bias
    builds no router and no expert, and its step's loss is the
    cross-entropy alone."""
    _, cfg, _, ids, tgt = tiny
    cfg = dataclasses.replace(
        cfg, ffn_pattern=("dense",) * 5, router_bias=False, shared_experts=0)
    model = DMoETransformerLM(cfg, _one_device_mesh())
    assert model.moe is None and cfg.mixture_layers() == 0
    params = model.init_params(jax.random.PRNGKey(2))
    assert all("ffn" in lp and "moe" not in lp for lp in params["layers"])
    from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

    optimizer = fused_adafactor(1e-3)
    opt_state = model.init_opt_state(optimizer, params)
    step = model.make_train_step(optimizer)
    losses = []
    for _ in range(4):
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        losses.append(float(loss))
    assert set(metrics) == {"ce"} and float(metrics["ce"]) == losses[-1]
    assert losses[-1] < losses[0]


def test_a_share_across_chips_and_the_cached_decoder_refuse_by_name(tiny):
    _, cfg, params, ids, _ = tiny
    mesh = make_mesh({"expert": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="ragged all-to-all"):
        DMoETransformerLM(cfg, mesh)
    model = DMoETransformerLM(cfg, _one_device_mesh())
    with pytest.raises(NotImplementedError, match="share of the experts"):
        model.generate(params, ids[:, :4], 2, use_cache=True)
    out = model.generate(params, ids[:1, :4], 2)  # the full forward decodes
    assert out.shape == (1, 6)


def test_layout_check_takes_a_dense_layer_and_a_share(tiny):
    """``chip_smoke._check_layout`` (and the runner's, which is its copy)
    accept a layer whose feed-forward part is dense and expert stacks
    smaller than the router's width; a step runs on a data mesh."""
    import chip_smoke
    from learning_at_home_tpu.parallel.mesh import batch_sharding

    mesh = make_mesh({"data": 2, "expert": 1}, devices=jax.devices()[:2])
    model, cfg, optimizer, batch = k_exaone_one_chip(mesh, tiny=True)
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = model.init_opt_state(optimizer, params)
    layout = chip_smoke._check_layout(model, params, opt_state, optimizer, mesh)
    assert layout["expert_param_bytes"] == 4 * 3 * 4 * 64 * 24 * 4
    ids = jax.device_put(
        jnp.asarray(np.random.RandomState(0).randint(0, 256, (batch, 33))),
        batch_sharding(mesh))
    _, _, loss, metrics = model.make_train_step(optimizer)(
        params, opt_state, ids[:, :-1], ids[:, 1:])
    assert np.isfinite(float(loss)) and float(metrics["dropped_fraction"]) == 0.0


def test_a_stack_that_holds_every_expert_scores_by_sigmoid_too(tiny):
    """``held_experts=None``: the dropless path that holds all the experts
    takes the sigmoid router, the bias and a shared expert as they are,
    and agrees with the reference given every expert."""
    _, cfg, _, ids, _ = tiny
    whole = dataclasses.replace(cfg, held_experts=None)
    model = DMoETransformerLM(whole, _one_device_mesh())
    params = _decisive(model.init_params(jax.random.PRNGKey(2)))
    logits, aux = jax.jit(model.apply)(params, ids)
    want, _, _ = reference.forward(params, ids, dict(SIZES, held=None))
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(want), atol=1e-4 * np.abs(want).max())
    assert "local_rows_over_level" not in aux and aux["expert_counts"].shape == (4, 16)


# ---- the benchmark's files ----


def test_flops_of_the_cell_are_the_issue_arithmetic():
    """2.79 GFLOP a token forward by part, as ISSUE.md reckons them."""
    parts = kexaone_flops.forward_flops_per_token(CELL_FILE)
    giga = {k: round(v / 1e9, 2) for k, v in parts.items()}
    assert giga == {"projections": 1.13, "attention_core": 0.29, "dense_ffn": 0.68,
                    "shared_expert": 0.30, "router": 0.01, "routed_experts": 0.15,
                    "head": 0.24}
    assert round(sum(parts.values()) / 1e9, 2) == 2.79
    assert kexaone_flops.train_flops_per_token(CELL_FILE) == 3 * sum(parts.values())
    assert kexaone_flops.level_rows_per_token(CELL_FILE) == 0.5
    assert kexaone_flops.counted_rows(CELL_FILE, 16384, 1.0) == 8192  # half the buffer
    assert kexaone_flops.grouped_matmul_flops(CELL_FILE, 16384, 1.25) == (
        2 * 10240 * 6144 * 2048)
    more = kexaone_flops.forward_flops_per_token(CELL_FILE, 1.5)
    assert more["routed_experts"] == 1.5 * parts["routed_experts"]
    assert kexaone_flops.admitted_scores(16384, 128) == 128 * 129 // 2 + 16256 * 128
    window = kexaone_flops.attention_kernel_flops(CELL_FILE, 16384, "window", "forward")
    assert window == 64 * kexaone_flops.admitted_scores(16384, 128) * 2 * 128 * 2


def test_parameters_of_the_cell_are_the_issue_arithmetic():
    """2.504 B parameters: the issue's count, from the recipe's shapes."""
    model, cfg, _, batch = k_exaone_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    attention = 2 * 6144 * 8192 + 2 * 6144 * 1024
    dense, sparse = shapes["layers"][0], shapes["layers"][1]
    assert count(dense) == attention + 3 * 6144 * 18432 + 2 * 6144 + 2 * 128
    assert count(sparse) == (attention + 9 * 3 * 6144 * 2048 + 6144 * 128 + 128
                             + 2 * 6144 + 2 * 128)
    assert count(shapes) == 2_504_068_864
    assert (cfg.seq_len, cfg.vocab_size, batch) == (16384, 19200, 1)
    runner._check_sizes(CELL_FILE, cfg)


def test_configuration_file_carries_the_catalog_entry():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the three in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if json.loads(line)["name"] == "K-EXAONE-236B-A23B")
    assert CELL_FILE["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CELL_FILE.get(k) != v}
    assert differs == {"num_experts", "vocab_size"}
    assert CELL_FILE["reduced"] == ["n_layers", "num_experts", "vocab_size"]
    assert (CELL_FILE["num_experts_published"], CELL_FILE["vocab_size_published"],
            CELL_FILE["num_hidden_layers"]) == (128, 153600, 48)
    assert CELL_FILE["num_experts"] * CELL_FILE["chips_sharing_a_layers_experts"] == 128
    assert CELL_FILE["vocab_size"] * CELL_FILE["chips_sharing_the_vocabulary"] == 153600
    assert CELL_FILE["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert CELL_FILE["not_built"] and any("next" in a or "prediction" in a
                                          for a in CELL_FILE["not_built"])


def test_reducers_read_the_counted_rows_and_nothing_where_there_is_none():
    """The new metrics' readers: operations at the rows the step counted;
    ``None`` (the metric is left out) on a program without the counter."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "reducers"))
    mfu = harness.load_path(os.path.join(
        REPO, "benchmarks", "reducers", "mfu_counted_rows.py"))
    roofline = harness.load_path(os.path.join(
        REPO, "benchmarks", "reducers", "grouped_matmul_counted_roofline.py"))
    obs = {"tokens_per_s_per_chip": 8000.0, "device_kind": "TPU v5 lite",
           "sizes": CELL_FILE, "tokens_per_step_per_chip": 16384,
           "local_rows_over_level": [1.0, 1.5],
           "scopes": {"grouped_matmul_s": 0.2, "grouped_matmul_calls": 48}}
    args = {"module": "kexaone_flops", "function": "train_flops_per_token"}
    want = kexaone_flops.train_flops_per_token(CELL_FILE, 1.25) * 8000 / 197e12
    assert mfu.reduce(obs, **args) == pytest.approx(100 * want)
    per_call = 2 * 8192 * 1.25 * 6144 * 2048
    assert roofline.reduce(obs, module="kexaone_flops") == pytest.approx(
        100 * 48 * per_call / (0.2 * 197e12))
    older = {k: v for k, v in obs.items() if k != "local_rows_over_level"}
    assert mfu.reduce(older, **args) is None
    assert roofline.reduce(older, module="kexaone_flops") is None
    assert roofline.reduce(dict(obs, scopes={}), module="kexaone_flops") is None


def test_the_swarm_cells_load_none_of_the_pod_step():
    """What the ``ffnserver`` cells import of the package (the server's
    side of ``runners/expert_server.py``, the clients' of
    ``runners/swarm_clients.py``) holds no module of the pod train step: an
    edit to the step, such as this configuration's, is not code those two
    cells run, and a reading that moves there is not this step's."""
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    code = (
        "import sys\n"
        "from learning_at_home_tpu.utils.chip import enable_compile_cache\n"
        "from learning_at_home_tpu.server import Server\n"
        "from learning_at_home_tpu.client import RemoteExpert\n"
        "from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts\n"
        "from learning_at_home_tpu.client.routing import StaticExpertSource\n"
        "from learning_at_home_tpu.models import make_expert\n"
        "print(sorted(m for m in sys.modules if m.startswith('learning_at_home_tpu.')))\n"
    )
    run = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env=clean_jax_subprocess_env(REPO, platform="cpu"),
        capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]
    loaded = ast.literal_eval(run.stdout.strip().splitlines()[-1])
    assert "learning_at_home_tpu.server.server" in loaded
    pod_step = ("learning_at_home_tpu.models.transformer",
                "learning_at_home_tpu.models.trunk",
                "learning_at_home_tpu.ops", "learning_at_home_tpu.parallel")
    assert [m for m in loaded if m.startswith(pod_step)] == []


# ---- (f) the chip's compiler accepts the step at published widths ----


def test_the_whole_step_fits_the_chip(v5e_chip, monkeypatch):
    """The 5-layer train step at published widths, compiled for a
    described chip (nothing runs): 2.504 B parameters, the compiler's own
    count of what is live in the step between a quarter of the chip's
    memory (the benchmark's floor for a cell) and all of it, and every
    grouped matmul of the four mixture layers at the tile rule's answers
    for 6144 x 2048 over a buffer of 16,384 rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = probe.step_memory(v5e_chip, "k_exaone_one_chip")
    assert memory["parameters"] == 2_504_068_864
    assert 0.25 < memory["share_of_chip"] < 0.9, memory
    assert memory["grouped_matmul_tilings"] == {
        "256,2048,1024": 4 * 9, "256,1024,1024": 4 * 3}
    assert memory["loss_layer_products"] == 3  # of the head's; four before PR 34
    assert moe_dispatch.grouped_matmul_tiles(16384, 6144, 2048, jnp.bfloat16) == (
        256, 2048, 1024)
    # the blocked kernel's tiles read the window (PR 36): the four window
    # layers' backward is a dK/dV and a dQ kernel (none of the latter in
    # the step before), the global layer's the fused one
    # and one forward a kernel layer (10 before PR 38): remat keeps the
    # kernel's output and row sums, 273 MB a layer, so the recompute holds
    # no forward call.  ``attention_kernel_calls`` reads the compiled
    # step's instructions, ``attention_kernel_tilings`` the traced step's
    # equations: the policy takes the call out before the compiler sees it
    assert memory["attention_kernel_calls"] == {
        "splash_mha_fwd_residuals": 5,
        "splash_mha_dkv_no_residuals": 5, "splash_mha_dq_no_residuals": 4}
    assert memory["kept_residual_bytes"] == 5 * 64 * 16384 * (128 * 2 + 4)
    # and the results of the attention part's products (PR 53): q, k, v and
    # the output projection's, bf16 [16384, 8192 + 1024 + 1024 + 6144] a
    # layer, 2.68 GB (13.79 GB live, 81.6 %, from 12.18: the band above
    # holds it), and the backward pass runs none of the four a second time
    assert memory["kept_product_bytes"] == 5 * 16384 * (8192 + 2 * 1024 + 6144) * 2
    assert memory["recomputed_attention_products"] == 0
    # the optimized HLO's instructions carry the attention part's stages
    # (PR 52), and the kernel's calls sit under ``flash``, not its ``layout``
    stages = memory["attention_stages"]
    assert stages["stages"] == [
        "flash", "flash/layout", "norm", "out_proj", "proj", "qk_norm", "rope"]
    assert stages["kernel_scopes"] == [
        f"attention/flash/vmap(jit(_splash_attention))/{name}/{name}"
        for name in ("splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals",
                     "splash_mha_fwd_residuals")]
    tilings = memory["attention_kernel_tilings"]
    assert {kind: {name: call["calls"] for name, call in calls.items()}
            for kind, calls in tilings.items()} == {
        "global": {"splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1},
        "window": {"splash_mha_fwd_residuals": 4, "splash_mha_dkv_no_residuals": 4,
                   "splash_mha_dq_no_residuals": 4}}
    want = trunk.flash_block_sizes((1, 16384, 64, 128), "tpu", 128)
    assert [(call["block_q"], call["block_kv"]) for call in tilings["window"].values()] == [
        (want.block_q, want.block_kv), (want.block_q_dkv, want.block_kv_dkv),
        (want.block_q_dq, want.block_kv_dq)]
    assert all(call["block_kv"] == 512 for call in tilings["window"].values())
    # a window layer's key-block axis is the two blocks its mask admits (a
    # query block's own and the one before), not the 32 of the sequence
    assert [call["grid"][-1] for call in tilings["window"].values()] == [2, 2, 2]
    # the queries' gradient once a key block of 1024, [16, 64, 16384, 128]
    # bf16, is the fused backward's: the global layer keeps it, and no
    # kernel of a window layer writes anything near it
    partials = 16 * 64 * 16384 * 128 * 2
    assert tilings["global"]["splash_mha_dkv_no_residuals"]["largest_result_bytes"] == partials
    assert all(call["largest_result_bytes"] <= partials // 8
               for call in tilings["window"].values())
