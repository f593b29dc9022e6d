"""Ling-3.0-flash-VL's language model in the pod step as one chip's share
(``__graft_entry__.ling_3_0_flash_one_chip``) against its plain reference
(``benchmarks/configs/ling_3_0_flash_vl_reference.py``): Kimi Delta
Attention (a delta rule whose decay is a number a key channel) beside gated
latent attention with no query latent in ONE stack, a dense leading layer,
and a sigmoid router that chooses inside the best groups of its experts;
the share (eight of them add up to the uncut layer); the forms that were
refusals; the refusals that stay; the benchmark's files for it (the
runner's limits: ``tests/test_ling3_runner.py``, a module of its own so
that ``--dist loadfile`` can give it a worker).  Tiny sizes on the CPU,
float32."""

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
import ling3_flops  # noqa: E402

from __graft_entry__ import ling_3_0_flash_one_chip  # noqa: E402
from benchmark_cells import layer_metric_file, readings_of_cell  # noqa: E402
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import (  # noqa: E402
    AttentionLayer,
    DMoETransformerLM,
)
from learning_at_home_tpu.ops import moe_dispatch  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "ling_3_0_flash_vl_reference.py"))
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_ling3.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "ling3-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "ling-3.0-flash-vl.json"))
CELL = "ling-3.0-flash-vl-train-zipf16k"
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference


def _one_device_mesh():
    return make_mesh({"expert": 1}, devices=jax.devices()[:1])


def _decisive(params, seed=7):
    """Seeded weights under which every part decides: a router that decides
    (the program's init gives near-equal scores), selection biases off zero,
    norm scales off 1."""
    rs = np.random.RandomState(seed)

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            return a * jnp.asarray(rs.uniform(0.5, 1.5, a.shape), a.dtype)
        if name.endswith("['router_bias']"):
            return jnp.asarray(rs.uniform(-0.2, 0.2, a.shape), a.dtype)
        return a * (20.0 if name.endswith("['gate']") else 1.0)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    model, cfg, _, batch = ling_3_0_flash_one_chip(_one_device_mesh(), tiny=True)
    params = _decisive(model.init_params(jax.random.PRNGKey(11)))
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len + 1)))
    return model, cfg, params, ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def want(tiny):
    """The reference on the tiny weights, once a module: logits, the stream
    after every layer, the loss, the gradients."""
    _, _, params, ids, tgt = tiny

    def everything(p):
        x = reference.embed(p, ids)
        streams = []
        for index, lp in enumerate(p["layers"]):
            x, _, _ = reference.layer(lp, x, SIZES, index)
            streams.append(x)
        return reference.head(p, x, SIZES), streams

    logits, streams = jax.jit(everything)(params)
    loss, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)
    return logits, streams, loss, grads


def _close(got, want, tol=1e-4, **kw):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=0, atol=tol * np.abs(want).max(), **kw)


# ---- (a) the program against the reference ----


def test_the_tiny_recipe_keeps_the_stack(tiny):
    """What ``tiny`` must keep of the published stack, and the rehearsal
    file's sizes are the tiny recipe's (the runner's own check)."""
    _, cfg, params, _, _ = tiny
    kinds = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    assert [k.mixer for k in kinds] == ["delta", "delta", "softmax", "delta"]
    assert [k.rotary for k in kinds] == [False, False, True, False]
    assert cfg.ffn_pattern == ("dense", "moe", "moe", "moe")
    assert cfg.q_latent_dim is None and cfg.kv_latent_dim == 32
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_head_dim) == (24, 16, 8)
    assert cfg.attention_gate == "head" and cfg.delta_decay_floor == -5.0
    assert cfg.router_groups == (4, 2) and cfg.held_experts * 4 == cfg.num_experts
    first, latent = params["layers"][0], params["layers"][2]
    assert "ffn" in first and "moe" not in first
    assert first["delta"]["w_decay"].shape == (48, 4 * 8)  # full rank, a channel
    assert first["delta"]["dt_bias"].shape == (4 * 8,) and first["delta"]["A_log"].shape == (4,)
    assert first["delta"]["w_gate"].shape == first["delta"]["w_beta"].shape == (48, 4)
    assert first["delta"]["w_in"].shape == (48, 3 * 4 * 8)  # [q | k | v]
    assert "wq_a" not in latent and latent["wq"].shape == (48, 4 * 24)
    assert latent["w_gate"].shape == (48, 4)
    assert latent["wkv_b"].shape == (32, 4 * (16 + 16))  # [k_nope 16 | v 16] a head
    runner._check_sizes(TINY_FILE, cfg)


def test_the_stream_after_every_layer_matches_the_reference(tiny, want):
    model, cfg, params, ids, _ = tiny
    x = params["embed"][ids]
    for index, lp in enumerate(params["layers"]):
        x, _ = jax.jit(model._layer, static_argnums=(2, 4))(
            lp, x, index, None, cfg.attention_layer(index))
        _close(x, want[1][index], 2e-5, err_msg=f"layer {index}")


def test_logits_and_loss_match_the_reference(tiny, want):
    model, _, params, ids, tgt = tiny
    got = jax.jit(model.apply)(params, ids)
    _close(got[0] if isinstance(got, tuple) else got, want[0], 2e-5)
    loss, metrics = jax.jit(model.loss_fn)(params, ids, tgt)
    assert abs(float(loss) - float(want[2])) < 2e-6 * abs(float(want[2]))
    assert float(metrics["dropped_fraction"]) == 0.0
    assert 0.0 < float(metrics["groups_reaching_share"]) < 1.0
    # the floor itself where a gate saturates (float32's sigmoid reads 1
    # from an argument of 17, and exp(A_log) is up to 16)
    assert np.exp(-5.0) * (1 - 1e-6) <= float(metrics["delta_decay_min"]) < 1.0
    assert 0.0 < float(metrics["delta_beta_max"]) <= 1.0


def test_gradients_of_every_parameter_match_the_reference(tiny, want):
    model, _, params, ids, tgt = tiny
    got = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0]))(params)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want[3])):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):  # selects, never weighs
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
            continue
        assert np.abs(np.asarray(w)).max() > 0, name
        _close(g, w, 2e-4, err_msg=name)


def test_a_latent_layer_with_no_query_latent_and_a_gate_matches_the_reference(tiny):
    """The form ``DMoETransformerLM.__init__`` refused (q_latent_dim None
    beside a kv_latent_dim) and the gate that was refused beside it, alone:
    queries one plain product, the head-wise gate on the output; and without
    the gate the reference's ungated form."""
    model, cfg, params, _, _ = tiny
    lp = params["layers"][2]
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.normal(0, 1, (2, 64, 48)), jnp.float32)
    kind = cfg.attention_layer(2)
    h, read, extremes = jax.jit(model._attention_part, static_argnums=(2,))(lp, x, kind)
    _close(h - x, reference.latent_mixer(lp, x, SIZES), 2e-5)
    assert 0.0 < float(extremes["attention_gate_mean"]) < 1.0
    ungated = {k: v for k, v in lp.items() if k != "w_gate"}
    h0, _, none = jax.jit(model._attention_part, static_argnums=(2,))(ungated, x, kind)
    _close(h0 - x, reference.latent_mixer(lp, x, SIZES, gated=False), 2e-5)
    assert not none and float(jnp.abs(h0 - h).max()) > 1e-3
    q, k, v = trunk.latent_qkv_projections(
        lp, read, cfg.n_heads, jnp.arange(64), cfg.rope_theta, cfg.norm_eps)
    assert q.shape == k.shape == (2, 64, 4, 24) and v.shape == (2, 64, 4, 16)
    assert trunk.head_gate(lp, read).shape == (2, 64, 4, 1)


def test_a_kda_layer_over_a_dense_block_matches_the_reference(tiny):
    """A leading dense layer whose mixer is the delta rule: the mixer's
    output and its state after the last position, then the block."""
    model, cfg, params, _, _ = tiny
    lp = params["layers"][0]
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.normal(0, 1, (2, 64, 48)), jnp.float32)
    out, state, decay_min, beta_max = jax.jit(
        lambda p, x: trunk.delta_mixer(
            p, model._norm(lp["ln1"], x), cfg.n_heads, cfg.delta_chunk,
            cfg.norm_eps, neg_eigval=False, decay_floor=-5.0))(lp["delta"], x)
    want_out, want_state = reference.kda_part(lp, x, SIZES)
    _close(out, want_out, 2e-5)
    _close(state, want_state, 2e-5)
    assert np.exp(-5.0) * (1 - 1e-6) <= float(decay_min) < 1.0
    assert 0.0 < float(beta_max) <= 1.0
    y, aux = model._layer(lp, x, 0, None, cfg.attention_layer(0))
    _close(y, reference.layer(lp, x, SIZES, 0)[0], 2e-5)
    assert set(aux) == {"delta_decay_min", "delta_beta_max"}


def test_the_bounded_gate_stays_inside_its_bound(tiny):
    """``g = -5 sigmoid(exp(A_log)(f + dt_bias))`` lies in (-5, 0) whatever
    the projection gives, in the program and in the reference alike."""
    _, cfg, params, _, _ = tiny
    p = jax.tree_util.tree_map(lambda a: a * 50.0, params["layers"][0]["delta"])
    a = jnp.asarray(np.random.RandomState(1).normal(0, 3, (1, 64, 48)), jnp.float32)
    g = reference.kda_decay(reference._f32(p), a, SIZES)
    assert g.shape == (1, 64, 4, 8)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01  # it saturates


def test_the_seeded_gates_cover_the_bounds_whole_range(tiny):
    """``dt_bias`` is drawn so that the gate at rest is uniform over the
    middle of (-5, 0) a channel: on a unit stream the seeded log-decays
    fill the range, its middle and both ends, in every KDA layer (a gate
    that hardly decays cannot tell a rule whose sums are kept in bf16 from
    the program: PERF.md section 6, PR 66)."""
    _, cfg, params, _, _ = tiny
    a = jnp.asarray(np.random.RandomState(2).normal(0, 1, (1, 256, 48)), jnp.float32)
    for lp in params["layers"]:
        if "delta" not in lp:
            continue
        p = reference._f32(lp["delta"])
        rest = np.asarray(reference.kda_decay(p, 0.0 * a, SIZES))[0, 0]  # f = 0
        assert rest.min() > -4.95 and rest.max() < -0.05
        assert np.histogram(rest, bins=5, range=(-5, 0))[0].min() >= 1
        g = np.asarray(reference.kda_decay(p, a, SIZES)).ravel()
        shares = np.histogram(g, bins=5, range=(-5, 0))[0] / g.size
        # exp(A_log) up to 16 times a unit stream's f saturates most gates
        # a position: the ends hold most, and no fifth of the range is empty
        assert shares.min() > 0.03 and shares[1:4].sum() > 0.1, shares


# ---- (b) the group-limited router ----


def _loop_router(scores, bias, k, n_group, topk_group):
    """A token at a time, written out: the groups' two best, the best
    groups, the k best inside them; ties to the lower index."""
    chosen = []
    for s in np.asarray(scores, np.float32):  # float32, as the router's are
        sel = s + np.asarray(bias, np.float32)
        size = len(sel) // n_group
        group = [np.float32(sum(sorted(sel[g * size:(g + 1) * size], reverse=True)[:2]))
                 for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-group[g], g))[:topk_group]
        inside = [e for e in range(len(sel)) if e // size in kept]
        chosen.append(sorted(inside, key=lambda e: (-sel[e], e))[:k])
    return chosen


@pytest.mark.parametrize("seed, ties", [(0, False), (1, False), (2, True), (3, True)])
def test_the_group_router_is_the_loop_over_tokens(seed, ties):
    """``router_choice`` under groups against the loop: the chosen experts
    in order, their weights (the scores of the chosen over their sum times
    the scale), with scores that tie (quantised to eighths) and without."""
    rs = np.random.RandomState(seed)
    logits = rs.normal(0, 2, (96, 32)).astype(np.float32)
    if ties:
        logits = np.round(logits * 2) / 2
    bias = (np.round(rs.uniform(-0.3, 0.3, 32) * 8) / 8).astype(np.float32)
    gates, top_w, top_i = jax.jit(lambda l, b: moe_dispatch.router_choice(
        l, 4, True, "sigmoid", b, 2.5, 8, 3))(logits, bias)
    s = np.asarray(jax.nn.sigmoid(logits))
    want = _loop_router(s, bias, 4, 8, 3)
    assert np.asarray(top_i).tolist() == want
    chosen = np.take_along_axis(s, np.asarray(top_i), axis=1)
    _close(top_w, 2.5 * chosen / chosen.sum(axis=1, keepdims=True), 1e-6)
    _close(gates, s / s.sum(axis=1, keepdims=True), 1e-6)
    # the reference's restatement chooses the same sets
    sizes = dict(SIZES, experts_per_token=4, n_group=8, topk_group=3)
    picked = reference._best(reference.selection(jnp.asarray(s) + bias, sizes), 4)
    assert [sorted(np.flatnonzero(row)) for row in np.asarray(picked)] == [
        sorted(row) for row in want]


def test_a_bias_moves_a_group_in_and_out():
    """One token, four groups of two, two kept: a bias on ONE expert lifts
    its group past another and every choice moves with it; no gradient
    reaches the bias and the weights never hold it."""
    logits = jnp.log(jnp.asarray(
        [[0.50, 0.45, 0.40, 0.40, 0.30, 0.30, 0.20, 0.10]]) / (1 - jnp.asarray(
            [[0.50, 0.45, 0.40, 0.40, 0.30, 0.30, 0.20, 0.10]])))

    def route(bias):
        return moe_dispatch.router_choice(logits, 3, True, "sigmoid", bias, 1.0, 4, 2)

    _, w0, i0 = route(jnp.zeros(8))
    assert sorted(np.asarray(i0)[0].tolist()) == [0, 1, 2]  # groups 0 and 1
    _, w1, i1 = route(jnp.zeros(8).at[6].set(0.7))  # group 3: 0.9 + 0.1 > 0.8
    assert sorted(np.asarray(i1)[0].tolist()) == [0, 1, 6]  # groups 0 and 3
    _close(w1.sum(), 1.0, 1e-6)
    _close(np.sort(np.asarray(w1)[0]), np.sort([0.5, 0.45, 0.2]) / 1.15, 1e-5)
    grad = jax.grad(lambda b: route(b)[1].sum())(jnp.zeros(8).at[6].set(0.7))
    assert not np.asarray(grad).any()
    kept = moe_dispatch.kept_groups(
        jax.nn.sigmoid(logits) + jnp.zeros(8).at[6].set(0.7), 4, 2)
    assert np.asarray(kept).tolist() == [[True, False, False, True]]


@pytest.mark.parametrize("groups", [(3, 1), (4, 0), (4, 5)])
def test_groups_that_do_not_divide_or_keep_none_are_refused(groups):
    with pytest.raises(ValueError, match="the groups are equal"):
        moe_dispatch.kept_groups(jnp.zeros((2, 8)), *groups)


def test_groups_go_with_a_sigmoid_router():
    with pytest.raises(ValueError, match="groups go with score='sigmoid'"):
        moe_dispatch.router_choice(jnp.zeros((2, 8)), 2, n_group=2, topk_group=1)


def test_levelling_under_the_group_rule_brings_the_loads_down():
    """``level_bias`` with groups: the counts it levels are the group
    rule's, and the largest load over the mean falls."""
    rs = np.random.RandomState(4)
    scores = jax.nn.sigmoid(jnp.asarray(
        rs.normal(0, 1, (2048, 32)) + rs.normal(0, 1, (1, 32)), jnp.float32))
    bias, (before, after) = moe_dispatch.level_bias(
        scores, jnp.zeros(32), 4, n_group=8, topk_group=4)
    assert after < before and after < 1.5
    counts = np.bincount(np.asarray(jax.lax.top_k(moe_dispatch.group_limited(
        scores + bias, 8, 4), 4)[1]).ravel(), minlength=32)  # a sort's answer
    assert abs(counts.max() / counts.mean() - after) < 1e-5


# ---- (c) the share ----


def test_eight_shares_of_a_mixture_layer_add_up_to_the_uncut_layer():
    """The share test: a mixture layer's feed-forward part from EIGHT shares
    (one routing group each), the shared expert counted once, adds up to the
    uncut reference's; a token whose kept groups miss a share gets nothing
    of it but the shared expert."""
    mesh = _one_device_mesh()
    cfg = dataclasses.replace(
        ling_3_0_flash_one_chip(mesh, tiny=True)[1], num_experts=32,
        router_groups=(8, 4), k=4)
    whole = DMoETransformerLM(dataclasses.replace(
        cfg, held_experts=None, first_held_expert=0), mesh)
    lp_whole = _decisive(whole.init_params(jax.random.PRNGKey(5)))["layers"][1]
    rs = np.random.RandomState(2)
    h = jnp.asarray(rs.normal(0, 1, (2, 64, 48)), jnp.float32)
    sizes = dict(SIZES, n_group=8, topk_group=4, held=None)
    y_whole = reference.ffn_part(lp_whole, h, sizes)[0] - h
    m = reference.norm(h, lp_whole["ln2"], sizes["norm_eps"]).reshape(-1, 48)
    shared = reference.gated(
        reference._f32(lp_whole["shared"]), m, lambda a: a).reshape(h.shape)
    total, reaching = 0.0, []
    for first in range(0, 32, 4):
        share = DMoETransformerLM(dataclasses.replace(
            cfg, held_experts=4, first_held_expert=first), mesh)
        moe = {k: (v[first:first + 4] if k in ("w_gate", "w_up", "w_down") else v)
               for k, v in lp_whole["moe"].items()}
        lp = {**lp_whole, "moe": moe}
        out, aux = jax.jit(share._ffn_block, static_argnums=(3,))(lp, h, None, 1)
        want = reference.ffn_part(lp, h, dict(sizes, held=(first, 4)))[0]
        _close(out, want, 2e-5)
        assert float(aux["dropped_fraction"]) == 0.0
        reaching.append(float(aux["groups_reaching_share"]))
        part = np.asarray(out - h - shared)
        kept = np.asarray(moe_dispatch.kept_groups(
            jax.nn.sigmoid(m @ lp["moe"]["gate"]) + lp["moe"]["router_bias"], 8, 4))
        assert abs(reaching[-1] - kept[:, first // 4].mean()) < 1e-6
        # (out - h - shared: the differences' last bits are all that is left)
        assert np.abs(part.reshape(-1, 48)[~kept[:, first // 4]]).max() < 1e-5
        assert np.abs(part.reshape(-1, 48)[kept[:, first // 4]]).max() > 1e-2
        total = total + part
    _close(total + shared, y_whole, 5e-5)
    assert abs(sum(reaching) - 4.0) < 1e-5  # every token keeps four groups


# ---- (d) the refusals that stay, by name; the forms that went ----


def _cfg(**replace):
    cfg = ling_3_0_flash_one_chip(_one_device_mesh(), tiny=True)[1]
    return dataclasses.replace(cfg, **replace)


def _build(**replace):
    return lambda: DMoETransformerLM(_cfg(**replace), _one_device_mesh())


REFUSALS = {
    "a_latent_without_its_rotated_part": (
        _build(rope_head_dim=None), ValueError,
        "latent attention is kv_latent_dim, rope_head_dim and head_dim together"),
    "a_query_latent_without_the_keys": (
        _build(kv_latent_dim=None, q_latent_dim=16, v_head_dim=None), ValueError,
        "latent attention is kv_latent_dim, rope_head_dim and head_dim together"),
    "a_channel_gate_beside_latents": (
        _build(attention_gate=True, delta_decay_floor=None, layer_pattern=None,
               n_layers=4), ValueError,
        "rotary_dim and attention_gate belong to the plain projections"),
    "a_gate_of_no_known_kind": (
        _build(attention_gate="channel"), ValueError,
        "attention_gate must be False, True or 'head'"),
    "a_channel_decay_under_a_channel_gate": (
        _build(attention_gate=False), ValueError,
        "delta_decay_floor .a decay a key channel. and attention_gate='head' go together"),
    "a_head_gate_on_a_head_decayed_rule": (
        _build(delta_decay_floor=None), ValueError,
        "delta_decay_floor .a decay a key channel. and attention_gate='head' go together"),
    "a_floor_too_deep_for_a_block": (
        _build(delta_decay_floor=-6.0), ValueError,
        "channel_decay_fits admits"),
    "grouped_value_heads_under_a_channel_decay": (
        _build(delta_value_heads=8), ValueError,
        "as many value heads as key heads"),
    "groups_that_cannot_hold_k": (
        _build(router_groups=(4, 1), k=8), ValueError,
        "router_groups=.4, 1. is .n_group, topk_group. of a sigmoid router"),
    "groups_on_a_softmax_router": (
        _build(router_score="softmax", router_bias=False, routed_scale=1.0),
        ValueError, "router_groups=.4, 2. is .n_group, topk_group. of a sigmoid router"),
    "the_ring": (
        lambda: DMoETransformerLM(
            _cfg(seq_parallel=True),
            make_mesh({"seq": 2}, devices=jax.devices()[:2])),
        NotImplementedError, "with a 'delta' layer"),
    "the_cached_decoder": (
        lambda: DMoETransformerLM(_cfg(), _one_device_mesh()).generate(
            None, jnp.zeros((1, 4), jnp.int32), 2, use_cache=True),
        NotImplementedError, "use_cache=True with a 'delta' layer"),
}


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_a_combination_not_built_is_refused_by_name(name):
    build, error, message = REFUSALS[name]
    with pytest.raises(error, match=message):
        build()


FORMS = {
    "latents_with_no_query_latent": dict(),
    "a_head_gate_on_plain_projections": dict(
        kv_latent_dim=None, rope_head_dim=None, v_head_dim=None, head_dim=12),
    "a_head_gate_on_latents_with_a_query_latent": dict(q_latent_dim=16),
    "no_groups": dict(router_groups=None),
}


@pytest.mark.parametrize("name", sorted(FORMS))
def test_a_form_that_was_a_refusal_builds_and_trains(name):
    """Each builds, gives a finite loss whose gradient reaches every leaf
    but the selection biases, and a gate where it holds one."""
    model = _build(**FORMS[name])()
    cfg = model.cfg
    params = model.init_params(jax.random.PRNGKey(1))
    latent = params["layers"][2]
    assert "w_gate" in latent and ("wq_a" in latent) == (cfg.q_latent_dim is not None)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 65)))
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, ids[:, :-1], ids[:, 1:]), has_aux=True))(params)
    assert np.isfinite(float(loss)) and 0.0 < float(metrics["attention_gate_mean"]) < 1.0
    assert ("groups_reaching_share" in metrics) == (cfg.router_groups is not None)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name_of = jax.tree_util.keystr(path)
        assert name_of.endswith("['router_bias']") or np.asarray(g).any(), name_of


def test_delta_layers_beside_a_latent_layer_train_through_the_step(tiny):
    """The stack through ``make_train_step`` (remat, fused_adafactor, the
    balancing rule): the loss falls and the selection biases move."""
    model, cfg, optimizer, batch = ling_3_0_flash_one_chip(_one_device_mesh(), tiny=True)
    params = model.init_params(jax.random.PRNGKey(2))
    state = model.init_opt_state(optimizer, params)
    step = model.make_train_step(optimizer)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (batch, 65)))
    losses = []
    for _ in range(4):
        params, state, loss, metrics = step(params, state, ids[:, :-1], ids[:, 1:])
        losses.append(float(loss))
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert float(metrics["router_bias_abs_max"]) > 0.0
    assert float(metrics["dropped_fraction"]) == 0.0


# ---- (f) the benchmark's files ----


def test_flops_of_the_cell_are_the_issue_arithmetic():
    """0.52 G parameters of matrix work a token, 17.2 TFLOP forward, the
    rule's 6 H dk dv, at level loads."""
    parts = ling3_flops.forward_flops_per_token(CELL_FILE)
    s = CELL_FILE["seq_len"]
    matrices = sum(v for k, v in parts.items()
                   if k not in ("kda_recurrence", "attention_core")) / 2
    assert abs(matrices / 1e9 - 0.52) < 0.01
    assert parts["kda_recurrence"] == 6 * 6 * 32 * 128 * 128
    assert ling3_flops.layers(CELL_FILE) == [("kda", "dense")] + [
        ("kda", "moe")] * 3 + [("latent", "moe")] + [("kda", "moe")] * 2
    assert abs(2 * matrices * s / 1e12 - 17.2) < 0.3  # the issue's 17.2 TFLOP
    # and what is no plain product: the rule 0.31, the latent layer's core 2.75
    assert abs(sum(parts.values()) * s / 1e12 - 20.2) < 0.3
    assert ling3_flops.level_rows_per_token(CELL_FILE) == 1.0  # 8 * 64 / 512
    assert ling3_flops.counted_rows(CELL_FILE, s, 1.0) == 16384
    least = ling3_flops.kda_core_least_seconds(CELL_FILE, s, "TPU v5 lite")
    assert 0.005 < least < 0.05
    assert ling3_flops.kda_core_bytes(CELL_FILE, s) > 6 * s * 4 * 4096 * 3


def test_parameters_of_the_cell_are_the_issue_arithmetic():
    """2.80 B: the file's count is the recipe's, layer by layer the
    issue's (99.8 M, 437.3 M x 5, 416.7 M, 50.3 M x 2)."""
    model, cfg, _, _ = ling_3_0_flash_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == CELL_FILE["parameters"] == 2803845056
    assert [round(count(lp) / 1e6, 1) for lp in shapes["layers"]] == [
        99.8, 437.3, 437.3, 437.3, 416.7, 437.3, 437.3]
    assert round(count(shapes["embed"]) / 1e6, 1) == 50.3
    assert moe_dispatch.share_buffer_rows(16384, 8, 64, 512) == 32768
    runner._check_sizes(CELL_FILE, cfg)


def test_configuration_file_carries_the_catalog_entry():
    """Every number of the catalog's row under the same key, unchanged but
    for ``reduced``; no width among the reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Ling-3.0-flash-VL")
    assert CELL_FILE["source"] == row["source_url"]
    assert CELL_FILE["reduced"] == ["n_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key not in CELL_FILE["reduced"]:
            assert CELL_FILE[key] == value, key
    assert CELL_FILE["num_experts_published"] == row["config"]["num_experts"]
    assert CELL_FILE["vocab_size_published"] == row["config"]["vocab_size"]
    assert CELL_FILE["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CELL_FILE["num_experts"] * row["config"]["n_group"] == row["config"]["num_experts"]


def test_reducers_read_this_cells_tables_and_nothing_where_there_is_none():
    """Every ``ling3.*`` metric's file names a reducer that returns a number
    on a table that holds its scopes and None where there is no table to read (and
    nothing, or a share of 0, on a table of another program's scopes)."""
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    readings = readings_of_cell(manifest, CELL)
    assert len(readings) == 26
    scopes = {name: 0.01 for name in runner.EXTRA_SCOPES + ("attention", "router")}
    scopes["delta/core"] = 0.2
    obs = {
        "scopes": {"by_scope": scopes, "total_s": 1.0, "grouped_matmul_s": 0.05,
                   "grouped_matmul_calls": 36, "attention_kernel_s": 0.15,
                   "attention_kernels": {"global.forward": {"s": 0.05, "calls": 2},
                                         "global.backward": {"s": 0.1, "calls": 2}},
                   "router_groups_s": 0.003, "attention_gate_s": 0.001},
        "trace": {"span_s": 2.0, "busy_s": 1.9}, "intervals_s": [1.0, 1.0],
        "device_kind": "TPU v5 lite", "sizes": CELL_FILE,
        "tokens_per_step_per_chip": 16384, "tokens_per_s_per_chip": 16384.0,
        "local_rows_over_level": [1.0], "dropped_fraction": [0.0],
        "groups_reaching_share": [0.5, 0.52],
    }
    bare = {"device_kind": "TPU v5 lite", "sizes": CELL_FILE,
            "tokens_per_step_per_chip": 16384, "intervals_s": [1.0]}
    other = dict(bare, scopes={"by_scope": {"attention": 0.5}, "total_s": 1.0},
                 trace={"span_s": 2.0, "busy_s": 1.9})
    for reading, entry in readings.items():
        if not entry["name"].startswith("ling3."):
            continue
        spec = layer_metric_file(manifest, entry)
        assert spec["workloads"] == [CELL] and spec["layer"] == entry["layer"]
        reducer = harness.load_module(manifest, "reducers", spec["reducer"])
        value = reducer.reduce(obs, **spec.get("args", {}))
        assert value is not None and 0.0 < value <= 100.0, (reading, value)
        assert reducer.reduce(bare, **spec.get("args", {})) is None, reading
        # a table of another program's scopes: nothing, or a share of 0
        assert not reducer.reduce(other, **spec.get("args", {})), reading
    assert readings["groups_reaching_share"]["source"] == "program_counter"


def test_the_scope_table_takes_in_the_new_scopes(tiny):
    """``delta/decay``, ``router/groups`` and the latent layer's ``gate``
    are scopes of the lowered step."""
    model, cfg, params, ids, tgt = tiny
    text = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0])).lower(
        params).as_text(debug_info=True)
    for scope in ("delta/decay", "delta/core", "delta/gate_norm", "router/groups",
                  "attention/global/gate", "latent_down", "latent_up",
                  "attention/global/proj"):
        assert scope in text, scope
    assert runner.GATE_SCOPES["router_groups_s"].search("/layer_1/router/groups/top_k/")
    assert runner.GATE_SCOPES["attention_gate_s"].search("/layer_2/attention/global/gate/mul/")
    assert isinstance(cfg.layer_pattern[0], AttentionLayer)
