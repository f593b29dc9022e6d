"""Ling-3.0-flash-VL's group-limited router, its share (eight of them add up
to the uncut layer), the forms that were refusals and the refusals that
stay: a module apart from ``tests/test_ling3.py`` (whose helpers it uses) so
that ``--dist loadfile`` can give it a worker.  Tiny sizes on the CPU,
float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ling3 import SIZES, _close, _decisive, _one_device_mesh, reference
from __graft_entry__ import ling_3_0_flash_one_chip
from learning_at_home_tpu.models.transformer import DMoETransformerLM
from learning_at_home_tpu.ops import moe_dispatch
from learning_at_home_tpu.parallel.mesh import make_mesh


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


def test_delta_layers_beside_a_latent_layer_train_through_the_step():
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
