"""K-EXAONE's share of the experts in the pod step: the shares add up to
the uncut layer, the selection bias and its balancing rule, the sorted-row
buffer, and the refusals beside that path.  A module apart from
``tests/test_kexaone.py`` (the block against its reference, and what must
fail that comparison), so that ``--dist loadfile`` can spread the two.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kexaone import (  # noqa: F401  (``tiny`` is a fixture)
    SIZES,
    _decisive,
    _one_device_mesh,
    reference,
    tiny,
)
from __graft_entry__ import k_exaone_one_chip
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.models.transformer import DMoETransformerLM
from learning_at_home_tpu.ops import moe_dispatch
from learning_at_home_tpu.parallel.mesh import make_mesh
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts


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
    want = jax.jit(lambda p: reference.forward(p, ids, dict(SIZES, held=None))[0])(params)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(want), atol=1e-4 * np.abs(want).max())
    assert "local_rows_over_level" not in aux and aux["expert_counts"].shape == (4, 16)
