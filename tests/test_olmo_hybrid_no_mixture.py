"""Olmo-Hybrid's stack holds no mixture layer: its step trains and reports no
expert counter, on one device and over a data mesh; and the norm on a part's
OUTPUT where the part is a mixture beside a shared expert.  A module apart
from ``tests/test_olmo_hybrid.py`` (three train steps' compiles), so that
``--dist loadfile`` can give them a worker of their own.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_olmo_hybrid import _close, _one_device_mesh, tiny  # noqa: F401  (a fixture)
from __graft_entry__ import k_exaone_one_chip, olmo_hybrid_7b_one_chip
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.models.transformer import DMoETransformerLM
from learning_at_home_tpu.parallel.mesh import make_mesh


def test_a_stack_with_no_mixture_trains_and_reports_no_expert_counter(tiny):
    """The loss falls over a few steps; the step's metrics are the
    cross-entropy and the delta rule's two counters; neither the parameter
    tree nor the optimizer's holds a router leaf; the set-up's levelling
    call and the step's balancing rule return what they were given."""
    _, _, _, ids, tgt = tiny
    model, cfg, optimizer, _ = olmo_hybrid_7b_one_chip(_one_device_mesh(), tiny=True)
    params = model.init_params(jax.random.PRNGKey(1))
    levelled, loads = model.level_router_bias(params, [ids])
    assert levelled is params and loads == []
    assert model._balance(params, model._router_biases(params), None) is params
    opt_state = model.init_opt_state(optimizer, params)
    names = "".join(
        jax.tree_util.keystr(p) for p, _ in
        jax.tree_util.tree_flatten_with_path((params, opt_state))[0])
    assert "router" not in names and "'moe'" not in names and "'gate'" not in names
    step = model.make_train_step(optimizer)
    losses = []
    for _ in range(6):
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05 and np.isfinite(losses).all()
    assert set(metrics) == {"ce", "delta_decay_min", "delta_beta_max"}
    assert float(metrics["ce"]) == pytest.approx(losses[-1])  # no auxiliary term
    assert 1.0 < float(metrics["delta_beta_max"]) <= 2.0
    assert 0.0 <= float(metrics["delta_decay_min"]) < 1.0


def test_a_stack_with_no_mixture_steps_on_a_data_mesh_as_on_one_device(tiny):
    """Every leaf replicated, the batch over ``data``: the same loss as on
    one device (the delta rule's scans and the convolution partition over
    the rows of the batch)."""
    from learning_at_home_tpu.parallel.mesh import batch_sharding

    single, _, params, ids, tgt = tiny
    want, _ = jax.jit(single.loss_fn)(params, ids, tgt)
    mesh = make_mesh({"data": 2, "expert": 1}, devices=jax.devices()[:2])
    model, _, optimizer, _ = olmo_hybrid_7b_one_chip(mesh, tiny=True)
    placed = jax.device_put(  # copies: the step donates what it is given
        jax.tree_util.tree_map(jnp.copy, params), model.param_shardings(params))
    opt_state = model.init_opt_state(optimizer, placed)
    rows = [jax.device_put(a, batch_sharding(mesh)) for a in (ids, tgt)]
    _, _, loss, metrics = model.make_train_step(optimizer)(placed, opt_state, *rows)
    assert abs(float(loss) - float(want)) < 1e-5
    assert set(metrics) == {"ce", "delta_decay_min", "delta_beta_max"}


def test_the_norm_on_the_output_of_a_mixture_layer_spans_all_the_part_gave():
    """``norm_place='output'`` where the feed-forward part is a mixture
    beside a shared expert: ONE norm over their sum, and the attention's
    over its out-projection; each part reads the stream as it is."""
    model, cfg, _, _ = k_exaone_one_chip(_one_device_mesh(), tiny=True)
    cfg = dataclasses.replace(cfg, norm_place="output")
    model = DMoETransformerLM(cfg, _one_device_mesh())
    params = model.init_params(jax.random.PRNGKey(4))
    lp = params["layers"][1]  # a mixture layer with a shared expert
    assert "moe" in lp and "shared" in lp
    x = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.d_model))
    kind = cfg.attention_layer(1)
    got, _ = jax.jit(lambda lp, x: model._layer(lp, x, 1, None, kind))(lp, x)

    @jax.jit
    def by_hand(lp, x):
        q, k, v, _ = model._qkv(lp, x, np.arange(cfg.seq_len), kind.rotary)
        h = x + model._norm(lp["ln1"], trunk.output_projection(
            lp, trunk.attention_core(q, k, v, "xla", kind.window)))
        routed, _ = model.moe(
            lp["moe"], h.reshape(-1, cfg.d_model), jitter_salt=1)
        shared = trunk.gated_mlp(lp["shared"], h, model._gate_act)
        return h + model._norm(lp["ln2"], routed.reshape(h.shape) + shared)

    want = by_hand(lp, x)
    _close(got, want, 1e-5)
    before, _ = jax.jit(lambda lp, x: DMoETransformerLM(
        dataclasses.replace(cfg, norm_place="input"), _one_device_mesh()
    )._layer(lp, x, 1, None, kind))(lp, x)
    assert float(jnp.abs(before - got).max()) > 0.1
