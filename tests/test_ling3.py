"""Ling-3.0-flash-VL's language model in the pod step as one chip's share
(``__graft_entry__.ling_3_0_flash_one_chip``) against its plain reference
(``benchmarks/configs/ling_3_0_flash_vl_reference.py``): Kimi Delta
Attention (a delta rule whose decay is a number a key channel) beside gated
latent attention with no query latent in ONE stack, a dense leading layer,
and a sigmoid router that chooses inside the best groups of its experts;
the benchmark's files for it.  The group router, the share (eight of them
add up to the uncut layer), the forms that were refusals and the refusals
that stay are ``tests/test_ling3_forms.py``'s and the runner's limits
``tests/test_ling3_runner.py``'s, modules of their own so that ``--dist
loadfile`` can give each a worker.  Tiny sizes on the CPU, float32."""

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
from learning_at_home_tpu.models.transformer import AttentionLayer  # noqa: E402
from learning_at_home_tpu.ops import moe_dispatch  # noqa: E402
from runner_limits import (  # noqa: E402
    close as _close,
    decisive,
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)

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


def _decisive(params, seed=7):
    """Seeded weights under which every part decides: a router that decides
    (the program's init gives near-equal scores), selection biases off zero,
    norm scales off 1."""
    return decisive(params, seed, drawn={"['router_bias']": 0.2}, scaled={"['gate']": 20.0})


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(ling_3_0_flash_one_chip, _decisive)


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
