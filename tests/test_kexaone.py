"""K-EXAONE's language model in the pod step as one chip's share
(``__graft_entry__.k_exaone_one_chip``) against its plain reference
(``benchmarks/configs/k_exaone_236b_a23b_reference.py``): a share of
sigmoid-routed experts beside a shared expert, a dense first layer, window
and global layers with a norm over each head's queries and keys; the
selection bias and its balancing rule; the sorted-row buffer; the refusals
beside that path; and the benchmark's files for it.

Tiny sizes on the CPU.  Here: the block against its reference, what must
fail that comparison, and the benchmark's files; the share, the bias, the
buffer and the refusals are ``tests/test_kexaone_share.py``'s, the AOT compile
at published widths for a described ``v5e`` chip ``tests/test_kexaone_chip.py``'s.
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
from runner_limits import (  # noqa: E402,F401  (``compiled_once`` is a fixture)
    compiled_once,
    decisive,
    Limits,
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)

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
limits = Limits(runner, reference, TINY_FILE)
pytestmark = pytest.mark.usefixtures("compiled_once")


def _decisive(params, seed=7):
    """Seeded weights under which every part of the block decides and
    bf16 still reads the block as it is: a router that decides (the
    program's init gives near-equal scores), selection biases off zero,
    embeddings whose mean square is near the norm's eps, feed-forward
    outputs small enough that the few tokens whose 4th and 5th scores swap
    under bf16 do not swamp the rest of 32, norm scales off 1."""
    return decisive(params, seed, drawn={"['router_bias']": 0.2}, scaled={
        "['gate']": 20.0, "['embed']": 0.3, "['w_down']": 0.3})


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(k_exaone_one_chip, _decisive)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's logits, loss and gradients on the tiny weights."""
    _, _, params, ids, tgt = tiny
    logits = jax.jit(lambda p: reference.forward(p, ids, SIZES)[0])(params)
    loss, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)
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
    model, cfg = tiny[:2]
    read = limits.read(tiny, _bf16_model(cfg, model.mesh))
    assert not runner.over_tolerance(read), read
    assert 0.0 < read["near_tie_share"] and read["near_tie_shares"][0] == 0.0
    assert len(read["embed_and_layers_rms"]) == 1 + cfg.n_layers


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    """The reference itself with every matmul operand rounded to
    float8_e4m3, the nearest precision below the configuration's bf16, is
    outside the runner's limits; rounded to bf16 it is inside."""
    for dtype, outside in ((jnp.float8_e4m3fn, True), (jnp.bfloat16, False)):
        read = limits.read(tiny, operand_dtype=dtype)
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
        read = limits.read(tiny, _bf16_model(cfg, model.mesh))
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
    model, cfg = tiny[:2]
    read = limits.read(tiny, MUTATIONS[name](cfg, model.mesh))
    assert runner.over_tolerance(read), read


def test_a_whole_that_composes_other_layers_fails_the_runner_tolerances(tiny):
    """The layers are compared one at a time; ``hidden_token_median`` holds
    ``_hidden`` (what ``apply`` and ``loss_fn`` run) to the same layers."""
    model, cfg = tiny[:2]
    mixed = _Without(_bf16_model(cfg, model.mesh), None)
    wrong = _bf16_model(cfg, model.mesh, **_no_window(cfg))
    mixed._hidden, mixed.loss_fn = wrong._hidden, wrong.loss_fn
    read = limits.read(tiny, mixed)
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
