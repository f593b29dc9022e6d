"""SmallThinker's block in the pod step
(``__graft_entry__.smallthinker_one_chip``) against its plain reference
(``benchmarks/configs/smallthinker_21b_a3b_reference.py``): grouped
key/value heads, window and global layers in one stack, rotary on the
window layers alone, ReGLU experts routed top-6 from the attention block's
input; the refusals beside that path; and the benchmark's files for it.

Tiny sizes on the CPU; the attention cores at these heads are
``tests/test_smallthinker_attention.py``'s, the AOT compiles at published widths for a described
(not attached) ``v5e`` chip are ``tests/test_smallthinker_chip.py``'s.
"""

import collections
import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)
import smallthinker_flops  # noqa: E402

from __graft_entry__ import smallthinker_one_chip  # noqa: E402
from learning_at_home_tpu.models.transformer import (  # noqa: E402
    AttentionLayer,
    DMoETransformerLM,
)
from learning_at_home_tpu.ops.moe_dispatch import grouped_matmul_tiles  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts  # noqa: E402
from runner_limits import (  # noqa: E402,F401  (``compiled_once`` is a fixture)
    compiled_once,
    decisive,
    Limits,
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "smallthinker_21b_a3b_reference.py"))
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_blocks.py"))
probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "smallthinker-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "smallthinker-21b-a3b.json"))


def _decisive(params):
    """Seeded weights under which every part of the block decides and
    bf16 still reads the block as it is: a router that decides (the
    program's init gives near-equal logits), embeddings whose mean square
    is near the norm's eps (as at 2560 wide: 1/2560 = 3.9e-4), experts'
    outputs small enough that the few tokens whose 6th and 7th logits
    swap under bf16 do not swamp the rest of 32, norm scales off 1."""
    return decisive(params, scaled={
        "['gate']": 10.0, "['embed']": 0.1, "['w_down']": 0.3})


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(smallthinker_one_chip, _decisive)


SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference
limits = Limits(runner, reference, TINY_FILE)
pytestmark = pytest.mark.usefixtures("compiled_once")


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's logits, loss and gradients on the tiny weights."""
    _, _, params, ids, tgt = tiny
    logits = jax.jit(lambda p: reference.forward(p, ids, SIZES)[0])(params)
    loss, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)
    return np.asarray(logits), float(loss), grads


def test_the_tiny_recipe_keeps_the_block(tiny):
    """What ``tiny`` must keep of the published block, and the rehearsal
    file's sizes are the tiny recipe's (the runner's own check)."""
    _, cfg, params, _, _ = tiny
    assert cfg.n_kv_heads < cfg.n_heads
    assert cfg.n_heads * cfg.head_dim != cfg.d_model
    kinds = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    assert kinds == [AttentionLayer(None, False)] + [AttentionLayer(8, True)] * 3
    assert kinds[1].window < cfg.seq_len and cfg.k < cfg.num_experts
    assert cfg.expert_kind == "gated_relu" and cfg.router_input == "attention_input"
    assert "pos" not in params and params["layers"][0]["wk"].shape == (64, 32)
    runner._check_sizes(TINY_FILE, cfg)
    with pytest.raises(harness.BenchError, match="rope_layout"):
        runner._check_sizes(dict(TINY_FILE, rope_layout=[1] * 52), cfg)


def test_block_matches_reference_in_float32(tiny, want):
    """Logits, loss and the gradient of EVERY leaf to 1e-4 of the
    reference's largest entry of that leaf: float32 on both sides, so the
    only differences are orders of summation."""
    model, _, params, ids, tgt = tiny
    want_logits, want_loss, want_grads = want
    logits, _ = jax.jit(model.apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits), want_logits, rtol=0,
        atol=1e-4 * np.abs(want_logits).max(),
    )
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss_fn, has_aux=True)
    )(params, ids, tgt)
    assert abs(float(loss) - want_loss) <= 1e-4 * abs(want_loss)
    assert float(metrics["dropped_fraction"]) == 0.0
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want_grads),
    ):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=0, atol=1e-4 * np.abs(w).max(),
            err_msg=jax.tree_util.keystr(path),
        )


class _OnOtherWeights:
    """What the runner's comparison calls of a model, with the PROGRAM's
    weights transformed first (the reference keeps the given ones)."""

    def __init__(self, model, transform):
        self.cfg = model.cfg
        self._head, self._logits, self._norm = model._head, model._logits, model._norm
        self._hidden = lambda p, i: model._hidden(transform(p), i)
        self.loss_fn = lambda p, i, t: model.loss_fn(transform(p), i, t)

        def one(lp):  # the transform is of a whole tree: layer by layer
            return transform({"layers": (lp,)})["layers"][0]

        self._layer = lambda lp, *rest: model._layer(one(lp), *rest)


def _bf16_readings(tiny, cfg=None, transform=None):
    """The runner's own comparison (one row, the logits in blocks) of the
    bf16 program of ``cfg`` with the reference given the FILE's sizes."""
    m16 = DMoETransformerLM(
        dataclasses.replace(cfg or tiny[1], dtype=jnp.bfloat16), tiny[0].mesh)
    if transform is not None:
        m16 = _OnOtherWeights(m16, transform)
    return limits.read(tiny, m16)


def test_block_in_bf16_is_inside_the_runner_tolerances(tiny):
    read = _bf16_readings(tiny)
    assert not runner.over_tolerance(read), read


def _pattern(cfg, **changes):
    return tuple(dataclasses.replace(a, **{
        k: v(a) if callable(v) else v for k, v in changes.items()
    }) for a in cfg.layer_pattern)


def _pair_heads_modulo(cfg, params):
    """Query head h reading key/value head ``h % n_kv``: the right
    program on weights whose query heads are reordered to match."""
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    order = np.argsort(np.arange(cfg.n_heads) % kv, kind="stable")
    cols = (order[:, None] * hd + np.arange(hd)[None, :]).reshape(-1)
    return {**params, "layers": tuple(
        {**lp, "wq": lp["wq"][:, cols], "wo": lp["wo"][cols, :]}
        for lp in params["layers"]
    )}


MUTATIONS = {
    # name: (config changes given the config, params transform)
    "window_ignored": (lambda c: {"layer_pattern": _pattern(c, window=None)}, None),
    "window_off_by_one_key": (lambda c: {"layer_pattern": _pattern(
        c, window=lambda a: a.window and a.window - 1)}, None),
    "rotary_on_the_global_layer": (
        lambda c: {"layer_pattern": _pattern(c, rotary=True)}, None),
    "theta_10000": (lambda c: {"rope_theta": 1e4}, None),
    "kv_heads_paired_modulo": (lambda c: {}, _pair_heads_modulo),
    "router_reads_post_attention": (lambda c: {"router_input": "moe_input"}, None),
    "silu_for_relu": (lambda c: {"expert_kind": "gated_silu"}, None),
    "gates_not_renormalised": (lambda c: {"renormalize": False}, None),
    "eps_1e-5": (lambda c: {"norm_eps": 1e-5}, None),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_block_fails_the_runner_tolerances(tiny, name):
    """The tolerance is tight: each of nine plausible misreadings of the
    block, computed in bf16 like the program, reads outside it."""
    changes, transform = MUTATIONS[name]
    cfg = tiny[1]
    wrong = dataclasses.replace(cfg, **changes(cfg))
    assert wrong != cfg or transform is not None
    read = _bf16_readings(
        tiny, wrong, transform and (lambda p: transform(cfg, p)))
    assert runner.over_tolerance(read), read


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    """What the runner promises of its limits: the reference itself with
    every matmul operand rounded to float8_e4m3, the nearest precision
    below the configuration's bf16, is outside them; rounded to bf16 it
    is inside."""
    for dtype, outside in ((jnp.float8_e4m3fn, True), (jnp.bfloat16, False)):
        read = limits.read(tiny, operand_dtype=dtype)
        assert bool(runner.over_tolerance(read)) is outside, (dtype, read)


def test_readings_from_blocks_equal_readings_from_whole_logits(tiny, monkeypatch):
    """Blocks of 8 positions: the rms and the median position's are the
    whole logits', and the histogram's 99.9th percentile is
    numpy's to the width of a bin; the whole logits are both sides' heads
    on the stream the program's layers leave."""
    model, cfg, params, ids, tgt = tiny
    m16 = DMoETransformerLM(dataclasses.replace(cfg, dtype=jnp.bfloat16), model.mesh)
    monkeypatch.setattr(runner, "LOGIT_BLOCK", 8)
    read = limits.read(tiny, m16)
    layer = jax.jit(m16._layer, static_argnums=(2, 4))
    x = params["embed"][ids[:1]].astype(jnp.bfloat16)
    for i, lp in enumerate(params["layers"]):
        x, _ = layer(lp, x, i, None, cfg.attention_layer(i))
    got = np.asarray(jax.jit(lambda p, x: m16._logits(
        m16._norm(p["ln_f"], x), m16._head(p)))(params, x), np.float64)
    ref = np.asarray(reference.head(params, x.astype(jnp.float32), SIZES), np.float64)
    scale = np.sqrt(np.mean(ref ** 2))
    diff = np.abs(got - ref)
    # (other compiled programs than the runner's: bf16 roundings may differ)
    np.testing.assert_allclose(read["reference_logits_rms"], scale, rtol=1e-3)
    np.testing.assert_allclose(
        read["logits_rms"], np.sqrt(np.mean(diff ** 2)) / scale, rtol=0.1)
    np.testing.assert_allclose(
        read["logits_p999"], np.quantile(diff, 0.999) / scale, rtol=0.1)
    per_position = np.sqrt((diff ** 2).mean(-1) / (ref ** 2).mean(-1))
    np.testing.assert_allclose(
        read["logits_token_median"], np.median(per_position), rtol=0.1)


def test_a_whole_that_composes_other_layers_fails_the_runner_tolerances(tiny):
    """The layers are compared one at a time; ``hidden_token_median`` holds
    ``_hidden`` (what ``apply`` and ``loss_fn`` run) to the same layers."""
    model, cfg, params, ids, tgt = tiny
    right = DMoETransformerLM(dataclasses.replace(cfg, dtype=jnp.bfloat16), model.mesh)
    wrong = DMoETransformerLM(dataclasses.replace(
        cfg, dtype=jnp.bfloat16, layer_pattern=_pattern(cfg, window=None)), model.mesh)
    mixed = _OnOtherWeights(right, lambda p: p)
    mixed._hidden, mixed.loss_fn = wrong._hidden, wrong.loss_fn
    read = limits.read(tiny, mixed)
    assert [p.split()[0] for p in runner.over_tolerance(read)] == [
        "loss", "hidden_token_median"], read


def test_a_token_between_two_experts_is_left_out_of_its_layer(tiny, monkeypatch):
    """A position whose 6th and 7th router logits lie within ``MARGIN`` in
    the reference is not compared in that layer; a margin that leaves no
    position to compare is itself outside the limits."""
    model, cfg, params, ids, tgt = tiny
    read = _bf16_readings(tiny)
    assert 0.0 < read["near_tie_share"] < 0.5, read
    x = params["embed"][ids[:1]].astype(jnp.float32)
    margin = np.asarray(reference.router_margin(params["layers"][0], x, SIZES))
    logits = np.sort(np.asarray(reference.rms(
        x, params["layers"][0]["ln1"]["scale"], SIZES["norm_eps"]
    ).reshape(-1, cfg.d_model) @ params["layers"][0]["moe"]["gate"]), axis=-1)
    np.testing.assert_allclose(margin, logits[:, -6] - logits[:, -7], rtol=1e-5, atol=1e-6)
    monkeypatch.setattr(runner, "MARGIN", np.inf)
    with np.errstate(invalid="ignore"):
        read = _bf16_readings(tiny)
    assert [p.split()[0] for p in runner.over_tolerance(read)] == [
        "layers_rms", "near_tie_share"], read


# ---- refusals ----


@pytest.mark.parametrize("changes, error, match", [
    ({"layer_pattern": (AttentionLayer(),) * 3}, ValueError, "do not divide"),
    ({"positions": "learned"}, ValueError, "positions must be 'rope'"),
    ({"n_kv_heads": 4}, ValueError, "multiple of"),
    ({"router_input": "before"}, ValueError, "router_input"),
    ({"routing": "capacity"}, NotImplementedError, "router input of its own"),
    ({"expert_kind": "reglu"}, ValueError, "gated_relu"),
], ids=["pattern-length", "rotary-without-rope", "kv-heads",
        "router-input", "capacity-with-router-input", "expert-kind"])
def test_a_configuration_the_step_cannot_run_is_refused_by_name(tiny, changes, error, match):
    model, cfg, *_ = tiny
    with pytest.raises(error, match=match):
        DMoETransformerLM(dataclasses.replace(cfg, **changes), model.mesh)


@pytest.mark.parametrize("changes", [
    {},                                                  # both
    {"layer_pattern": None, "seq_len": 32},              # grouped heads alone
    {"n_kv_heads": None},                                # a window alone
], ids=["block", "kv-heads", "window"])
def test_ring_attention_refuses_grouped_heads_and_windows(tiny, changes):
    _, cfg, *_ = tiny
    mesh = make_mesh({"expert": 1, "seq": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="ring attention"):
        DMoETransformerLM(
            dataclasses.replace(cfg, seq_parallel=True, **changes), mesh)


def test_cached_decode_refuses_the_block_and_the_full_forward_decodes(tiny):
    model, cfg, params, ids, _ = tiny
    prompt = ids[:, :8]
    out = model.generate(params, prompt, 4)
    assert out.shape == (prompt.shape[0], 12)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), np.asarray(prompt))
    with pytest.raises(NotImplementedError, match="KV-cache decoder"):
        model.generate(params, prompt, 4, use_cache=True)


def test_router_input_is_taken_only_where_it_is_declared():
    mesh = _one_device_mesh()
    kw = dict(hidden_dim=32, num_experts=8, k=3, ffn_dim=16, dtype=jnp.float32,
              expert_kind="gated_relu", routing="dropless")
    x = jnp.asarray(np.random.RandomState(0).randn(24, 32), jnp.float32)
    own = ShardedMixtureOfExperts(mesh, router_input=True, **kw)
    params = own.init_params(jax.random.PRNGKey(0))
    params["gate"] = params["gate"] * 100.0
    shared = ShardedMixtureOfExperts(mesh, **kw)
    y_shared, _ = jax.jit(shared)(params, x)
    routed = jax.jit(lambda p, x, router_x: own(p, x, router_x=router_x))
    y_same, _ = routed(params, x, x)
    # two compiled programs of the same sums: an ulp or two apart at most
    np.testing.assert_allclose(
        np.asarray(y_same), np.asarray(y_shared), rtol=1e-6, atol=1e-6)
    y_other, _ = routed(params, x, x[::-1])
    assert not np.allclose(np.asarray(y_other), np.asarray(y_shared))
    with pytest.raises(ValueError, match="router_x is missing"):
        own(params, x)
    with pytest.raises(ValueError, match="router_x is given"):
        shared(params, x, router_x=x)


# ---- the benchmark's files for it ----


@pytest.mark.parametrize("seq_len, window", [
    (1, None), (37, None), (37, 1), (37, 8), (37, 37), (37, 50), (64, 16)])
def test_admitted_scores_equal_a_count_of_the_mask(seq_len, window):
    i, j = np.arange(seq_len)[:, None], np.arange(seq_len)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (j > i - window)
    assert smallthinker_flops.admitted_scores(seq_len, window) == mask.sum()


def test_flops_of_the_cell_are_the_issue_arithmetic():
    s = CELL_FILE["seq_len"]
    assert smallthinker_flops.admitted_scores(s) == s * (s + 1) // 2
    assert smallthinker_flops.admitted_scores(s, 4096) == sum(
        min(i + 1, 4096) for i in range(s))
    per_token = smallthinker_flops.train_flops_per_token(CELL_FILE)
    assert abs(per_token / 1e9 - 4.505) < 5e-3
    assert smallthinker_flops.grouped_matmul_flops(CELL_FILE, s) == 2 * s * 6 * 2560 * 768
    forward = smallthinker_flops.attention_kernel_flops(CELL_FILE, s, "window", "forward")
    assert forward == 28 * smallthinker_flops.admitted_scores(s, 4096) * 4 * 128
    assert smallthinker_flops.attention_kernel_flops(
        CELL_FILE, s, "global", "backward") == 2.5 * smallthinker_flops.attention_kernel_flops(
        CELL_FILE, s, "global", "forward")


def test_configuration_file_carries_the_catalog_entry():
    """Every key of the catalog's ``config`` unchanged; what is run beside
    it; the reference named; every assumption listed."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(e for e in map(json.loads, open(catalog))
                 if e["name"] == "SmallThinker-21BA3B-Instruct")
    assert CELL_FILE["source"] == entry["source_url"]
    assert {k: CELL_FILE[k] for k in entry["config"]} == entry["config"]
    assert (CELL_FILE["n_layers"], CELL_FILE["reduced"], CELL_FILE["seq_len"]) == (
        4, ["n_layers"], 16384)
    assert os.path.isfile(os.path.join(REPO, CELL_FILE["reference"]))
    assert len(CELL_FILE["assumed"]) >= 10
    source = open(os.path.join(REPO, CELL_FILE["reference"])).read()
    assert "learning_at_home_tpu" not in source.split('"""', 2)[2]


def test_runner_finds_the_attention_kernel_across_the_newline():
    """``op_names`` joins an instruction with the ``op_name`` on a later
    line of its text; ``scope_times`` puts the kernel's calls under
    ``attention`` and reports them alone by kind of layer and direction."""
    hlo = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(layer_1)/attention/window/rope/mul"}
  %splash_mha_fwd_residuals.1 = (f32[8]{0}, bf16[8]{0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
}}, metadata={op_name="jit(train_step)/jvp(layer_1)/attention/window/flash/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=67}, backend_config={"x":1}
  %pallas_call.2 = f32[8]{0} get-tuple-element(%splash_mha_fwd_residuals.1), index=1, frontend_attributes={kernel_metadata={
}}, metadata={op_name="jit(train_step)/jvp(layer_1)/attention/window/flash/pallas_call"}
  %splash_mha_dkv_no_residuals.3 = (f32[8]{0}) custom-call(%a), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
}}, metadata={op_name="jit(train_step)/transpose(jvp(layer_0))/attention/global/flash/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/pallas_call"}
  %ragged-dot-none.7 = bf16[8,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.3 = f32[] fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(ce))/while/body/dot_general"}
  %copy.9 = bf16[8]{0} copy(%p)
"""
    names = runner.op_names(hlo)
    assert names["splash_mha_fwd_residuals.1"].endswith("splash_mha_fwd_residuals/pallas_call")
    assert "attention/global" in names["splash_mha_dkv_no_residuals.3"]
    assert "copy.9" not in names
    base = harness.load_path(os.path.join(REPO, "benchmarks", "runners", "train_recipe.py"))
    s = 10 ** 9
    ops = [("fusion.1", 0, s), ("splash_mha_fwd_residuals.1", s, 3 * s),
           ("splash_mha_fwd_residuals.1", 3 * s, 5 * s),
           ("splash_mha_dkv_no_residuals.3", 5 * s, 9 * s),
           ("ragged-dot-none.7", 9 * s, 10 * s), ("fusion.3", 10 * s, 12 * s),
           ("copy.9", 12 * s, 16 * s)]
    got = runner.make_scope_times(base)(ops, hlo)
    assert got["by_scope"] == {"attention": 9.0, "experts": 1.0, "ce": 2.0, "other": 4.0}
    assert got["attention_kernel_s"] == 8.0
    assert got["attention_kernels"] == {
        "global.backward": {"s": 4.0, "calls": 1},
        "window.forward": {"s": 4.0, "calls": 2}}
    assert (got["grouped_matmul_s"], got["grouped_matmul_calls"]) == (1.0, 1)
    # the reducers: kernel time over all, and operations over time x peak
    roofline = harness.load_path(os.path.join(
        REPO, "benchmarks", "reducers", "attention_kernel_roofline.py"))
    share = harness.load_path(os.path.join(
        REPO, "benchmarks", "reducers", "scope_table_share.py"))
    obs = {"scopes": got, "device_kind": "TPU v5 lite", "sizes": CELL_FILE,
           "tokens_per_step_per_chip": 16384}
    assert share.reduce(obs, key="attention_kernel_s") == 50.0
    flops = smallthinker_flops.attention_kernel_flops
    np.testing.assert_allclose(
        roofline.reduce(obs, module="smallthinker_flops"),
        100 * (flops(CELL_FILE, 16384, "global", "backward")
               + 2 * flops(CELL_FILE, 16384, "window", "forward")) / (8.0 * 197e12))
    assert roofline.reduce({"scopes": {}, "device_kind": "TPU v5 lite"},
                           module="smallthinker_flops") is None
    assert share.reduce({}, key="attention_kernel_s") is None


# ---- every grouped matmul of a layer runs at tiles read from its shape ----


def test_every_grouped_matmul_of_a_layer_carries_its_tiles():
    """One window layer of the recipe at the cell's sizes (98,304 rows,
    experts of 2560 x 768), traced from shapes and lowered for the TPU
    platform (nothing compiles, nothing runs): the 3 forward grouped
    matmuls, the 3 rows' gradients and the 3 weights' gradients each carry
    the ``ragged_dot_tiling`` that ``grouped_matmul_tiles`` reads from
    their shape, which at these widths is no power of two; with remat's
    second forward 12 of a layer's 12 calls, where 4 did (PERF.md section
    6, PR 32)."""
    model, cfg, _, batch = smallthinker_one_chip(_one_device_mesh())
    lp = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))["layers"][1]
    x = jax.ShapeDtypeStruct((batch, cfg.seq_len, cfg.d_model), cfg.dtype)

    def layer_loss(lp, x):
        y, aux = model._layer(lp, x, 1, None, cfg.attention_layer(1))
        return (y.astype(jnp.float32) ** 2).mean() + aux["aux_loss"]

    text = (
        jax.jit(jax.value_and_grad(layer_loss, argnums=(0, 1)))
        .trace(lp, x).lower(lowering_platforms=("tpu",)).as_text()
    )
    calls = [line for line in text.splitlines() if '"chlo.ragged_dot"' in line]
    assert calls == [line for line in text.splitlines() if "ragged_dot_tiling" in line]
    seen = collections.Counter()
    for line in calls:
        tiles = tuple(map(int, re.search(
            r'ragged_dot_tiling = "([\d,]+)"', line).group(1).split(",")))
        (m, a), _, out = (
            tuple(map(int, dims.split("x")))
            for dims in re.findall(r"tensor<([\dx]+)xbf16>", line)
        )
        weights = len(out) == 3  # [m, a], [m, b] -> [G, a, b]
        assert m == batch * cfg.seq_len * cfg.k
        assert tiles == grouped_matmul_tiles(
            m, a, out[-1], jnp.bfloat16, weights_gradient=weights
        ), line
        seen[tiles] += 1
    # gate, up and down's rows' gradient; down and the other two rows'
    # gradients; the weights' gradients of gate and up; of down
    assert seen == {(256, 2560, 768): 3, (256, 768, 2560): 3,
                    (256, 1280, 768): 2, (256, 768, 1280): 1}
