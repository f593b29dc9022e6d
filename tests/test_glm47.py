"""GLM-4.7-Flash's language model in the pod step as one chip's share
(``__graft_entry__.glm_4_7_flash_one_chip``) against its plain reference
(``benchmarks/configs/glm_4_7_flash_reference.py``): attention whose
queries, keys and values are expanded from latents, the block that
predicts the next-but-one token with its loss, a share of sigmoid-routed
experts; the masked cross-entropy; the kernel's tiles at heads of 256; the
refusals beside that path; and the benchmark's files for it.

Tiny sizes on the CPU.  Here: the block against its reference, what must
fail that comparison, the masked cross-entropy and the benchmark's files; the
share, the refusals and the tiles are ``tests/test_glm47_share.py``'s, the AOT
compile at published widths for a described (not attached) ``v5e`` chip
``tests/test_glm47_chip.py``'s.
"""

import dataclasses
import functools
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

import glm47_flops  # noqa: E402
import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

from __graft_entry__ import glm_4_7_flash_one_chip  # noqa: E402
from learning_at_home_tpu.models import transformer, trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from benchmark_cells import layer_metric_file, readings_of_cell  # noqa: E402
from runner_limits import (  # noqa: E402,F401  (``compiled_once`` is a fixture)
    close as _close,
    compiled_once,
    decisive,
    Limits,
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)

REFERENCE = os.path.join(REPO, "benchmarks", "configs", "glm_4_7_flash_reference.py")
reference = harness.load_path(REFERENCE)
runner = harness.load_path(os.path.join(
    REPO, "benchmarks", "runners", "train_recipe_latent.py"))
probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))
TINY_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "rehearsal", "configs", "glm47-tiny.json"))
CELL_FILE = harness.load_json(os.path.join(
    REPO, "benchmarks", "configs", "glm-4.7-flash.json"))
CELL = "glm-4.7-flash-train-zipf16k"
SIZES = runner.reference_sizes(TINY_FILE)  # what the runner hands the reference
limits = Limits(runner, reference, TINY_FILE)
pytestmark = pytest.mark.usefixtures("compiled_once")


def _decisive(params, seed=7):
    """Seeded weights under which every part of the block decides: a router
    that decides (the program's init gives near-equal scores), selection
    biases off zero, norm scales off 1."""
    return decisive(params, seed, drawn={"['router_bias']": 0.2}, scaled={"['gate']": 20.0})


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(glm_4_7_flash_one_chip, _decisive)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference on the tiny weights, each one compiled program, once a
    module: both heads' float32 logits, the three losses, the gradients."""
    _, _, params, ids, tgt = tiny
    logits, logits_mtp = jax.jit(
        lambda p: reference.forward(p, ids, tgt, SIZES)[:2])(params)
    losses = jax.jit(lambda p: reference.losses(p, ids, tgt, SIZES))(params)
    _, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, SIZES))(params)
    return logits, logits_mtp, losses, grads


# ---- (a) the program against the reference ----


def test_the_tiny_recipe_keeps_the_block(tiny):
    """What ``tiny`` must keep of the published block, and the rehearsal
    file's sizes are the tiny recipe's (the runner's own check)."""
    _, cfg, params, _, _ = tiny
    assert cfg.kv_latent_dim < cfg.n_heads * cfg.head_dim  # a latent: narrower
    assert cfg.q_latent_dim < cfg.n_heads * cfg.head_dim
    assert 0 < cfg.rope_head_dim < cfg.head_dim
    assert cfg.held_experts < cfg.num_experts and cfg.k < cfg.num_experts
    assert cfg.ffn_pattern == ("dense", "moe", "moe", "moe", "moe")
    assert (cfg.mtp_layers, cfg.mtp_loss_weight) == (1, 0.3)
    first, second = params["layers"][:2]
    assert "wq" not in second and "wk" not in second and "wv" not in second
    assert second["wq_a"].shape == (64, 24) and second["wq_b"].shape == (24, 4 * 16)
    assert second["wkv_a"].shape == (64, 16 + 4)  # the latent and ONE rotated part
    assert second["wkv_b"].shape == (16, 4 * (12 + 16))  # [k_nope | v] a head
    assert second["q_a_norm"]["scale"].shape == (24,)
    assert second["kv_a_norm"]["scale"].shape == (16,)
    assert "ffn" in first and "moe" in second and "shared" in second
    block = params["mtp"]
    assert sorted(block) == ["e_norm", "h_norm", "layer", "out_norm", "w_eh"]
    assert block["w_eh"].shape == (128, 64)
    assert sorted(block["layer"]) == sorted(second)  # a mixture layer of its own
    assert block["layer"]["moe"]["w_gate"].shape == (8, 64, 24)  # the same share
    runner._check_sizes(TINY_FILE, cfg)
    for key, value in (("first_k_dense_replace", 2), ("n_routed_experts", 16),
                       ("qk_nope_head_dim", 8), ("num_nextn_predict_layers", 0)):
        with pytest.raises(harness.BenchError, match=key):
            runner._check_sizes(dict(TINY_FILE, **{key: value}), cfg)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_latent_attention_layer_matches_the_reference(tiny, impl):
    """One layer's attention block alone, float32, the projections' q,
    k, v themselves, and both cores on them (``flash`` has no tiles off
    the TPU and runs the xla core: the path a CPU takes)."""
    model, cfg, params, ids, _ = tiny
    lp = params["layers"][1]
    x = reference.embed(params, ids)
    got, _ = jax.jit(model._attention_block, static_argnums=(2,))(
        lp, x, cfg.attention_layer(1))
    _close(got, reference.attention_part(lp, x, SIZES, 1), 1e-5)
    a = trunk.rms_norm(lp["ln1"], x, cfg.norm_eps)
    q, k, v = trunk.latent_qkv_projections(
        lp, a, cfg.n_heads, jnp.arange(cfg.seq_len), cfg.rope_theta, cfg.norm_eps)
    want = reference.queries_keys_values(reference._f32(lp), a, SIZES)
    for got_part, want_part in zip((q, k, v), want):
        assert got_part.shape == (2, 32, 4, 16)
        _close(got_part, want_part, 1e-5)
    _close(trunk.attention_core(q, k, v, impl),
           reference.attention(*want, lambda a: a), 1e-5)
    # ONE rotated key part a token: every head's last 4 are the same
    assert np.ptp(np.asarray(k[..., 12:]), axis=2).max() == 0.0
    assert np.ptp(np.asarray(k[..., :12]), axis=2).min() > 0.0


def test_both_heads_logits_and_both_losses_match_the_reference(tiny, want):
    model, cfg, params, ids, tgt = tiny
    want, want_mtp, (want_loss, want_ce, want_ce_mtp), _ = want
    logits, _ = jax.jit(model.apply)(params, ids)
    _close(logits, want)
    x, x_mtp, aux = jax.jit(
        lambda p, i, t: model._hidden(p, i, next_ids=t))(params, ids, tgt)
    _close(model._logits(x, model._head(params)), want)
    _close(model._logits(x_mtp, model._head(params)), want_mtp)
    loss, metrics = jax.jit(model.loss_fn)(params, ids, tgt)
    for got, wanted in ((loss, want_loss), (metrics["ce"], want_ce),
                        (metrics["ce_mtp"], want_ce_mtp)):
        assert abs(float(got) - float(wanted)) <= 1e-5 * abs(float(wanted))
    assert float(loss) == pytest.approx(
        float(metrics["ce"]) + 0.3 * float(metrics["ce_mtp"]), rel=1e-6)
    # the step's counters take in the block's mixture: one more row, the last
    assert metrics["expert_counts"].shape == (5, 16)
    assert int(metrics["expert_counts"].sum()) == 5 * ids.size * 4
    assert float(metrics["dropped_fraction"]) == 0.0
    # without the next ids the stack runs alone, over its own four routers
    _, stack_aux = jax.jit(model._hidden)(params, ids)
    assert stack_aux["expert_counts"].shape == (4, 16)
    np.testing.assert_array_equal(
        stack_aux["expert_counts"], aux["expert_counts"][:4])


def test_gradients_of_every_parameter_match_the_reference(tiny, want):
    """The gradient of EVERY leaf (the block's and the shared table's and
    head's among them) to 1e-4 of the reference's largest entry of that
    leaf; the selection biases' are exactly zero on both sides."""
    model, _, params, ids, tgt = tiny
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(p, ids, tgt)[0]))(params)
    names = []
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want[3]),
    ):
        name, w = jax.tree_util.keystr(path), np.asarray(w)
        names.append(name)
        if name.endswith("['router_bias']"):
            assert not np.asarray(g).any() and not w.any(), name
            continue
        assert np.abs(w).max() > 0, name
        _close(g, w, err_msg=name)
    assert "['mtp']['w_eh']" in names and "['mtp']['layer']['wkv_b']" in names


# ---- (b) the negatives: the comparison can fail ----


def _unshifted(model):
    """A model whose prediction block is fed each position's OWN id."""
    hidden = model._hidden
    model._hidden = lambda p, i, mask=None, next_ids=None: hidden(
        p, i, mask, None if next_ids is None else i)
    return model


NEGATIVES = {
    # the keys' shared part left out of the rotation: every layer is wrong
    "keys_lack_the_rotated_part": (
        lambda cfg: (DMoETransformerLM(cfg, _one_device_mesh()), limits.reference_with(
            queries_keys_values=functools.partial(
                reference.queries_keys_values, rotate_keys=False))),
        ("layers_rms",)),
    "loss_fn_without_ce_mtp": (
        lambda cfg: (DMoETransformerLM(dataclasses.replace(
            cfg, mtp_loss_weight=0.0), _one_device_mesh()), reference),
        ("loss",)),
    "block_fed_the_unshifted_ids": (
        lambda cfg: (_unshifted(DMoETransformerLM(cfg, _one_device_mesh())),
                     reference),
        ("loss", "hidden_token_median")),
    "halves_of_the_concatenation_swapped": (
        lambda cfg: (DMoETransformerLM(cfg, _one_device_mesh()), limits.reference_with(
            mtp_input=functools.partial(
                reference.mtp_input, embedding_first=False))),
        ("layers_rms",)),
}


def test_the_block_as_it_is_reads_inside_the_runner_tolerances(tiny):
    read = limits.read(tiny)
    assert limits.outside(read) == []
    # the embedding, five layers, the block's combine, the block's layer
    assert len(read["embed_and_layers_rms"]) == 8
    assert len(read["near_tie_shares"]) == 6 and read["near_tie_shares"][0] == 0.0


@pytest.mark.parametrize("name", sorted(NEGATIVES))
def test_a_wrong_block_fails_the_runner_tolerances(tiny, name):
    build, outside = NEGATIVES[name]
    model, ref = build(tiny[1])
    read = limits.read(tiny, model, ref)
    assert limits.none_inside(read, *outside), read


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    """The reference with float8 operands in the program's place reads
    outside the layer and logits limits; with bf16 operands inside."""
    heads = ("layers_rms", "logits_rms", "mtp_logits_rms")
    assert limits.none_inside(
        limits.read(tiny, operand_dtype=jnp.float8_e4m3fn), *heads)
    assert limits.inside(limits.read(tiny, operand_dtype=jnp.bfloat16), *heads)


# ---- (c) the loss layer's masked pass ----


@pytest.mark.parametrize("n, chunk", [(64, 64), (64, 16), (50, 16)])
def test_the_masked_cross_entropy_leaves_out_positions_without_a_target(n, chunk):
    """``_ce_of_chunks(masked=True)``: value and both gradients equal the
    plain mean over the positions that have a target; a position without
    one gets no gradient at all; unmasked it is the pass it was."""
    rs = np.random.RandomState(n + chunk)
    x = jnp.asarray(rs.randn(n, 24), jnp.float32)
    head = jnp.asarray(rs.randn(24, 40), jnp.float32)
    targets = jnp.asarray(rs.randint(0, 40, n)).at[jnp.asarray([3, n - 1])].set(-1)
    has = np.asarray(targets) >= 0

    def plain(x, head):
        logp = jax.nn.log_softmax(x @ head, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(targets, 0)[:, None], -1)
        return -jnp.sum(jnp.where(has, picked[:, 0], 0.0)) / has.sum()

    def chunked(x, head):
        return transformer._ce_of_chunks(
            x, head, targets, chunk, int(has.sum()), True)

    want, (want_x, want_head) = jax.value_and_grad(plain, (0, 1))(x, head)
    got, (got_x, got_head) = jax.value_and_grad(chunked, (0, 1))(x, head)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(chunked(x, head)) == pytest.approx(float(want), rel=1e-6)
    _close(got_x, want_x, 1e-5)
    _close(got_head, want_head, 1e-5)
    assert not np.asarray(got_x)[~has].any()
    every = jnp.maximum(targets, 0)
    same = [transformer._ce_of_chunks(x, head, every, chunk, n, flag)
            for flag in (False, True)]
    assert float(same[0]) == float(same[1])


# ---- (g) the benchmark's files ----


def test_flops_of_the_cell_are_the_issue_arithmetic():
    """1,853 MFLOP a token forward by part, as ISSUE.md reckons them."""
    parts = glm47_flops.forward_flops_per_token(CELL_FILE)
    mega = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mega == {
        "latent_projections": 6 * 43.5 + 0.1, "attention_core": 1006.7,
        "dense_ffn": 125.8, "shared_expert": 94.4, "router": 1.3,
        "routed_experts": 188.7, "mtp_combine": 16.8, "head": 158.6}
    layer = glm47_flops.attention_forward_flops_per_token(CELL_FILE)
    assert round(layer["latent_projections"] / 1e6, 1) == 43.5
    assert round(layer["attention_core"] / 1e6, 1) == 167.8
    assert round(sum(parts.values()) / 1e6) == 1853
    assert round(glm47_flops.train_flops_per_token(CELL_FILE) / 1e9, 2) == 5.56
    assert glm47_flops.level_rows_per_token(CELL_FILE) == 2.0
    assert glm47_flops.counted_rows(CELL_FILE, 16384, 1.0) == 32768  # half the buffer
    assert glm47_flops.grouped_matmul_flops(CELL_FILE, 16384, 1.25) == (
        2 * 40960 * 2048 * 1536)
    more = glm47_flops.forward_flops_per_token(CELL_FILE, 1.5)
    assert more["routed_experts"] == 1.5 * parts["routed_experts"]
    pairs = 16384 * 16385 // 2
    assert glm47_flops.attention_kernel_flops(
        CELL_FILE, 16384, "global", "forward") == 20 * pairs * 2 * (256 + 256)
    assert glm47_flops.attention_kernel_flops(
        CELL_FILE, 16384, "global", "backward") == 20 * pairs * 2 * (3 * 256 + 2 * 256)
    with pytest.raises(ValueError, match="window"):
        glm47_flops.attention_kernel_flops(CELL_FILE, 16384, "window", "forward")


def test_parameters_of_the_cell_are_the_issue_arithmetic():
    """1,838,980,928 parameters: the issue's count, from the recipe's shapes."""
    model, cfg, _, batch = glm_4_7_flash_one_chip(_one_device_mesh())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    attention = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512 + 512 * 8960
                 + 5120 * 2048)
    assert attention == 21_759_232
    dense, sparse = shapes["layers"][0], shapes["layers"][1]
    assert count(dense) == attention + 3 * 2048 * 10240 + 2 * 2048 == 84_677_888
    outside = attention + 2 * 2048 + 2048 * 64 + 64 + 3 * 2048 * 1536
    assert outside == 31_331_648
    assert count(sparse) == outside + 32 * 3 * 2048 * 1536
    assert count(shapes["mtp"]) == count(sparse) + 4096 * 2048 + 3 * 2048
    assert count(shapes) == 1_838_980_928
    assert (cfg.seq_len, cfg.vocab_size, batch) == (16384, 19360, 1)
    runner._check_sizes(CELL_FILE, cfg)


def test_configuration_file_carries_the_catalog_entry():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the two of ``reduced`` it has."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if json.loads(line)["name"] == "GLM-4.7-Flash")
    assert CELL_FILE["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CELL_FILE.get(k) != v}
    assert differs == {"n_routed_experts", "vocab_size"}
    assert CELL_FILE["reduced"] == ["n_layers", "n_routed_experts", "vocab_size"]
    assert (CELL_FILE["n_routed_experts_published"], CELL_FILE["vocab_size_published"],
            CELL_FILE["num_hidden_layers"], CELL_FILE["n_layers"]) == (
        64, 154880, 47, 5)
    assert CELL_FILE["n_routed_experts"] * CELL_FILE["chips_sharing_a_layers_experts"] == 64
    assert CELL_FILE["vocab_size"] * CELL_FILE["chips_sharing_the_vocabulary"] == 154880
    assert CELL_FILE["num_nextn_predict_layers"] == 1 and "not_built" not in CELL_FILE
    assert any("mtp_loss_weight 0.3" in a for a in CELL_FILE["assumed"])


def test_reducers_read_this_cells_tables_and_nothing_where_there_is_none():
    """This cell's readings through the accepted reducers, each from the
    file of the entry the manifest reports it by: operations at the rows
    the step counted; the prediction block's share from the table's
    ``mtp_s``; ``None`` (the metric is left out) where a program or a trace
    has nothing to read."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "reducers"))
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    readings = readings_of_cell(manifest, CELL)

    obs = {"tokens_per_s_per_chip": 14000.0, "device_kind": "TPU v5 lite",
           "sizes": CELL_FILE, "tokens_per_step_per_chip": 16384,
           "local_rows_over_level": [1.0, 1.5],
           "scopes": {"grouped_matmul_s": 0.2, "grouped_matmul_calls": 60,
                      "total_s": 2.0, "mtp_s": 0.4, "attention_kernel_s": 1.0,
                      "by_scope": {"attention": 1.1, "latent_down": 0.1,
                                   "latent_up": 0.12, "rope": 0.03, "ce": 0.2},
                      "attention_kernels": {
                          "global.forward": {"s": 0.4, "calls": 24},
                          "global.backward": {"s": 0.6, "calls": 12}}}}

    def read(reading, observations=obs):
        spec = layer_metric_file(manifest, readings[reading])
        reducer = harness.load_module(manifest, "reducers", spec["reducer"])
        return reducer.reduce(observations, **spec["args"])

    want = glm47_flops.train_flops_per_token(CELL_FILE, 1.25) * 14000 / 197e12
    assert read("mfu") == pytest.approx(100 * want)
    assert read("expert_matmul_roofline") == pytest.approx(
        100 * 60 * 2 * 32768 * 1.25 * 2048 * 1536 / (0.2 * 197e12))
    pairs = 20 * (16384 * 16385 // 2)
    assert read("attention_core_roofline") == pytest.approx(
        100 * (24 * pairs * 1024 + 12 * pairs * 2560) / (1.0 * 197e12))
    assert read("mtp_share") == pytest.approx(20.0)
    assert read("attention_latent_share") == pytest.approx(12.5)
    assert read("attention_share") == pytest.approx(67.5)
    assert read("attention_core_share") == pytest.approx(50.0)
    older = {k: v for k, v in obs.items() if k != "local_rows_over_level"}
    assert read("mfu", older) is None
    assert read("expert_matmul_roofline", older) is None
    bare = dict(obs, scopes={"total_s": 2.0, "by_scope": {}})
    assert read("mtp_share", bare) is None
    assert read("attention_core_roofline", bare) is None
    assert read("attention_latent_share", bare) == 0.0


def test_the_scope_table_takes_in_the_prediction_block():
    """The runner's table over a made-up trace: the latent's scopes come
    out of ``attention``, ``mtp`` holds what lies under no other scope, and
    ``mtp_s`` everything under ``mtp``."""
    import re
    import types

    base = types.SimpleNamespace(
        SCOPES=tuple((n, re.compile(r"[/(]%s[/)]" % n)) for n in (
            *runner.EXTRA_SCOPES, "experts", "attention", "ce")),
        GROUPED_MATMUL="ragged-dot", GROUPED_MATMUL_LAYOUT="ragged-dot-metadata")
    names = {"a": "jit(train_step)/layer_1/attention/latent_up/dot_general",
             "b": "jit(train_step)/layer_1/attention/dot_general",
             "c": "jit(train_step)/mtp/layer_0/attention/rope/mul",
             "d": "jit(train_step)/mtp/combine/dot_general",
             "e": "jit(train_step)/transpose(jvp(mtp))/ce/dot_general",
             "f": "jit(train_step)/ce/dot_general"}
    hlo = "\n".join(
        f'  %{n} = f32[] fusion(), metadata={{op_name="{p}"}}' for n, p in names.items())
    ops = [(n, i * 10, i * 10 + 1 + i) for i, n in enumerate(names)]
    table = runner._blocks_with_mtp().make_scope_times(base)(ops, hlo)
    by_scope = {k: round(v * 1e9) for k, v in table["by_scope"].items()}
    assert by_scope == {"latent_up": 1, "attention": 2, "rope": 3, "mtp": 4, "ce": 5 + 6}
    assert round(table["mtp_s"] * 1e9) == 3 + 4 + 5


def test_a_program_without_the_recipe_fails_at_once_with_no_result(tmp_path):
    """The new runner on a program from before this configuration (no
    ``glm_4_7_flash_one_chip`` in ``__graft_entry__``): ``no recipe``, exit
    code 2, no result line: what the parent commit does on the new cell."""
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    tiny_file = dict(TINY_FILE, recipe="a_recipe_from_the_future")
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "glm47-tiny.json"
    path.write_text(json.dumps(tiny_file))
    manifest = harness.load_json(os.path.join(
        REPO, "benchmarks", "rehearsal", "manifest_glm47.json"))
    rel = os.path.relpath(path, REPO)
    manifest["configs"][0]["file"] = rel
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
