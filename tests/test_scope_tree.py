"""The attention part's stages by name in the pod step's scope paths
(``norm``, ``proj``, ``qk_norm``, ``rope``, ``flash/layout``, ``out_proj``;
docs/OBSERVABILITY.md "Pod train step"), what the benchmark's runners make
of the new paths, and ``tools/scope_tree.py``, which reads a traced step by
the full path.  Tiny sizes on the CPU; no chip.
"""

import os
import re
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

from __graft_entry__ import (  # noqa: E402
    flagship_one_chip,
    glm_4_7_flash_one_chip,
    k_exaone_one_chip,
    nemotron_labs_twotower_one_chip,
    olmo_hybrid_7b_one_chip,
    olmoe_one_chip,
    smallthinker_one_chip,
)
from test_olmoe import lowered_tiny_step  # noqa: E402

scope_tree = harness.load_path(os.path.join(REPO, "tools", "scope_tree.py"))
MANIFEST = harness.load_manifest("BENCHMARK.json")

# what the attention scope (``attention``, or ``attention/<kind>``) holds,
# stage by stage; the residual add, the ``xla`` core (jax's
# ``dot_product_attention``: its einsums and softmax read ``vmap(..)``) and
# the latent form's slices of q lie directly under it
STAGES = {"norm", "proj", "qk_norm", "rope", "out_proj", "latent_down",
          "latent_up", "flash"}
PRODUCTS = {"proj", "out_proj", "latent_down", "latent_up"}


# ---- (a) every equation of the attention part lies under one stage ----


@pytest.mark.parametrize("recipe, axes, stages", [
    (flagship_one_chip, {"expert": 1}, {"norm", "proj", "out_proj"}),
    (flagship_one_chip, {"data": 2, "expert": 2}, {"norm", "proj", "out_proj"}),
    (olmoe_one_chip, {"expert": 1},
     {"norm", "proj", "qk_norm", "rope", "out_proj"}),
    (smallthinker_one_chip, {"expert": 1}, {"norm", "proj", "rope", "out_proj"}),
    (k_exaone_one_chip, {"expert": 1},
     {"norm", "proj", "qk_norm", "rope", "out_proj"}),
    (glm_4_7_flash_one_chip, {"expert": 1},
     {"norm", "latent_down", "latent_up", "rope", "out_proj"}),
    (nemotron_labs_twotower_one_chip, {"expert": 1},
     {"norm", "proj", "out_proj"}),
    (olmo_hybrid_7b_one_chip, {"expert": 1},
     {"norm", "proj", "qk_norm", "out_proj"}),
], ids=["dmoe-one-chip", "dmoe-pod4", "olmoe-one-chip", "smallthinker-one-chip",
        "k-exaone-one-chip", "glm-4.7-flash-one-chip", "nemotron-one-chip",
        "olmo-hybrid-one-chip"])
def test_every_equation_of_the_attention_part_lies_under_one_stage(
    recipe, axes, stages
):
    """The eight tiny train steps, from the name stacks in the lowered text
    with debug information (the text the hashes of ``tests/test_olmoe.py``
    hold carries none): under the attention scope an equation lies under
    ONE documented stage, or directly under the scope (the residual add;
    the latent form's slices of q) or in the ``xla`` core's ``vmap(..)``;
    every product is a projection's or the core's; every norm's ``rsqrt``
    is under ``norm``, ``qk_norm`` or ``latent_down``, so where the norm is
    on the part's OUTPUT (Olmo-Hybrid: nothing normalizes the input) it is
    under ``norm`` too."""
    below_attention = {}  # (components below the scope) -> primitives
    lowered = lowered_tiny_step(recipe, axes).as_text(debug_info=True)
    for loc in scope_tree.LOC.findall(lowered):
        path, _ = scope_tree.fold(loc, also=scope_tree.ATTENTION_KINDS)
        if "attention" in path:
            below_attention.setdefault(
                path[path.index("attention") + 1:], set()
            ).add(scope_tree.components(loc)[-1])
    assert below_attention
    found = set()
    for below, primitives in below_attention.items():
        stage = below[0] if below else None
        if stage is None or stage.startswith("vmap("):  # the add, the core
            assert "rsqrt" not in primitives, below
            continue
        assert stage in STAGES, below
        assert not STAGES & set(below[1:]), below  # no stage inside a stage
        found.add(stage)
        if "dot_general" in primitives:
            assert stage in PRODUCTS, below
        if "rsqrt" in primitives:
            assert stage in {"norm", "qk_norm", "latent_down"}, below
    assert found == stages  # the CPU: the ``xla`` core, no ``flash``
    assert "rsqrt" in below_attention[("norm",)]
    assert "add" in below_attention[()]  # the residual add stays there


# ---- (c) the runners' tables file the new paths where they filed the old ----

HLO = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, metadata={op_name="jit(train_step)/jvp(layer_0)/attention/proj/dot_general"}
  %copy.2 = bf16[8]{0} copy(%p), metadata={op_name="jit(train_step)/transpose(jvp(layer_1))/jvp(layer_1)/checkpoint/rematted_computation/attention/window/flash/layout/transpose"}
  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(mtp)/layer_0/attention/rope/mul"}
  %fusion.4 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(layer_2))/jvp(layer_2)/checkpoint/attention/global/norm/mul"}
  %convolution.5 = bf16[8]{0} convolution(%p, %q), metadata={op_name="jit(train_step)/jvp(layer_2)/attention/global/out_proj/dot_general"}
  %splash_mha_fwd_residuals.6 = (f32[8]{0}, bf16[8]{0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
}}, metadata={op_name="jit(train_step)/jvp(layer_1)/attention/window/flash/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(layer_3)/ssm/out_proj/dot_general"}
  %fusion.8 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(layer_4)/delta/out_proj/dot_general"}
  %ragged-dot-none.9 = bf16[8,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.10 = bf16[8]{0} copy(%p)
"""
NAMES = ("fusion.1", "copy.2", "fusion.3", "fusion.4", "convolution.5",
         "splash_mha_fwd_residuals.6", "fusion.7", "fusion.8",
         "ragged-dot-none.9", "copy.10")
# instruction i runs 2**i ns a step, two steps: every sum names its parts
OPS = [(name, step * 4096 + 2 ** i, step * 4096 + 2 ** (i + 1))
       for step in range(2) for i, name in enumerate(NAMES)]


def _ns(name: str) -> int:
    return 2 * 2 ** NAMES.index(name)


def _runner_scope_times(name: str):
    """``scope_times`` as the runner ``name`` builds it in its ``run()``."""
    base = harness.load_module(MANIFEST, "runners", "train_recipe")
    if name == "train_recipe":
        return base.scope_times
    blocks = harness.load_module(MANIFEST, "runners", "train_recipe_blocks")
    if name == "train_recipe_blocks":
        return blocks.make_scope_times(base)
    runner = harness.load_module(MANIFEST, "runners", name)
    table = types.SimpleNamespace(
        SCOPES=tuple((n, re.compile(r"[/(]%s[/)]" % n))
                     for n in runner.EXTRA_SCOPES) + base.SCOPES,
        GROUPED_MATMUL=base.GROUPED_MATMUL,
        GROUPED_MATMUL_LAYOUT=base.GROUPED_MATMUL_LAYOUT)
    if name == "train_recipe_latent":
        return runner._blocks_with_mtp().make_scope_times(table)
    return blocks.make_scope_times(table)


ATTENTION = ("fusion.1", "copy.2", "fusion.3", "fusion.4", "convolution.5",
             "splash_mha_fwd_residuals.6")


@pytest.mark.parametrize("name, moved", [
    # the line-by-line join: the kernel's call reads as ``other``
    ("train_recipe", {"other": ("splash_mha_fwd_residuals.6",)}),
    ("train_recipe_blocks", {}),
    ("train_recipe_share", {}),
    ("train_recipe_latent", {"rope": ("fusion.3",)}),
    ("train_recipe_hybrid", {"ssm/out_proj": ("fusion.7",)}),
    ("train_recipe_delta", {"delta/out_proj": ("fusion.8",)}),
])
def test_the_runners_tables_file_the_stages_under_attention(name, moved, capsys):
    """Instructions under the new paths through each runner's table: filed
    under ``attention`` as ``attention/...`` was before the stages had
    names (``rope`` in the latent form's table, whose key it is); no stage's
    name is a key of a table, and ``out_proj`` under ``attention`` is not
    the mixers' ``ssm/out_proj`` or ``delta/out_proj``.  The tool's top
    level files every instruction as the runner does."""
    keys = scope_tree.runner_filing(MANIFEST, name)[0]
    assert not {"norm", "proj", "layout", "out_proj"} & {
        part for key in keys for part in [key, key.split("/")[0]]}
    got = _runner_scope_times(name)(OPS, HLO)
    elsewhere = {n for names in moved.values() for n in names}
    want = {
        "attention": sum(_ns(n) for n in ATTENTION if n not in elsewhere),
        "experts": _ns("ragged-dot-none.9"),
        "other": sum(_ns(n) for n in ("fusion.7", "fusion.8", "copy.10")
                     if n not in elsewhere),
    }
    for scope, names in moved.items():
        want[scope] = want.get(scope, 0) + sum(_ns(n) for n in names)
    assert {k: round(v * 1e9) for k, v in got["by_scope"].items()} == want
    rows = scope_tree.instruction_rows(OPS, HLO)
    mine = scope_tree.top_level(rows, scope_tree.runner_filing(MANIFEST, name))
    assert mine == pytest.approx(got["by_scope"])
    assert scope_tree.check_top_level(mine, got)
    mine["attention"] += 1e-3
    assert not scope_tree.check_top_level(mine, got)
    assert "DIFFERS" in capsys.readouterr().out


# ---- (d) the tool's tree, and its refusal of a stale executable ----


def test_the_tree_folds_the_paths_and_splits_the_passes():
    """``fold`` and ``tree`` on the fixture: one node a stage whatever the
    layer, the pass and the kind of layer (``--fold``), the prediction
    block's attention with the stack's under ``--under attention``, ms a
    step as the node's share of the traced self time, the instructions
    with their HLO kind."""
    fold = scope_tree.fold
    assert fold("jit(train_step)/jvp(layer_0)/attention/proj/dot_general") == (
        ("attention", "proj"), "forward")
    assert fold("jit(train_step)/transpose(jvp(layer_1))/jvp(layer_1)/checkpoint/"
                "rematted_computation/attention/window/flash/layout/transpose") == (
        ("attention", "window", "flash", "layout"), "recompute")
    assert fold("jit(train_step)/transpose(jvp(mtp))/layer_0/attention/rope/mul",
                also=("window",)) == (("mtp", "attention", "rope"), "backward")
    assert fold("jit(train_step)/transpose(jvp(mtp))/jvp(mtp)/checkpoint/layer_0/"
                "attention/flash/vmap(jit(_splash_attention))/splash_mha_dkv/"
                "splash_mha_dkv/pallas_call")[0] == (
        "mtp", "attention", "flash", "vmap(jit(_splash_attention))",
        "splash_mha_dkv", "splash_mha_dkv")
    assert fold("ragged-dot-none") == ((), "forward")
    rows = scope_tree.instruction_rows(OPS, HLO)
    assert rows[0][:3] == ["copy.10", _ns("copy.10"), 2]
    by_name = {row[0]: row for row in rows}
    assert by_name["splash_mha_fwd_residuals.6"][3:] == [
        "jit(train_step)/jvp(layer_1)/attention/window/flash/"
        "vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call",
        False, "custom-call", {}]
    assert by_name["fusion.1"][4:] == [True, "fusion kOutput", {}]
    total = sum(row[1] for row in rows)
    lines = scope_tree.tree(rows, step_ms=float(total), under="attention",
                            also=("global", "window"), top=1)
    assert lines[1] == "      under attention, mtp/attention"
    text = "\n".join(lines)
    # step_ms == the traced ns: a node's ms is its ns; 2 steps traced

    def node(label):
        return re.search(
            "^ *" + re.escape(label)
            + r" +([\d.]+) ms +[\d.]+ % +([\d.]+) \| ([\d.]+) \| ([\d.]+)$",
            text, re.M).groups()

    assert [float(x) for x in node("attention")] == [
        sum(_ns(n) for n in ATTENTION),
        _ns("fusion.1") + _ns("fusion.3") + _ns("convolution.5")
        + _ns("splash_mha_fwd_residuals.6"), _ns("copy.2"), _ns("fusion.4")]
    assert float(node("layout")[0]) == _ns("copy.2")
    assert float(node("flash")[0]) == _ns("copy.2") + _ns("splash_mha_fwd_residuals.6")
    assert ". copy.2  [copy]  transpose  4.000 ms, 1 a step" in text
    assert "ssm" not in text and "ragged" not in text
    whole = "\n".join(scope_tree.tree(rows, float(total), depth=1))
    assert re.search(r"^  \(no scope\) +%d\.000 ms" % (
        _ns("ragged-dot-none.9") + _ns("copy.10")), whole, re.M)
    assert "proj" not in whole


def test_a_fusion_says_what_it_holds_of_other_stages():
    """A fusion is filed under its ROOT's ``op_name``; the tree says which
    other scopes its own instructions name, so a stage that reads near 0
    can be found in the neighbour that took it in."""
    hlo = """
%fused_computation.7 (param_0.1: bf16[8], param_1.2: bf16[8]) -> bf16[8] {
  %param_0.1 = bf16[8]{0} parameter(0)
  %multiply.3 = bf16[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/window/norm/mul"}
  %rsqrt.4 = bf16[8]{0} rsqrt(%multiply.3), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/window/norm/rsqrt"}
  %transpose.5 = bf16[8]{0} transpose(%rsqrt.4), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/window/flash/layout/transpose"}
  ROOT %convolution.6 = bf16[8]{0} convolution(%transpose.5, %param_1.2), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/window/proj/dot_general"}
}

ENTRY %main.9 (p: bf16[8], q: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %fusion.1 = bf16[8]{0} fusion(%p, %q), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_step)/jvp(layer_0)/attention/window/proj/dot_general"}
}
"""
    rows = scope_tree.instruction_rows([("fusion.1", 0, 1000)], hlo)
    assert rows == [[
        "fusion.1", 1000, 1,
        "jit(train_step)/jvp(layer_0)/attention/window/proj/dot_general", True,
        "fusion kOutput",
        {"jit(train_step)/jvp(layer_0)/attention/window/norm/mul": 1,
         "jit(train_step)/jvp(layer_0)/attention/window/norm/rsqrt": 1,
         "jit(train_step)/jvp(layer_0)/attention/window/flash/layout/transpose": 1,
         "jit(train_step)/jvp(layer_0)/attention/window/proj/dot_general": 1}]]
    text = "\n".join(scope_tree.tree(rows, 1.0, under="attention", also=("window",)))
    assert ". fusion.1  [fusion kOutput]  dot_general  1.000 ms, 1 a step; " \
        "holds also norm x2, flash/layout x1" in text


LOWERED = """
#loc7 = loc("jit(train_step)/jvp(layer_0)/attention/proj/dot_general"(#loc3))
#loc8 = loc("jit(train_step)/jvp(layer_0)/attention/flash/layout/transpose"(#loc3))
#loc9 = loc("jit(train_step)/jvp(layer_0)/attention/flash/vmap(jit(_splash_attention))"(#loc3))
#loc10 = loc("jit(train_step)/jvp(layer_0)/attention/add"(#loc3))
"""
COMPILED_BEFORE_THE_NAMES = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, metadata={op_name="jit(train_step)/jvp(layer_0)/attention/dot_general"}
  %copy.2 = bf16[8]{0} copy(%p), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/flash/transpose"}
"""
COMPILED = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, metadata={op_name="jit(train_step)/jvp(layer_0)/attention/proj/dot_general"}
  %copy.2 = bf16[8]{0} copy(%p), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/flash/layout/transpose"}
  %splash_mha_fwd_residuals.3 = (f32[8]{0}) custom-call(%a), metadata={op_name="jit(train_step)/jvp(layer_0)/attention/flash/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}
"""


def test_a_stale_executable_is_refused(capsys):
    """The lowered step names stages that the compiled text lacks: the
    executable came out of a cache filled before the names.  Exit 3 and
    what to delete; the same program's own compile passes."""
    assert scope_tree.stale_stages(LOWERED, COMPILED) == []
    scope_tree.refuse_stale(LOWERED, COMPILED)
    assert scope_tree.stale_stages(LOWERED, COMPILED_BEFORE_THE_NAMES) == [
        "flash/layout", "proj"]
    with pytest.raises(SystemExit) as refused:
        scope_tree.refuse_stale(LOWERED, COMPILED_BEFORE_THE_NAMES)
    assert refused.value.code == scope_tree.STALE == 3
    said = capsys.readouterr().err
    assert "Delete .jax_compile_cache" in said and "'proj'" in said


SCOPED = """
import re, sys, jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.tanh(x @ x)
text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
print(sorted(set(re.findall(r'op_name="jit.f./(\\w+)/', text))))
"""


def test_the_persistent_cache_hands_back_the_names_it_was_filled_under(tmp_path):
    """Why the tool must refuse: JAX keys its persistent cache on the
    lowered text without debug information, so a process that names a scope
    ``after`` loads the executable a process that named it ``before``
    compiled, and its text, like a trace of it, says ``before``
    (docs/OBSERVABILITY.md "whose names a trace shows")."""
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    env = clean_jax_subprocess_env(REPO, platform="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)

    def names(scope):
        run = subprocess.run([sys.executable, "-c", SCOPED, scope], env=env,
                             capture_output=True, text=True, timeout=120)
        assert run.returncode == 0, run.stderr[-2000:]
        return run.stdout.strip().splitlines()[-1]

    assert names("before") == "['before']"
    assert names("after") == "['before']"
