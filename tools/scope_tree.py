#!/usr/bin/env python3
"""A cell's traced train step as a tree: device self time by the FULL
``jax.named_scope`` path of the compiled step's instructions
(docs/OBSERVABILITY.md "reading a pod step's trace by scope").

    chiprun --timeout 1500 -- python tools/scope_tree.py <cell> --seed <n> \\
        [--under attention] [--fold global,window] [--depth n] [--top k]
    python tools/scope_tree.py --from chiprun_out/scope_tree.<cell>.<seed>.json \\
        [--under ...] [--fold ...] [--depth n] [--top k]     # here, no chip

The run is the cell's OWN traced run, ``benchmarks/run.py --workload <cell>
--seed <n> --seconds <run_seconds> --trace 1``, in this process: the tool
keeps what the runner hands ``trace_reduce.self_times`` (one device's
traced operations) and what ``Compiled.as_text`` gives it (the compiled
step), edits no file of the benchmark and has no loop of its own.  The
run's own lines come first (``REFERENCE`` .. ``SCOPES``, the result), then:

- ``TOP LEVEL``: every instruction filed as the cell's RUNNER files it
  (its scope table, first match wins; its way of joining an instruction
  to its ``op_name``), beside the run's ``SCOPES`` line.  The two must
  agree: the tool exits 1 where they do not.
- ``TREE``: the same self times by scope path, ``jit(..)`` dropped,
  ``jvp(x)`` / ``transpose(x)`` read as ``x``, and ``layer_<i>``,
  ``checkpoint``, ``rematted_computation`` (and what ``--fold`` names)
  folded, in ms a step (the node's share of all traced self time times the
  run's ``train.step_ms_p50``) and as forward | recompute | backward (under
  ``rematted_computation``: recompute; else under ``transpose(..)``:
  backward).  A node's time is its own instructions' and its children's;
  under a node with instructions of its own, its ``--top`` largest with
  their HLO kind.  A fusion is filed under its ROOT's ``op_name``, so a
  stage that reads near 0 may live in a neighbour's fusion: ``holds also
  norm x11`` after a fusion counts its own instructions that name another
  scope (the compiled text's fused computations).  ``--under a/b``
  roots the tree at every path that holds the components ``a/b`` (as the
  runners' tables match a name anywhere in a path: the prediction block's
  ``mtp/attention`` with the stack's ``attention``); a mixer's scope
  likewise: ``--under delta/core``, ``--under ssm``, ``--under shortconv``
  (the conv mixer's three parts and the norm and add beside them, PR 61),
  ``--under hc`` (the residual streams' coefficients, Sinkhorn iterations,
  read and write of every part, the stack's and the prediction block's,
  forward, remat's second forward and backward, PR 64); ``--under delta``
  in ``ling-3.0-flash-vl``'s cell folds its six channel-decayed (KDA)
  layers into one node with ``in_proj``, ``conv``, ``decay``, ``core``,
  ``gate_norm``, ``out_proj`` below it (PR 66).

The instructions are written to ``chiprun_out/scope_tree.<cell>.<seed>.json``;
``--from`` renders such a file again without a run.

**A stale executable is refused.**  JAX's persistent compile cache keys on
the lowered text WITHOUT debug information, so an executable compiled before
a scope was named is loaded after it under the OLD names.  Where the step's
lowered text with debug information holds a stage of the attention part
that the compiled text does not, the tool says so and exits 3: delete
``.jax_compile_cache`` (or point ``JAX_COMPILATION_CACHE_DIR`` at an empty
directory) and run again.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import re
import runpy
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]

import harness  # noqa: E402
import trace_reduce  # noqa: E402

PASSES = ("forward", "recompute", "backward")
FOLDED = re.compile(r"^(?:layer_\d+|checkpoint|rematted_computation)$")
WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
LOC = re.compile(r'loc\("([^"]+)"')
FUSION_KIND = re.compile(r"\bkind=(k\w+)")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
ATTENTION_KINDS = ("global", "window")  # attention/<kind> where layers differ
NO_SCOPE = "(no scope)"
STALE = 3  # exit code


def components(op_name: str) -> list:
    """``op_name`` split at the slashes outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def fold(op_name: str, also: tuple = ()) -> tuple:
    """``(scope path, pass)`` of an instruction's ``op_name``: the path
    without the leading ``jit(..)``, the wrappers of differentiation, the
    per-layer and remat components, the names in ``also`` and the
    primitive at its end."""
    pass_ = ("recompute" if "rematted_computation" in op_name
             else "backward" if "transpose(" in op_name else "forward")
    parts = components(op_name)[:-1]  # the last is the primitive
    while parts and parts[0].startswith("jit("):
        parts = parts[1:]
    path: list = []
    for part in parts:
        wrapped = False
        while (m := WRAPPED.match(part)):
            part, wrapped = m.group(1), True
        for p in components(part):
            # transpose(jvp(x))/jvp(x)/.. is ONE x, the backward's way there
            if not (FOLDED.match(p) or p in also
                    or (wrapped and path and path[-1] == p)):
                path.append(p)
    return tuple(path), pass_


def attention_stages(op_names) -> set:
    """The stages of the attention part that ``op_names`` hold: the
    component under ``attention`` (or ``attention/<kind>``), ``flash/layout``
    apart from ``flash``."""
    stages = set()
    for op_name in op_names:
        path, _ = fold(op_name, also=ATTENTION_KINDS)
        if "attention" in path:
            below = path[path.index("attention") + 1:]
            if below:
                stages.add("/".join(below[:2]) if below[:2] == ("flash", "layout")
                           else below[0])
    return stages


def stale_stages(lowered_debug_text: str, compiled_text: str) -> list:
    """The attention stages the lowered step names and the compiled text
    lacks: none for an executable compiled from this program's text."""
    wanted = attention_stages(LOC.findall(lowered_debug_text))
    return sorted(wanted - attention_stages(
        _blocks().OP_NAME_ANYWHERE.findall(compiled_text)))


def refuse_stale(lowered_debug_text: str, compiled_text: str) -> None:
    """Exit ``STALE`` where the compiled text is an older program's."""
    stale = stale_stages(lowered_debug_text, compiled_text)
    if stale:
        print(f"scope_tree: the executable is STALE: the step names the "
              f"attention stages {stale} and the compiled text has none of "
              "them (the persistent cache keys on the text without debug "
              "information). Delete .jax_compile_cache (or point "
              "JAX_COMPILATION_CACHE_DIR at an empty directory) and run "
              "again.", file=sys.stderr)
        sys.exit(STALE)


@functools.cache
def _blocks():
    """``train_recipe_blocks``: its ``op_names`` joins an instruction to the
    ``op_name`` on a LATER line (the attention kernel writes a newline into
    its call's attributes)."""
    return harness.load_module(
        harness.load_manifest("BENCHMARK.json"), "runners", "train_recipe_blocks")


def instruction_rows(ops: list, hlo_text: str) -> list:
    """``[name, self_ns, calls, op_name, own_line, kind, inside]`` of every
    traced instruction, largest first: ``own_line`` whether the ``op_name``
    sits on the instruction's own line of the compiled text (what a
    line-by-line reader finds), ``kind`` its HLO opcode, a fusion's with its
    kind, ``inside`` a fusion's own instructions by ``op_name`` (``{op_name:
    how many}``: the fusion is filed under its root's alone)."""
    blocks = _blocks()
    op_name = blocks.op_names(hlo_text)
    own_line, kind, called, bodies = {}, {}, {}, {}
    body = None  # the computation the line belongs to
    for line in hlo_text.splitlines():
        if (m := COMPUTATION.match(line)):
            body = bodies.setdefault(m.group(1), {})
        elif (m := blocks.INSTRUCTION.match(line)) and m.group(1) not in kind:
            code = trace_reduce.opcode(line)
            fusion = FUSION_KIND.search(line) if code == "fusion" else None
            kind[m.group(1)] = f"{code} {fusion.group(1)}" if fusion else code
            found = blocks.OP_NAME_ANYWHERE.search(line)
            own_line[m.group(1)] = bool(found)
            if fusion and (calls_ := CALLS.search(line)):
                called[m.group(1)] = calls_.group(1)
            if found and body is not None:
                body[found.group(1)] = body.get(found.group(1), 0) + 1
    calls: dict = {}
    for name, _, _ in ops:
        calls[name] = calls.get(name, 0) + 1
    return sorted(
        ([name, ns, calls[name], op_name.get(name, ""),
          own_line.get(name, False), kind.get(name, ""),
          bodies.get(called.get(name), {})]
         for name, ns in trace_reduce.self_times(ops).items()),
        key=lambda row: -row[1])


def runner_filing(manifest: dict, runner: str) -> tuple:
    """``(scope names in the table's order, line_by_line, the grouped
    matmul's instruction prefix)`` as the runner named ``runner`` builds
    them in its ``run()``: its ``EXTRA_SCOPES`` before
    ``train_recipe.SCOPES``, ``mtp`` after every other where the runner has
    the pattern; ``train_recipe`` itself joins line by line; a grouped
    matmul's call has lost its path and is the experts'.
    This is the tool's one piece of knowledge of the runners' tables
    (ROADMAP.md, the ``benchmark`` queue: a table keyed by the folded path
    takes it away)."""
    base = harness.load_module(manifest, "runners", "train_recipe")
    module = harness.load_module(manifest, "runners", runner)
    names = tuple(getattr(module, "EXTRA_SCOPES", ())) + tuple(
        name for name, _ in base.SCOPES)
    if hasattr(module, "MTP"):
        names += ("mtp",)
    return names, runner == "train_recipe", base.GROUPED_MATMUL


def top_level(rows: list, filing: tuple) -> dict:
    """Seconds by the runner's scope, as its ``scope_times`` files them."""
    names, line_by_line, grouped_matmul = filing
    table = [(name, re.compile(r"[/(]%s[/)]" % name)) for name in names]
    by_scope: dict = {}
    for name, ns, _, op_name, own_line, *_ in rows:
        if name.startswith(grouped_matmul):
            scope = "experts"
        else:
            path = "/" + (op_name if own_line or not line_by_line else "") + "/"
            scope = next((s for s, pattern in table if pattern.search(path)),
                         "other")
        by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
    return by_scope


def check_top_level(mine: dict, scopes_line: dict) -> bool:
    """Print the two side by side; whether they agree to a millionth."""
    theirs = scopes_line["by_scope"]
    total = scopes_line["total_s"]
    agree = True
    print(f"TOP LEVEL  {'scope':<16}{'this tool, s':>14}{'SCOPES line, s':>16}"
          f"{'share':>9}")
    for scope in sorted(set(mine) | set(theirs), key=lambda s: -mine.get(s, 0.0)):
        a, b = mine.get(scope, 0.0), theirs.get(scope, 0.0)
        same = abs(a - b) <= 1e-6 * max(total, 1e-9)
        agree &= same
        print(f"           {scope:<16}{a:>14.6f}{b:>16.6f}{100 * a / total:>8.2f}%"
              f"{'' if same else '   DIFFERS'}")
    return agree


def tree(rows: list, step_ms: float, under: str | None = None,
         also: tuple = (), depth: int | None = None, top: int = 3) -> list:
    """The lines of the tree (see the module's text)."""
    total_ns = sum(row[1] for row in rows)
    steps = statistics.mode(row[2] for row in rows)  # an instruction runs once a step
    root = tuple(components(under)) if under else ()
    own: dict = {}  # path -> [ns by pass]
    held: dict = {}  # path -> its instructions
    merged = set()
    for row in rows:
        path, pass_ = fold(row[3], also)
        path = path or (NO_SCOPE,)
        if root:
            at = next((i for i in range(len(path) - len(root) + 1)
                       if path[i:i + len(root)] == root), None)
            if at is None:
                continue
            merged.add("/".join(path[:at + len(root)]))
            path = path[at + len(root):]
        if depth is not None:
            path = path[:depth]
        own.setdefault(path, [0, 0, 0])[PASSES.index(pass_)] += row[1]
        held.setdefault(path, []).append(row)

    def ms(ns: float) -> float:
        return ns / total_ns * step_ms

    def whole(path: tuple) -> list:
        return [sum(v[i] for p, v in own.items() if p[:len(path)] == path)
                for i in range(3)]

    def line(indent: int, label: str, by_pass: list) -> str:
        all_ = sum(by_pass)
        return (f"{'  ' * indent}{label:<{44 - 2 * indent}}{ms(all_):>10.3f} ms"
                f"{100 * all_ / total_ns:>8.3f} %   "
                + " | ".join(f"{ms(ns):.3f}" for ns in by_pass))

    lines = [
        f"TREE  ms a step of {step_ms:.3f} ({steps} steps traced), share of all "
        "traced self time, forward | recompute | backward",
    ]
    if root:
        lines.append("      under " + ", ".join(sorted(merged)))

    def walk(path: tuple, indent: int) -> None:
        children = sorted({p[:len(path) + 1] for p in own
                           if len(p) > len(path) and p[:len(path)] == path},
                          key=lambda p: -sum(whole(p)))
        if path in own and children:  # what lies directly under the node
            lines.append(line(indent, "(directly here)", own[path]))
        if path in own:
            for name, ns, calls, op_name, _, kind, inside in held[path][:top]:
                lines.append(
                    f"{'  ' * (indent + 1)}. {name}  [{kind}]  "
                    f"{components(op_name)[-1] or '-'}  {ms(ns):.3f} ms, "
                    f"{calls / steps:g} a step"
                    + _also_holds(fold(op_name, also)[0], inside, also))
        for child in children:
            lines.append(line(indent, child[-1], whole(child)))
            walk(child, indent + 1)

    lines.append(line(0, under or "(the step)", whole(())))
    walk((), 1)
    return lines


def _also_holds(own_path: tuple, inside: dict, also: tuple) -> str:
    """What a fusion holds of OTHER scopes than the one it is filed under
    (its root's): ``; holds also norm x11, qk_norm x1``, each path as far
    as it differs from the fusion's own."""
    others: dict = {}
    for op_name, n in inside.items():
        path = fold(op_name, also)[0]
        if path != own_path:
            shared = 0
            while (shared < min(len(path), len(own_path) - 1)
                   and path[shared] == own_path[shared]):
                shared += 1
            label = "/".join(path[shared:]) or "/".join(path) or NO_SCOPE
            others[label] = others.get(label, 0) + n
    if not others:
        return ""
    return "; holds also " + ", ".join(
        f"{label} x{n}" for label, n in sorted(others.items(), key=lambda kv: -kv[1]))


class _Tee:
    """Standard output that also keeps what it is given."""

    def __init__(self, under):
        self.under, self.text = under, []

    def write(self, s):
        self.text.append(s)
        return self.under.write(s)

    def flush(self):
        self.under.flush()


def traced_run(cell: str, seed: int, seconds: float, manifest: str) -> dict:
    """The cell's own traced run in this process; what the tool keeps of
    it (see the module's text).  Exits as the run does where it fails, and
    ``STALE`` on a stale executable."""
    import jax

    kept: dict = {}
    self_times, as_text = trace_reduce.self_times, jax.stages.Compiled.as_text
    compile_ = jax.stages.Lowered.compile

    def keep_ops(ops):
        kept["ops"] = ops
        return self_times(ops)

    def keep_text(compiled, *a, **k):
        kept["hlo"] = as_text(compiled, *a, **k)
        return kept["hlo"]

    def keep_lowered(lowered, *a, **k):
        kept["lowered"] = lowered.as_text(debug_info=True)
        return compile_(lowered, *a, **k)

    trace_reduce.self_times = keep_ops
    jax.stages.Compiled.as_text = keep_text
    jax.stages.Lowered.compile = keep_lowered
    tee = _Tee(sys.stdout)
    argv = sys.argv
    sys.argv = ["benchmarks/run.py", "--workload", cell, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", "1", "--manifest", manifest]
    try:
        with contextlib.redirect_stdout(tee):
            runpy.run_path(os.path.join(REPO, "benchmarks", "run.py"),
                           run_name="__main__")
    except SystemExit as e:
        if e.code:
            raise
    finally:
        sys.argv = argv
        trace_reduce.self_times = self_times
        jax.stages.Compiled.as_text = as_text
        jax.stages.Lowered.compile = compile_
    if not {"ops", "hlo", "lowered"} <= set(kept):
        sys.exit("scope_tree: the run traced no device operation of a "
                 "compiled step (is the cell a train cell?)")
    refuse_stale(kept["lowered"], kept["hlo"])
    lines = "".join(tee.text).splitlines()
    result = json.loads(next(l for l in reversed(lines) if l.startswith("{")))
    return {
        "cell": cell, "seed": seed,
        "step_ms": next(v["value"] for k, v in result["metrics"].items()
                        if k.endswith("step_ms_p50")),
        "scopes_line": json.loads(next(
            l for l in lines if l.startswith("SCOPES "))[len("SCOPES "):]),
        "result": result,
        "rows": instruction_rows(kept["ops"], kept["hlo"]),
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell", nargs="?")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, help="the manifest's run_seconds")
    p.add_argument("--manifest", default="BENCHMARK.json")
    p.add_argument("--from", dest="dump", help="render a file an earlier run wrote")
    p.add_argument("--under", help="root the tree at this scope, e.g. attention")
    p.add_argument("--fold", default="", help="components to fold besides, "
                   "comma separated, e.g. global,window")
    p.add_argument("--depth", type=int, help="levels shown below the root")
    p.add_argument("--top", type=int, default=3,
                   help="largest instructions shown under a node")
    args = p.parse_args()
    if (args.cell is None) == (args.dump is None):
        p.error("a cell to run, or --from a file of an earlier run")

    manifest = harness.load_manifest(args.manifest)
    if args.dump:
        with open(args.dump) as f:
            run = json.load(f)
    else:
        run = traced_run(args.cell, args.seed,
                         args.seconds or manifest["run_seconds"], args.manifest)
        out_dir = os.path.join(REPO, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"scope_tree.{run['cell']}.{run['seed']}.json"), "w") as f:
            json.dump(run, f)
    cell = harness.by_name(manifest["workloads"], run["cell"], "workload")
    config = harness.load_json(os.path.join(harness.ROOT, harness.by_name(
        manifest["configs"], cell["config"], "config")["file"]))
    print(f"SCOPE_TREE {run['cell']} seed {run['seed']} runner {config['runner']}")
    agree = check_top_level(
        top_level(run["rows"], runner_filing(manifest, config["runner"])),
        run["scopes_line"])
    print("\n".join(tree(
        run["rows"], run["step_ms"], args.under,
        tuple(n for n in args.fold.split(",") if n), args.depth, args.top)))
    if not agree:
        print("scope_tree: the top level is not the run's SCOPES line",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
