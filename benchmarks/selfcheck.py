#!/usr/bin/env python3
"""Checks that need no chip: the manifest against the contract's limits and
against the files it names, and the trace reduction against a recorded
trace.  Run before every submission:

    python3 benchmarks/selfcheck.py [manifest ...]

Exit code 0 and ``selfcheck: ok`` when nothing is wrong; otherwise every
finding on a line of its own and exit code 1.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|_dim$|"
                   r"_rank$|expansion|experts_per_tok)")
MAX_RUN_SECONDS = 51
FIXTURE = os.path.join(HERE, "fixtures", "six_steps.xplane.pb")
FIXTURE_EXPECTED = os.path.join(HERE, "fixtures", "six_steps.expected.json")


def line_ok(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def check_manifest(path: str) -> list:
    bad: list = []
    say = bad.append
    raw = open(os.path.join(harness.ROOT, path)).read()
    if len(raw.encode()) > 64 * 1024:
        say("the manifest is over 64 KiB")
    m = json.loads(raw)
    if set(m) != TOP_KEYS:
        say(f"top-level keys {sorted(set(m) ^ TOP_KEYS)} missing or unknown")
        return bad
    if not (isinstance(m["run_seconds"], int)
            and 1 <= m["run_seconds"] <= MAX_RUN_SECONDS):
        say(f"run_seconds {m['run_seconds']!r} outside 1..{MAX_RUN_SECONDS}")
    if not (1 <= len(m["command"]) <= 32 and all(map(line_ok, m["command"]))):
        say("command: 1 to 32 strings of 1 to 200 characters")
    if not 1 <= len(m["paths"]) <= 16:
        say("paths: 1 to 16 directories")
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            say(f"path {p!r}")
        elif not os.path.isdir(os.path.join(harness.ROOT, p)):
            say(f"path {p!r} is no directory")
    for word in m["command"]:
        if "/" in word and not any(
            word == p or word.startswith(p + "/") for p in m["paths"]
        ):
            say(f"command names {word!r}, outside paths")

    for section, allowed in KEYS.items():
        seen = set()
        limit = {"configs": 24, "workloads": 24, "end_to_end": 16,
                 "per_layer": 128}[section]
        if not 1 <= len(m[section]) <= limit:
            say(f"{section}: 1 to {limit} entries")
        for e in m[section]:
            extra = set(e) - allowed - (
                {"workloads"} if section in ("end_to_end", "per_layer") else set()
            )
            if extra or allowed - set(e):
                say(f"{section} {e.get('name')!r}: keys "
                    f"{sorted(extra | (allowed - set(e)))}")
                continue
            if not NAME.match(e["name"]):
                say(f"{section}: name {e['name']!r}")
            if e["name"] in seen:
                say(f"{section}: {e['name']!r} twice")
            seen.add(e["name"])
    if bad:
        return bad
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    if len(set(metric_names)) != len(metric_names):
        say("a metric name is used twice")

    configs = {c["name"]: c for c in m["configs"]}
    files = set()
    for c in m["configs"]:
        if not line_ok(c["source"]) or not line_ok(c["why"]):
            say(f"config {c['name']!r}: source/why of 1 to 200 characters")
        if not any(c["file"].startswith(p + "/") for p in m["paths"]):
            say(f"config {c['name']!r}: file outside paths")
        if c["file"] in files:
            say(f"config file {c['file']!r} serves two configurations")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            say(f"config {c['name']!r}: more than 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key) or WIDTH.search(key):
                say(f"config {c['name']!r}: reduced names {key!r}")
        full = os.path.join(harness.ROOT, c["file"])
        if not os.path.isfile(full):
            say(f"config {c['name']!r}: {c['file']} is missing")
            continue
        body = json.load(open(full))
        for key in ("runner", "platform", "source", "reduced"):
            if key not in body:
                say(f"{c['file']}: no {key!r}")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            say(f"{c['file']}: reduced differs from the manifest's")
        try:
            harness.find_file(m, "runners", body.get("runner", "?") + ".py")
        except harness.BenchError as e:
            say(f"{c['file']}: {e}")

    cells = {}
    pairs = set()
    for w in m["workloads"]:
        cells[w["name"]] = w
        if w["config"] not in configs:
            say(f"cell {w['name']!r}: no configuration {w['config']!r}")
        if not NAME.match(w["traffic"]) or not NAME.match(w["config"]):
            say(f"cell {w['name']!r}: config/traffic name")
        if w["chips"] not in (1, 4):
            say(f"cell {w['name']!r}: chips {w['chips']!r}")
        if not line_ok(w["why"]):
            say(f"cell {w['name']!r}: why of 1 to 200 characters, one line")
        if (w["config"], w["traffic"]) in pairs:
            say(f"cell {w['name']!r}: its pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            harness.find_file(m, "traffic", w["traffic"] + ".json")
        except harness.BenchError as e:
            say(f"cell {w['name']!r}: {e}")
    for name in configs:
        if not any(w["config"] == name for w in m["workloads"]):
            say(f"configuration {name!r} has no cell")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    if four > max(1, len(m["workloads"]) // 4):
        say(f"{four} four-chip cells of {len(m['workloads'])}: at most a "
            "quarter, rounded down, and one always")

    def cells_of(metric: dict) -> list:
        listed = metric.get("workloads")
        if listed is None:
            return list(cells)
        for name in listed:
            if name not in cells:
                say(f"metric {metric['name']!r} lists unknown cell {name!r}")
        return [n for n in listed if n in cells]

    for e in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(e["unit"]):
            say(f"metric {e['name']!r}: unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            say(f"metric {e['name']!r}: better {e['better']!r}")
        if e["source"] not in SOURCES:
            say(f"metric {e['name']!r}: source {e['source']!r}")
    reported = {name: set() for name in cells}
    for e in m["end_to_end"]:
        if e["source"] not in ("host_clock", "device_trace"):
            say(f"end-to-end {e['name']!r}: source {e['source']!r}")
        if not (isinstance(e["bound"], float) and 0.01 <= e["bound"] <= 0.1):
            say(f"end-to-end {e['name']!r}: bound {e['bound']!r} outside "
                "0.01..0.1")
        for name in cells_of(e):
            reported[name].add(e["name"])
    setup = [e for e in m["end_to_end"] if e["name"] == "setup_s"]
    if not setup or "workloads" in setup[0]:
        say("setup_s must be an end-to-end metric of every cell")
    for name, have in reported.items():
        if len(have - {"setup_s"}) < 1:
            say(f"cell {name!r} reports no end-to-end metric but setup_s")

    layered = {name: 0 for name in cells}
    readings: dict = {}  # (cell, the name after the first dot) -> entry's name
    for e in m["per_layer"]:
        if not line_ok(e["layer"]):
            say(f"per-layer {e['name']!r}: layer of 1 to 200 characters")
        mine = cells_of(e)
        reading = e["name"].split(".", 1)[-1]
        for name in mine:
            layered[name] += 1
            if e["moves"] not in reported[name]:  # PR 22's refusal
                say(f"per-layer {e['name']!r} is reported in {name!r}, where "
                    f"{e['moves']!r}, which it should move, is not")
            first = readings.setdefault((name, reading), e["name"])
            if first != e["name"]:
                say(f"per-layer {first!r} and {e['name']!r} are both the "
                    f"reading {reading!r} in cell {name!r}: a fold that left "
                    "a copy behind")
        try:
            spec = json.load(open(harness.find_file(
                m, "layer_metrics", e["name"] + ".json")))
            harness.find_file(m, "reducers", spec["reducer"] + ".py")
            for key in ("layer", "unit", "moves"):
                if spec.get(key) != e[key]:
                    say(f"layer_metrics/{e['name']}.json: {key} differs "
                        "from the manifest's")
        except (harness.BenchError, KeyError) as err:
            say(f"per-layer {e['name']!r}: {err}")
            continue
        # the file's own copy of where the metric is read, where it keeps
        # one, says what the manifest says (a folded file keeps none)
        if "workloads" in spec and spec["workloads"] != e.get("workloads"):
            say(f"layer_metrics/{e['name']}.json: workloads differs from "
                "the manifest's list")
        configs_of_mine = {cells[n]["config"] for n in mine}
        one = spec.get("config")  # a rehearsal's cells name theirs otherwise
        if one is not None and (len(configs_of_mine) > 1 or (
                one in configs and one not in configs_of_mine)):
            say(f"layer_metrics/{e['name']}.json: config {one!r} is not "
                "that of every cell the manifest lists")
        if len(configs_of_mine) > 1 and (
                {"module", "function"} & set(spec.get("args", {}))):
            say(f"per-layer {e['name']!r} lists cells of "
                f"{len(configs_of_mine)} configurations, and its file's args "
                "name a module or a function: ONE model's count of "
                "operations, which may list the cells of one configuration "
                "only")
    for name, n in layered.items():
        if not n:
            say(f"cell {name!r} reports no per-layer metric")
    return bad


def check_trace() -> list:
    """The recorded trace: six runs of one small program with sleeps
    between, taken on a TPU v5e (PR 24).  The expected numbers were read
    off it by hand when it was recorded."""
    bad = []
    want = json.load(open(FIXTURE_EXPECTED))
    events = trace_reduce.load_events(FIXTURE, ("step", "between_steps"))
    got = trace_reduce.reduce_events(events)
    runs = len(events["devices"][0]["modules"])
    if runs != want["program_runs"]:
        bad.append(f"trace: {runs} program runs, expected {want['program_runs']}")
    for key in ("busy_s", "span_s", "collective_s"):
        if abs(got[key] - want[key]) > 1e-9 + 1e-6 * abs(want[key]):
            bad.append(f"trace: {key} {got[key]!r}, expected {want[key]!r}")
    if [n for n, _ in got["idle_gaps"]][:1] != want["largest_gap"]:
        bad.append(f"trace: gaps {got['idle_gaps']}, expected "
                   f"{want['largest_gap']} first")
    if got["device_ops"][0][0] != want["top_op"]:
        bad.append(f"trace: top operation {got['device_ops'][0]}, expected "
                   f"{want['top_op']}")
    # the pieces, on intervals small enough to check by eye
    if trace_reduce.union([(0, 4), (2, 6), (8, 9)]) != [(0, 6), (8, 9)]:
        bad.append("union of intervals")
    nested = [("while", 0, 10), ("body", 1, 4), ("body", 5, 9), ("add", 12, 13)]
    if trace_reduce.self_times(nested) != {"while": 3, "body": 7, "add": 1}:
        bad.append(f"self times {trace_reduce.self_times(nested)}")
    return bad


def main(argv: list) -> int:
    manifests = argv or ["BENCHMARK.json",
                         "benchmarks/rehearsal/manifest.json"]
    bad = []
    for path in manifests:
        bad += [f"{path}: {finding}" for finding in check_manifest(path)]
    bad += check_trace()
    for finding in bad:
        print(finding)
    print("selfcheck: " + ("ok" if not bad else f"{len(bad)} finding(s)"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
