#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the manifest (``BENCHMARK.json``
at the root of the checkout).  Its configuration file names the runner;
its traffic file parametrises it; with ``--trace 1`` each per-layer metric
of the cell is taken by the reducer its own file names.  Everything is
found by name (see README.md): a new cell needs new files, not an edit.

The last line of standard output is the result, one JSON object.  Earlier
lines starting ``INTERVALS`` and ``SETUP`` hold the quantiles of the run's
step or request intervals and the split of its set-up.  A run that cannot
produce a result (no accelerator, too few chips, files missing, the
program absent) prints none and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402  (starts the set-up clock)
from harness import BenchError  # noqa: E402

# a rehearsal (a configuration whose platform is "cpu") prints its numbers
# under this prefix: no CPU number under a device metric's name
REHEARSAL_PREFIX = "cpu_rehearsal."


def per_layer(manifest: dict, cell: dict, observations: dict) -> dict:
    out = {}
    for entry in harness.metrics_of_cell(manifest["per_layer"], cell["name"]):
        spec = harness.load_json(
            harness.find_file(manifest, "layer_metrics", entry["name"] + ".json")
        )
        reducer = harness.load_module(manifest, "reducers", spec["reducer"])
        value = reducer.reduce(observations, **spec.get("args", {}))
        if value is not None:  # nothing to read: the metric is left out
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--manifest", default="BENCHMARK.json",
                   help="relative to the checkout; benchmarks/rehearsal/"
                        "manifest.json rehearses on the CPU at tiny sizes")
    args = p.parse_args()

    manifest = harness.load_manifest(args.manifest)
    cell = harness.by_name(manifest["workloads"], args.workload, "workload")
    config_entry = harness.by_name(manifest["configs"], cell["config"], "config")
    config = harness.load_json(os.path.join(harness.ROOT, config_entry["file"]))
    traffic = harness.load_json(
        harness.find_file(manifest, "traffic", cell["traffic"] + ".json")
    )
    runner = harness.load_module(manifest, "runners", config["runner"])
    sys.path.insert(0, harness.ROOT)  # the program under test
    if not os.path.isdir(os.path.join(harness.ROOT, "learning_at_home_tpu")):
        raise BenchError("the program is not in this checkout")

    clock = harness.SetupClock()
    result = runner.run(cell, config, traffic, args, clock)

    if args.trace:
        metrics = per_layer(manifest, cell, result["observations"])
    else:
        metrics = {}
        for entry in harness.metrics_of_cell(manifest["end_to_end"], cell["name"]):
            if entry["name"] not in result["end_to_end"]:
                raise BenchError(
                    f"runner {config['runner']!r} gave no {entry['name']!r}"
                )
            metrics[entry["name"]] = {
                "value": float(result["end_to_end"][entry["name"]]),
                "unit": entry["unit"],
            }
    if not metrics:
        raise BenchError("no metric to report")
    if config["platform"] == "cpu":
        metrics = {REHEARSAL_PREFIX + k: v for k, v in metrics.items()}

    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": harness.device_report(result["devices"]),
    }
    trace = result["observations"].get("trace") or {}
    if args.trace and "busy_s" in trace:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["span_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
