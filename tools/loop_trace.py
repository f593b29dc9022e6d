#!/usr/bin/env python3
"""A swarm cell's traced run, with the host threads' annotations read once
more: which host line carries the asyncio loop's ``loop.run`` stretches,
which the runtime thread's five stages, and what share of the traced span
each covers (docs/OBSERVABILITY.md "Span names", "Threads").

    chiprun --timeout 900 -- python tools/loop_trace.py <cell> --seed <n> \\
        [--out chiprun_out/loop_trace.<cell>.<seed>.json]

The run is the cell's OWN traced run, ``benchmarks/run.py --workload <cell>
--seed <n> --seconds <run_seconds> --trace 1``, in this process: the tool
reads the ``.xplane.pb`` before the runner deletes it, edits no file of the
benchmark and has no loop of its own.  The run's own lines come first (the
result last among them), then one ``LOOP`` line: the annotated span, and per
host line the seconds under each name, the union of its ``loop.run`` events
and where they start and end.  The loop's line is the one that also carries
``server.decode``; its union over the span is the traced seconds' own
``server.loop_busy_share``, which the result reads over the run's LAST
seconds (``threads``: ``Timeline.thread_stats`` over ``stages_extent_s``,
the extent of the result's ``server.*`` readings): the two agree within a
few points where the traffic is steady.
``trace_reduce`` itself is handed the five ``runtime.*`` names alone until
``ROADMAP.md`` Speed 6(b)'s ``benchmark`` PR lists ``loop.run`` too.
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))  # trace_reduce, run.py's
LOOP_STAGE = "loop.run"
RUNTIME_STAGES = ("runtime.idle", "runtime.stack", "runtime.dispatch",
                  "runtime.materialize", "runtime.handoff")


def host_lines(xplane: str, prefixes: tuple = ("server.",)) -> dict:
    """Per host line of the trace that carries a ``loop.run``, a
    ``runtime.*`` stage or a name under ``prefixes``: ``names`` (count and
    seconds a name) and the union, first start and last end of its
    ``loop.run`` events, on the clock of ``annotated_span_s`` (first to
    last such event of any line)."""
    import trace_reduce
    from jax.profiler import ProfileData

    lines, lo, hi = [], None, None
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events
                if e.name == LOOP_STAGE or e.name in RUNTIME_STAGES
                or e.name.startswith(prefixes)
            ]
            if not events:
                continue
            lo = min([lo or events[0][1], *(a for _, a, _ in events)])
            hi = max([hi or 0, *(b for _, _, b in events)])
            names: dict = {}
            for name, start, end in events:
                entry = names.setdefault(name, {"count": 0, "seconds": 0.0})
                entry["count"] += 1
                entry["seconds"] += (end - start) / 1e9
            turns = [(a, b) for name, a, b in events if name == LOOP_STAGE]
            lines.append({"names": dict(sorted(names.items())),
                          "loop_run_union_s": sum(
                              b - a for a, b in trace_reduce.union(turns)
                          ) / 1e9,
                          "loop_run": [min(a for a, _ in turns),
                                       max(b for _, b in turns)]
                          if turns else None})
    for line in lines:
        if line["loop_run"]:
            line["loop_run"] = [(t - lo) / 1e9 for t in line["loop_run"]]
    return {"annotated_span_s": (hi - lo) / 1e9 if lines else None,
            "lines": lines}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out")
    args = p.parse_args()
    import trace_reduce

    seconds = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["run_seconds"]
    reduce_dir, report = trace_reduce.reduce_dir, {}

    def reading_the_lines_too(trace_dir, host_spans=()):
        report.update(host_lines(trace_reduce.find_xplane(trace_dir)))
        return reduce_dir(trace_dir, host_spans)

    trace_reduce.reduce_dir = reading_the_lines_too
    sys.argv = [os.path.join(REPO, "benchmarks", "run.py"),
                "--workload", args.cell, "--seed", str(args.seed),
                "--seconds", str(seconds), "--trace", "1"]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        if e.code:
            return e.code
    finally:
        trace_reduce.reduce_dir = reduce_dir
    # the same threads on the CPU's clock, over the extent the result's
    # server.* readings were read over (reducers/stage_stat.py's arguments)
    from learning_at_home_tpu.utils.profiling import timeline

    extent = timeline.stage_extent(("server.", "pool.", "runtime."),
                                   window_s=20.0, skip_tail_s=2.0)
    if extent is not None:
        report["stages_extent_s"] = extent[1] - extent[0]
        report["threads"] = timeline.thread_stats(*extent)
    print("LOOP " + json.dumps(report), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0 if report.get("lines") else 1


if __name__ == "__main__":
    sys.exit(main())
