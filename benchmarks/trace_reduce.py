"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, the operations that took most of it, the idle
gaps by what the host was doing, and the collectives' share.

The trace is read with ``jax.profiler.ProfileData`` and nothing else.  A
device plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per operation (a ``while`` holds its body's operations nested inside its
own interval) and its line ``XLA Modules`` one event per run of a
program.  Host spans written with ``jax.profiler.TraceAnnotation`` are
events of that name on the host plane's thread lines, on the same clock.

``selfcheck.py`` reduces the recorded trace in ``fixtures/`` to known
numbers with these functions.
"""

from __future__ import annotations

import glob
import os
import re


def short_name(event_name: str) -> str:
    """``fusion.2153`` from the whole HLO line that the TPU's tracer gives
    an operation as its name (``%fusion.2153 = bf16[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def opcode(event_name: str) -> str:
    """``all-reduce`` from ``%psum.304 = bf16[...]{...} all-reduce(...)``:
    the first lower-case word before a parenthesis after the shape (the
    shape's own ``T(8,128)`` and ``S(1)`` are upper-case).  An instruction
    is named after the JAX primitive as often as after its opcode, so the
    name alone does not tell a collective."""
    m = OPCODE.search(event_name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO opcodes of the operations that move data between chips
COLLECTIVE = re.compile(
    r"^(all-to-all|ragged-all-to-all|all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|collective-broadcast)(-start|-done)?$"
)
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str, host_spans: tuple = ()) -> dict:
    """``{"devices": {n: {"ops": [...], "modules": [...]}}, "host": [...],
    "opcodes": {name: opcode}}`` with every event as ``(name, start_ns,
    end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    opcodes: dict = {}
    host = []
    wanted = set(host_spans)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = short_name(e.name)
                    lines[key].append(
                        (name, e.start_ns, e.start_ns + e.duration_ns)
                    )
                    if key == "ops" and name not in opcodes:
                        opcodes[name] = opcode(e.name)
            devices[int(m.group(1))] = lines
        elif wanted and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        )
    return {"devices": devices, "host": host, "opcodes": opcodes}


def union(intervals: list) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def self_times(ops: list) -> dict:
    """Nanoseconds by operation name, a parent's time less its children's:
    events of one line nest (a ``while`` and its body) and never cross."""
    out: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0) + entry[2]

    for name, start, end in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        close(stack.pop())
    return out


def _label_gap(start: int, end: int, modules: list, host: list) -> str:
    mid = (start + end) // 2
    for name, m0, m1 in modules:
        if m0 <= mid < m1:
            return "inside_a_program:" + re.sub(r"\(\d+\)$", "", name)
    for name, h0, h1 in host:
        if h0 <= mid < h1:
            return name
    return "between_programs"


def reduce_events(events: dict) -> dict:
    """The numbers, from what ``load_events`` returns.  Busy time and the
    span are averaged over the devices that ran anything; the top
    operations, the gaps and the collectives are device 0's (the lowest
    numbered one that ran anything)."""
    used = {n: d for n, d in sorted(events["devices"].items()) if d["ops"]}
    if not used:
        return {}
    busy_ns = span_ns = 0
    for d in used.values():
        merged = union([(s, e) for _, s, e in d["ops"]])
        busy_ns += sum(e - s for s, e in merged)
        span_ns += merged[-1][1] - merged[0][0]
    first = next(iter(used.values()))
    merged = union([(s, e) for _, s, e in first["ops"]])
    span0_ns = merged[-1][1] - merged[0][0]
    selfs = self_times(first["ops"])
    gaps: dict = {}
    for (_, a_end), (b_start, _) in zip(merged, merged[1:]):
        label = _label_gap(a_end, b_start, first["modules"], events["host"])
        gaps[label] = gaps.get(label, 0) + b_start - a_end
    opcodes = events.get("opcodes", {})
    collective_ns = sum(ns for name, ns in selfs.items()
                        if COLLECTIVE.match(opcodes.get(name, "")))

    def top(table: dict) -> list:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9] for name, ns in ranked]

    return {
        "devices_traced": len(used),
        "busy_s": busy_ns / 1e9 / len(used),
        "span_s": span_ns / 1e9 / len(used),
        "device0_span_s": span0_ns / 1e9,
        "collective_s": collective_ns / 1e9,
        "device_ops": top(selfs),
        "idle_gaps": top(gaps),
    }


def reduce_dir(trace_dir: str, host_spans: tuple = ()) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir), host_spans))
