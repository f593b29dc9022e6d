"""What every runner shares: finding a cell's files by name, the set-up
clock, the compile counter, and the arithmetic that turns completion
times into rates and quantiles.

Importing this module imports neither jax nor the program.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import statistics
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROCESS_T0 = time.perf_counter()  # as near the interpreter's start as we get

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class BenchError(RuntimeError):
    """The run cannot produce a result line; the process exits non-zero."""


# --------------------------------------------------------------------------
# files found by name
# --------------------------------------------------------------------------


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str) -> dict:
    manifest = load_json(os.path.join(ROOT, path))
    for key in ("paths", "configs", "workloads", "end_to_end", "per_layer"):
        if key not in manifest:
            raise BenchError(f"{path}: no {key!r}")
    return manifest


def find_file(manifest: dict, *parts: str) -> str:
    """``<path>/<parts...>`` in the first of the manifest's ``paths`` that
    has it: a later PR brings a directory of its own and edits none."""
    tried = []
    for base in manifest["paths"]:
        path = os.path.join(ROOT, base, *parts)
        if os.path.isfile(path):
            return path
        tried.append(os.path.relpath(path, ROOT))
    raise BenchError(f"none of {tried} exists")


def load_path(path: str):
    """The Python file at ``path`` as a module of its own."""
    name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_module(manifest: dict, kind: str, name: str):
    """``<path>/<kind>/<name>.py`` as a module (runners, reducers)."""
    return load_path(find_file(manifest, kind, name + ".py"))


def by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchError(
        f"no {what} named {name!r} (known: {[e['name'] for e in entries]})"
    )


def metrics_of_cell(entries: list, cell: str) -> list:
    """Metrics reported in ``cell``: those that list it, or list nothing."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


# --------------------------------------------------------------------------
# set-up split
# --------------------------------------------------------------------------


class SetupClock:
    """Seconds of set-up by phase, from the interpreter's start to the
    window's; ``mark`` closes the phase that began at the last mark."""

    def __init__(self):
        self._last = PROCESS_T0
        self.phases: dict[str, float] = {}

    def mark(self, phase: str) -> None:
        now = time.perf_counter()
        self.phases[phase] = self.phases.get(phase, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return self._last - PROCESS_T0


class CompileCounter:
    """``jax.monitoring`` listeners (chip_smoke.run_trainer's): every
    program handed to the backend, whether compiled or loaded from the
    persistent cache, and the cache's hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.programs = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.programs += 1
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1
        elif event == CACHE_MISS_EVENT:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "compile_or_load_s": self.compile_s}


@contextlib.contextmanager
def quiet_gc():
    """No collector pause inside the window: what is alive at its start is
    frozen out of the collector's sight, and the collector is off."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


TRACE_START_S = 2.0  # into the window
TRACE_SECONDS = 3.0  # traces are large; a few seconds hold what is needed


def start_trace(trace_dir: str) -> None:
    """The device and the host's own spans, without a span for every
    Python call: that tracer slows the host it is measuring."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


# --------------------------------------------------------------------------
# completion times -> numbers
# --------------------------------------------------------------------------


def rate_between_completions(times: list, work_each: float) -> float:
    """``(n - 1) * work / (t_n - t_1)``: the work completed after the first
    completion up to the last, over the time between those two.  Never
    over the window's nominal length, so where the window's edges fall
    between completions cannot move it."""
    if len(times) < 2:
        raise BenchError(f"{len(times)} completion(s) in the window: no rate")
    return (len(times) - 1) * work_each / (times[-1] - times[0])


def quantile(values: list, q: float) -> float:
    """Linear interpolation between order statistics, ``q`` in [0, 1]."""
    ordered = sorted(values)
    if not ordered:
        raise BenchError("quantile of nothing")
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def five_numbers(values: list) -> dict:
    return {"n": len(values), "min": min(values),
            "p25": quantile(values, 0.25), "p50": statistics.median(values),
            "p75": quantile(values, 0.75), "max": max(values)}


def intervals(times: list) -> list:
    return [b - a for a, b in zip(times, times[1:])]


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------


def require_devices(platform: str, chips: int) -> list:
    """The first ``chips`` devices, all on ``platform``; anything else
    ends the run with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchError(
            f"JAX runs on {devices[0].platform!r} [{devices[0].device_kind}]"
            f"; this cell's configuration asks for {platform!r}"
        )
    if len(devices) < chips:
        raise BenchError(
            f"{len(devices)} {platform} device(s) found, the cell asks for "
            f"{chips}"
        )
    return devices[:chips]


def device_report(devices: list) -> dict:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def seed_words(seed: int, n: int) -> list:
    """``n`` 32-bit words from a seed of any size (the driver's exceed 31
    bits, which ``jax.random.PRNGKey`` refuses without x64)."""
    import numpy as np

    return [int(w) for w in np.random.SeedSequence(seed).generate_state(n)]
