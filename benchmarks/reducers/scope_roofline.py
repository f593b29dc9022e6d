"""Share of its roofline that the work under scopes of the program
reached, in percent: the least time a step needs for that work on this
chip (``<module>.<function>(sizes, tokens a step, device_kind)``: the
larger of its operations at the bf16 peak and its least bytes at the HBM
peak, ``peaks.py``) over the device time a step spent under the scopes.

That time is the scopes' share of the traced operations' device self time
(``observations["scopes"]``) times the device's busy share of the traced
span times the median interval between step completions: the trace's
window begins and ends inside a step, so shares are taken from it and the
step's length from the host clock, whose median the profiler's start does
not move.  The work is the same whatever implements it, so the share
cannot pass 100.  ``None`` where the trace holds no operation under the
scopes (a program without them) or on a CPU."""

import importlib
import statistics


def reduce(obs: dict, scopes: list, module: str, function: str) -> float | None:
    table = obs.get("scopes") or {}
    trace = obs.get("trace") or {}
    under = sum(table.get("by_scope", {}).get(s, 0.0) for s in scopes)
    if not under or not trace.get("span_s") or obs["device_kind"] == "cpu":
        return None
    step_s = (
        under / table["total_s"] * trace["busy_s"] / trace["span_s"]
        * statistics.median(obs["intervals_s"])
    )
    least_s = getattr(importlib.import_module(module), function)(
        obs["sizes"], obs["tokens_per_step_per_chip"], obs["device_kind"])
    return 100.0 * least_s / step_s
