"""Share of the chip's bf16 peak that the grouped matmuls reached, in
percent: the calls the trace holds (forward, remat's recompute and
backward alike: what ran) times the operations one call executes
(``<module>.grouped_matmul_flops(sizes, tokens)``), over the device time
of exactly those calls times the peak (``peaks.py``).  Compute bounds
these calls: a call's least bytes over the HBM peak is under its
operations over the bf16 peak (``text`` of the metric)."""

import importlib

import peaks


def reduce(obs: dict, module: str) -> float | None:
    table = obs.get("scopes") or {}
    seconds, calls = table.get("grouped_matmul_s"), table.get(
        "grouped_matmul_calls")
    if not seconds or not calls or obs["device_kind"] == "cpu":
        return None
    per_call = importlib.import_module(module).grouped_matmul_flops(
        obs["sizes"], obs["tokens_per_step_per_chip"]
    )
    peak = peaks.peak_bf16_flops(obs["device_kind"])
    return 100.0 * calls * per_call / (seconds * peak)
