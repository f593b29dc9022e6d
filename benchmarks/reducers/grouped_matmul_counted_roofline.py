"""Share of the chip's bf16 peak that the grouped matmuls of a SHARE of
the experts reached, in percent: the calls the trace holds (forward,
remat's recompute and backward alike: what ran) times the operations one
call executes on the rows the step COUNTED
(``<module>.grouped_matmul_flops(sizes, tokens, rows_over_level)`` at
``<module>.rows_over_level(observations)``: the buffer's empty rows are no
work), over the device time of exactly those calls times the peak
(``peaks.py``).  ``None`` where the trace holds no such call or the
program reports no such counter."""

import importlib

import peaks


def reduce(obs: dict, module: str) -> float | None:
    table = obs.get("scopes") or {}
    seconds, calls = table.get("grouped_matmul_s"), table.get(
        "grouped_matmul_calls")
    flops = importlib.import_module(module)
    rows = flops.rows_over_level(obs)
    if not seconds or not calls or rows is None or obs["device_kind"] == "cpu":
        return None
    per_call = flops.grouped_matmul_flops(
        obs["sizes"], obs["tokens_per_step_per_chip"], rows
    )
    peak = peaks.peak_bf16_flops(obs["device_kind"])
    return 100.0 * calls * per_call / (seconds * peak)
