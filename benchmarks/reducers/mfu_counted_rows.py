"""Model FLOP/s utilization, in percent, of a step whose routed work is
what landed on this chip: ``<module>.<function>(sizes, rows_over_level)``
operations a token, the routed experts at the rows the step COUNTED
(``<module>.rows_over_level(observations)``: the run's
``local_rows_over_level`` counter less what ``dropped_fraction`` says the
buffer dropped), times tokens per second per chip, over the chip's bf16
peak (``peaks.py``).  ``None`` where the program reports no such counter."""

import importlib

import peaks


def reduce(obs: dict, module: str, function: str) -> float | None:
    flops = importlib.import_module(module)
    rate, rows = obs.get("tokens_per_s_per_chip"), flops.rows_over_level(obs)
    if rate is None or rows is None or obs["device_kind"] == "cpu":
        return None
    per_token = getattr(flops, function)(obs["sizes"], rows)
    return 100.0 * per_token * rate / peaks.peak_bf16_flops(obs["device_kind"])
