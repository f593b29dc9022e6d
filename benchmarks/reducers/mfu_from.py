"""Model FLOP/s utilization, in percent, with the operations function
named by module: ``<module>.<function>(sizes)`` operations a token (a
file beside ``flops.py``) times tokens per second per chip, over the
chip's bf16 peak (``peaks.py``)."""

import importlib

import peaks


def reduce(obs: dict, module: str, function: str) -> float | None:
    rate = obs.get("tokens_per_s_per_chip")
    if rate is None or obs["device_kind"] == "cpu":  # a CPU has no peak here
        return None
    per_token = getattr(importlib.import_module(module), function)(obs["sizes"])
    return 100.0 * per_token * rate / peaks.peak_bf16_flops(obs["device_kind"])
