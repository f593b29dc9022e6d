"""Model FLOP/s utilization, in percent: the operations the forward and
backward passes need per token (``flops.<function>``, from the
configuration's sizes) times tokens per second per chip, over the chip's
peak (``peaks.py``).  The operations function credits every routed
assignment, dropped or not: see flops.py."""

import flops
import peaks


def reduce(obs: dict, function: str) -> float | None:
    rate = obs.get("tokens_per_s_per_chip")
    if rate is None or obs["device_kind"] == "cpu":  # a CPU has no peak here
        return None
    per_token = getattr(flops, function)(obs["sizes"])
    return 100.0 * per_token * rate / peaks.peak_bf16_flops(obs["device_kind"])
