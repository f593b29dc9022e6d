"""Share of the chip's bf16 peak that the blocked attention kernel's calls
reached, in percent: each call the trace holds (forward, remat's
recompute and the fused backward, on global and on window layers: what
ran) times the operations such a call executes on the elements its mask
admits (``<module>.attention_kernel_flops``), over the device time of
exactly those calls times the peak (``peaks.py``).  ``None`` where the
runner found no such call (a program without the kernel, a CPU run)."""

import importlib

import peaks


def reduce(obs: dict, module: str) -> float | None:
    kernels = (obs.get("scopes") or {}).get("attention_kernels") or {}
    seconds = sum(k["s"] for k in kernels.values())
    if not seconds or obs["device_kind"] == "cpu":
        return None
    flops = importlib.import_module(module).attention_kernel_flops
    operations = sum(
        k["calls"] * flops(obs["sizes"], obs["tokens_per_step_per_chip"],
                           *name.split("."))
        for name, k in kernels.items()
    )
    return 100.0 * operations / (seconds * peaks.peak_bf16_flops(obs["device_kind"]))
