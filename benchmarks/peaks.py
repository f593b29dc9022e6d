"""Peaks of one chip, keyed by ``jax.Device.device_kind``.

A copy of ``learning_at_home_tpu/utils/chip.py:PEAK_BF16_FLOPS``, kept
here so that a change to the program cannot move the yardstick.  Source:
Google Cloud TPU documentation, system architecture pages "TPU v4",
"TPU v5e", "TPU v5p", "TPU v6e" (peak compute and HBM bandwidth per
chip).  A kind that is not in the table is an error, not a default.
"""

PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}

PEAK_HBM_BYTES_PER_S = {
    "TPU v4": 1200e9,
    "TPU v5 lite": 819e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
}


def peak_bf16_flops(device_kind: str) -> float:
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {device_kind!r}; add "
            f"it with its source (known: {sorted(PEAK_BF16_FLOPS)})"
        )
    return PEAK_BF16_FLOPS[device_kind]
