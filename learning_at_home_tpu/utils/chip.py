"""What the program knows about the accelerator it runs on.

Three facts, each read from the device or from one table — never from an
environment variable and never assumed:

- where compiled programs are kept between processes
  (:func:`enable_compile_cache`),
- how much memory the device has (:func:`hbm_bytes`, from the runtime),
- the device's peak bf16 FLOP/s (:data:`PEAK_BF16_FLOPS`, keyed by
  ``device_kind``; a kind that is not in the table is an error).

Importing this module does not import jax.
"""

from __future__ import annotations

import os

# One fixed in-checkout path: the directory is part of JAX's cache key, so
# a path that moves (tempfile, pid, timestamp) never hits.  Git-ignored.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Call first thing in every entry point that jits for a device.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and this
    sets no other directory; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.

    Every entry is persisted, however quickly it compiled: JAX's default
    skips programs that compile in under a second, which is each of an
    expert server's per-bucket programs — so four identical FFN experts
    compiled four times, cold or warm (16 programs, 31 s measured)."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


# Peak dense bf16 FLOP/s of one chip, by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, system architecture pages
# "TPU v4", "TPU v5e", "TPU v5p", "TPU v6e" (peak compute per chip); the
# kind strings are the ones jax 0.9.0 matches in
# jax/_src/pallas/mosaic/tpu_info.py.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_bf16_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``; ``KeyError`` for a kind the table
    does not hold (a utilization against a guessed peak is not a
    measurement)."""
    kind = device.device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {kind!r}; add it to "
            f"PEAK_BF16_FLOPS with its source (known: "
            f"{sorted(PEAK_BF16_FLOPS)})"
        )
    return PEAK_BF16_FLOPS[kind]


def hbm_bytes(device) -> int:
    """The device's memory limit as its runtime reports it."""
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{device.platform} device {device.device_kind!r} reports no "
            "memory_stats()['bytes_limit']; its memory cannot be sized"
        )
    return int(stats["bytes_limit"])
