"""Median of the run's completion intervals, in milliseconds."""

import statistics


def reduce(obs: dict) -> float | None:
    values = obs.get("intervals_s")
    return 1e3 * statistics.median(values) if values else None
