"""Mean of a list of numbers the runner observed, times ``scale``."""

import statistics


def reduce(obs: dict, key: str, scale: float = 1.0) -> float | None:
    values = obs.get(key)
    return scale * statistics.fmean(values) if values else None
