"""A sum of the run's counters over another sum, times ``scale``."""


def reduce(obs: dict, numerator: list, denominator: list,
           scale: float = 1.0) -> float | None:
    counters = obs.get("counters") or {}
    if any(name not in counters for name in (*numerator, *denominator)):
        return None
    below = sum(counters[name] for name in denominator)
    if not below:
        return None
    return scale * sum(counters[name] for name in numerator) / below
