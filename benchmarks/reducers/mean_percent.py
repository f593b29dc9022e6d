"""Mean of a list of fractions the runner observed, in percent."""

import statistics


def reduce(obs: dict, key: str) -> float | None:
    values = obs.get(key)
    return 100.0 * statistics.fmean(values) if values else None
