"""The mean of one list of numbers the runner observed over the mean of
another (two of the step's counters, one a step each)."""
import statistics


def reduce(obs: dict, numerator: str, denominator: str) -> float | None:
    above, below = obs.get(numerator), obs.get(denominator)
    if not above or not below or not statistics.fmean(below):
        return None
    return statistics.fmean(above) / statistics.fmean(below)
