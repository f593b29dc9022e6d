"""One of the run's counters, as it was read."""


def reduce(obs: dict, key: str) -> float | None:
    return (obs.get("counters") or {}).get(key)
