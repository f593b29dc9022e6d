"""Largest of a list of byte counts (one per device), in GB (1e9 bytes)."""


def reduce(obs: dict, key: str) -> float | None:
    values = [v for v in obs.get(key) or [] if v is not None]
    return max(values) / 1e9 if values else None
