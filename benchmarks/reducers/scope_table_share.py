"""One entry of the runner's scope table (``observations["scopes"]``) over
the device self time of all traced operations, in percent."""


def reduce(obs: dict, key: str) -> float | None:
    table = obs.get("scopes") or {}
    if not table.get("total_s") or key not in table:
        return None
    return 100.0 * table[key] / table["total_s"]
