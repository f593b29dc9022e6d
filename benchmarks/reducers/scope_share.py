"""Device self time of the named scopes of the program over that of all
traced operations, in percent (``observations["scopes"]``: the trace's
self time by operation joined with the compiled step's ``op_name``)."""


def reduce(obs: dict, scopes: list) -> float | None:
    table = obs.get("scopes") or {}
    if not table.get("total_s"):
        return None
    by_scope = table["by_scope"]
    return 100.0 * sum(by_scope.get(s, 0.0) for s in scopes) / table["total_s"]
