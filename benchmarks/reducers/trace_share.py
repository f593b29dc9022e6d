"""One reduced-trace quantity over another, in percent; ``complement``
gives what is left of the whole (idle from busy)."""


def reduce(obs: dict, numerator: str, denominator: str,
           complement: bool = False) -> float | None:
    trace = obs.get("trace") or {}
    if numerator not in trace or not trace.get(denominator):
        return None
    share = trace[numerator] / trace[denominator]
    return 100.0 * (1.0 - share if complement else share)
