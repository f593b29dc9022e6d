"""One statistic of one stage of the program, on the program's own clock:
``key`` (``p50_ms``, ``p95_ms``, ``share``) of the spans under ``name``,
times ``scale``, from ``Timeline.stage_stats`` in the process that ran it.

The expert server runs in ``run.py``'s process, and its ``Timeline``
(``learning_at_home_tpu/utils/profiling.py``) keeps the last 4096 spans of
every stage whether or not profiling is on, past the server's shutdown.
``stage_stats`` reads all the server's stages over ONE extent of time.  It
ends ``TAIL_S`` before the run's last span: after the measured window the
runner sends its check requests, one at a time to an otherwise idle
server (a few tenths of a second), and they are no part of the traffic.
It is at most ``WINDOW_S`` long, lies inside the measured window, and
starts no earlier than the oldest entry of a reservoir that is full.  So
a stage with two spans a dispatch (a ``multi`` request) and one with 93
(a batch) are read over the same seconds, none of them the warm-up's.
That rule, the median and the share are the program's; only the count
floor is here.

The module is taken from ``sys.modules`` and never imported: a cell that
has not loaded it, or a program that has no ``stage_stats``, has nothing
to read and the metric is left out.
"""

import sys

# A request of ffnserver-train-bulk is a whole ``multi`` of 48 parts, two a
# dispatch: about 80 in the extent at 0.4 s a dispatch.  A floor of 100 would
# leave its medians out; under 30 spans a median is not reported, nor a
# share of an extent in which no stage has 30.
MIN_SPANS = 30
WINDOW_S = 20.0
TAIL_S = 2.0
SERVER_STAGES = ("server.", "pool.", "runtime.")


def reduce(obs: dict, name: str, key: str, scale: float = 1.0) -> float | None:
    module = sys.modules.get("learning_at_home_tpu.utils.profiling")
    stage_stats = getattr(getattr(module, "timeline", None), "stage_stats",
                          None)
    if stage_stats is None:
        return None
    window_s = WINDOW_S
    if obs.get("intervals_s"):  # first to last completion of the window
        window_s = min(window_s, sum(obs["intervals_s"]) - TAIL_S)
    if window_s <= 0:
        return None
    stats = stage_stats(SERVER_STAGES, window_s=window_s, skip_tail_s=TAIL_S)
    stat = stats.get(name)
    if stat is None:
        return None
    # a median needs spans of its own stage; a share is as true at no span
    # (the stage did not run: 0) and needs only that the extent is a real
    # one, which the group's busiest stage shows
    if key == "share":
        floor = max(s["count"] for s in stats.values())
    else:
        floor = stat["count"]
    return scale * stat[key] if floor >= MIN_SPANS else None
