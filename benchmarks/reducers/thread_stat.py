"""One reading of one thread of the program, on the CPU's clock beside the
wall's: ``key`` of ``Timeline.thread_stats`` for the thread named
``thread``, times ``scale``, in the process that ran it.

A thread of the expert server (``lah-server``, the asyncio loop;
``lah-runtime``, the device's one consumer) samples, four times a second at
most, ``time.monotonic``, ``time.thread_time``, ``time.process_time`` and,
for a loop, the sums of its time outside ``select`` and of its turns
(``learning_at_home_tpu/utils/profiling.py``: ``ThreadClock``).  The reading
is taken between the first and the last sample inside THE extent that
``stage_stat.py`` reads the server's stages over, so a thread's shares and
the stage medians describe the same seconds: the program's one extent
function, ``Timeline.stage_extent``, is called with ``stage_stat.py``'s
``SERVER_STAGES``, ``WINDOW_S`` and ``TAIL_S``, taken from the file beside
this one, and the window is held inside the measured one as that file
holds it.  The two samples lie inside the extent and at most a sample's
spacing from either end.

Left out (``None``) where the module was never loaded (a train cell), where
the program has no ``thread_stats`` or no ``stage_extent`` (this PR's
parent), where there is no extent, where the thread has fewer than two
samples inside or they are under ``MIN_EXTENT_S`` apart, and where the
thread keeps no such sum (the runtime thread has no turns).
"""

import os
import sys

import harness

_stage_stat = harness.load_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "stage_stat.py")
)

# two samples under 2 s apart are eight samples at most: no share of that
MIN_EXTENT_S = 2.0


def reduce(obs: dict, thread: str, key: str, scale: float = 1.0) -> float | None:
    module = sys.modules.get("learning_at_home_tpu.utils.profiling")
    timeline = getattr(module, "timeline", None)
    stage_extent = getattr(timeline, "stage_extent", None)
    thread_stats = getattr(timeline, "thread_stats", None)
    if stage_extent is None or thread_stats is None:
        return None
    window_s = _stage_stat.WINDOW_S
    if obs.get("intervals_s"):  # first to last completion of the window
        window_s = min(window_s, sum(obs["intervals_s"]) - _stage_stat.TAIL_S)
    if window_s <= 0:
        return None
    extent = stage_extent(_stage_stat.SERVER_STAGES, window_s=window_s,
                          skip_tail_s=_stage_stat.TAIL_S)
    if extent is None:
        return None
    stat = thread_stats(*extent).get(thread)
    if stat is None or stat["extent_s"] < MIN_EXTENT_S or stat[key] is None:
        return None
    return scale * stat[key]
