"""Tracing / profiling: per-RPC timing spans + device trace hooks.

The reference has nothing beyond logging and its benchmark scripts
(SURVEY.md §5.1); the TPU build prescribes jax.profiler traces plus
per-RPC timing spans.  This module provides both:

- a process-wide :class:`Timeline` of timing spans used by the RPC client,
  the expert server and the MoE dispatcher.  One primitive
  (:meth:`Timeline.span`, a small class with ``__enter__`` / ``__exit__``;
  :meth:`Timeline.record` where both clock readings are already in hand)
  with three sinks: a bounded **reservoir of recent spans per name**, and
  a second one per name and **kind** (always on: :meth:`Timeline.recent`,
  :meth:`Timeline.stage_stats`), a ``jax.profiler.TraceAnnotation`` of the
  same name (records only while a profiler session is live, and then sits
  in the ``.xplane.pb`` on the device trace's clock), and the full record
  with trace id, thread and attributes (only under ``LAH_PROFILE=1``);
- named **event counters** on the same Timeline (:meth:`Timeline.count`,
  only under ``LAH_PROFILE=1``) where a duration span is the wrong shape:
  the client's per-codec payload counts and bytes
  (``client.pack.codec.<codec>[.bytes]``) and the averager's
  ``averaging.rounds`` / ``.degraded_rounds`` / ``.bytes_sent``.  The hot
  path's headline counts (overlapped jobs, pack bytes, hedges) are the
  registry's alone: nothing read their Timeline twins;
- a **thread's clock** (:class:`ThreadClock`, :meth:`Timeline.register_thread`,
  :meth:`Timeline.thread_stats`): a named thread's CPU seconds
  (``time.thread_time``) and the process's beside its wall seconds, four
  samples a second at most, always on.  The spans are WALL time: a thread
  that is "never idle" by them may be computing, waiting for the device, or
  waiting for the interpreter lock, and only its CPU seconds tell those
  apart.  The asyncio loops (utils/asyncio_utils.py: the chain
  ``loop.select | loop.run``) and the server's Runtime thread tick one;
- :func:`device_trace`, a thin wrapper over ``jax.profiler.trace`` that
  captures an XLA/TensorBoard trace directory for the jitted compute.

Enable the full records and the counters with ``LAH_PROFILE=1`` in the
environment or ``timeline.enable()``; read them with
``timeline.summary()`` / ``timeline.counters()``.  The reservoirs need
neither.

A span's NAME is its stage (``runtime.stack``), fixed by the code that
takes it; what is data (``pool``, ``rows``, ``bucket``, the message
``type``, the ``kind``) goes into its attributes, so one stage has one
reservoir and one p50 however many pools a server hosts.  One attribute
is also read with profiling off: a ``kind`` of :data:`KINDS` (``forward``
or ``backward``: a request's message type, a ``multi``'s ``op``, a pool's
side of its expert) tags the span's entry in its stage's reservoir, and
``stage_stats`` and ``recent`` read the tagged entries under the key
``<name>:<kind>`` (``server.request:backward``) beside ``<name>``, so a
forward can be told from a backward without tier 3.  The key is the
reader's alone: name, annotation and full record do not carry it, and a
span pays for its kind with one dictionary lookup, not a second append.
Like ``trace``, it may be set until the span's exit.

A span may start at a reading the caller already has
(``span(name, start=previous.end)``): the stages of one thread are then
contiguous by construction, and each costs one clock call.

**Distributed tracing** (ISSUE 4): spans may carry a compact *trace id*
(:func:`new_trace_id`, 16 hex chars) allocated once per logical operation
— the MoE dispatcher mints one per forward dispatch, carries it in RPC
meta (``{"trace": ...}``, docs/PROTOCOL.md), and the server stamps it
onto its handler/pool/runtime spans — so one forward+backward yields a
JOINABLE end-to-end trace across processes.  Export with
:meth:`Timeline.chrome_trace` (Chrome ``trace_event`` JSON for
chrome://tracing): span start times are rebased from ``time.monotonic``
to the wall clock at export, so traces merged from multiple processes on
one machine align.  Trace ids are only allocated while the timeline is
enabled — disabled-path requests carry no extra meta and record nothing.

The expert server partitions a request's life without a hole, from the
first byte to the last: ``server.conn.idle`` (the client's time between
two requests, seen at the socket) | ``server.read`` | ``server.request``
| ``server.write`` on the loop; inside ``server.request``
``server.decode``, ``pool.wait`` in the task pool, ``runtime.queue`` /
``runtime.stack`` / ``runtime.dispatch`` / ``runtime.materialize`` on the
Runtime thread, ``runtime.deliver`` and ``server.resume`` back on the
loop, ``server.encode``.  The Runtime thread's own time is the chain
``runtime.idle | stack | dispatch | materialize | handoff``.  All but
``runtime.idle`` are filed by kind; the table of every span, its thread
and its boundaries is in docs/OBSERVABILITY.md.

The CLIENT mirrors this, by kind too: ``client.dispatch.fire``
(selection + payload prep + non-blocking fan-out submit, on the host
thread), ``client.pack`` inside it (host-thread serialization — off the
event loop by construction), ``client.dispatch.join`` (the time the
caller actually BLOCKED waiting for replies — emitted from the join's
finally, so a timed-out join still records), and per exchange on the
client loop ``rpc.<msg_type>`` with its two halves ``rpc.send`` and
``rpc.decode`` (what is left of it is the wait for the server and the
socket).  The gap between a dispatch's fire span and its join span is
trunk compute overlapped with the in-flight RPCs; the time-weighted
aggregate surfaces always-on as ``lah_client_overlap_fraction``
(utils/metrics.py).  ``RemoteMixtureOfExperts.dispatch_stats()`` carries
the process's client stages (``stages``) beside the mixture's own pack
and wait medians (``pack_times`` / ``wait_times``).

The trainer-side AVERAGING subsystem (ISSUE 3) records per-round
``averaging.round`` spans; like the client dispatch path, its headline
numbers (round p50/p99, group sizes, degraded fraction) also surface
without profiling via ``DecentralizedAverager.stats()`` /
``AveragingSession.averaging_stats()``.

Headline counters do NOT live here: the always-on cheap metrics a
production peer exports by default belong to the registry in
``utils/metrics.py`` (which also re-exports this timeline's counters as
a collector).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import sys
import threading
import time
from collections import defaultdict, deque
from itertools import chain
from typing import Iterator, Optional

import numpy as np

from learning_at_home_tpu.utils import sanitizer

_TRACE_ID_RE = re.compile(r"^[0-9a-f]{16}$")


def new_trace_id() -> str:
    """A compact (16 hex chars, 64-bit) globally-unlikely-to-collide trace
    id — small enough to ride in every RPC's msgpack meta."""
    return os.urandom(8).hex()


def valid_trace_id(value: object) -> bool:
    """Structural check for the 16-hex trace-id contract: handlers echo
    ids that pass, and silently drop anything else (a peer-supplied meta
    string must never flow into spans/replies unvalidated)."""
    return isinstance(value, str) and bool(_TRACE_ID_RE.match(value))


# Spans kept per name in the always-on reservoirs: at 730 batches a second
# (PERF.md, ffnserver-infer-small) the last five or six seconds of a stage.
RESERVOIR_LEN = 4096
# The longest stretch of recent time ``stage_stats`` describes: a stage with
# a few spans a second (a ``multi`` request, the runtime's idle waits) must
# not reach back into a server's start-up for its sample.
STAGE_WINDOW_S = 30.0

# The kinds a stage's spans can be told apart by with profiling off: a
# closed set, so that a stage is read under at most three keys (``<name>``,
# ``<name>:forward``, ``<name>:backward``) whatever its callers pass.  A
# reservoir entry is ``(start, duration, code)`` with the kind's code as a
# float (0.0: none), so that telling the kinds apart costs a span no second
# append and ``stage_stats`` still reads a reservoir as one flat array.
KINDS = ("forward", "backward")
_KIND_CODES = {kind: float(i) for i, kind in enumerate(KINDS, 1)}

# A thread's clock: no sample sooner than this after the thread's last one,
# and the samples kept a thread (at four a second, seventeen minutes).
THREAD_SAMPLE_S = 0.25
THREAD_HISTORY_LEN = 4096

_annotation_cls = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _resolve_annotation_cls():
    """``jax.profiler.TraceAnnotation`` if this process has imported jax,
    else None: a process that never imports jax does not import it here."""
    global _annotation_cls
    if "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # jax is half imported on another thread
            return None
        _annotation_cls = TraceAnnotation
    return _annotation_cls


def live_annotation(name: str):
    """An ENTERED ``jax.profiler.TraceAnnotation`` while a profiler session
    is live (the caller exits it, on the same thread), else None: what
    ``Span.__enter__`` does in line, for a stage that is no span."""
    cls = _annotation_cls or _resolve_annotation_cls()
    if cls is None or not cls.is_enabled():
        return None
    annotation = cls(name)
    annotation.__enter__()
    return annotation


class ThreadClock:
    """One thread's CPU seconds beside its wall seconds: a bounded history
    of ``(monotonic, thread CPU s, process CPU s, busy_s, turns)``, at most
    one sample in ``THREAD_SAMPLE_S``.  ``busy_s`` and ``turns`` are running
    sums the thread keeps itself (an asyncio loop's time outside its
    blocking selects and its passes, utils/asyncio_utils.py), and so is
    ``waited_cpu_s``, CPU seconds the thread burned inside its own waits and
    wants left out of its CPU; a thread whose spans already say when it was
    busy (the Runtime's) passes none.

    Made by :meth:`Timeline.register_thread` ON the thread it times, and
    ticked by that thread alone: ``time.thread_time`` is the caller's, so a
    tick from any other thread is dropped.  The samples lie in no reservoir
    and under no span name (a reservoir entry a turn would fill 4096 slots
    in a second and cut the extent of every stage read beside it); read
    them with :meth:`Timeline.thread_stats`."""

    __slots__ = ("name", "ident", "samples", "due", "_thread_time",
                 "_process_time")

    def __init__(self, name: str, thread_time=time.thread_time,
                 process_time=time.process_time):
        self.name = name
        self.ident = threading.get_ident()
        self.samples: deque[tuple] = deque(maxlen=THREAD_HISTORY_LEN)
        self.due = float("-inf")  # a caller may compare before it calls
        self._thread_time = thread_time
        self._process_time = process_time

    def tick(self, now: float, busy_s: float = 0.0, turns: int = 0,
             waited_cpu_s: float = 0.0) -> None:
        """``now`` is a ``time.monotonic`` reading the thread already has:
        a tick is one comparison, and a sample two clock calls at most once
        in ``THREAD_SAMPLE_S``."""
        if now < self.due or threading.get_ident() != self.ident:
            return
        self.due = now + THREAD_SAMPLE_S
        self.samples.append((  # atomic: the GIL
            now, self._thread_time() - waited_cpu_s, self._process_time(),
            busy_s, turns,
        ))


class Span:
    """One timed stage: ``with timeline.span(name, pool=..., rows=...)``.

    On exit it goes to the Timeline's three sinks (module docstring).
    ``trace`` and ``attrs`` may be set until then: a request's trace id
    and its ``kind`` are known only once its meta is decoded.  ``start``
    is the entry's ``time.monotonic`` reading (or the one the caller gave);
    ``duration`` (seconds) and ``end`` (the exit's reading) are there after
    the exit: for a caller that keeps a running sum, and for one whose
    next stage begins where this one ended (``start=``)."""

    __slots__ = ("_timeline", "_reservoir", "name", "trace", "attrs",
                 "_annotation", "start", "duration", "end")

    def __init__(self, timeline: "Timeline", reservoir: deque, name: str,
                 trace: Optional[str], attrs: dict,
                 start: Optional[float] = None):
        self._timeline = timeline
        self._reservoir = reservoir
        self.name = name
        self.trace = trace
        self.attrs = attrs
        self.start = start

    def __enter__(self) -> "Span":
        # a profiler annotation only while a profiler session is live:
        # outside one a span pays the ``is_enabled()`` call and no more
        cls = _annotation_cls or _resolve_annotation_cls()
        if cls is not None and cls.is_enabled():
            self._annotation = cls(self.name, **self.attrs)
            self._annotation.__enter__()
        else:
            self._annotation = None
        if self.start is None:
            self.start = time.monotonic()
        return self

    def exclude(self) -> None:
        """Keep this span out of its stage's reservoir: what it timed is
        not that stage's work (a wait that ended in shutdown, a
        control-plane request among the data plane's).  With profiling on
        its full record is still kept, attributes and all."""
        self._reservoir = None

    def __exit__(self, exc_type, exc, tb) -> None:
        t0 = self.start
        self.end = end = time.monotonic()
        self.duration = duration = end - t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self._reservoir is not None:
            try:
                code = _KIND_CODES.get(self.attrs.get("kind"), 0.0)
            except TypeError:  # an unhashable kind is no kind
                code = 0.0
            self._reservoir.append((t0, duration, code))  # atomic: the GIL
        if self._timeline.enabled:
            self._timeline._record_full(
                self.name, t0, duration, self.trace, self.attrs
            )


class Timeline:
    """Thread-safe collection of timing spans and event counters.

    Every span lands in a bounded **reservoir** of the most recent
    ``(start, duration, kind code)`` under its name, profiling on or off
    (:meth:`recent`, :meth:`stage_stats`).  While :attr:`enabled`, it is
    also recorded in full — ``(name, start, duration, trace id, thread
    id, attributes)`` — for :meth:`spans`, :meth:`summary` and the Chrome
    ``trace_event`` exporter.

    Distinct COUNTER keys and span NAMES are capped (``max_counter_keys``
    each): a name that embeds data must not grow a long-lived server's
    tables without bound.  Counts for keys beyond the cap fold into one
    ``timeline.overflow`` bucket and each folded call increments
    ``timeline.dropped_keys``; spans under names beyond the cap fold into
    the ``timeline.overflow`` reservoir.
    """

    # counter names that must survive even at the cap (they ARE the
    # overflow accounting)
    _RESERVED_KEYS = ("timeline.overflow", "timeline.dropped_keys")

    def __init__(self, maxlen: int = 100_000, max_counter_keys: int = 512):
        # (name, start_monotonic, duration_s, trace_id|None, thread_id,
        #  attributes)
        self._spans: deque[
            tuple[str, float, float, Optional[str], int, dict]
        ] = deque(maxlen=maxlen)
        self._recent: dict[str, deque[tuple[float, float, float]]] = {}
        # the newest clock of each thread name; a thread keeps its own
        self._threads: dict[str, ThreadClock] = {}
        self._counters: defaultdict[str, float] = defaultdict(float)
        self.max_counter_keys = max_counter_keys
        self._lock = sanitizer.lock("profiling.timeline")
        self.enabled = os.environ.get("LAH_PROFILE", "") not in ("", "0")
        # rebase for cross-process merges: monotonic + offset ≈ wall clock
        self._clock_offset = time.time() - time.monotonic()

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._recent = {}  # a span in flight keeps its old reservoir
            for clock in self._threads.values():
                clock.samples.clear()  # a live thread goes on ticking it
            self._counters.clear()

    def span(
        self, name: str, trace: Optional[str] = None,
        start: Optional[float] = None, **attrs,
    ) -> Span:
        """A context manager timing the enclosed code as one span.
        ``start`` is a ``time.monotonic`` reading the caller already has,
        the ``end`` of the stage before: the two are then contiguous by
        construction, for one clock call less.  An attribute ``kind`` of
        :data:`KINDS` makes the span one of ``<name>:<kind>`` too."""
        reservoir = self._recent.get(name)
        if reservoir is None:
            reservoir = self._new_reservoir(name)
        return Span(self, reservoir, name, trace, attrs, start)

    def record(
        self, name: str, start: float, duration: float,
        trace: Optional[str] = None, **attrs,
    ) -> None:
        """A span whose two ``time.monotonic`` readings the caller has
        already (a wait that is known only once it is over)."""
        reservoir = self._recent.get(name)
        if reservoir is None:
            reservoir = self._new_reservoir(name)
        try:
            code = _KIND_CODES.get(attrs.get("kind"), 0.0)
        except TypeError:  # an unhashable kind is no kind
            code = 0.0
        reservoir.append((start, duration, code))
        if self.enabled:
            self._record_full(name, start, duration, trace, attrs)

    def _record_full(self, name, start, duration, trace, attrs) -> None:
        entry = (name, start, duration, trace, threading.get_ident(), attrs)
        with self._lock:
            self._spans.append(entry)

    def _new_reservoir(self, name: str) -> deque:
        with self._lock:
            if (
                name not in self._recent
                and len(self._recent) >= self.max_counter_keys
            ):
                name = "timeline.overflow"
            return self._recent.setdefault(name, deque(maxlen=RESERVOIR_LEN))

    def recent(self, key: str) -> list[tuple[float, float]]:
        """The most recent ``(start, duration)`` spans under ``key``
        (``time.monotonic`` seconds), oldest first: a span name, or
        ``<name>:<kind>`` for those of its spans that carried that kind.
        At most ``RESERVOIR_LEN`` a name, from process start or the last
        ``clear()``."""
        name, _, kind = key.partition(":")
        reservoir = self._recent.get(name)
        if reservoir is None or (kind and kind not in KINDS):
            return []
        # list(deque) copies without releasing the GIL: no append can
        # fall inside it
        code = _KIND_CODES.get(kind)
        return [(s, d) for s, d, c in list(reservoir)
                if code is None or c == code]

    def register_thread(
        self, name: str, thread_time=time.thread_time,
        process_time=time.process_time,
    ) -> ThreadClock:
        """A clock for the CALLING thread, read under ``name`` by
        :meth:`thread_stats`.  The thread keeps the clock and ticks it; a
        name is read from the clock registered last, so two live threads
        of one name (two servers in one process) never mix their samples,
        and the table holds a clock a name however many threads come and
        go (names beyond ``max_counter_keys`` get a clock nobody reads)."""
        clock = ThreadClock(name, thread_time, process_time)
        with self._lock:
            if name in self._threads or (
                len(self._threads) < self.max_counter_keys
            ):
                self._threads[name] = clock
        return clock

    def thread_stats(self, begin: float, end: float) -> dict[str, dict]:
        """Every registered thread between its first sample at or after
        ``begin`` and its last at or before ``end`` (``time.monotonic``
        seconds; :meth:`stage_extent` gives those the stages are read
        over): ``cpu_share``, the thread's CPU seconds over the wall
        seconds between the two samples, ``extent_s``;
        ``process_cpu_cores``, the whole process's CPU seconds over the
        same; and of the sums the
        thread keeps itself ``busy_share``, ``turns_per_s`` and
        ``turn_ms_mean`` (``busy_s / turns``), None for a thread that keeps
        none.  Nothing for a thread with fewer than two samples inside."""
        out = {}
        for name, clock in list(self._threads.items()):
            inside = [s for s in list(clock.samples) if begin <= s[0] <= end]
            if len(inside) < 2:
                continue
            wall, cpu, process_cpu, busy, turns = (
                b - a for a, b in zip(inside[0], inside[-1])
            )
            out[name] = {
                "busy_share": round(busy / wall, 6) if turns else None,
                "cpu_share": round(cpu / wall, 6),
                "turns_per_s": round(turns / wall, 3) if turns else None,
                "turn_ms_mean": round(busy / turns * 1e3, 6) if turns else None,
                "process_cpu_cores": round(process_cpu / wall, 6),
                "extent_s": round(wall, 4),
            }
        return out

    def _extent(
        self, prefix: str | tuple, window_s: float, skip_tail_s: float,
    ) -> Optional[tuple[dict, float, float]]:
        """THE rule of the common extent (:meth:`stage_stats`' docstring):
        per key under ``prefix`` the arrays ``(starts, durations, ends)`` of
        its reservoir, and the extent's ``begin`` and ``end``; None where
        there is no extent."""
        spans_of, forgotten_before = {}, []
        for name in list(self._recent):
            keys = [k for k in (name, *(f"{name}:{kind}" for kind in KINDS))
                    if k.startswith(prefix)]
            reservoir = list(self._recent.get(name, ())) if keys else []
            if not reservoir:
                continue
            # fromiter over the flattened triples: half the cost of
            # np.asarray(list of tuples), and this runs on a serving loop
            starts, durations, codes = np.fromiter(
                chain.from_iterable(reservoir), float, 3 * len(reservoir)
            ).reshape(-1, 3).T
            ends = starts + durations
            if len(reservoir) == RESERVOIR_LEN:  # appended in order of ends
                forgotten_before.append(float(ends[0]))
            for key in keys:
                kind = key[len(name) + 1:]
                of = codes == _KIND_CODES[kind] if kind else slice(None)
                if len(starts[of]):
                    spans_of[key] = (starts[of], durations[of], ends[of])
        if not spans_of:
            return None
        end = max(float(e.max()) for _, _, e in spans_of.values()) - skip_tail_s
        first = min(float(s.min()) for s, _, _ in spans_of.values())
        begin = max(end - window_s, first, *forgotten_before)
        return (spans_of, begin, end) if end > begin else None

    def stage_extent(
        self, prefix: str | tuple = "", window_s: float = STAGE_WINDOW_S,
        skip_tail_s: float = 0.0,
    ) -> Optional[tuple[float, float]]:
        """``(begin, end)`` of the extent :meth:`stage_stats` reads under
        the same arguments, in ``time.monotonic`` seconds, or None where it
        reads nothing: what :meth:`thread_stats` takes, so that a thread's
        shares and the stages' medians describe the same seconds."""
        read = self._extent(prefix, window_s, skip_tail_s)
        return read and read[1:]

    def stages_and_threads(
        self, prefix: str | tuple = "", window_s: float = STAGE_WINDOW_S,
        skip_tail_s: float = 0.0,
    ) -> dict[str, dict]:
        """``{"stages": stage_stats(..), "threads": thread_stats(..)}`` over
        one extent, from one pass over the reservoirs: what a ``stats``
        surface carries (``Runtime.stats``, ``dispatch_stats``)."""
        read = self._extent(prefix, window_s, skip_tail_s)
        if read is None:
            return {"stages": {}, "threads": {}}
        return {"stages": self._stats_over(*read),
                "threads": self.thread_stats(*read[1:])}

    def stage_stats(
        self, prefix: str | tuple = "", window_s: float = STAGE_WINDOW_S,
        skip_tail_s: float = 0.0,
    ) -> dict[str, dict]:
        """The keys under ``prefix`` (one, or a tuple of several), all
        read over ONE common extent of time, so that a stage with two
        spans a second and one with seven hundred describe the same
        seconds.  A key is a span name or, for the spans of it that
        carried a kind, ``<name>:<kind>``.  Per key ``count``, ``p50_ms``,
        ``p95_ms`` of the spans that ended inside the extent, ``share``,
        the part of the extent the stage was running (a span that reaches
        over either end counts with its part inside), and ``extent_s``
        itself.

        The extent ends ``skip_tail_s`` before the last span any of the
        keys ended (a reader that knows the run closed with traffic of
        another kind leaves that out) and is at most ``window_s`` long; it
        starts no earlier than their first span, nor than the first entry
        of any FULL reservoir among their names, which has forgotten what
        ended before that.  A key with no span in the extent has ``count``
        0, ``share`` 0 and no percentiles."""
        read = self._extent(prefix, window_s, skip_tail_s)
        return self._stats_over(*read) if read else {}

    @staticmethod
    def _stats_over(spans_of: dict, begin: float, end: float) -> dict:
        extent = end - begin
        out = {}
        for key, (starts, durations, ends) in spans_of.items():
            inside = (ends >= begin) & (ends <= end)
            count = int(inside.sum())
            # a stage that did not run in the extent has a share, 0, and
            # no median
            p50, p95 = (
                (round(float(q) * 1e3, 4)
                 for q in np.percentile(durations[inside], (50, 95)))
                if count else (None, None)
            )
            running = np.clip(ends, begin, end) - np.clip(starts, begin, end)
            out[key] = {
                "count": count,
                "p50_ms": p50,
                "p95_ms": p95,
                "share": round(float(running.sum()) / extent, 6),
                "extent_s": round(extent, 4),
            }
        return out

    def count(self, name: str, value: float = 1.0) -> None:
        """Accumulate a named event counter (no duration semantics).

        New keys beyond ``max_counter_keys`` fold into
        ``timeline.overflow`` (+``timeline.dropped_keys`` per folded
        call) instead of growing the dict — see class docstring."""
        if self.enabled:
            with self._lock:
                if (
                    name not in self._counters
                    and len(self._counters) >= self.max_counter_keys
                    and name not in self._RESERVED_KEYS
                ):
                    self._counters["timeline.overflow"] += value
                    self._counters["timeline.dropped_keys"] += 1
                    return
                self._counters[name] += value

    def counters(self, prefix: str = "") -> dict[str, float]:
        with self._lock:
            return {
                name: v
                for name, v in self._counters.items()
                if name.startswith(prefix)
            }

    def spans(
        self, prefix: str = ""
    ) -> list[tuple[str, float, float, Optional[str], int, dict]]:
        with self._lock:
            return [s for s in self._spans if s[0].startswith(prefix)]

    def summary(self) -> dict[str, dict]:
        """Per-span-name count / total / p50 / p99 (milliseconds) of the
        full records (profiling on)."""
        groups: dict[str, list[float]] = defaultdict(list)
        with self._lock:
            for name, _, duration, *_ in self._spans:
                groups[name].append(duration * 1000)
        out = {}
        for name, durs in groups.items():
            arr = np.asarray(durs)
            out[name] = {
                "count": len(arr),
                "total_ms": round(float(arr.sum()), 2),
                "p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3),
            }
        return out

    # ---- Chrome trace_event export (chrome://tracing / Perfetto) ----

    def chrome_trace(self, process_name: Optional[str] = None) -> list[dict]:
        """The recorded spans as Chrome ``trace_event`` complete ("X")
        events.  ``ts`` is wall-clock microseconds (monotonic start +
        the offset captured at construction), so event lists exported by
        several processes on one machine merge into one aligned trace;
        a span's attributes and its trace id, where it carried one, are
        its ``args`` (``{"pool": ..., "trace": id}``).
        ``pid`` is the real OS pid and ``tid`` the recording thread —
        chrome://tracing nests same-tid events by time containment."""
        pid = os.getpid()
        events: list[dict] = [
            {
                "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
                "args": {"name": process_name or f"lah-{pid}"},
            }
        ]
        for name, start, duration, trace, tid, attrs in self.spans():
            ev = {
                "ph": "X",
                "name": name,
                "cat": name.split(".", 1)[0],
                "pid": pid,
                "tid": tid,
                "ts": (start + self._clock_offset) * 1e6,
                "dur": duration * 1e6,
            }
            args = dict(attrs)
            if trace is not None:
                args["trace"] = trace
            if args:
                ev["args"] = args
            events.append(ev)
        return events

    def save_chrome_trace(
        self, path: str, extra_events: Iterator[dict] | list = (),
        process_name: Optional[str] = None,
    ) -> int:
        """Write ``{"traceEvents": [...]}`` JSON; ``extra_events`` lets a
        caller merge event lists fetched from OTHER processes' ``/trace``
        telemetry endpoints into one file.  Returns the event count."""
        events = self.chrome_trace(process_name) + list(extra_events)
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return len(events)


timeline = Timeline()


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler (XLA/TensorBoard) trace of the enclosed block."""
    import jax

    with jax.profiler.trace(log_dir):
        yield
