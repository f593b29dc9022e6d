"""Runtime: the double-buffered device-consumer loop executing formed batches.

Contract from the reference's ``hivemind/server/runtime.py`` (SURVEY.md §2
[BJ]; unverifiable refs, mount empty): repeatedly pick the
**highest-priority (oldest-waiting) non-empty pool** across all experts, run
its batch on the device, push outputs back to the pool's futures.  A single
serialized consumer per device → no intra-device contention and per-expert
update serialization for free.

TPU-native realization: a dedicated Python thread per process draining a
thread-safe priority queue of :class:`BatchJob`s.  The jitted XLA call
releases the GIL, so the asyncio networking loop keeps serving while the
device computes.  Results are handed back to the event loop via
``call_soon_threadsafe``.

The loop is **double-buffered** to exploit XLA's async dispatch: while job
N's outputs materialize (``np.asarray`` blocks until the device finishes),
job N+1 has already been stacked — into reusable staging buffers from
:mod:`.staging` — and its jitted call dispatched, so host work (stacking,
output copies, future delivery) overlaps device execution instead of
serializing with it.  The one hard exception: two jobs sharing a pool
``serial_key`` (forward/backward of the SAME expert — backward donates the
param buffers forward reads) are never in flight together.
"""

from __future__ import annotations

import asyncio
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from learning_at_home_tpu.server.staging import StagingBuffers
from learning_at_home_tpu.server.task_pool import BatchJob
from learning_at_home_tpu.utils.profiling import timeline

logger = logging.getLogger(__name__)

# Sentinel must be a tuple so it compares cleanly inside the PriorityQueue;
# -inf priority drains it ahead of any real job.
_SENTINEL = (float("-inf"), -1, None)


@dataclass
class _Inflight:
    """A dispatched-but-not-materialized job (the second pipeline stage)."""

    job: BatchJob
    raw_outputs: list
    staging: list = field(default_factory=list)
    dispatch_s: float = 0.0  # duration of the process_fn call itself
    trace: Optional[str] = None  # the batch's BatchJob.owner_trace()


class Runtime:
    """Double-buffered device executor fed by all TaskPools of a Server."""

    def __init__(self, loop: Optional[asyncio.AbstractEventLoop] = None):
        self._queue: queue.PriorityQueue = queue.PriorityQueue()
        self._loop = loop
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.staging = StagingBuffers()
        # telemetry (written by the runtime thread; read anywhere)
        self.jobs_processed = 0
        self.jobs_overlapped = 0  # dispatched while another job was in flight
        self.device_time = 0.0  # process_fn + materialization (busy time)
        self.queue_time = 0.0
        self.stack_time = 0.0
        self.materialize_time = 0.0
        self.queue_depth_max = 0

    def attach_loop(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop

    def start(self) -> None:
        assert self._loop is not None, "attach_loop() before start()"
        self._thread = threading.Thread(
            target=self._run, name="lah-runtime", daemon=True
        )
        self._thread.start()

    def submit(self, job: BatchJob) -> None:
        """Called from the event loop when a pool has formed a batch."""
        self._queue.put((job.priority, job.seq, job))
        depth = self._queue.qsize()
        if depth > self.queue_depth_max:
            self.queue_depth_max = depth

    @property
    def queue_depth(self) -> int:
        return self._queue.qsize()

    def _run(self) -> None:
        """The thread's time is one chain of stages, ``runtime.idle | stack
        | dispatch | materialize | handoff``.  ``mark`` is the reading at
        which its last stage ended, and ``idle``, ``materialize`` and
        ``handoff`` start there; ``dispatch`` starts where ``stack`` ended.
        ``stack`` alone takes a reading of its own, so that it times
        ``BatchJob.stack`` and nothing else: what precedes it is a few
        lines of this loop (``runtime.queue`` ends at that reading too).  The queue's next entry is taken inside the
        hand-off that precedes it (``item``), so the fetch has a name too.
        A batch costs the thread one clock call a stage and one.

        The thread's CPU seconds are its clock's (``stats()["threads"]``),
        ticked at readings the chain already has: the end of every
        ``runtime.idle`` and of every hand-off.  Its busy time is what the
        spans say, so it keeps no sums of its own."""
        self._clock = timeline.register_thread(threading.current_thread().name)
        pending: Optional[_Inflight] = None
        item = None  # the queue's next entry, where a hand-off fetched it
        mark = time.monotonic()
        while True:
            if item is None and pending is None:
                # nothing in flight and nothing to do until a pool forms
                # a batch: the pace is set upstream of this thread
                with timeline.span("runtime.idle", start=mark) as idle:
                    item = self._queue.get()
                    if item[2] is None:
                        idle.exclude()  # waited for shutdown, not for work
                mark = idle.end
                if mark >= self._clock.due:
                    self._clock.tick(mark)
            elif item is None:
                # don't wait: if no new job is ready, spend the idle
                # time materializing the in-flight one instead
                item = self._poll()
                if item is None:
                    mark, item = self._finish(pending, mark)
                    pending = None
                    continue
            (_, _, job), item = item, None
            if job is None or self._stop.is_set():
                if pending is not None:
                    self._finish(pending, mark, fetch=False)
                    pending = None
                if job is not None:
                    self._deliver(job, None, RuntimeError("runtime shut down"))
                break
            if (
                pending is not None
                and pending.job.pool.serial_key == job.pool.serial_key
            ):
                # per-expert serialization: never overlap two jobs of the
                # same expert/pool — drain the pipeline first
                mark, _ = self._finish(pending, mark, fetch=False)
                pending = None
            overlapped = pending is not None
            inflight, mark = self._dispatch_job(job)
            if pending is not None:
                mark, item = self._finish(pending, mark)
                pending = None
            if inflight is not None and overlapped:
                self.jobs_overlapped += 1
            pending = inflight
        if pending is not None:
            self._finish(pending, mark, fetch=False)
        self._drain_remaining()

    def _poll(self):
        """The queue's next entry if one is ready, else None."""
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            return None

    def _dispatch_job(
        self, job: BatchJob
    ) -> tuple[Optional[_Inflight], float]:
        """Stage one: stack the batch into staging buffers and dispatch the
        jitted call.  Returns the in-flight record, or None if the job
        failed (error already delivered), and the reading the thread's
        next stage starts at.  ``runtime.stack`` reads the clock for
        itself, and that reading ends the batch's wait in the queue."""
        pool = job.pool
        buffers: list = []
        # trace ids exist only on profiled requests: see BatchJob
        trace = job.owner_trace() if timeline.enabled else None
        try:
            with timeline.span(
                "runtime.stack", trace, pool=pool.name, rows=job.n_rows,
                bucket=job.target_rows, kind=pool.kind,
            ) as stack:
                inputs, buffers = job.stack(self.staging)
            self.stack_time += stack.duration
            pool.stack_time += stack.duration
            queued = stack.start - job.formed_at
            self.queue_time += queued
            timeline.record(
                "runtime.queue", job.formed_at, queued, trace,
                pool=pool.name, kind=pool.kind,
            )
            with timeline.span(
                "runtime.dispatch", trace, start=stack.end, pool=pool.name,
                kind=pool.kind,
            ) as launch:
                raw = list(pool.process_fn(inputs))
        except BaseException as e:  # deliver, don't kill the device loop
            logger.exception("runtime job failed in pool %s", pool.name)
            self.staging.release(buffers)
            self.jobs_processed += 1
            self._deliver(job, None, e)
            return None, job.finished_at
        return (
            _Inflight(job, raw, buffers, launch.duration, trace), launch.end
        )

    def _finish(
        self, inflight: _Inflight, mark: float, fetch: bool = True
    ) -> tuple[float, Optional[tuple]]:
        """Stage two: materialize the outputs (blocks until the device
        finishes — this is the wait the NEXT job's dispatch overlaps),
        then hand off: recycle the staging buffers, deliver to the pool's
        futures and, with ``fetch``, be back at the queue for its next
        entry if one is ready.  Returns the reading at which the hand-off
        ended, and that entry or None."""
        job = inflight.job
        pool = job.pool
        outputs, error = None, None
        try:
            with timeline.span(
                "runtime.materialize", inflight.trace, start=mark,
                pool=pool.name, kind=pool.kind,
            ) as materialize:
                outputs = self._to_host(inflight)
        except BaseException as e:
            logger.exception(
                "runtime job failed to materialize in pool %s", pool.name
            )
            error = e
        with timeline.span(
            "runtime.handoff", inflight.trace, start=materialize.end,
            pool=pool.name, kind=pool.kind,
        ) as handoff:
            self.materialize_time += materialize.duration
            # device_time keeps its pre-pipeline meaning — process_fn call
            # + output materialization, the job's own busy time.  Under
            # overlap, wall time from dispatch to materialized also
            # contains the NEXT job's stack/dispatch; folding that in
            # would double-count and make the pipelined runtime read as a
            # device-time regression.
            self.device_time += inflight.dispatch_s + materialize.duration
            self.jobs_processed += 1
            self.staging.release(inflight.staging)
            # the request's wait for the loop (runtime.deliver) starts
            # where its materialization ended: the hand-off is this
            # thread's time, and no part of the request's goes unnamed
            self._deliver(job, outputs, error, materialize.end)
            # the device's output buffers are freed HERE, under a name (20
            # to 40 us a batch on the chip, and a wait for the GIL where
            # the free lets it go), not wherever this thread happens to
            # drop its last reference to them
            del inflight.raw_outputs[:]
            item = self._poll() if fetch else None
        if handoff.end >= self._clock.due:
            self._clock.tick(handoff.end)
        return handoff.end, item

    @staticmethod
    def _to_host(inflight: _Inflight) -> list:
        """The in-flight job's outputs as host arrays: blocks until the
        device has finished, then copies.  (A function of its own so that
        no local of the caller keeps a device buffer alive past it.)"""
        outputs = []
        for o in inflight.raw_outputs:
            arr = np.asarray(o)
            # a pure-host process_fn can return views INTO the staging
            # buffers; those must be copied out before the buffer is
            # recycled under the delivered results
            if inflight.staging and any(
                np.may_share_memory(arr, buf) for buf in inflight.staging
            ):
                arr = np.array(arr)
            outputs.append(arr)
        return outputs

    def stats(self) -> dict:
        """Hot-path telemetry snapshot for the server ``stats`` surface."""
        jobs = self.jobs_processed
        return {
            "jobs_processed": jobs,
            "jobs_overlapped": self.jobs_overlapped,
            "overlap_fraction": round(self.jobs_overlapped / jobs, 4) if jobs else 0.0,
            "device_time_ms": round(self.device_time * 1e3, 2),
            "queue_time_ms": round(self.queue_time * 1e3, 2),
            "stack_time_ms": round(self.stack_time * 1e3, 2),
            "materialize_time_ms": round(self.materialize_time * 1e3, 2),
            "queue_depth": self.queue_depth,
            "queue_depth_max": self.queue_depth_max,
            "staging": self.staging.stats(),
            # the request's life by stage, every stage over the same recent
            # seconds (count, p50_ms, p95_ms, share, extent_s: stage_stats
            # in utils/profiling.py), where the *_time_ms sums above run
            # from process start; and "threads", over those same seconds
            # the loop's and this thread's CPU seconds beside their wall
            # seconds (thread_stats): device_time_ms above is this thread's
            # WALL time in launch plus materialize, not the chip's busy time
            **timeline.stages_and_threads(("server.", "pool.", "runtime.")),
        }

    def _deliver(
        self, job: BatchJob, outputs, error,
        finished_at: Optional[float] = None,
    ) -> None:
        # runtime.deliver starts here
        job.finished_at = finished_at or time.monotonic()
        try:
            self._loop.call_soon_threadsafe(job.pool.deliver, job, outputs, error)
        except RuntimeError:
            pass  # event loop already closed; the futures died with it

    def _drain_remaining(self) -> None:
        """Fail queued-but-never-run jobs fast instead of leaving their
        clients to hit the full RPC timeout."""
        while True:
            try:
                _, _, job = self._queue.get_nowait()
            except queue.Empty:
                return
            if job is not None:
                self._deliver(job, None, RuntimeError("runtime shut down"))

    def shutdown(self, timeout: float = 5.0) -> None:
        self._stop.set()
        self._queue.put(_SENTINEL)
        if self._thread is not None:
            self._thread.join(timeout=timeout)
