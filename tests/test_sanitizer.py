"""Seeded-violation tests for the runtime concurrency sanitizer (ISSUE 6).

Each detector must TRIP on a deliberately constructed violation — a
sanitizer that never fires is indistinguishable from one that works.
Expected findings are drained through ``sanitizer.expect_violations()``
so the shared conftest zero-violation guard stays green."""

import asyncio
import threading
import time

import numpy as np
import pytest

from learning_at_home_tpu.utils import sanitizer
from learning_at_home_tpu.utils.asyncio_utils import BackgroundLoop

pytestmark = pytest.mark.skipif(
    not sanitizer.enabled(), reason="sanitizer disabled (LAH_SANITIZE=0)"
)


# ---------------------------------------------------------------------------
# thread-identity detectors
# ---------------------------------------------------------------------------


def test_seeded_blocking_encode_on_client_loop_trips():
    """A deliberate 8-bit encode ON the lah-client loop — the exact
    blocking-work-on-the-loop regression PR 2/5 guard against — must be
    recorded as a thread violation naming the site and the loop."""
    from learning_at_home_tpu.client.rpc import client_loop, reset_client_rpc
    from learning_at_home_tpu.utils.serialization import EncodedBatch

    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)

    async def encode_on_loop():
        return EncodedBatch.encode(x, "blockq8")

    with sanitizer.expect_violations("EncodedBatch.encode") as seen:
        eb = client_loop().run(encode_on_loop())
    reset_client_rpc()
    assert eb.codec == "blockq8"  # the check diagnoses, never intervenes
    hits = [
        v for v in seen
        if v["kind"] == "thread" and v["site"] == "EncodedBatch.encode"
    ]
    assert hits, f"seeded on-loop encode not detected: {seen}"
    assert hits[0]["thread"].startswith("lah-client")


def test_seeded_wrong_thread_stack_trips():
    """``BatchJob.stack`` called on an event loop (instead of the
    Runtime thread) must trip the ``runs_on("runtime")`` assertion."""
    from learning_at_home_tpu.server.staging import StagingBuffers
    from learning_at_home_tpu.server.task_pool import BatchJob, TaskPool

    pool = TaskPool(lambda i: list(i), "seeded", max_batch_size=8)
    tensors = [
        (np.ones((2, 4), np.float32),),
        (np.zeros((3, 4), np.float32),),
    ]
    job = BatchJob(
        priority=0.0, seq=0, pool=pool, task_tensors=tensors,
        row_spans=[], n_rows=5, target_rows=8,
        dtypes=[np.dtype(np.float32)],
    )

    async def stack_on_loop():
        return job.stack(StagingBuffers())

    with sanitizer.expect_violations("BatchJob.stack") as seen:
        inputs, buffers = asyncio.run(stack_on_loop())
    np.testing.assert_array_equal(inputs[0][:2], 1.0)  # still correct
    hits = [
        v for v in seen
        if v["kind"] == "thread" and v["site"] == "BatchJob.stack"
    ]
    assert hits, f"seeded on-loop stack not detected: {seen}"


def test_seeded_pack_frames_on_runtime_thread_trips():
    """The device thread must never serialize wire frames
    (``runs_on("not:lah-runtime")``)."""
    from learning_at_home_tpu.utils.serialization import (
        WireTensors,
        pack_frames,
    )

    wire = WireTensors.prepare([np.zeros(4, np.float32)])
    out = {}

    def on_fake_runtime():
        out["parts"] = pack_frames("forward", wire, {"uid": "x"})

    with sanitizer.expect_violations("pack_frames") as seen:
        t = threading.Thread(target=on_fake_runtime, name="lah-runtime-seed")
        t.start()
        t.join()
    assert out["parts"]  # frame still produced
    hits = [v for v in seen if v["site"] == "pack_frames"]
    assert hits, f"seeded runtime-thread pack_frames not detected: {seen}"


def test_allowed_scope_suppresses_and_is_thread_local():
    """``sanitizer.allowed(site)`` silences exactly that site, exactly in
    scope — the runtime twin of the lint suppression annotation."""
    from learning_at_home_tpu.utils.serialization import EncodedBatch

    x = np.ones((4, 4), np.float32)

    async def encode_allowed():
        with sanitizer.allowed("EncodedBatch.encode"):
            return EncodedBatch.encode(x, "u8")

    with sanitizer.expect_violations() as seen:
        asyncio.run(encode_allowed())
    assert not seen, f"allowed() scope did not suppress: {seen}"

    async def encode_after_scope():
        return EncodedBatch.encode(x, "u8")

    with sanitizer.expect_violations() as seen:
        asyncio.run(encode_after_scope())
    assert seen, "check must re-arm once the allowed() scope exits"


def test_expect_violations_site_filter_keeps_unrelated():
    """A scoped drain must only swallow the sites the test seeded — a
    genuine violation from an unrelated site during the scope stays
    visible to the guard/summary instead of vanishing as 'expected'."""
    from learning_at_home_tpu.utils.serialization import EncodedBatch

    x = np.ones((4, 4), np.float32)

    async def bad():
        return EncodedBatch.encode(x, "u8")

    with sanitizer.expect_violations() as outer:  # test-hygiene drain
        with sanitizer.expect_violations("some.other.site") as inner:
            asyncio.run(bad())
        assert not inner, "filtered scope must not capture unrelated sites"
        assert any(
            v["site"] == "EncodedBatch.encode"
            for v in sanitizer.violations()
        ), "the genuine violation must survive the filtered drain"
    assert any(v["site"] == "EncodedBatch.encode" for v in outer)


# ---------------------------------------------------------------------------
# lock-order cycle detector
# ---------------------------------------------------------------------------


def test_seeded_lock_cycle_trips():
    """Thread 1 takes A→B, thread 2 takes B→A: the classic deadlock
    shape must be flagged from the ORDER GRAPH alone — the two threads
    here run sequentially, no actual deadlock is ever at risk."""
    a = sanitizer.lock("seeded.A")
    b = sanitizer.lock("seeded.B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    with sanitizer.expect_violations("seeded.") as seen:
        t1 = threading.Thread(target=ab)
        t1.start()
        t1.join()
        t2 = threading.Thread(target=ba)
        t2.start()
        t2.join()
    cycles = [v for v in seen if v["kind"] == "lock-cycle"]
    assert cycles, f"seeded A->B/B->A cycle not detected: {seen}"
    assert "seeded.A" in cycles[0]["site"] and "seeded.B" in cycles[0]["site"]
    edges = sanitizer.lock_edges()
    assert ("seeded.A", "seeded.B") in edges
    assert ("seeded.B", "seeded.A") in edges


def test_seeded_same_name_instance_nesting_trips():
    """Two *different instances* of one lock class nested on one thread:
    name-level edges cannot order instances, so this ABBA-within-a-class
    shape is flagged directly (another thread nesting them the other way
    around would deadlock)."""
    e1 = sanitizer.lock("seeded.expert_state")
    e2 = sanitizer.lock("seeded.expert_state")

    with sanitizer.expect_violations("seeded.") as seen:
        with e1:
            with e2:
                pass
    hits = [
        v for v in seen
        if v["kind"] == "lock-cycle" and "instances nested" in v["detail"]
    ]
    assert hits, f"cross-instance same-name nesting not detected: {seen}"
    # reentrant re-acquire of the SAME instance stays clean
    r = sanitizer.lock("seeded.reentrant", reentrant=True)
    with sanitizer.expect_violations("seeded.") as seen:
        with r:
            with r:
                pass
    assert not seen, f"reentrant same-instance acquire false-flagged: {seen}"


def test_consistent_lock_order_is_clean():
    """Same nesting order on every thread: edges recorded, no cycle."""
    c = sanitizer.lock("seeded.C")
    d = sanitizer.lock("seeded.D")

    def cd():
        with c:
            with d:
                pass

    with sanitizer.expect_violations("seeded.") as seen:
        threads = [threading.Thread(target=cd) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not [v for v in seen if v["kind"] == "lock-cycle"]
    assert ("seeded.C", "seeded.D") in sanitizer.lock_edges()


# ---------------------------------------------------------------------------
# event-loop stall detector
# ---------------------------------------------------------------------------


def test_seeded_loop_stall_is_recorded():
    """A callback holding a loop past LAH_SANITIZE_STALL_MS (default
    100 ms) must be counted, with the live stack captured mid-stall by
    the monitor thread.  Stalls are diagnostics, not violations — the
    conftest guard does not fail on them."""
    before = sanitizer.stall_stats()

    async def stall():
        # deliberate: blocks this loop's only thread, UNTIL the monitor's
        # record of it has landed (a count, not a sleep the monitor thread
        # has to be scheduled inside of: under six loaded workers it was
        # not, and the stall went unseen) and 200 ms more
        deadline = time.monotonic() + 60.0
        while (sanitizer.stall_stats()["count"] == before["count"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        time.sleep(0.2)

    asyncio.run(stall())
    after = sanitizer.stall_stats()
    assert after["count"] > before["count"], (
        f"seeded stall not recorded: {before} -> {after}"
    )
    # the callback's own end refreshes what the monitor read mid-flight
    assert after["max_ms"] >= 200.0
    last = after["last"]
    assert last is not None
    # the monitor captured the blocked frame: the seeded sleep is in it
    if last.get("stack"):
        assert "time.sleep(0.01)" in last["stack"] or "stall" in last["stack"]


# ---------------------------------------------------------------------------
# BackgroundLoop self-deadlock guard (R2's runtime twin — always on)
# ---------------------------------------------------------------------------


def test_background_loop_run_from_own_thread_raises():
    """``BackgroundLoop.run()`` from the loop's own thread is a
    guaranteed self-deadlock (the io_callback hang shape): the guard
    must raise instead of hanging, sanitizer on or off."""
    bg = BackgroundLoop(name="lah-loop-guard-test")
    try:

        async def noop():
            return 42

        async def call_run_from_loop():
            # we ARE the loop thread here: .run() would block forever
            with pytest.raises(RuntimeError, match="self-deadlock"):
                bg.run(noop())
            return "guarded"

        assert bg.run(call_run_from_loop(), timeout=10) == "guarded"
        # and from a host thread the same call works fine
        assert bg.run(noop(), timeout=10) == 42
    finally:
        bg.shutdown()


def test_site_stats_record_thread_classes():
    """site_stats is the observable the replaced monkeypatch tests assert
    on: it must bucket calls by thread class."""
    from learning_at_home_tpu.utils.serialization import EncodedBatch

    before = sanitizer.site_stats().get("EncodedBatch.encode", {})
    EncodedBatch.encode(np.ones((2, 2), np.float32), "u8")  # host thread
    after = sanitizer.site_stats().get("EncodedBatch.encode", {})
    assert after.get("host", 0) == before.get("host", 0) + 1
