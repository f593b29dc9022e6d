"""Tests for the cross-request batcher + single-consumer device loop (M0)."""

import asyncio

import numpy as np
import pytest

from learning_at_home_tpu.server import Runtime, TaskPool, bucket_rows


def test_bucket_rows():
    assert bucket_rows(1, 64) == 1
    assert bucket_rows(2, 64) == 2
    assert bucket_rows(3, 64) == 4
    assert bucket_rows(33, 64) == 64
    assert bucket_rows(64, 64) == 64
    assert bucket_rows(100, 64) == 64  # clamped


def run_pool(coro):
    return asyncio.run(coro)


def test_single_task_roundtrip():
    async def main():
        calls = []

        def process(inputs):
            calls.append(tuple(a.shape for a in inputs))
            return [inputs[0] * 2]

        pool = TaskPool(process, "p", max_batch_size=8, batch_timeout=0.001)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        x = np.arange(6, dtype=np.float32).reshape(3, 2)
        (out,) = await pool.submit_task(x)
        runtime.shutdown()
        np.testing.assert_array_equal(out, x * 2)
        # 3 rows → padded to bucket 4
        assert calls == [((4, 2),)]
        assert pool.padded_rows == 1 and pool.total_rows == 3

    run_pool(main())


def test_cross_request_batching():
    """Concurrent tasks coalesce into one padded device batch."""

    async def main():
        batch_rows = []

        def process(inputs):
            batch_rows.append(inputs[0].shape[0])
            return [inputs[0] + 1]

        pool = TaskPool(process, "p", max_batch_size=64, batch_timeout=0.05)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)

        xs = [np.full((3, 2), i, np.float32) for i in range(5)]
        outs = await asyncio.gather(*(pool.submit_task(x) for x in xs))
        runtime.shutdown()
        for i, (out,) in enumerate(outs):
            np.testing.assert_array_equal(out, xs[i] + 1)
        # 5 tasks × 3 rows = 15 → one batch bucketed to 16
        assert batch_rows == [16]
        assert pool.batches_formed == 1

    run_pool(main())


def test_oversized_task_rejected():
    async def main():
        pool = TaskPool(lambda i: [i[0]], "p", max_batch_size=4)
        with pytest.raises(ValueError):
            await pool.submit_task(np.zeros((5, 1), np.float32))

    run_pool(main())


def test_error_propagates_to_futures():
    async def main():
        def process(inputs):
            raise RuntimeError("device on fire")

        pool = TaskPool(process, "p", max_batch_size=4, batch_timeout=0.001)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        with pytest.raises(RuntimeError, match="device on fire"):
            await pool.submit_task(np.zeros((1, 1), np.float32))
        runtime.shutdown()

    run_pool(main())


def test_priority_oldest_first():
    """Runtime drains jobs oldest-submission-first across pools."""

    async def main():
        order = []

        def mk(name):
            def process(inputs):
                order.append(name)
                return [inputs[0]]

            return process

        pool_a = TaskPool(mk("a"), "a", max_batch_size=2, batch_timeout=0.0)
        pool_b = TaskPool(mk("b"), "b", max_batch_size=2, batch_timeout=0.0)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        # don't start the runtime yet: let both pools enqueue first
        fut_a = asyncio.ensure_future(pool_a.submit_task(np.zeros((1, 1), np.float32)))
        await asyncio.sleep(0.01)
        fut_b = asyncio.ensure_future(pool_b.submit_task(np.zeros((1, 1), np.float32)))
        await asyncio.sleep(0.01)
        pool_a.start(runtime)
        pool_b.start(runtime)
        await asyncio.sleep(0.05)  # managers form both jobs into the queue
        runtime.start()
        await asyncio.gather(fut_a, fut_b)
        runtime.shutdown()
        assert order == ["a", "b"]  # a arrived first

    run_pool(main())


def test_batcher_property_randomized():
    """Property test (SURVEY §5.2): under random task sizes, arrival jitter,
    and pool parameters, every task gets EXACTLY its own rows back —
    batching must never mix, drop, or reorder rows within a task."""
    import random

    rng = random.Random(0)
    for trial in range(5):
        max_bs = rng.choice([4, 8, 32])
        timeout = rng.choice([0.0, 0.001, 0.01])

        async def main():
            def process(inputs):
                # tag rows so misrouting is detectable: f(x) = x * 2 + 1
                return [inputs[0] * 2 + 1]

            pool = TaskPool(
                process, "prop", max_batch_size=max_bs, batch_timeout=timeout
            )
            runtime = Runtime()
            runtime.attach_loop(asyncio.get_running_loop())
            runtime.start()
            pool.start(runtime)

            async def one_task(i):
                n = rng.randint(1, max_bs)
                # unique payload per task: task id in col 0, row id in col 1
                x = np.stack(
                    [np.full(n, i, np.float32), np.arange(n, dtype=np.float32)],
                    axis=1,
                )
                if rng.random() < 0.5:
                    await asyncio.sleep(rng.random() * 0.01)
                (out,) = await pool.submit_task(x)
                np.testing.assert_array_equal(out, x * 2 + 1)

            await asyncio.gather(*(one_task(i) for i in range(40)))
            runtime.shutdown()

        run_pool(main())


def test_no_stacking_on_event_loop():
    """Regression for the pipelined hot path: per-batch stacking must not
    run on the asyncio loop thread.  The old version monkeypatched
    ``np.concatenate`` to track threads; now the sanitizer's first-class
    ``@runs_on("runtime")`` assertion on ``BatchJob.stack`` carries the
    invariant — the shared conftest guard fails this test on any
    violation, and the site stats prove the Runtime thread really did
    the stacking."""
    from learning_at_home_tpu.utils import sanitizer

    if not sanitizer.enabled():
        pytest.skip("sanitizer disabled (LAH_SANITIZE=0)")
    before = sanitizer.site_stats().get("BatchJob.stack", {})

    async def main():
        def process(inputs):
            return [inputs[0] * 2]

        pool = TaskPool(process, "p", max_batch_size=64, batch_timeout=0.01)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        xs = [np.full((3, 2), i, np.float32) for i in range(4)]
        outs = await asyncio.gather(*(pool.submit_task(x) for x in xs))
        runtime.shutdown()
        for i, (out,) in enumerate(outs):
            np.testing.assert_array_equal(out, xs[i] * 2)
        # the copies really happened runtime-side, into staging buffers
        assert runtime.stack_time >= 0.0
        assert runtime.staging.allocated >= 1

    run_pool(main())
    after = sanitizer.site_stats().get("BatchJob.stack", {})
    ran_on_runtime = after.get("runtime", 0) - before.get("runtime", 0)
    assert ran_on_runtime > 0, (
        f"BatchJob.stack never ran on the lah-runtime thread: {after}"
    )
    # loop-thread stacking would ALSO have tripped the conftest guard;
    # assert the observable here too so the failure reads locally
    for cls in after:
        if cls != "runtime" and after.get(cls, 0) > before.get(cls, 0):
            raise AssertionError(
                f"BatchJob.stack ran on a {cls!r} thread during this test"
            )


def test_staging_buffer_reuse_and_isolation():
    """Two in-flight batches of the same bucket must not share a staging
    buffer; once both retire, a later batch reuses one of them."""

    async def main():
        seen = {"a": [], "b": [], "c": []}

        def mk(name):
            def process(inputs):
                seen[name].append(id(inputs[0]))
                return [inputs[0] + 1]

            return process

        # distinct pools (distinct serial keys) with identical bucket
        # shapes: the runtime dispatches b while a is still in flight
        pool_a = TaskPool(mk("a"), "a", max_batch_size=8, batch_timeout=0.0)
        pool_b = TaskPool(mk("b"), "b", max_batch_size=8, batch_timeout=0.0)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        # two tasks per pool so each batch stacks (multi-task → staging)
        xs = [np.full((1, 2), i, np.float32) for i in range(2)]
        futs = [
            asyncio.ensure_future(pool.submit_task(x))
            for pool in (pool_a, pool_b)
            for x in xs
        ]
        await asyncio.sleep(0.01)
        pool_a.start(runtime)
        pool_b.start(runtime)
        await asyncio.sleep(0.05)  # both jobs formed and queued
        runtime.start()
        await asyncio.gather(*futs)
        assert seen["a"] and seen["b"]
        # in-flight together → disjoint buffers
        assert not (set(seen["a"]) & set(seen["b"]))

        # a third batch, after both retired, reuses a pooled buffer
        pool_c = TaskPool(mk("c"), "c", max_batch_size=8, batch_timeout=0.0)
        pool_c.start(runtime)
        reused_before = runtime.staging.reused
        await asyncio.gather(*(pool_c.submit_task(x) for x in xs))
        runtime.shutdown()
        assert runtime.staging.reused > reused_before
        assert set(seen["c"]) <= (set(seen["a"]) | set(seen["b"]))

    run_pool(main())


class _LazyArray:
    """Output whose materialization (np.asarray) is observable — stands in
    for an async XLA array that blocks when fetched."""

    def __init__(self, value, events, tag):
        self.value = value
        self.events = events
        self.tag = tag

    def __array__(self, dtype=None):
        self.events.append(("materialize", self.tag))
        return np.asarray(self.value, dtype)


def test_double_buffering_overlap_and_serialization():
    """Different serial keys: job B dispatches BEFORE job A materializes
    (the overlap).  Same serial key: A must fully materialize before B
    dispatches (per-expert update serialization)."""

    def run_pair(key_a, key_b):
        events = []

        async def main():
            def mk(tag):
                def process(inputs):
                    events.append(("dispatch", tag))
                    return [_LazyArray(inputs[0], events, tag)]

                return process

            pool_a = TaskPool(mk("A"), "pa", max_batch_size=4,
                              batch_timeout=0.0, serial_key=key_a)
            pool_b = TaskPool(mk("B"), "pb", max_batch_size=4,
                              batch_timeout=0.0, serial_key=key_b)
            runtime = Runtime()
            runtime.attach_loop(asyncio.get_running_loop())
            x = np.ones((1, 2), np.float32)
            fut_a = asyncio.ensure_future(pool_a.submit_task(x))
            await asyncio.sleep(0.01)
            fut_b = asyncio.ensure_future(pool_b.submit_task(x))
            await asyncio.sleep(0.01)
            pool_a.start(runtime)
            pool_b.start(runtime)
            await asyncio.sleep(0.05)  # both jobs queued before the loop runs
            runtime.start()
            await asyncio.gather(fut_a, fut_b)
            runtime.shutdown()
            return runtime

        runtime = run_pool(main())
        return events, runtime

    events, runtime = run_pair("k1", "k2")
    assert events == [
        ("dispatch", "A"), ("dispatch", "B"),
        ("materialize", "A"), ("materialize", "B"),
    ]
    assert runtime.jobs_overlapped == 1
    assert runtime.stats()["overlap_fraction"] == 0.5

    events, runtime = run_pair("same", "same")
    assert events == [
        ("dispatch", "A"), ("materialize", "A"),
        ("dispatch", "B"), ("materialize", "B"),
    ]
    assert runtime.jobs_overlapped == 0


def test_padding_accounting_parity_and_buckets():
    """The off-loop path must account padding exactly like the old on-loop
    path, and track per-bucket compile/hit telemetry."""

    async def main():
        def process(inputs):
            return [inputs[0]]

        pool = TaskPool(process, "p", max_batch_size=16, batch_timeout=0.0)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        # 3 rows → bucket 4 (1 pad row); then 5 rows → bucket 8 (3 pad);
        # then 3 rows again → bucket 4 is now a cache hit
        for rows in (3, 5, 3):
            await pool.submit_task(np.zeros((rows, 2), np.float32))
        runtime.shutdown()
        assert pool.total_rows == 11
        assert pool.padded_rows == (4 - 3) + (8 - 5) + (4 - 3)
        assert pool.batches_formed == 3
        assert pool.padding_waste == pool.padded_rows / (11 + pool.padded_rows)
        bs = pool.bucket_stats()
        assert bs["batches_per_bucket"] == {4: 2, 8: 1}
        assert bs["cold_compiles"] == 2 and bs["cache_hits"] == 1

    run_pool(main())


def test_stale_padding_rezeroed_on_buffer_reuse():
    """A recycled staging buffer holds the previous batch's rows — the pad
    region must read as zeros, not stale data."""

    async def main():
        pad_sums = []

        def process(inputs):
            pad_sums.append(float(np.abs(inputs[0][3:]).sum()))  # pad rows
            return [inputs[0]]

        pool = TaskPool(process, "p", max_batch_size=8, batch_timeout=0.05)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        # two tasks → stacked batch of 3 rows in a 4-bucket, all ones
        a, b = np.ones((2, 2), np.float32), np.ones((1, 2), np.float32)
        await asyncio.gather(pool.submit_task(a), pool.submit_task(b))
        # same shape again: reuses the dirty buffer
        await asyncio.gather(pool.submit_task(a), pool.submit_task(b))
        runtime.shutdown()
        # every batch's pad region (if any) must read as zeros
        assert pad_sums and all(s == 0.0 for s in pad_sums)
        assert runtime.staging.reused >= 1

    run_pool(main())


def test_output_aliasing_staging_buffer_is_copied():
    """A process_fn returning its input (a view of the staging buffer)
    must not hand clients memory that a later batch will overwrite."""

    async def main():
        def process(inputs):
            return [inputs[0]]  # alias of the staging buffer

        pool = TaskPool(process, "p", max_batch_size=8, batch_timeout=0.05)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        a = np.full((2, 2), 7.0, np.float32)
        b = np.full((1, 2), 9.0, np.float32)
        (out_a,), (out_b,) = await asyncio.gather(
            pool.submit_task(a), pool.submit_task(b)
        )
        # overwrite the same bucket with different values
        c = np.full((3, 2), -1.0, np.float32)
        await pool.submit_task(c)
        runtime.shutdown()
        np.testing.assert_array_equal(out_a, a)
        np.testing.assert_array_equal(out_b, b)

    run_pool(main())


def test_mixed_dtype_tasks_promote_like_concatenate():
    """Old-path parity: co-batched tasks of different float dtypes promote
    via np.result_type (f32 + f64 → f64 batch), they do not fail the
    innocent co-batched request."""

    async def main():
        seen_dtypes = []

        def process(inputs):
            seen_dtypes.append(inputs[0].dtype)
            return [inputs[0] * 2]

        pool = TaskPool(process, "p", max_batch_size=8, batch_timeout=0.05)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        a = np.ones((2, 2), np.float32)
        b = np.ones((1, 2), np.float64)
        (out_a,), (out_b,) = await asyncio.gather(
            pool.submit_task(a), pool.submit_task(b)
        )
        runtime.shutdown()
        np.testing.assert_array_equal(out_a, a * 2)
        np.testing.assert_array_equal(out_b, b * 2)
        # both puts land on the loop before the manager wakes, and the
        # 50 ms grace window dwarfs a loop tick: the tasks co-batch, and
        # the mixed batch must have promoted to f64 (concatenate parity)
        assert pool.batches_formed == 1, seen_dtypes
        assert seen_dtypes == [np.float64]

    run_pool(main())


def test_many_concurrent_clients_stress():
    async def main():
        def process(inputs):
            return [inputs[0] * 3.0]

        pool = TaskPool(process, "p", max_batch_size=32, batch_timeout=0.002)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        xs = [np.random.randn(np.random.randint(1, 5), 3).astype(np.float32) for _ in range(100)]
        outs = await asyncio.gather(*(pool.submit_task(x) for x in xs))
        runtime.shutdown()
        for x, (out,) in zip(xs, outs):
            np.testing.assert_allclose(out, x * 3.0, rtol=1e-6)
        assert pool.batches_formed >= 1

    run_pool(main())


def test_runtime_thread_ticks_its_clock_a_batch():
    """ISSUE 68: the runtime thread samples its CPU seconds at the end of
    a hand-off or an idle wait, at most four times a second, on a clock
    registered from its own thread; it keeps no sums of its own."""
    import time

    from learning_at_home_tpu.utils.profiling import timeline

    async def main():
        pool = TaskPool(lambda inputs: [inputs[0] + 1], "p",
                        max_batch_size=8, batch_timeout=0.001)
        runtime = Runtime()
        runtime.attach_loop(asyncio.get_running_loop())
        runtime.start()
        pool.start(runtime)
        begin = time.monotonic()
        batches = 0
        while time.monotonic() - begin < 0.7:
            await pool.submit_task(np.ones((2, 2), np.float32))
            batches += 1
        stats = runtime.stats()
        runtime.shutdown()
        stats["samples"] = [s for s in runtime._clock.samples if s[0] >= begin]
        return runtime, begin, batches, stats

    try:
        runtime, begin, batches, stats = run_pool(main())
    finally:
        timeline.clear()
    clock = runtime._clock
    assert clock.name == "lah-runtime" and clock.ident == runtime._thread.ident
    assert timeline._threads["lah-runtime"] is clock
    samples = stats.pop("samples")
    assert 3 <= len(samples) <= 4 < batches
    assert all(b[0] - a[0] >= 0.25 for a, b in zip(samples, samples[1:]))
    assert all(s[3:] == (0.0, 0) for s in samples)  # no busy_s, no turns
    assert samples[-1][1] > samples[0][1]  # the thread's own CPU seconds
    mine = stats["threads"]["lah-runtime"]
    assert 0 < mine["cpu_share"] <= 1.05 and mine["busy_share"] is None
    assert mine["extent_s"] <= stats["stages"]["runtime.idle"]["extent_s"]


def test_runtime_stats_name_the_loop_and_the_runtime_thread():
    """``stats()["threads"]`` is read over the extent of ``"stages"`` and
    names the server's two busy threads; a loop keeps its sums."""
    import time

    from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
    from learning_at_home_tpu.server.server import background_server
    from learning_at_home_tpu.utils.profiling import timeline

    try:
        with background_server(
            num_experts=1, hidden_dim=16, expert_prefix="ffn", seed=0
        ) as (endpoint, srv):
            expert = RemoteExpert("ffn.0", endpoint, timeout=30.0)
            x = np.ones((4, 16), np.float32)
            expert.forward_blocking([x])  # compiles
            timeline.clear()  # the stages' extent is this server's alone
            begin = time.monotonic()
            while time.monotonic() - begin < 0.8:
                expert.forward_blocking([x])
            stats = srv.runtime.stats()
    finally:
        timeline.clear()
        reset_client_rpc()
    threads, stages = stats["threads"], stats["stages"]
    assert {"lah-server", "lah-runtime"} <= set(threads)
    extent = stages["server.request"]["extent_s"]
    for name in ("lah-server", "lah-runtime"):
        assert set(threads[name]) == {
            "busy_share", "cpu_share", "turns_per_s", "turn_ms_mean",
            "process_cpu_cores", "extent_s"}
        assert extent - 0.5 - 1e-3 <= threads[name]["extent_s"] <= extent
    loop = threads["lah-server"]
    assert 0 < loop["busy_share"] <= 1 and loop["turns_per_s"] > 0
    assert loop["turn_ms_mean"] == pytest.approx(
        1e3 * loop["busy_share"] / loop["turns_per_s"], rel=1e-2)
    assert threads["lah-runtime"]["busy_share"] is None
    import msgpack

    msgpack.packb(threads, use_bin_type=True)  # the stats reply's wire
