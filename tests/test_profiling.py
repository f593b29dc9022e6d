"""Profiling spans: disabled by default, capture RPC/dispatch timings when on."""

import numpy as np
import pytest

from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
from learning_at_home_tpu.server.server import background_server
from learning_at_home_tpu.utils.profiling import Timeline, timeline


def test_timeline_env_default(monkeypatch):
    monkeypatch.delenv("LAH_PROFILE", raising=False)
    assert not Timeline().enabled
    monkeypatch.setenv("LAH_PROFILE", "1")
    assert Timeline().enabled
    monkeypatch.setenv("LAH_PROFILE", "0")
    assert not Timeline().enabled


def test_timeline_basic():
    tl = Timeline()
    tl.enable()
    with tl.span("work"):
        pass
    tl.record("manual", 0.0, 0.25)
    summary = tl.summary()
    assert "work" in summary and "manual" in summary
    assert summary["manual"]["p50_ms"] == 250.0
    tl.clear()
    assert tl.summary() == {}


def test_spans_capture_rpc_path():
    timeline.enable()
    timeline.clear()
    try:
        with background_server(num_experts=1, hidden_dim=16, seed=0) as (ep, srv):
            expert = RemoteExpert("expert.0", ep)
            x = np.zeros((2, 16), np.float32)
            expert.forward_blocking([x])
            expert.forward_blocking([x])
        summary = timeline.summary()
        assert summary["rpc.forward"]["count"] == 2
        for stage in ("stack", "dispatch", "materialize"):
            assert summary[f"runtime.{stage}"]["count"] == 2
        assert {s[5]["pool"] for s in timeline.spans("runtime.dispatch")} == {
            "expert.0.forward"
        }
    finally:
        timeline.disable()
        timeline.clear()
        reset_client_rpc()


def test_disabled_timeline_records_nothing():
    tl = Timeline()
    tl.disable()
    with tl.span("x"):
        pass
    tl.record("y", 0, 1)
    assert tl.summary() == {} and tl.spans() == []


def test_device_trace_captures(tmp_path):
    import os

    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.utils.profiling import device_trace

    with device_trace(str(tmp_path / "trace")):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    # a jax.profiler trace directory with at least one artifact appeared
    found = [
        os.path.join(root, f)
        for root, _, files in os.walk(tmp_path / "trace")
        for f in files
    ]
    assert found, "device_trace produced no trace artifacts"


# ---- a thread's clock (ISSUE 68): CPU seconds beside wall seconds --------


class _FakeClocks:
    """``thread_time`` and ``process_time`` as numbers the test sets."""

    def __init__(self):
        self.thread = self.process = 0.0

    def register(self, tl: Timeline, name: str):
        return tl.register_thread(
            name, thread_time=lambda: self.thread,
            process_time=lambda: self.process,
        )


def _synthetic_history(tl: Timeline, name: str = "loop"):
    """A sample every 0.5 s from t=100 to t=110: the thread is on a CPU
    30 % of the time, busy 60 % (2 ms a turn, 300 turns a second), and the
    process takes 1.5 cores."""
    clocks = _FakeClocks()
    clock = clocks.register(tl, name)
    for i in range(21):
        dt = 0.5 * i
        clocks.thread, clocks.process = 7.0 + 0.3 * dt, 50.0 + 1.5 * dt
        clock.tick(100.0 + dt, busy_s=3.0 + 0.6 * dt, turns=10 + int(300 * dt))
    return clock


@pytest.mark.parametrize("ticks, kept", [
    ((0.0, 0.1, 0.2, 0.2499), (0.0,)),
    ((0.0, 0.25), (0.0, 0.25)),
    ((0.0, 0.1, 0.3, 0.5, 0.54, 0.55, 0.7), (0.0, 0.3, 0.55)),
    ((5.0, 4.0, 5.2, 9.0, 9.2), (5.0, 9.0)),  # a reading from before: none
])
def test_thread_clock_keeps_no_sample_sooner_than_a_quarter_second(ticks, kept):
    tl = Timeline()
    clock = tl.register_thread("loop")
    for t in ticks:
        clock.tick(t)
    assert tuple(s[0] for s in clock.samples) == kept


@pytest.mark.parametrize("begin, end, extent", [
    (0.0, 1e9, 10.0),       # everything
    (100.0, 110.0, 10.0),   # the ends are inside
    (100.1, 109.9, 9.0),    # first sample at or after, last at or before
    (104.0, 104.5, 0.5),    # exactly two samples
])
def test_thread_stats_are_the_exact_shares_of_a_history(begin, end, extent):
    tl = Timeline()
    _synthetic_history(tl)
    stats = tl.thread_stats(begin, end)
    assert set(stats) == {"loop"}
    assert stats["loop"] == pytest.approx({
        "busy_share": 0.6, "cpu_share": 0.3, "turns_per_s": 300.0,
        "turn_ms_mean": 2.0, "process_cpu_cores": 1.5, "extent_s": extent,
    })


@pytest.mark.parametrize("begin, end", [
    (104.1, 104.9),   # one sample inside
    (104.1, 104.4),   # none
    (111.0, 120.0),   # after the last
    (110.0, 100.0),   # no extent at all
])
def test_thread_stats_say_nothing_from_fewer_than_two_samples(begin, end):
    tl = Timeline()
    _synthetic_history(tl)
    assert tl.thread_stats(begin, end) == {}


def test_a_thread_that_keeps_no_sums_has_cpu_shares_alone():
    """The Runtime's thread: its spans say when it was busy."""
    tl = Timeline()
    clocks = _FakeClocks()
    clock = clocks.register(tl, "runtime")
    for i in range(5):
        clocks.thread, clocks.process = 0.2 * i, 0.9 * i
        clock.tick(float(i))
    assert tl.thread_stats(0.0, 4.0)["runtime"] == {
        "busy_share": None, "cpu_share": 0.2, "turns_per_s": None,
        "turn_ms_mean": None, "process_cpu_cores": 0.9, "extent_s": 4.0,
    }


def test_cpu_a_thread_burned_inside_its_own_waits_is_left_out():
    """A loop reads the CPU seconds of its blocking selects itself (the
    kernel's sleep and wake-up) and its clock keeps the rest: the CPU of
    ``loop.run``, which the wall's ``busy_s`` bounds."""
    tl = Timeline()
    clocks = _FakeClocks()
    clock = clocks.register(tl, "loop")
    for i in range(5):  # on a CPU 0.5 s a second, 0.2 of it inside waits
        clocks.thread = 0.5 * i
        clock.tick(float(i), busy_s=0.4 * i, turns=100 * i,
                   waited_cpu_s=0.2 * i)
    stats = tl.thread_stats(0.0, 4.0)["loop"]
    assert stats["cpu_share"] == pytest.approx(0.3)
    assert stats["busy_share"] == pytest.approx(0.4)


def test_two_threads_of_one_name_do_not_mix_their_samples():
    """Tests start many servers in a process: each thread keeps its own
    clock, and the name reads the one registered last."""
    tl = Timeline()
    old = _synthetic_history(tl)
    clocks = _FakeClocks()
    new = clocks.register(tl, "loop")
    for i in range(9):  # on a CPU all the time, over the same seconds
        clocks.thread = clocks.process = float(i)
        new.tick(101.0 + i, busy_s=float(i), turns=i)
        old.tick(120.0 + i, busy_s=99.0, turns=999_999)  # still alive
    assert len(old.samples) == 30 and len(new.samples) == 9
    assert {s[1] for s in new.samples} == {float(i) for i in range(9)}
    stats = tl.thread_stats(100.0, 110.0)["loop"]
    assert (stats["cpu_share"], stats["busy_share"]) == (1.0, 1.0)
    assert stats["extent_s"] == 8.0


def test_a_tick_from_another_thread_is_dropped():
    """``time.thread_time`` is the caller's: a foreign tick would file
    another thread's CPU seconds under this one's name."""
    import threading

    tl = Timeline()
    clock = tl.register_thread("mine")
    clock.tick(1.0)
    foreign = threading.Thread(target=clock.tick, args=(2.0,))
    foreign.start()
    foreign.join(timeout=10)
    assert not foreign.is_alive()
    clock.tick(3.0)
    assert [s[0] for s in clock.samples] == [1.0, 3.0]
    made_elsewhere = []
    other = threading.Thread(
        target=lambda: made_elsewhere.append(tl.register_thread("other")))
    other.start()
    other.join(timeout=10)
    made_elsewhere[0].tick(1.0)  # from here: not its thread
    assert not made_elsewhere[0].samples


def test_clear_empties_a_threads_history_and_keeps_its_clock():
    """A live thread registered once: after ``clear()`` it must still be
    read under its name."""
    tl = Timeline()
    clock = _synthetic_history(tl)
    tl.clear()
    assert tl.thread_stats(0.0, 1e9) == {} and not clock.samples
    clock.tick(200.0)
    clock.tick(201.0)
    assert tl.thread_stats(0.0, 1e9)["loop"]["extent_s"] == 1.0


def test_thread_histories_and_names_are_bounded():
    from learning_at_home_tpu.utils.profiling import THREAD_HISTORY_LEN

    tl = Timeline(max_counter_keys=4)
    clock = tl.register_thread("loop")
    for i in range(THREAD_HISTORY_LEN + 10):
        clock.tick(float(i))
    assert len(clock.samples) == THREAD_HISTORY_LEN
    for i in range(10):
        tl.register_thread(f"leak.{i}").tick(0.0)
    assert len(tl._threads) == 4 and "loop" in tl._threads
    tl.register_thread("loop")  # a known name is always replaced
    assert not tl._threads["loop"].samples
