"""The life of a swarm request on the program's own clock (ISSUE 25):
always-on stage reservoirs, profiler annotations on the device trace's
clock, the benchmark's stage reducer, and the pod step's scope names.
Since ISSUE 35 the life has no hole: the socket's two sides, the gap
between a client's requests, the hand-off back to the loop, and every
stage by kind (``<name>:forward`` / ``<name>:backward``).

No chip: every time here is a host time of a CPU run and is compared only
with other times of the same run.
"""

import importlib.util
import os
import sys
import time
import types

import numpy as np
import pytest

from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.server import connection_handler
from learning_at_home_tpu.server.server import background_server
from learning_at_home_tpu.utils import profiling
from learning_at_home_tpu.utils.profiling import (
    KINDS,
    RESERVOIR_LEN,
    Timeline,
    timeline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HID = 16
REQUESTS = 200

# table B of the issue: every stage of a request's stay in the server
SERVER_STAGES = (
    "server.decode", "server.request", "pool.wait", "runtime.queue",
    "runtime.stack", "runtime.dispatch", "runtime.materialize",
    "runtime.deliver", "runtime.idle", "server.encode",
)
# the stages a request passes through one after another inside
# ``server.request`` (decode and encode aside)
SERIAL_STAGES = (
    "pool.wait", "runtime.queue", "runtime.stack", "runtime.dispatch",
    "runtime.materialize", "runtime.deliver",
)
# the runtime thread's own time: it is in exactly one of these, each
# starting at the reading the one before ended at
RUNTIME_THREAD_STAGES = (
    "runtime.idle", "runtime.stack", "runtime.dispatch",
    "runtime.materialize", "runtime.handoff",
)
# ISSUE 35: the socket's two sides, the gap between a client's requests,
# the coroutine's resumption, the runtime thread's hand-off
NEW_STAGES = (
    "server.read", "server.write", "server.conn.idle", "server.resume",
    "runtime.handoff",
)
# every stage but the runtime thread's idle wait is also filed by kind
KINDED_STAGES = tuple(
    n for n in SERVER_STAGES + NEW_STAGES if n != "runtime.idle"
)
STAGE_PREFIXES = ("server.", "pool.", "runtime.")


def _load(relative_path: str):
    path = os.path.join(REPO, relative_path)
    name = "stage_spans_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def served():
    """200 forward requests against a loopback server, profiling OFF;
    yields what was seen while they ran."""
    assert not timeline.enabled
    metas = []
    real_unpack = connection_handler.unpack_message

    def spying_unpack(payload):
        out = real_unpack(payload)
        metas.append(out[2])
        return out

    connection_handler.unpack_message = spying_unpack
    try:
        with background_server(
            num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=0
        ) as (endpoint, srv):
            experts = [RemoteExpert(f"ffn.{i}", endpoint, timeout=30.0)
                       for i in range(2)]
            x = np.ones((4, HID), np.float32)
            for e in experts:  # compile outside what is counted
                e.forward_blocking([x])
            timeline.clear()
            metas.clear()
            for i in range(REQUESTS):
                experts[i % 2].forward_blocking([x])
            for e in experts:  # the control plane, among the data plane's
                e.info()
            seen = {
                "recent": {n: timeline.recent(n)
                           for n in SERVER_STAGES + NEW_STAGES},
                "stats": timeline.stage_stats(STAGE_PREFIXES),
                "runtime_stats": srv.runtime.stats(),
                "full_spans": timeline.spans(),
                "metas": list(metas),
            }
        yield seen
    finally:
        connection_handler.unpack_message = real_unpack
        timeline.clear()
        reset_client_rpc()


@pytest.mark.parametrize("stage", SERVER_STAGES + NEW_STAGES)
def test_every_stage_has_a_reservoir_with_profiling_off(served, stage):
    spans = served["recent"][stage]
    assert len(spans) >= 100, f"{stage}: {len(spans)} spans of {REQUESTS}"
    assert all(d >= 0 for _, d in spans)
    stats = served["stats"][stage]
    assert stats["count"] == len(spans)
    assert 0 <= stats["p50_ms"] <= stats["p95_ms"]


def test_control_plane_requests_stay_out_of_the_reservoirs(served):
    """The two ``info`` requests were served and decoded, and neither is
    a sample of ``server.request`` or ``server.decode``."""
    assert len(served["metas"]) == REQUESTS + 2
    assert len(served["recent"]["server.request"]) == REQUESTS
    assert len(served["recent"]["server.decode"]) == REQUESTS


def test_profiling_off_keeps_no_full_record_and_sends_no_trace_id(served):
    assert served["full_spans"] == []
    assert len(served["metas"]) >= REQUESTS
    assert not [m for m in served["metas"] if "trace" in m]


def test_stages_of_the_median_request_fit_inside_server_request(served):
    """pool.wait + runtime.queue + stack + dispatch + materialize +
    deliver <= server.request, for the median request: a sum of medians
    against a median, so within a tenth and 0.2 ms."""
    medians = {
        n: 1e3 * float(np.median([d for _, d in served["recent"][n]]))
        for n in SERIAL_STAGES + ("server.request",)
    }
    inner = sum(medians[n] for n in SERIAL_STAGES)
    assert inner <= 1.1 * medians["server.request"] + 0.2, medians
    # and they are most of it: nothing large is left untimed
    assert inner >= 0.5 * medians["server.request"], medians


def test_runtime_thread_shares_sum_to_its_time(served):
    """idle + stack + dispatch + materialize + handoff is the runtime
    thread's own time (``runtime.queue`` and ``runtime.deliver`` are waits
    of others), each stage starting at the reading the one before ended
    at: the shares of their reservoirs' extents sum to one."""
    shares = {n: served["stats"][n]["share"] for n in RUNTIME_THREAD_STAGES}
    assert 0.95 <= sum(shares.values()) <= 1.02, shares


def test_runtime_thread_stages_are_contiguous(served):
    """By construction, not by luck: put in order, every stage of the
    runtime thread starts at the very reading its predecessor ended at.
    ``runtime.stack`` alone reads the clock for itself (it times
    ``BatchJob.stack`` and nothing else, as it did before the chain): what
    precedes it is a few lines of the thread's loop."""
    chain = sorted(
        (start, start + duration, name)
        for name in RUNTIME_THREAD_STAGES
        for start, duration in served["recent"][name]
    )
    assert len(chain) >= 4 * REQUESTS  # the thread is not idle before each
    before_stack, elsewhere = [], []
    for (_, a_end, _), (b_start, _, b_name) in zip(chain, chain[1:]):
        (before_stack if b_name == "runtime.stack" else elsewhere).append(
            b_start - a_end)
    assert max(abs(h) for h in elsewhere) < 1e-9, max(elsewhere)
    assert min(before_stack) >= 0
    assert float(np.median(before_stack)) < 200e-6, np.median(before_stack)
    # and a batch's wait in the queue ends at the reading its stacking
    # starts at
    stacks = {start for start, _ in served["recent"]["runtime.stack"]}
    queue_ends = [s + d for s, d in served["recent"]["runtime.queue"]]
    assert all(min(abs(e - s) for s in stacks) < 1e-9 for e in queue_ends)


def test_a_connections_time_is_read_request_write_or_idle(served):
    """One client, one request at a time: from the first frame's length
    prefix to the last reply's write, the connection is being read,
    serving, being written or waiting for the client.  What the four
    leave out is the step from a complete frame to the handler (the
    header's peek, the muxed request's task): within 5 % and 1 ms a
    request."""
    recent = served["recent"]
    begin = min(s for s, _ in recent["server.read"])
    end = max(s + d for s, d in recent["server.write"])
    named = sum(
        d for name in ("server.read", "server.request", "server.write",
                       "server.conn.idle")
        for s, d in recent[name] if begin <= s and s + d <= end + 1e-9
    )
    wall = end - begin
    assert named <= wall * (1 + 1e-6)
    assert wall - named <= 0.05 * wall, (named, wall)
    assert (wall - named) / REQUESTS <= 1e-3


def test_every_gap_between_two_requests_is_an_idle_span(served):
    """One ``server.conn.idle`` a gap: before each of the 200 requests
    (the first follows the warm-up's reply) and before the first ``info``;
    none after an ``info`` reply, which has no kind."""
    idle = served["recent"]["server.conn.idle"]
    assert len(idle) == REQUESTS + 1
    reads = sorted(s for s, _ in served["recent"]["server.read"])
    ends = sorted(s + d for s, d in idle)[:REQUESTS]
    # each gap ends at the reading the next frame's read starts at
    assert max(abs(e - r) for e, r in zip(ends, reads)) < 1e-9


def test_the_wait_that_ends_in_eof_is_no_idle_span():
    """A client that answers nothing for 0.3 s and then leaves: the wait
    stays out, so the extent ``stage_stats`` reads ends with the last
    request, where it ended before the connection's stages existed."""
    timeline.clear()
    try:
        with background_server(
            num_experts=1, hidden_dim=HID, expert_prefix="ffn", seed=0
        ) as (endpoint, _srv):
            expert = RemoteExpert("ffn.0", endpoint, timeout=30.0)
            x = np.ones((4, HID), np.float32)
            began = time.monotonic()
            for _ in range(3):
                expert.forward_blocking([x])
            served_s = time.monotonic() - began
            time.sleep(0.3)
            reset_client_rpc()  # the pools close: EOF at the server
            time.sleep(0.2)
            idle = timeline.recent("server.conn.idle")
            stats = timeline.stage_stats(STAGE_PREFIXES)
            last = max(s + d for s, d in timeline.recent("server.write"))
            ends = [s + d for n in SERVER_STAGES + NEW_STAGES
                    for s, d in timeline.recent(n)]
    finally:
        timeline.clear()
        reset_client_rpc()
    assert len(idle) == 2 and max(d for _, d in idle) < 0.25, idle
    # nothing ends long after the last reply's write (the runtime thread's
    # hand-off of that batch may: it waits for the loop that is writing)
    assert 0 <= max(ends) - last < 0.1
    # the extent is the three requests', without the 0.5 s that followed
    assert stats["server.request"]["extent_s"] <= served_s + 0.01


def test_resume_is_inside_the_request(served):
    recent = served["recent"]
    resume = float(np.median([d for _, d in recent["server.resume"]]))
    request = float(np.median([d for _, d in recent["server.request"]]))
    assert 0 <= resume <= request
    requests = sorted(recent["server.request"])
    for (start, duration), (r_start, r_duration) in zip(
        sorted(recent["server.resume"]), requests
    ):  # one part a request here: the n-th resume lies in the n-th request
        assert r_start <= start
        assert start + duration <= r_start + r_duration + 1e-9


def test_stats_rpc_carries_the_stages(served):
    import msgpack

    stages = served["runtime_stats"]["stages"]
    assert set(SERVER_STAGES) <= set(stages)
    assert set(stages["runtime.stack"]) == {
        "count", "p50_ms", "p95_ms", "share", "extent_s"
    }
    msgpack.packb(stages, use_bin_type=True)  # the stats reply's wire


def test_span_names_in_the_server_carry_no_data(served):
    """The names are the stages' closed set; the only suffix a reservoir's
    key takes is one of the two kinds."""
    stages = set(SERVER_STAGES + NEW_STAGES)
    for key in served["stats"]:
        name, _, kind = key.partition(":")
        assert name in stages, key
        assert kind in ("",) + KINDS, key


DISPATCHES = 40  # of the mixture: one forward and one backward ``multi``
SINGLES = 35  # of each kind, by a ``RemoteExpert``: above the reducer's floor
CLIENT_STAGES = (
    "client.dispatch.fire", "client.dispatch.join", "client.pack",
    "rpc.multi", "rpc.send", "rpc.decode",
)


@pytest.fixture(scope="module")
def served_by_kind():
    """Both kinds of request in both forms, profiling OFF: 40 jitted
    forward+grad dispatches of a mixture over two experts (a forward and a
    backward ``multi`` of two parts each) and 35 plain ``forward`` and
    ``backward`` requests; yields every reservoir and the mixture's
    ``dispatch_stats()``."""
    import jax

    assert not timeline.enabled
    timeline.clear()
    try:
        with background_server(
            num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=0
        ) as (endpoint, srv):
            moe = RemoteMixtureOfExperts(
                in_features=HID, grid_size=(2,), uid_prefix="ffn", k_best=2,
                k_min=1,
                source=StaticExpertSource({u: endpoint for u in srv.experts}),
            )
            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            x = np.random.RandomState(0).randn(4, HID).astype(np.float32)
            grad = jax.jit(jax.grad(lambda g, x: jax.numpy.sum(moe(x, g) ** 2)))
            expert = RemoteExpert("ffn.0", endpoint, timeout=30.0)
            jax.block_until_ready(grad(gate, x))  # compiles, both sides
            expert.forward_blocking([x])
            expert.backward_blocking([x], [x])
            timeline.clear()
            for _ in range(DISPATCHES):
                jax.block_until_ready(grad(gate, x))
            for _ in range(SINGLES):
                expert.forward_blocking([x])
                expert.backward_blocking([x], [x])
            # a reply reaches its client before the server's loop closes the
            # ``server.write`` span around the send: the last request's may
            # still be open.  Wait for the COUNT (what the tests compare)
            wanted = 2 * (DISPATCHES + SINGLES)
            for _ in range(2000):
                if len(timeline.recent("server.write")) >= wanted:
                    break
                time.sleep(0.005)
            seen = {
                "recent": {key: timeline.recent(key)
                           for key in timeline.stage_stats(window_s=1e9)},
                "dispatch_stats": moe.dispatch_stats(),
                "server_stats": srv.runtime.stats()["stages"],
            }
        yield seen
    finally:
        timeline.clear()
        reset_client_rpc()


@pytest.mark.parametrize("stage", KINDED_STAGES + CLIENT_STAGES)
def test_a_stage_is_also_filed_by_kind_with_profiling_off(
    served_by_kind, stage
):
    """``<stage>:forward`` and ``<stage>:backward`` exist, share no span,
    and together are the stage's own reservoir: each holds only its kind."""
    recent = served_by_kind["recent"]
    forward, backward = (recent.get(f"{stage}:{k}", []) for k in KINDS)
    assert forward and backward, sorted(recent)
    assert not set(forward) & set(backward)
    assert sorted(forward + backward) == sorted(recent[stage])


@pytest.mark.parametrize("stage, forward, backward", [
    # a request: a ``multi`` a dispatch and kind, and the plain ones
    ("server.request", DISPATCHES + SINGLES, DISPATCHES + SINGLES),
    ("server.read", DISPATCHES + SINGLES, DISPATCHES + SINGLES),
    ("server.write", DISPATCHES + SINGLES, DISPATCHES + SINGLES),
    # a task: two parts a ``multi``
    ("pool.wait", 2 * DISPATCHES + SINGLES, 2 * DISPATCHES + SINGLES),
    ("server.resume", 2 * DISPATCHES + SINGLES, 2 * DISPATCHES + SINGLES),
    ("rpc.multi", DISPATCHES, DISPATCHES),
    ("rpc.forward", SINGLES, 0),
    ("rpc.backward", 0, SINGLES),
    ("client.dispatch.fire", DISPATCHES, DISPATCHES),
    ("client.dispatch.join", DISPATCHES, DISPATCHES),
    ("client.pack", DISPATCHES, DISPATCHES),
    ("rpc.send", DISPATCHES + SINGLES, DISPATCHES + SINGLES),
    ("rpc.decode", DISPATCHES + SINGLES, DISPATCHES + SINGLES),
    # the thread's idle wait has no kind, and the control plane no span
    ("runtime.idle", 0, 0),
    ("rpc.hello", 0, 0),
])
def test_kinds_hold_what_was_sent(served_by_kind, stage, forward, backward):
    recent = served_by_kind["recent"]
    counts = [len(recent.get(f"{stage}:{k}", [])) for k in KINDS]
    assert counts == [forward, backward]


def test_a_backward_request_is_not_a_forward_one(served_by_kind):
    """Why the kinds exist: a backward stays longer in the server than a
    forward, and the two-kind median describes neither."""
    stages = served_by_kind["server_stats"]
    assert {"server.request:forward", "server.request:backward",
            "runtime.queue:backward", "server.conn.idle:forward",
            "server.conn.idle:backward"} <= set(stages)
    assert stages["server.request:forward"]["count"] == DISPATCHES + SINGLES
    assert (stages["server.request"]["count"]
            == 2 * stages["server.request:forward"]["count"])


def test_dispatch_stats_carry_the_clients_stages_by_kind(served_by_kind):
    """``dispatch_stats()["stages"]``: fire, join, pack and the two
    halves of an exchange, by kind, over one extent; the halves fit
    inside their exchange.  The mixture's own two medians stay."""
    import msgpack

    stats = served_by_kind["dispatch_stats"]
    stages = stats["stages"]
    for stage in CLIENT_STAGES:
        for key in (stage, f"{stage}:forward", f"{stage}:backward"):
            assert stages[key]["count"] >= DISPATCHES, key
    assert len({s["extent_s"] for s in stages.values()}) == 1
    for kind in KINDS:
        halves = (stages[f"rpc.send:{kind}"]["p50_ms"]
                  + stages[f"rpc.decode:{kind}"]["p50_ms"])
        assert halves <= stages[f"rpc.multi:{kind}"]["p50_ms"]
    # every ``multi`` exchange holds its own two halves, to the reading
    recent = served_by_kind["recent"]
    for outer_start, outer_s in recent["rpc.multi"]:
        inside = [
            d for name in ("rpc.send", "rpc.decode")
            for s, d in recent[name]
            if outer_start <= s and s + d <= outer_start + outer_s + 1e-9
        ]
        assert len(inside) == 2 and sum(inside) <= outer_s
    assert stats["pack_p50_ms"] > 0 and stats["wait_p50_ms"] > 0
    json_safe = msgpack.packb(stages, use_bin_type=True)  # /metrics.json
    assert json_safe


@pytest.mark.parametrize("kind, keys", [
    ("forward", {"stage", "stage:forward"}),
    ("backward", {"stage", "stage:backward"}),
    ("multi", {"stage"}),  # not one of the two: a closed set
    ("", {"stage"}),
    (None, {"stage"}),
    (["forward"], {"stage"}),  # peer-supplied meta can be anything
    (7, {"stage"}),
])
def test_only_the_two_kinds_make_a_key(kind, keys):
    tl = Timeline()
    with tl.span("stage", kind=kind):
        pass
    tl.record("stage", 1.0, 0.5, kind=kind)
    with tl.span("stage") as late:  # known only inside, as a request's is
        late.attrs["kind"] = kind
    assert set(tl.stage_stats(window_s=float("inf"))) == keys
    for key in keys:
        assert len(tl.recent(key)) == 3
    with tl.span("stage", kind=kind) as aside:
        aside.exclude()  # out of the stage's reservoir: out of its kind's too
    assert [len(tl.recent(key)) for key in sorted(keys)] == [3] * len(keys)


def test_a_kind_is_an_attribute_in_the_full_record_and_no_name():
    tl = Timeline()
    tl.enable()
    with tl.span("stage", "ab" * 8, kind="backward", pool="p.0"):
        pass
    (name, _, _, trace, _, attrs) = tl.spans()[0]
    assert (name, trace) == ("stage", "ab" * 8)
    assert attrs == {"kind": "backward", "pool": "p.0"}
    assert [e["name"] for e in tl.chrome_trace()[1:]] == ["stage"]
    assert set(tl.summary()) == {"stage"}


def test_a_span_can_start_where_the_last_one_ended():
    tl = Timeline()
    with tl.span("first") as first:
        pass
    with tl.span("second", start=first.end) as second:
        pass
    (start, duration), = tl.recent("second")
    assert start == first.end and second.end == start + duration
    assert tl.recent("first")[0][0] + first.duration == pytest.approx(
        first.end, abs=1e-12)


def test_the_ten_stages_read_the_same_with_and_without_the_new_names(served):
    """``stage_stats`` on a recorded set of reservoirs: PR 25's ten stages
    alone, then with the five new stages and every key by kind beside
    them.  Counts, medians and seconds of running time are the same; the
    extent runs from the first request's read to the last reply's write
    (and the client's gaps around them), not from the first handler entry
    to the last frame built, and grows by exactly that."""
    old, new = Timeline(), Timeline()
    for name in SERVER_STAGES + NEW_STAGES:
        for start, duration in served["recent"][name]:
            if name in SERVER_STAGES:
                old.record(name, start, duration)
            kind = None if name == "runtime.idle" else "forward"
            new.record(name, start, duration, kind=kind)
    before = old.stage_stats(STAGE_PREFIXES)
    after = new.stage_stats(STAGE_PREFIXES)
    assert set(before) == set(SERVER_STAGES)
    assert len(after) == 2 * len(SERVER_STAGES + NEW_STAGES) - 1
    # the first request's read and the client's gap before it begin
    # before the first of the ten stages' spans, and the last reply's write
    # and the gap after it reach beyond the last: all the extent grows by
    recent = served["recent"]
    slack = (
        min(s for n in SERVER_STAGES for s, _ in recent[n])
        - min(s for n in NEW_STAGES for s, _ in recent[n])
        + max(s + d for n in NEW_STAGES for s, d in recent[n])
        - max(s + d for n in SERVER_STAGES for s, d in recent[n])
    )
    assert 0 <= slack < 0.1
    for name in SERVER_STAGES:
        for key in ("count", "p50_ms", "p95_ms"):
            assert after[name][key] == before[name][key], (name, key)
        was, now = before[name], after[name]
        assert now["extent_s"] - was["extent_s"] == pytest.approx(
            slack, abs=2e-4)
        # the same seconds of running time, over an extent that much longer
        assert now["share"] * now["extent_s"] == pytest.approx(
            was["share"] * was["extent_s"], rel=1e-3, abs=1e-5)
        if name != "runtime.idle":
            assert after[f"{name}:forward"] == now


def test_reservoirs_are_bounded_and_names_capped():
    tl = Timeline(max_counter_keys=4)
    for i in range(RESERVOIR_LEN + 500):
        tl.record("stage", float(i), 0.5)
    spans = tl.recent("stage")
    assert len(spans) == RESERVOIR_LEN
    assert spans[0][0] == 500.0 and spans[-1][0] == RESERVOIR_LEN + 499.0
    for i in range(20):  # names that embed data fold, as counter keys do
        with tl.span(f"leak.{i}"):
            pass
    # 4 names and the overflow ("stage" has made-up times: no time bound)
    assert len(tl.stage_stats(window_s=float("inf"))) == 5
    assert len(tl.recent("timeline.overflow")) == 17
    assert tl.recent("leak.19") == []
    tl.clear()
    assert tl.stage_stats() == {} and tl.recent("stage") == []


def test_stage_stats_share_and_quantiles():
    tl = Timeline()
    for i in range(10):  # ten 0.25 s spans, one a second
        tl.record("work", float(i), 0.25)
    stats = tl.stage_stats("work")["work"]
    assert stats["count"] == 10
    assert stats["p50_ms"] == 250.0 and stats["p95_ms"] == 250.0
    assert stats["extent_s"] == 9.25
    assert stats["share"] == pytest.approx(2.5 / 9.25, abs=1e-6)
    assert tl.stage_stats("other") == {}
    assert set(tl.stage_stats(("wo", "zz"))) == {"work"}


def test_stage_stats_reads_every_stage_over_one_extent():
    """A slow stage (one span every 10 s since a long start-up) and a
    fast one (a full reservoir) are read over the fast one's seconds:
    the slow stage's old spans are not in its median or its share."""
    tl = Timeline()
    for i in range(20):  # start-up: 20 slow requests of 5 s, back to back
        tl.record("slow", 5.0 * i, 5.0)
    t0 = 100.0
    for i in range(RESERVOIR_LEN + 1000):  # then 100 batches a second
        tl.record("fast", t0 + 0.01 * i, 0.005)
    for i in range(5):  # and a 0.2 s request every 10 s
        tl.record("slow", t0 + 10.0 * i + 0.75, 0.2)
    stats = tl.stage_stats(("slow", "fast"), window_s=60.0)
    end = t0 + 0.01 * (RESERVOIR_LEN + 999) + 0.005
    begin = t0 + 0.01 * 1000 + 0.005  # the full reservoir's first end
    assert stats["fast"]["count"] == RESERVOIR_LEN
    assert stats["fast"]["extent_s"] == stats["slow"]["extent_s"]
    assert stats["fast"]["extent_s"] == pytest.approx(end - begin, abs=1e-3)
    assert stats["fast"]["share"] == pytest.approx(0.5, abs=1e-3)
    # of the slow stage only the four that ended inside those 41 s
    assert stats["slow"]["count"] == 4 and stats["slow"]["p50_ms"] == 200.0
    assert stats["slow"]["share"] == pytest.approx(0.8 / (end - begin),
                                                   abs=1e-4)
    # the time bound alone: the last 15 s hold two slow spans
    assert tl.stage_stats("slow", window_s=15.0)["slow"]["count"] == 2
    # a stage that did not run in the extent: share 0, no median
    late = tl.stage_stats(("slow", "fast"), window_s=5.0)["slow"]
    assert late == {"count": 0, "p50_ms": None, "p95_ms": None,
                    "share": 0.0, "extent_s": 5.0}
    # the last seconds left out: the extent ends before them, and a span
    # that reaches over its end counts with its part inside
    cut = tl.stage_stats(("slow", "fast"), window_s=10.0, skip_tail_s=10.105)
    assert cut["slow"]["count"] == 1 and cut["fast"]["count"] == 1000
    # half of the slow span that ended inside, half of the one that began
    assert cut["slow"]["share"] == pytest.approx(0.02, abs=1e-4)
    # a span that began before the extent counts from the extent's start
    tl.clear()
    tl.record("idle", 0.0, 10.0)
    tl.record("work", 10.0, 1.0)
    stats = tl.stage_stats("", window_s=2.0)
    assert stats["idle"]["share"] == pytest.approx(0.5)
    assert stats["idle"]["p50_ms"] == 10_000.0
    assert stats["work"]["share"] == pytest.approx(0.5)


def test_span_attributes_trace_and_exclude():
    tl = Timeline()
    tl.enable()
    with tl.span("stage", pool="p.0", rows=3) as span:
        span.trace = "ab" * 8  # known only inside, like a request's
        span.attrs["type"] = "multi"
    assert span.duration >= 0
    (name, _, duration, trace, _, attrs) = tl.spans()[0]
    assert (name, trace) == ("stage", "ab" * 8)
    assert attrs == {"pool": "p.0", "rows": 3, "type": "multi"}
    assert duration == span.duration
    event = tl.chrome_trace()[1]
    assert event["args"] == {"pool": "p.0", "rows": 3, "type": "multi",
                             "trace": "ab" * 8}
    with tl.span("stage", type="stats") as aside:
        aside.exclude()  # out of the reservoir, still in the full record
    assert len(tl.recent("stage")) == 1
    assert [s[5].get("type") for s in tl.spans()] == ["multi", "stats"]


ANNOTATED = ("server.decode", "server.request", "server.encode",
             "server.write") + RUNTIME_THREAD_STAGES
RECORDED = ("pool.wait", "runtime.queue", "runtime.deliver",
            "server.read", "server.conn.idle", "server.resume")


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Five forward requests under a CPU profiler session: the benchmark's
    trace reader, and the path of the ``.xplane.pb`` it would read."""
    import jax

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import trace_reduce
    finally:
        sys.path.remove(os.path.join(REPO, "benchmarks"))
    trace_dir = str(tmp_path_factory.mktemp("profiled"))
    try:
        with background_server(
            num_experts=1, hidden_dim=HID, expert_prefix="ffn", seed=0
        ) as (endpoint, _srv):
            expert = RemoteExpert("ffn.0", endpoint, timeout=30.0)
            x = np.ones((4, HID), np.float32)
            expert.forward_blocking([x])
            with jax.profiler.trace(trace_dir):
                for _ in range(5):
                    expert.forward_blocking([x])
    finally:
        timeline.clear()
        reset_client_rpc()
    return trace_reduce, trace_reduce.find_xplane(trace_dir)


def test_spans_are_profiler_annotations_on_the_device_traces_clock(profiled):
    """Under a profiler session the spans that enclose running code are
    in the ``.xplane.pb`` by name, read the way the benchmark reads host
    spans; the ones recorded afterwards from two readings are not."""
    trace_reduce, xplane = profiled
    annotated, recorded = ANNOTATED, RECORDED
    events = trace_reduce.load_events(
        xplane, host_spans=annotated + recorded,
    )
    seen = {name for name, _, _ in events["host"]}
    assert set(annotated) <= seen, sorted(seen)
    assert not seen & set(recorded)
    for name, start, end in events["host"]:
        assert end >= start
    # the runtime thread's five stages, read as the benchmark would read
    # them to name a device gap: from its first annotation to its last
    # they cover the thread's time (what is between two of them is the
    # loop's own few lines)
    thread = sorted(
        (start, end) for name, start, end in trace_reduce.load_events(
            xplane, host_spans=RUNTIME_THREAD_STAGES,
        )["host"]
    )
    assert len(thread) >= 5 * 4
    covered = sum(end - start for start, end in thread)
    span = thread[-1][1] - thread[0][0]
    assert covered <= span * (1 + 1e-9)
    assert covered >= 0.95 * span, (covered, span)


def test_loop_run_is_an_annotation_on_the_loops_own_thread(profiled):
    """ISSUE 68: in a profiler session every ``loop.run`` (a blocking
    select's return to the next one's entry) is in the trace on the loop's
    thread, read here as ``tools/loop_trace.py`` reads a cell's: the line
    that carries the handler's ``server.decode`` carries ``loop.run``, the
    runtime thread's line carries none, the benchmark's reader finds the
    name, and neither stage of the chain has a reservoir."""
    trace_reduce, xplane = profiled
    events = trace_reduce.load_events(
        xplane, host_spans=("loop.run", "loop.select", "server.decode"))
    seen = {name for name, _, _ in events["host"]}
    assert seen == {"loop.run", "server.decode"}  # loop.select: what is left
    turns = sorted((a, b) for n, a, b in events["host"] if n == "loop.run")
    assert all(b >= a for a, b in turns)
    report = _load("tools/loop_trace.py").host_lines(xplane)
    server = [l for l in report["lines"] if "server.decode" in l["names"]]
    runtime = [l for l in report["lines"]
               if set(l["names"]) & set(RUNTIME_THREAD_STAGES)]
    assert len(server) == 1 and len(runtime) == 1
    assert "loop.run" not in runtime[0]["names"]
    assert runtime[0]["loop_run"] is None
    mine = server[0]
    assert mine["names"]["server.decode"]["count"] == 5
    assert mine["names"]["loop.run"]["count"] >= 5
    # stretches of one thread never overlap: their union is their sum, and
    # every decode (a callback) runs inside them
    assert mine["loop_run_union_s"] == pytest.approx(
        mine["names"]["loop.run"]["seconds"], rel=1e-9)
    assert mine["loop_run_union_s"] > mine["names"]["server.decode"]["seconds"]
    assert 0 <= mine["loop_run"][0] < mine["loop_run"][1] <= (
        report["annotated_span_s"] + 1e-9)
    assert not timeline.recent("loop.run") and not timeline.recent("loop.select")


@pytest.mark.parametrize("key, scale, want", [
    ("p50_ms", 1.0, 250.0), ("share", 100.0, 100 * 7.5 / 14.75),
])
def test_stage_reducer_reads_the_loaded_module_or_nothing(
    monkeypatch, key, scale, want
):
    module = _load("benchmarks/reducers/stage_stat.py")
    args = {"name": "runtime.bench", "key": key, "scale": scale}
    floor = module.MIN_SPANS
    timeline.clear()
    try:
        # a 0.25 s span every half second; the last four end inside the
        # 2 s tail the reducer leaves out
        assert (floor, module.TAIL_S) == (30, 2.0)
        for i in range(floor + 3):
            timeline.record("runtime.bench", 0.5 * i, 0.25)
        assert module.reduce({}, **args) is None  # under the floor
        timeline.record("runtime.bench", 0.5 * (floor + 3), 0.25)
        assert module.reduce({}, **args) == pytest.approx(want)
        assert module.reduce({}, **{**args, "name": "runtime.absent"}) is None
        # inside the measured window: its 10.5 s less the tail hold 18 spans
        assert module.reduce({"intervals_s": [5.0, 5.5]}, **args) is None
        assert module.reduce({"intervals_s": [1.5]}, **args) is None
        # a stage that did not run there has a share, 0, when another
        # stage of the group shows the extent is a real one; and no median
        timeline.record("server.rare", 0.0, 1.0)
        rare = module.reduce(
            {"intervals_s": [5.0]}, **{**args, "name": "server.rare"})
        assert rare is None
        for i in range(floor):
            timeline.record("pool.busy", 11.75 + 0.1 * i, 0.05)
        rare = module.reduce(
            {"intervals_s": [5.0]}, **{**args, "name": "server.rare"})
        assert rare == (0.0 if key == "share" else None)
        # a program without stage_stats (this PR's parent): nothing to read
        monkeypatch.setitem(
            sys.modules, "learning_at_home_tpu.utils.profiling",
            types.SimpleNamespace(timeline=object()),
        )
        assert module.reduce({}, **args) is None
        # a cell that never loaded the module (a train cell)
        monkeypatch.delitem(
            sys.modules, "learning_at_home_tpu.utils.profiling"
        )
        assert module.reduce({}, **args) is None
        assert "learning_at_home_tpu.utils.profiling" not in sys.modules
    finally:
        timeline.clear()


def _stage_metric_specs() -> list:
    import glob
    import json

    specs = [json.load(open(p)) for p in sorted(glob.glob(
        os.path.join(REPO, "benchmarks/layer_metrics/server.*.json")))]
    return [s for s in specs if s["source"] == "program_span"]


def test_the_stage_metrics_are_the_ten_and_the_fourteen():
    specs = _stage_metric_specs()
    assert len(specs) == 24
    by_reducer = [s["reducer"] for s in specs]
    assert by_reducer.count("stage_stat") == 23
    assert by_reducer.count("stage_remainder") == 1
    keys = [s["args"].get("name") for s in specs if "name" in s["args"]]
    assert len(set(keys)) == len(keys)  # no stage is read twice


@pytest.mark.parametrize(
    "spec", _stage_metric_specs(), ids=lambda spec: spec["name"])
def test_every_stage_metric_names_the_one_reducer_and_a_server_stage(spec):
    """Each file reads a stage the program takes (by a kind, where it
    says so), through ``stage_stat``; the one remainder reads five of
    them through it too.  And its entry in the manifest says the same."""
    import json

    module = _load("benchmarks/reducers/stage_stat.py")
    stages = SERVER_STAGES + NEW_STAGES
    if spec["reducer"] == "stage_stat":
        name, _, kind = spec["args"]["name"].partition(":")
        assert name in stages
        assert kind in (("",) + KINDS if name != "runtime.idle" else ("",))
        assert name.startswith(module.SERVER_STAGES)
        assert spec["args"]["key"] in ("p50_ms", "share")
        assert f"span {name}:" in spec["text"]
    else:
        assert spec["reducer"] == "stage_remainder"
        assert tuple(spec["args"]["names"]) == RUNTIME_THREAD_STAGES
        assert spec["args"]["scale"] == 100.0 and spec["unit"] == "%"
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [e for e in manifest["per_layer"] if e["name"] == spec["name"]]
    for key, value in entry.items():
        assert spec[key] == value, key
    assert set(entry["workloads"]) <= {
        "ffnserver-infer-small", "ffnserver-train-bulk"}


def test_stage_remainder_is_what_the_stages_leave_or_nothing(monkeypatch):
    """100 x (1 - the five shares) over ``stage_stat``'s extent; ``None``
    wherever ``stage_stat`` has none: under the floor, a stage the
    program does not take (this PR's parent has no ``runtime.handoff``),
    a program without ``stage_stats``, a cell that never loaded it."""
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    module = _load("benchmarks/reducers/stage_remainder.py")
    stage_stat = _load("benchmarks/reducers/stage_stat.py")
    args = {"names": list(RUNTIME_THREAD_STAGES), "scale": 100.0}
    timeline.clear()
    try:
        # a batch every 0.1 s: idle 50 ms, then 10 ms a stage, 10 unnamed
        for i in range(stage_stat.MIN_SPANS + 25):
            t = 0.1 * i
            timeline.record("runtime.idle", t, 0.05)
            for j, name in enumerate(RUNTIME_THREAD_STAGES[1:4]):
                timeline.record(name, t + 0.05 + 0.01 * j, 0.01, kind="forward")
        assert module.reduce({}, **args) is None  # no handoff: the parent
        one = {"names": args["names"][:4], "scale": 100.0}
        assert module.reduce({}, **one) == pytest.approx(20.0, abs=0.7)  # edges
        for i in range(stage_stat.MIN_SPANS + 25):
            timeline.record("runtime.handoff", 0.1 * i + 0.08, 0.01,
                            kind="forward")
        assert module.reduce({}, **args) == pytest.approx(10.0, abs=0.7)
        shares = [stage_stat.reduce({}, n, "share") for n in args["names"]]
        assert module.reduce({}, **args) == pytest.approx(
            100.0 * (1.0 - sum(shares)))
        # inside a measured window too short for the floor
        assert stage_stat.reduce({"intervals_s": [4.0]}, "runtime.idle",
                                 "share") is None
        assert module.reduce({"intervals_s": [4.0]}, **args) is None
        monkeypatch.setitem(
            sys.modules, "learning_at_home_tpu.utils.profiling",
            types.SimpleNamespace(timeline=object()),
        )
        assert module.reduce({}, **args) is None
        monkeypatch.delitem(
            sys.modules, "learning_at_home_tpu.utils.profiling"
        )
        assert module.reduce({}, **args) is None
    finally:
        timeline.clear()


# ---- ISSUE 68: the threads' clocks beside the stages -------------------

THREAD_METRICS = {
    # entry: (thread, key, scale, unit)
    "server.loop_busy_share": ("lah-server", "busy_share", 100.0, "%"),
    "server.loop_cpu_share": ("lah-server", "cpu_share", 100.0, "%"),
    "server.runtime_cpu_share": ("lah-runtime", "cpu_share", 100.0, "%"),
    "server.process_cpu_cores": ("lah-server", "process_cpu_cores", 1.0,
                                 "cores"),
    "server.loop_turn_ms_mean": ("lah-server", "turn_ms_mean", 1.0, "ms"),
}


def _a_servers_half_minute(tl: Timeline) -> None:
    """Spans of a server's three prefixes from t=100 to t=130 (a batch
    every 10 ms, a request every 25), and both threads' clocks ticked
    every 50 ms of it: the loop busy 80 % and on a CPU 50 %, 0.4 ms a
    turn; the runtime thread on a CPU 30 %; the process 1.25 cores."""
    for i in range(3000):
        t = 100.0 + 0.01 * i
        tl.record("runtime.idle", t, 0.004)
        tl.record("runtime.dispatch", t + 0.004, 0.006, kind="forward")
    for i in range(1200):
        tl.record("server.request", 100.0 + 0.025 * i, 0.02, kind="forward")
        tl.record("pool.wait", 100.0 + 0.025 * i, 0.01, kind="forward")
    at = {"now": 0.0}
    loop = tl.register_thread(
        "lah-server", thread_time=lambda: 0.5 * at["now"],
        process_time=lambda: 1.25 * at["now"])
    runtime = tl.register_thread(
        "lah-runtime", thread_time=lambda: 0.3 * at["now"],
        process_time=lambda: 1.25 * at["now"])
    for i in range(601):
        at["now"] = now = 100.0 + 0.05 * i
        loop.tick(now, busy_s=0.8 * now, turns=int(2000 * now))
        runtime.tick(now)


def test_thread_samples_leave_the_stages_reading_as_it_was():
    """Thread samples are in no reservoir and under no span name: with
    them present ``stage_stats`` over the server's prefixes returns the
    dictionary it returned without, extent and all."""
    bare, ticked = Timeline(), Timeline()
    _a_servers_half_minute(bare)
    bare._threads.clear()  # the same spans, and no thread ever registered
    _a_servers_half_minute(ticked)
    assert len(ticked._threads["lah-server"].samples) == 121
    for kwargs in ({}, {"window_s": 20.0, "skip_tail_s": 2.0},
                   {"window_s": 3.0}):
        assert (ticked.stage_stats(STAGE_PREFIXES, **kwargs)
                == bare.stage_stats(STAGE_PREFIXES, **kwargs) != {})
    assert set(ticked._recent) == set(bare._recent) == {
        "runtime.idle", "runtime.dispatch", "server.request", "pool.wait"}
    assert not any(n.startswith(("loop.", "lah-")) for n in ticked._recent)


@pytest.mark.parametrize("kwargs, begin, end", [
    ({}, 100.0, 130.0),
    ({"window_s": 20.0, "skip_tail_s": 2.0}, 108.0, 128.0),
    ({"window_s": 3.0, "skip_tail_s": 0.5}, 126.5, 129.5),
    ({"window_s": 60.0, "skip_tail_s": 29.0}, 100.0, 101.0),
])
def test_one_extent_serves_the_stages_and_the_threads(kwargs, begin, end):
    """``stage_extent`` is the rule ``stage_stats`` reads by, as two
    numbers; ``thread_stats`` over it lies inside and at most a sample's
    spacing from each end, and ``stages_and_threads`` is both at once."""
    tl = Timeline()
    _a_servers_half_minute(tl)
    assert tl.stage_extent(STAGE_PREFIXES, **kwargs) == pytest.approx(
        (begin, end))
    stages = tl.stage_stats(STAGE_PREFIXES, **kwargs)
    assert {s["extent_s"] for s in stages.values()} == {round(end - begin, 4)}
    threads = tl.thread_stats(*tl.stage_extent(STAGE_PREFIXES, **kwargs))
    assert set(threads) == {"lah-server", "lah-runtime"}
    for stat in threads.values():
        assert end - begin - 2 * 0.25 <= stat["extent_s"] <= end - begin + 1e-9
        assert stat["process_cpu_cores"] == pytest.approx(1.25)
    assert threads["lah-server"] == pytest.approx({
        "busy_share": 0.8, "cpu_share": 0.5, "turns_per_s": 2000.0,
        "turn_ms_mean": 0.4, "process_cpu_cores": 1.25,
        "extent_s": threads["lah-server"]["extent_s"]}, abs=2e-3)
    assert threads["lah-runtime"]["cpu_share"] == pytest.approx(0.3)
    assert threads["lah-runtime"]["busy_share"] is None
    assert tl.stages_and_threads(STAGE_PREFIXES, **kwargs) == {
        "stages": stages, "threads": threads}
    assert tl.stage_extent("absent.") is None
    assert tl.stages_and_threads("absent.") == {"stages": {}, "threads": {}}


@pytest.mark.parametrize("name", sorted(THREAD_METRICS))
def test_a_thread_metric_reads_its_thread_or_nothing(monkeypatch, name):
    """Each of the five files has the keys ``selfcheck`` holds it to and
    says what its manifest entry says; its reducer reads the program's
    ``thread_stats`` over ``stage_stat``'s extent, and reads nothing under
    2 s between the samples, from a program without ``thread_stats`` (this
    PR's parent) and in a cell that never loaded the module."""
    import json

    thread, key, scale, unit = THREAD_METRICS[name]
    spec = json.load(open(os.path.join(
        REPO, "benchmarks/layer_metrics", name + ".json")))
    kin = json.load(open(os.path.join(
        REPO, "benchmarks/layer_metrics/server.queue_wait_ms_p50.json")))
    assert list(spec) == list(kin)
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [e for e in manifest["per_layer"] if e["name"] == name]
    assert entry == {k: spec[k] for k in entry}
    assert manifest["per_layer"].index(entry) >= 119  # appended
    assert (spec["layer"], spec["config"], spec["moves"], spec["source"],
            spec["better"], spec["unit"], spec["reducer"]) == (
        "expert server", "ffnserver", "swarm_samples_per_s",
        "program_counter", "lower", unit, "thread_stat")
    assert spec["workloads"] == ["ffnserver-infer-small",
                                 "ffnserver-train-bulk"]
    assert spec["args"] == {"thread": thread, "key": key, **(
        {"scale": scale} if scale != 1.0 else {})}
    assert "LOCATES" in spec["text"] and "no target" in spec["text"]

    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    module = _load("benchmarks/reducers/thread_stat.py")
    want = {"busy_share": 80.0, "cpu_share": 50.0 if "loop" in name else 30.0,
            "process_cpu_cores": 1.25, "turn_ms_mean": 0.4}[key]
    timeline.clear()
    saved = dict(timeline._threads)
    try:
        _a_servers_half_minute(timeline)
        assert module.reduce({}, **spec["args"]) == pytest.approx(
            want, rel=2e-3)
        # held inside the measured window as stage_stat holds the stages
        assert module.reduce({"intervals_s": [5.0, 5.5]}, **spec["args"]) == (
            pytest.approx(want, rel=5e-3))
        # 4.3 s of window less the 2 s tail hold samples 2.25 s apart; 3.9 s
        # hold them 1.75 s apart, under the reducer's floor
        assert module.reduce({"intervals_s": [4.3]}, **spec["args"]) == (
            pytest.approx(want, rel=2e-2))
        assert module.MIN_EXTENT_S == 2.0
        assert module.reduce({"intervals_s": [3.9]}, **spec["args"]) is None
        assert module.reduce({"intervals_s": [1.5]}, **spec["args"]) is None
        # a sum the thread does not keep, a thread that is not there
        assert module.reduce({}, "lah-runtime", "busy_share") is None
        assert module.reduce({}, "lah-absent", key) is None
        # a program without thread_stats (this PR's parent): nothing to read
        monkeypatch.setitem(
            sys.modules, "learning_at_home_tpu.utils.profiling",
            types.SimpleNamespace(timeline=types.SimpleNamespace(
                stage_stats=timeline.stage_stats)),
        )
        assert module.reduce({}, **spec["args"]) is None
        # a cell that never loaded the module (a train cell)
        monkeypatch.delitem(
            sys.modules, "learning_at_home_tpu.utils.profiling"
        )
        assert module.reduce({}, **spec["args"]) is None
        assert "learning_at_home_tpu.utils.profiling" not in sys.modules
    finally:
        timeline.clear()
        timeline._threads.clear()
        timeline._threads.update(saved)


def test_a_real_loops_shares_are_its_callbacks_time_and_cpu():
    """One wall-clock case, wide on purpose: callbacks that burn 200 ms of
    CPU and sleep 200 ms in every second read ``busy_share`` 0.4 and
    ``cpu_share`` 0.2, each within 0.2.  It runs under ``LAH_SANITIZE=1``
    like all of tier-1, and the sanitizer's stall detector goes on seeing
    the 400 ms callback through the selector's wrapper."""
    import asyncio

    from learning_at_home_tpu.utils import sanitizer
    from learning_at_home_tpu.utils.asyncio_utils import BackgroundLoop

    async def second():
        t0 = time.monotonic()
        cpu0 = time.thread_time()
        while time.thread_time() - cpu0 < 0.2:
            pass
        time.sleep(0.2)  # busy, and on no CPU
        await asyncio.sleep(max(0.0, 1.0 - (time.monotonic() - t0)))

    async def seconds(n):
        for _ in range(n):
            await second()

    stalls = sanitizer.stall_stats()["count"]
    loop = BackgroundLoop(name="lah-test-shares")
    try:
        begin = time.monotonic()
        loop.run(seconds(3), timeout=60)
        stats = timeline.thread_stats(begin, time.monotonic())
    finally:
        loop.shutdown()
        timeline._threads.pop("lah-test-shares", None)
    mine = stats["lah-test-shares"]
    assert mine["extent_s"] >= 1.5
    assert mine["busy_share"] == pytest.approx(0.4, abs=0.2)
    assert mine["cpu_share"] == pytest.approx(0.2, abs=0.2)
    # by construction: the CPU readings lie outside the wall readings
    assert mine["busy_share"] >= mine["cpu_share"] - 0.002
    assert mine["turns_per_s"] > 0 and mine["turn_ms_mean"] > 0
    assert mine["process_cpu_cores"] >= mine["cpu_share"] - 0.02
    if sanitizer.enabled():
        assert sanitizer.stall_stats()["count"] >= stalls + 3


POD_STEP_SCOPES = (
    "embed", "layer_0", "layer_1", "attention", "router", "moe_dispatch",
    "experts", "moe_combine", "ce", "optimizer",
)


@pytest.fixture(scope="module")
def pod_step_locations():
    """The scope path of every operation of the tiny flagship one-chip
    train step, from the lowered text's locations."""
    import re

    import jax
    import jax.numpy as jnp

    entry = _load("__graft_entry__.py")
    from learning_at_home_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    model, cfg, optimizer, batch = entry.flagship_one_chip(mesh, tiny=True)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    ids = jax.ShapeDtypeStruct((batch, cfg.seq_len), jnp.int32)
    lowered = model.make_train_step(optimizer).lower(
        params, opt_state, ids, ids
    )
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("scope", POD_STEP_SCOPES)
def test_pod_step_operations_carry_scope_names(pod_step_locations, scope):
    """Forward operations sit under ``jvp(<scope>)`` or, inside a layer,
    under ``<scope>``; the backward under ``transpose(jvp(<scope>))``."""
    parts = {p for loc in pod_step_locations for p in loc.split("/")}
    assert {scope, f"jvp({scope})"} & parts, sorted(parts)[:40]
    if scope != "optimizer":
        assert any(f"transpose(jvp({scope}))" in loc or
                   (f"/{scope}/" in loc and "transpose(" in loc)
                   for loc in pod_step_locations)


def test_pod_step_scopes_nest_under_their_layer(pod_step_locations):
    for inner in ("attention", "router", "moe_dispatch", "experts",
                  "moe_combine"):
        assert any(f"jvp(layer_0)/{inner}/" in loc
                   for loc in pod_step_locations), inner


@pytest.mark.parametrize("attrs", [
    {"pool": "p.0", "rows": 64},
    {"pool": "p.0", "rows": 64, "kind": "backward"},  # a tag on the entry
])
def test_span_costs_microseconds_on_the_default_path(attrs):
    """The budget is 1 us a span with profiling off, its kind included (a
    dictionary lookup, no second append); held at 5 us here so that a
    slow shared core does not fail it."""
    assert not timeline.enabled
    n = 20_000
    best = float("inf")
    try:
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with timeline.span("bench.span", **attrs):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert len(timeline.recent("bench.span:backward")) == (
            RESERVOIR_LEN if "kind" in attrs else 0)
    finally:
        timeline.clear()
    assert best < 5e-6, f"{best * 1e9:.0f} ns a span"


def test_no_jax_import_from_a_span(monkeypatch):
    """A process that has not imported jax does not import it for a
    span: the annotation class is resolved only once jax is loaded."""
    monkeypatch.setattr(profiling, "_annotation_cls", None)
    modules = dict(sys.modules)
    modules.pop("jax")
    monkeypatch.setattr(sys, "modules", modules)
    assert profiling._resolve_annotation_cls() is None
    assert profiling._annotation_cls is None
    with Timeline().span("stage"):
        pass
    assert "jax" not in sys.modules
