"""The life of a swarm request on the program's own clock (ISSUE 25):
always-on stage reservoirs, profiler annotations on the device trace's
clock, the benchmark's stage reducer, and the pod step's scope names.

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
from learning_at_home_tpu.server import connection_handler
from learning_at_home_tpu.server.server import background_server
from learning_at_home_tpu.utils import profiling
from learning_at_home_tpu.utils.profiling import (
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
# the runtime thread's own time: it is in exactly one of these, or between
RUNTIME_THREAD_STAGES = (
    "runtime.idle", "runtime.stack", "runtime.dispatch",
    "runtime.materialize",
)


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
                "recent": {n: timeline.recent(n) for n in SERVER_STAGES},
                "stats": timeline.stage_stats(("server.", "pool.", "runtime.")),
                "runtime_stats": srv.runtime.stats(),
                "full_spans": timeline.spans(),
                "metas": list(metas),
            }
        yield seen
    finally:
        connection_handler.unpack_message = real_unpack
        timeline.clear()
        reset_client_rpc()


@pytest.mark.parametrize("stage", SERVER_STAGES)
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
    """idle + stack + dispatch + materialize is the runtime thread's own
    time (``runtime.queue`` and ``runtime.deliver`` are waits of others):
    the shares of their reservoirs' extents sum to about one."""
    shares = {n: served["stats"][n]["share"] for n in RUNTIME_THREAD_STAGES}
    assert 0.8 <= sum(shares.values()) <= 1.02, shares


def test_stats_rpc_carries_the_stages(served):
    import msgpack

    stages = served["runtime_stats"]["stages"]
    assert set(SERVER_STAGES) <= set(stages)
    assert set(stages["runtime.stack"]) == {
        "count", "p50_ms", "p95_ms", "share", "extent_s"
    }
    msgpack.packb(stages, use_bin_type=True)  # the stats reply's wire


def test_span_names_in_the_server_carry_no_data(served):
    for name in served["stats"]:
        assert name.count(".") == 1, name


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


def test_spans_are_profiler_annotations_on_the_device_traces_clock(tmp_path):
    """Under a profiler session the spans that enclose running code are
    in the ``.xplane.pb`` by name, read the way the benchmark reads host
    spans; the ones recorded afterwards from two readings are not."""
    import jax

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import trace_reduce
    finally:
        sys.path.remove(os.path.join(REPO, "benchmarks"))

    annotated = ("server.decode", "server.request", "server.encode",
                 "runtime.idle", "runtime.stack", "runtime.dispatch",
                 "runtime.materialize")
    recorded = ("pool.wait", "runtime.queue", "runtime.deliver")
    try:
        with background_server(
            num_experts=1, hidden_dim=HID, expert_prefix="ffn", seed=0
        ) as (endpoint, _srv):
            expert = RemoteExpert("ffn.0", endpoint, timeout=30.0)
            x = np.ones((4, HID), np.float32)
            expert.forward_blocking([x])
            with jax.profiler.trace(str(tmp_path)):
                for _ in range(5):
                    expert.forward_blocking([x])
    finally:
        timeline.clear()
        reset_client_rpc()
    events = trace_reduce.load_events(
        trace_reduce.find_xplane(str(tmp_path)),
        host_spans=annotated + recorded,
    )
    seen = {name for name, _, _ in events["host"]}
    assert set(annotated) <= seen, sorted(seen)
    assert not seen & set(recorded)
    for name, start, end in events["host"]:
        assert end >= start


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


def test_every_stage_metric_names_the_one_reducer_and_a_server_stage():
    import glob
    import json

    module = _load("benchmarks/reducers/stage_stat.py")
    specs = [json.load(open(p)) for p in glob.glob(
        os.path.join(REPO, "benchmarks/layer_metrics/server.*.json"))]
    staged = [s for s in specs if s["source"] == "program_span"]
    assert len(staged) == 10
    for spec in staged:
        assert spec["reducer"] == "stage_stat", spec["name"]
        assert spec["args"]["name"] in SERVER_STAGES
        assert spec["args"]["name"].startswith(module.SERVER_STAGES)
        assert spec["args"]["key"] in ("p50_ms", "share")


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


def test_span_costs_microseconds_on_the_default_path():
    """The budget is 1 us a span with profiling off; held at 5 us here so
    that a slow shared core does not fail it."""
    assert not timeline.enabled
    n = 20_000
    best = float("inf")
    try:
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with timeline.span("bench.span", pool="p.0", rows=64):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
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
