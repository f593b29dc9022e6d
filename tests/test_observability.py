"""End-to-end observability (ISSUE 4): cross-peer trace propagation over
both wire protocols, Chrome trace export with correct nesting, the
per-peer metrics endpoint, and the DHT-discovered lah_top swarm view."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import jax
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.client.rpc import client_loop, pool_registry
from learning_at_home_tpu.server import background_server
from learning_at_home_tpu.utils import connection as conn_mod
from learning_at_home_tpu.utils.profiling import timeline

HID = 16
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def profiled():
    """Timeline on + clean, pools reset afterwards."""
    timeline.enable()
    timeline.clear()
    yield timeline
    timeline.disable()
    timeline.clear()
    reset_client_rpc()


def _make_moe(srv, endpoint, **kw):
    source = StaticExpertSource({uid: endpoint for uid in srv.experts})
    kw.setdefault("k_best", 2)
    kw.setdefault("k_min", 1)
    return RemoteMixtureOfExperts(
        in_features=HID, grid_size=(2,), uid_prefix="ffn", source=source,
        **kw,
    )


def _fwd_bwd(moe):
    gate = moe.init_gate_params(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).randn(4, HID).astype(np.float32)

    def loss(g, x):
        return jax.numpy.sum(moe(x, g) ** 2)

    jax.grad(loss)(gate, jax.numpy.asarray(x))


def _traces_by_name(spans, **attrs):
    """{span_name: set(trace ids)} over spans that carry one (and, where
    given, these attributes: a span's name is its stage, the pool and the
    message type are attributes)."""
    out = {}
    for name, _, _, trace, _, span_attrs in spans:
        if trace is not None and attrs.items() <= span_attrs.items():
            out.setdefault(name, set()).add(trace)
    return out


def _interval(spans, name, trace, **attrs):
    """(start, end) of the one span with this name+trace+attributes."""
    match = [
        (s, s + d) for n, s, d, t, _, a in spans
        if n == name and t == trace and attrs.items() <= a.items()
    ]
    assert match, f"no span {name!r} {attrs} with trace {trace}"
    return match[0]


# ---------------------------------------------------------------------------
# trace propagation
# ---------------------------------------------------------------------------


def test_trace_joins_client_and_server_spans_v2_merged(profiled):
    """One traced dispatch over the merged-multi v2 path: client pack,
    rpc, server request, and the server's stack/dispatch/materialize
    spans all share ONE trace id — and nest on the time axis."""
    with background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=0
    ) as (endpoint, srv):
        moe = _make_moe(srv, endpoint)
        _fwd_bwd(moe)
    spans = timeline.spans()
    by_name = _traces_by_name(spans)
    # the dispatch umbrella carries exactly one trace id per dispatch
    assert "moe.dispatch" in _traces_by_name(spans, prefix="ffn")
    (trace,) = by_name["moe.dispatch"]
    forward, backward = {"pool": "ffn.0.forward"}, {"pool": "ffn.0.backward"}
    for name, attrs in (
        ("client.pack", {"kind": "forward"}),
        ("rpc.multi", {}),
        ("server.request", {"type": "multi"}),
        ("server.encode", {}),
        # the socket's two sides and the way back to the loop (ISSUE 35),
        # each with the request's kind as an attribute
        ("rpc.send", {"kind": "forward"}),
        ("rpc.decode", {"kind": "backward"}),
        ("server.read", {"kind": "forward"}),
        ("server.request", {"kind": "backward"}),
        ("server.write", {"kind": "backward"}),
        ("server.resume", {"kind": "forward", **forward}),
        ("runtime.handoff", {"kind": "backward", **backward}),
        ("pool.wait", forward),
        ("runtime.queue", forward),
        ("runtime.stack", forward),
        ("runtime.dispatch", forward),
        ("runtime.materialize", forward),
        ("runtime.deliver", forward),
        # backward joins the SAME trace (the session carries it)
        ("moe.backward", {"prefix": "ffn"}),
        ("client.pack", {"kind": "backward"}),
        ("runtime.dispatch", backward),
    ):
        assert trace in _traces_by_name(spans, **attrs).get(name, set()), (
            f"{name} {attrs} not stamped with the dispatch trace; "
            f"got {by_name}"
        )
    # no span name carries a pool, a prefix, a message type or a kind:
    # the server's names are its fifteen stages
    assert {n for n, *_ in spans
            if n.startswith(("server.", "pool.", "runtime."))} <= {
        "server.read", "server.request", "server.decode", "server.encode",
        "server.write", "server.resume", "server.conn.idle", "pool.wait",
        "runtime.queue", "runtime.stack", "runtime.dispatch",
        "runtime.materialize", "runtime.handoff", "runtime.deliver",
        "runtime.idle",
    }
    # nesting: server stage spans inside the server request span, which
    # sits inside the client's rpc span (same process, one clock)
    rpc_s, rpc_e = _interval(spans, "rpc.multi", trace)
    req_s, req_e = _interval(spans, "server.request", trace, type="multi")
    assert rpc_s <= req_s and req_e <= rpc_e
    for stage in ("queue", "stack", "dispatch", "materialize", "deliver"):
        s, e = _interval(spans, f"runtime.{stage}", trace, **forward)
        assert req_s <= s and e <= req_e, f"runtime.{stage} escapes request"
    # the way back to the handler is inside the request too; its frame's
    # read precedes it and its reply's write follows it, without a hole
    s, e = _interval(spans, "server.resume", trace, **forward)
    assert req_s <= s and e <= req_e
    read_s, read_e = _interval(spans, "server.read", trace, kind="forward")
    write_s, write_e = _interval(spans, "server.write", trace, kind="forward")
    assert rpc_s <= read_s <= read_e <= req_s and write_s == req_e


def test_trace_survives_disaggregated_retry(profiled, monkeypatch):
    """A failed merged call disaggregates into per-expert singles — each
    retry carries the ORIGINAL dispatch's trace id."""
    real = conn_mod.ConnectionPool.rpc_prepared
    failed = {"n": 0}

    async def flaky(self, msg_type, wire, meta=None, timeout=None):
        if msg_type == "multi" and failed["n"] == 0:
            failed["n"] += 1
            raise ConnectionError("injected merged-call failure")
        return await real(self, msg_type, wire, meta, timeout)

    monkeypatch.setattr(conn_mod.ConnectionPool, "rpc_prepared", flaky)
    with background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=0
    ) as (endpoint, srv):
        moe = _make_moe(srv, endpoint)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = jax.numpy.asarray(
            np.random.RandomState(0).randn(4, HID).astype(np.float32)
        )
        y = moe(x, gate)
        assert np.isfinite(np.asarray(y)).all()
    assert failed["n"] == 1, "the merged call was never failed"
    by_name = _traces_by_name(timeline.spans())
    (trace,) = by_name["moe.dispatch"]
    # the disaggregated singles went out as rpc.forward with the trace
    assert trace in by_name.get("rpc.forward", set())
    assert trace in _traces_by_name(
        timeline.spans(), type="forward").get("server.request", set())


def test_trace_echoed_in_reply_meta_and_always_on_stats(profiled):
    """The reply meta echoes a valid request trace; a malformed
    (non-string) trace is dropped, never trusted.  The same exchange
    shows the stats RPC's always-on registry section (satellite: a
    server is never blind without LAH_PROFILE)."""
    timeline.disable()  # always-on means: works with profiling OFF
    with background_server(
        num_experts=1, hidden_dim=HID, expert_prefix="ffn", seed=0
    ) as (endpoint, _srv):

        async def call(meta):
            pool = pool_registry().get(endpoint)
            return await pool.rpc("stats", (), meta, timeout=15)

        _, meta = client_loop().run(call({"trace": "ab" * 8}))
        assert meta["trace"] == "ab" * 8
        assert "metrics" in meta and "collected" in meta["metrics"]
        headline = meta["metrics"]["collected"]
        assert "lah_server_jobs_processed_total" in headline
        # span summaries are OPT-IN (O(spans) work on the serving loop);
        # the default stats reply omits them entirely
        assert "spans" not in meta
        _, meta_s = client_loop().run(
            call({"trace": "ab" * 8, "spans": True})
        )
        assert meta_s["spans"] == {}  # profiling off → present but empty
        _, meta2 = client_loop().run(call({"trace": 12345}))
        assert "trace" not in meta2


def test_merged_multi_trainer_batch_is_unstamped():
    """A batch that merged tasks from TWO different traces has no single
    owner: the runtime stage spans stay trace-free instead of
    misattributing shared work to one request."""
    from learning_at_home_tpu.server.task_pool import BatchJob, TaskPool

    def job(traces):
        return BatchJob(
            priority=0.0, seq=0, pool=TaskPool(lambda i: i, "p"),
            task_tensors=[], row_spans=[], n_rows=0, traces=traces,
        )

    assert job(["aa", "aa"]).owner_trace() == "aa"
    assert job(["aa", None]).owner_trace() == "aa"
    assert job(["aa", "bb"]).owner_trace() is None
    assert job([None]).owner_trace() is None
    assert job([]).owner_trace() is None


# ---------------------------------------------------------------------------
# the per-peer metrics endpoint
# ---------------------------------------------------------------------------


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    )


def test_metrics_endpoint_routes(profiled):
    with background_server(
        num_experts=1, hidden_dim=HID, expert_prefix="ffn", seed=0
    ) as (endpoint, srv):
        expert = RemoteExpert("ffn.0", endpoint, timeout=30.0)
        expert.forward_blocking([np.ones((2, HID), np.float32)])
        assert srv.metrics_port
        text = _get(srv.metrics_port, "/metrics").read().decode()
        assert "lah_server_jobs_processed_total" in text
        assert "lah_server_updates_total" in text
        doc = json.loads(_get(srv.metrics_port, "/metrics.json").read())
        assert doc["meta"]["role"] == "server"
        assert doc["experts"] == {"ffn.0": 0}
        assert doc["runtime"]["jobs_processed"] >= 1
        assert (
            doc["metrics"]["collected"]["lah_server_jobs_processed_total"]
            >= 1
        )
        trace_doc = json.loads(_get(srv.metrics_port, "/trace").read())
        assert any(
            ev.get("name", "").startswith("runtime.")
            for ev in trace_doc["traceEvents"]
        )
        assert _get(srv.metrics_port, "/healthz").read() == b"ok"
        with pytest.raises(urllib.error.HTTPError):
            _get(srv.metrics_port, "/nope")


# ---------------------------------------------------------------------------
# the acceptance smoke: 2 servers + 1 trainer, one joined chrome trace,
# lah_top aggregation via DHT discovery (no endpoint on the CLI)
# ---------------------------------------------------------------------------


def test_two_server_trainer_smoke_chrome_trace_and_lah_top(
    profiled, tmp_path
):
    from learning_at_home_tpu.dht import DHT
    from learning_at_home_tpu.server.server import Server
    from learning_at_home_tpu.utils.telemetry import (
        TelemetryPublisher,
        discover_telemetry,
    )

    bootstrap = DHT()
    dht = DHT(initial_peers=[bootstrap.endpoint])
    servers, telemetry = [], None
    try:
        for i in range(2):
            servers.append(
                Server.create(
                    num_experts=1, expert_cls="ffn", hidden_dim=HID,
                    expert_prefix="ffn", expert_offset=i,
                    optimizer=optax.sgd(0.05), max_batch_size=64,
                    host="127.0.0.1", dht=dht, update_period=2.0,
                )
            )
        # the trainer: drives one traced fwd+bwd through the DHT-routed
        # MoE and advertises its own metrics endpoint
        telemetry = TelemetryPublisher(
            dht, role="trainer", period=2.0
        ).start()
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(2,), uid_prefix="ffn", source=dht,
            k_best=2, k_min=1,
        )
        deadline = time.time() + 30
        while time.time() < deadline:
            alive = client_loop().run(
                moe.alive_cache.get(force_refresh=True)
            )
            if len(alive) >= 2:
                break
            time.sleep(0.5)
        assert len(alive) >= 2, f"experts never appeared via DHT: {alive}"
        _fwd_bwd(moe)

        # (a) ONE exported Chrome trace: a single dispatch's client pack
        # / rpc / server stack / dispatch / materialize spans share one
        # trace id and nest correctly
        spans = timeline.spans()
        (trace,) = _traces_by_name(spans)["moe.dispatch"]
        path = tmp_path / "swarm_trace.json"
        timeline.save_chrome_trace(str(path), process_name="smoke")
        events = json.loads(path.read_text())["traceEvents"]
        traced = [
            e for e in events
            if e.get("ph") == "X" and e.get("args", {}).get("trace") == trace
        ]
        names = {e["name"] for e in traced}
        assert any(e["name"] == "client.pack"
                   and e["args"]["kind"] == "forward" for e in traced)
        assert any(n.startswith("rpc.") for n in names)
        for stage in ("stack", "dispatch", "materialize"):
            assert any(
                e["name"] == f"runtime.{stage}"
                and e["args"]["pool"].startswith("ffn.") for e in traced
            ), f"no {stage} span in the exported trace: {names}"
        # nesting in the EXPORTED events (µs timeline)
        reqs = [e for e in traced if e["name"] == "server.request"]
        # (the hand-off is the runtime thread's own time once the results
        # have left: the loop can finish the request before it ends)
        stages = [e for e in traced if e["name"].startswith("runtime.")
                  and e["name"] != "runtime.handoff"]
        assert reqs and stages
        for st in stages:
            assert any(
                r["ts"] <= st["ts"]
                and st["ts"] + st["dur"] <= r["ts"] + r["dur"]
                for r in reqs
            ), f"{st['name']} nests in no server.request span"

        # (b) lah_top --once aggregates BOTH servers' live metrics,
        # discovered via the DHT — only the DHT bootstrap is on the CLI
        deadline = time.time() + 30
        while time.time() < deadline:
            peers = discover_telemetry(bootstrap)
            if sum(1 for p in peers.values() if p["role"] == "server") >= 2:
                break
            time.sleep(0.5)
        assert sum(1 for p in peers.values() if p["role"] == "server") >= 2, peers
        r = subprocess.run(
            [
                sys.executable, os.path.join(REPO, "tools", "lah_top.py"),
                "--once", "--initial-peers",
                f"{bootstrap.endpoint[0]}:{bootstrap.endpoint[1]}",
                "--dump-trace", str(tmp_path / "fetched_trace.json"),
            ],
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        for srv in servers:
            assert f"server-127.0.0.1:{srv.port}" in r.stdout, r.stdout
        assert "trainer-" in r.stdout, r.stdout
        assert "ffn.0" in r.stdout and "ffn.1" in r.stdout, r.stdout
        # the merged /trace dump is valid chrome trace JSON too
        fetched = json.loads((tmp_path / "fetched_trace.json").read_text())
        assert isinstance(fetched["traceEvents"], list)
    finally:
        if telemetry is not None:
            telemetry.stop()
        for srv in servers:
            srv.shutdown()
        dht.shutdown()
        bootstrap.shutdown()
