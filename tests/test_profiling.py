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
