"""Stream-level serving traces (ISSUE 19 tentpole layer 1).

The contracts under test:

- **continuity**: ONE trace id stamps every lifecycle span of a stream
  — admission, pending wait, slot assignment, prefill chunks,
  preemption plus the token-identical recompute re-admit, coalesced
  expert dispatch, speculative verify accept markers, tokens, and the
  closing ``gateway.stream`` umbrella — even when the stream is evicted
  and re-queued mid-flight;
- **nesting**: the umbrella span contains every other span of its
  stream by time containment (what the merged Chrome trace renders);
- **echo**: ``gen_submit`` and ``gen_poll`` replies carry the trace so
  callers can join client-side and gateway-side spans;
- **zero cost off**: with profiling disabled no ids are minted and no
  spans recorded, while a client-supplied valid id still echoes
  (distributed callers keep their correlation even on unprofiled
  gateways) and malformed ids are dropped, never echoed.
"""

import contextlib
import time

import jax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.gateway import Gateway, GatewayClient
from learning_at_home_tpu.models.swarm_decoder import SwarmKVDecoder
from learning_at_home_tpu.models.transformer_swarm import (
    SwarmDMoETransformerLM,
    SwarmTransformerConfig,
)
from learning_at_home_tpu.server.server import background_server
from learning_at_home_tpu.utils.profiling import (
    new_trace_id,
    timeline,
    valid_trace_id,
)

D = 16
VOCAB = 32
SEQ = 16
LAYERS = 2
UIDS = [f"ffn{layer}.{e}" for layer in range(LAYERS) for e in range(2)]


def _cfg():
    return SwarmTransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=4,
        seq_len=SEQ, grid_size=(2,), k_best=2, k_min=2, uid_prefix="ffn",
        timeout_after_k_min=30.0,
        forward_timeout=60.0, backward_timeout=60.0,
        wire_codec="none", routing_cost_weight=0,
    )


@pytest.fixture()
def swarm():
    with contextlib.ExitStack() as stack:
        endpoint, _srv = stack.enter_context(
            background_server(expert_uids=UIDS, hidden_dim=D, seed=0)
        )
        src = StaticExpertSource({u: endpoint for u in UIDS})
        model = SwarmDMoETransformerLM(_cfg(), src)
        params = model.init_params(jax.random.PRNGKey(0))
        yield model, params
    reset_client_rpc()


@pytest.fixture()
def profiled():
    timeline.enable()
    timeline.clear()
    yield
    timeline.disable()
    timeline.clear()


def _poll_done(client, sid, deadline_s=60.0):
    deadline = time.monotonic() + deadline_s
    cursor = 0
    tokens = []
    while time.monotonic() < deadline:
        out = client.poll(sid, cursor)
        tokens.extend(out.get("tokens") or [])
        cursor = int(out.get("cursor") or cursor)
        if out.get("done"):
            out["tokens"] = tokens
            return out
        time.sleep(0.01)
    raise AssertionError(f"stream {sid} never finished")


def _spans_by_trace(tid):
    return [s for s in timeline.spans() if s[3] == tid]


# a self-revisiting continuation so the n-gram drafter accepts something
REPETITIVE = [5, 6, 7, 5, 6, 7, 5, 6]


def test_one_trace_id_through_preempt_coalesce_and_spec_verify(
    swarm, profiled
):
    """Two traced streams into a pool too small for both (11 usable
    pages vs 2 × 8-page streams decoding in lockstep) on a coalescing,
    speculative gateway: the victim's trace id survives eviction and the
    recompute re-admit, and every span of each stream nests inside its
    umbrella.  (The pool is NOT the 9-page squeeze of the paged-KV
    preemption test: spec lookahead raises page demand enough that two
    streams there evict each other forever.)"""
    model, params = swarm
    prompts = [REPETITIVE, [1, 2, 1, 2, 1, 2, 1, 2]]
    n_new = SEQ - len(REPETITIVE)
    with Gateway(
        model, params, max_slots=2, max_pending=64,
        page_len=2, num_pages=12, prefix_cache=False,
        prefill_chunk_tokens=4, coalesce=True,
        spec_k=3, spec_drafter="ngram",
    ) as gw:
        client = GatewayClient(gw.endpoint)
        tids = [new_trace_id(), new_trace_id()]
        # enqueue directly on the scheduler (admission would serialise
        # the streams and hide the contention that forces preemption)
        sids = [
            gw.scheduler.submit(p, n_new, trace=t)
            for p, t in zip(prompts, tids)
        ]
        for sid, tid in zip(sids, tids):
            out = _poll_done(client, sid)
            assert out.get("error") is None, out
            assert out["trace"] == tid  # gen_poll echoes the stream's id
        assert gw.scheduler.preemptions_total >= 1
        assert gw.scheduler.stats()["spec_rounds_total"] >= 1

    # --- continuity: the full lifecycle rides each stream's one id ---
    for tid in tids:
        names = {s[0] for s in _spans_by_trace(tid)}
        assert "gateway.stream" in names, names
        assert "gateway.pending.wait" in names
        assert "gateway.prefill.chunk" in names
        assert "gateway.token.first" in names
        assert names & {"gateway.slot.assign", "gateway.recompute.admit"}
        # spec verify rounds stamp per-stream accepted-k markers
        assert "gateway.spec.accept" in names, (
            names
        )

    # the victim's eviction AND its recompute re-admit share its id
    preempted = {s[3] for s in timeline.spans("gateway.preempt")}
    assert preempted and preempted <= set(tids)
    for tid in preempted:
        names = {s[0] for s in _spans_by_trace(tid)}
        assert "gateway.recompute.admit" in names

    # coalesced expert dispatch fan-out joins some stream's trace (the
    # group rides its anchoring member's id)
    fires = [s for s in timeline.spans("client.dispatch.fire") if s[3]]
    assert fires and {s[3] for s in fires} <= set(tids)

    # --- nesting: every gateway lifecycle span sits inside the stream
    # umbrella.  client.* wire spans carry the trace purely for
    # correlation: a coalesced GROUP rides its anchoring member's id, so
    # a fan-out serving the group's survivors may outlive the anchor's
    # umbrella — correlation, not containment, is their contract.
    eps = 0.05
    for tid in tids:
        spans = _spans_by_trace(tid)
        umbrella = [s for s in spans if s[0] == "gateway.stream"]
        assert len(umbrella) == 1
        _, u_start, u_dur, *_ = umbrella[0]
        for name, start, dur, *_ in spans:
            if not name.startswith("gateway."):
                continue
            assert start >= u_start - eps, (name, tid)
            assert start + dur <= u_start + u_dur + eps, (name, tid)


def test_cancel_marker_carries_trace(swarm, profiled):
    model, params = swarm
    with Gateway(model, params, max_slots=1, max_pending=8) as gw:
        client = GatewayClient(gw.endpoint)
        tid = new_trace_id()
        sub = client.submit([1, 2, 3], 8, trace=tid)
        assert sub.get("accepted") and sub["trace"] == tid
        assert client.cancel(sub["sid"])
        # ``cancel`` only marks the stream: the scheduler ends it (and writes
        # the marker) on its next pass.  Wait for that pass, a state and not
        # a time, before the gateway goes down with the mark unread
        for _ in range(2000):
            if client.poll(sub["sid"]).get("done"):
                break
            time.sleep(0.005)
    cancels = timeline.spans("gateway.stream.cancel")
    assert any(s[3] == tid for s in cancels)
    # the umbrella still closes, on the same id
    assert any(s[3] == tid for s in timeline.spans("gateway.stream"))


def test_disabled_profiling_mints_nothing_but_echoes_valid_ids(swarm):
    model, params = swarm
    timeline.disable()
    timeline.clear()
    with Gateway(model, params, max_slots=2) as gw:
        client = GatewayClient(gw.endpoint)
        # no caller id + profiling off → no id minted anywhere
        sub = client.submit([1, 2, 3], 2)
        assert sub.get("accepted") and "trace" not in sub
        out = _poll_done(client, sub["sid"])
        assert "trace" not in out
        # a valid caller-supplied id still echoes end to end
        tid = new_trace_id()
        assert valid_trace_id(tid)
        sub = client.submit([1, 2, 3], 2, trace=tid)
        assert sub["trace"] == tid
        assert _poll_done(client, sub["sid"])["trace"] == tid
        # malformed ids are dropped, never echoed back
        for bad in ("ZZZZZZZZZZZZZZZZ", "abc", "A" * 16, "0" * 17):
            sub = client.submit([1, 2, 3], 2, trace=bad)
            assert sub.get("accepted") and "trace" not in sub, bad
            _poll_done(client, sub["sid"])
    assert timeline.spans() == []  # zero spans recorded while disabled
