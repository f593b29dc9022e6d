"""Serving gateway (ISSUE 12): continuous batching over the pack-once
swarm dispatch, cross-user expert-set coalescing, admission control, and
the slot/KV lifecycle.

The contracts under test:

- decoder parity: the slot-table KV decoder's greedy tokens match a full
  re-forward argmax chain through ``model.apply`` exactly;
- coalescing is invisible: grouping streams with overlapping expert sets
  into one dispatch gives each stream the output of its own dispatch
  (selection and combine are row-wise) to a few ulp, the two being
  different compiled programs, and the same greedy tokens;
- admission: a saturated gateway sheds with a well-formed retry-after
  reply instead of queueing unboundedly;
- churn: streams killed mid-decode free their slot and KV rows — no slot
  or stream-table leak across 100 churned streams;
- lah_top renders gateway telemetry as STREAMS/SLOTS/SHED columns and
  dashes for peers without (or with malformed) gateway sections.
"""

import contextlib
import time

import jax
import numpy as np
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.gateway import (
    AdmissionController,
    ExpertCoalescer,
    Gateway,
    GatewayClient,
)
from learning_at_home_tpu.models.swarm_decoder import SwarmKVDecoder
from learning_at_home_tpu.models.transformer_swarm import (
    SwarmDMoETransformerLM,
    SwarmTransformerConfig,
)
from learning_at_home_tpu.server.server import background_server

D = 16
VOCAB = 32
SEQ = 16
LAYERS = 2
UIDS = [f"ffn{layer}.{e}" for layer in range(LAYERS) for e in range(2)]


def _cfg(**overrides):
    base = dict(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=4,
        seq_len=SEQ, grid_size=(2,), k_best=2, k_min=2, uid_prefix="ffn",
        timeout_after_k_min=30.0,
        forward_timeout=60.0, backward_timeout=60.0,
        # pin codec + blind gate: the equality contracts here must not
        # depend on adaptive wire precision or cost-model bias state
        wire_codec="none", routing_cost_weight=0,
    )
    base.update(overrides)
    return SwarmTransformerConfig(**base)


@pytest.fixture()
def swarm():
    """One in-process server hosting all experts + a swarm model."""
    with contextlib.ExitStack() as stack:
        endpoint, _srv = stack.enter_context(
            background_server(expert_uids=UIDS, hidden_dim=D, seed=0)
        )
        src = StaticExpertSource({u: endpoint for u in UIDS})
        model = SwarmDMoETransformerLM(_cfg(), src)
        params = model.init_params(jax.random.PRNGKey(0))
        yield model, params
    reset_client_rpc()


# ---------------------------------------------------------------------------
# decoder parity
# ---------------------------------------------------------------------------


def test_swarm_decoder_matches_reforward(swarm):
    """Greedy tokens from the KV decoder == re-forward argmax chains."""
    model, params = swarm
    dec = SwarmKVDecoder(model, params, max_slots=3)
    prompts = [[1, 2, 3], [4, 5], [7, 8, 9, 10]]
    outs = dec.generate(prompts, max_new_tokens=4)
    for prompt, toks in zip(prompts, outs):
        seqtoks = list(prompt)
        ref = []
        for _ in range(4):
            logits = model.apply(params, np.asarray([seqtoks], np.int32))
            t = int(np.asarray(logits)[0, -1].argmax())
            ref.append(t)
            seqtoks.append(t)
        assert toks == ref
    # every slot was vacated by generate()
    assert dec.free_slots() == [0, 1, 2]


# ---------------------------------------------------------------------------
# coalescing: invisible grouping
# ---------------------------------------------------------------------------


def _assert_equal_to_a_few_ulp(got, want):
    """One [4, D] dispatch and four [1, D] dispatches are two compiled
    programs of one float32 computation, and the server's pool stacks
    single rows fired together into whatever batches its 2 ms timer catches
    (a row bucket, so a program, by run): the outputs agree to a few ulp of
    their scale, not bit for bit (half the runs, a row or two).  A stream
    handed another stream's row would be off by the scale itself."""
    ulp = np.finfo(np.float32).eps * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=8 * ulp)  # 0.72 seen


def test_coalesced_dispatch_bitwise_equals_ungrouped(swarm):
    """("bitwise" in the name is historical: it is the ledger's id.  The
    outputs agree to a few ulp since PR 49, the counters exactly.)  The
    hook-level contract: one grouped dispatch over many streams' rows
    returns each stream the output its own dispatch would, and fires ONE
    dispatch where the ungrouped arm fires one a stream."""
    model, params = swarm
    moe = model.moes[0]
    gate = params["layers"][0]["gate"]
    x = np.random.RandomState(0).randn(4, D).astype(np.float32)
    streams = ["a", "b", "c", "d"]
    grouped = ExpertCoalescer(coalesce=True)
    ungrouped = ExpertCoalescer(coalesce=False)
    y_g = np.asarray(grouped.dispatch(0, moe, gate, x, streams))
    y_u = np.asarray(ungrouped.dispatch(0, moe, gate, x, streams))
    _assert_equal_to_a_few_ulp(y_g, y_u)
    # k_best == grid_size here, so every stream shares the expert set:
    # the grouped arm must have fired ONE dispatch for all four streams
    assert grouped.group_dispatches_total == 1
    assert grouped.coalesced_dispatches_total == 3
    assert ungrouped.group_dispatches_total == 4
    assert ungrouped.coalesced_dispatches_total == 0


def test_coalesced_generation_tokens_equal_ungrouped(swarm):
    """End-to-end: two decoders over the same weights, one coalescing
    and one not, emit identical token streams."""
    model, params = swarm
    prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7]]
    co = ExpertCoalescer(coalesce=True)
    dec_g = SwarmKVDecoder(model, params, max_slots=3,
                           moe_dispatch=co.dispatch)
    dec_u = SwarmKVDecoder(model, params, max_slots=3)
    outs_g = dec_g.generate(prompts, max_new_tokens=5)
    outs_u = dec_u.generate(prompts, max_new_tokens=5)
    assert outs_g == outs_u
    assert co.coalesced_dispatches_total > 0


def test_preview_failure_falls_back_to_singletons(swarm):
    """A preview failure degrades to ungrouped dispatch — coalescing is
    an optimization, never a correctness dependency."""
    model, params = swarm
    moe = model.moes[0]
    gate = params["layers"][0]["gate"]
    x = np.random.RandomState(1).randn(2, D).astype(np.float32)
    co = ExpertCoalescer(coalesce=True)
    orig = moe.preview_expert_sets
    moe.preview_expert_sets = lambda *_a, **_k: (_ for _ in ()).throw(
        RuntimeError("preview down")
    )
    try:
        y = co.dispatch(0, moe, gate, x, ["a", "b"])
    finally:
        moe.preview_expert_sets = orig
    y_ref = ExpertCoalescer(coalesce=False).dispatch(
        0, moe, gate, x, ["a", "b"]
    )
    # both arms fire two single-row dispatches, and the server's pool
    # stacks them into whatever batches its 2 ms timer catches: a few ulp,
    # as above; that the arm fell back is the counters' to say, exactly
    _assert_equal_to_a_few_ulp(np.asarray(y), np.asarray(y_ref))
    assert co.preview_failures_total == 1
    assert co.coalesced_dispatches_total == 0
    assert co.group_dispatches_total == 2  # one a stream: ungrouped


# ---------------------------------------------------------------------------
# gateway end-to-end over RPC
# ---------------------------------------------------------------------------


def test_gateway_rpc_end_to_end(swarm):
    """Submit/poll/cancel/stats over the real wire; tokens match the
    bare decoder's output for the same prompt."""
    model, params = swarm
    ref = SwarmKVDecoder(model, params, max_slots=1).generate(
        [[1, 2, 3]], max_new_tokens=5
    )[0]
    with Gateway(model, params, max_slots=4) as gw:
        client = GatewayClient(gw.endpoint)
        out = client.generate([1, 2, 3], 5)
        assert not out.get("shed") and not out.get("error")
        assert out["tokens"] == ref
        st = client.stats()
        assert st["gateway"]["streams_finished_total"] >= 1
        assert st["gateway"]["slots"] == 4
        m = st["metrics"]["collected"]
        assert m["lah_gateway_streams_total"] >= 1
        assert m["lah_gateway_tokens_total"] >= 5
        # malformed submits are rejected with an error frame, not a
        # hang: the pinned battery (tests/fuzz_corpus, ISSUE 15) covers
        # empty/out-of-vocab/no-decode-room/over-long prompts and bool
        # token ids / token budgets.  Raw frames, since
        # GatewayClient.submit int-coerces its arguments.
        import json
        import os

        from learning_at_home_tpu.utils.connection import RemoteCallError

        path = os.path.join(os.path.dirname(__file__), "fuzz_corpus",
                            "gateway_submit.json")
        with open(path) as fh:
            corpus = json.load(fh)
        assert corpus["format"] == "lah-fuzz-battery-v1"
        scope = {"VOCAB": VOCAB, "SEQ": SEQ}
        for case in corpus["cases"]:
            meta = {
                k: eval(v[1:], dict(scope))
                if isinstance(v, str) and v.startswith("$") else v
                for k, v in case["meta"].items()
            }
            with pytest.raises(RemoteCallError):
                client._rpc("gen_submit", meta)
                raise AssertionError(
                    f"malformed submit accepted: {case['name']}"
                )
        # the gateway survived the whole battery: still serving
        out = client.generate([1, 2, 3], 5)
        assert not out.get("shed") and not out.get("error")
        assert out["tokens"] == ref


def test_saturated_gateway_sheds_not_queues(swarm):
    """Past ``max_pending`` the gateway sheds with a well-formed
    retry-after reply; the pending queue stays bounded throughout."""
    model, params = swarm
    with Gateway(model, params, max_slots=1, max_pending=2) as gw:
        client = GatewayClient(gw.endpoint)
        replies = [client.submit([1, 2], SEQ - 3) for _ in range(12)]
        shed = [r for r in replies if r.get("shed")]
        accepted = [r for r in replies if r.get("accepted")]
        assert shed, "12 submits into 1 slot + 2 pending never shed"
        for r in shed:
            assert r["accepted"] is False
            assert r["retry_after_s"] > 0
            # either signal is a legitimate shed on a 1-slot gateway:
            # pending-bound saturation or KV page pressure
            assert (
                "saturated" in r["message"]
                or "page pressure" in r["message"]
            )
        # bounded: at no point can more than max_pending streams wait
        assert gw.scheduler.pending_count() <= 2
        assert gw.admission.shed_total == len(shed)
        for r in accepted:
            client.cancel(r["sid"])


def test_admission_server_queue_signal():
    """The DHT-advertised expert-server queue depth sheds on its own,
    independent of gateway occupancy (pure, no swarm)."""
    class _StubSched:
        def pending_count(self):
            return 0

        def estimate_retry_after_s(self):
            return 1.5

    ctrl = AdmissionController(
        _StubSched(), max_pending=4, max_server_queue=8.0,
        load_fn=lambda: {"srv": {"q": 99.0}, "junk": "not-a-dict"},
    )
    ok, retry, reason = ctrl.admit()
    assert ok and retry is None and reason is None
    ctrl._refresh_once()
    assert ctrl.server_queue_depth == 99.0
    ok, retry, reason = ctrl.admit()
    assert not ok and retry == 1.5 and "servers saturated" in reason
    # refresh failures are counted and tolerated, never raised
    ctrl._load_fn = lambda: (_ for _ in ()).throw(OSError("dht down"))
    ctrl._refresh_once()
    assert ctrl.load_refresh_failures == 1


# ---------------------------------------------------------------------------
# churn: cancelled streams must free slots and KV rows
# ---------------------------------------------------------------------------


def test_stream_churn_no_slot_leak(swarm):
    """100 streams submitted with long budgets and killed mid-decode:
    every slot and stream-table entry must come back."""
    model, params = swarm
    with Gateway(model, params, max_slots=4, max_pending=400,
                 stream_ttl_s=0.5) as gw:
        client = GatewayClient(gw.endpoint)

        def submit(prompt):
            # a cancel is a mark the decode thread acts on at its next
            # step, and its first step compiles (seconds, beside five
            # other workers): a submit that finds the four killed streams
            # still holding their slots' page reserve is refused (``shed``,
            # ``retry_after_s``: the admission contract).  Wait as told (a
            # second at most: the hint counts that compile into its step
            # time) and ask again.
            deadline = time.monotonic() + 60
            while True:
                r = client.submit(prompt, SEQ - 3)
                if not r.get("shed") or time.monotonic() > deadline:
                    break
                time.sleep(min(r["retry_after_s"], 1.0))
            assert r.get("accepted"), r
            return r["sid"]

        sids = []
        for i in range(100):
            sids.append(submit([1 + (i % 8), 2]))
            if i % 4 == 3:
                # let a few decode steps run so cancels land mid-decode,
                # then kill the whole batch in flight
                time.sleep(0.02)
                for sid in sids[-4:]:
                    client.cancel(sid)
        for sid in sids:
            client.cancel(sid)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            s = gw.scheduler.stats()
            if s["streams_active"] == 0 and s["pending"] == 0:
                break
            time.sleep(0.05)
        s = gw.scheduler.stats()
        assert s["streams_active"] == 0 and s["pending"] == 0, s
        assert s["slots_in_use"] == 0
        assert gw.decoder.free_slots() == [0, 1, 2, 3]
        assert not any(gw.decoder.live)
        assert (
            s["streams_cancelled_total"] + s["streams_finished_total"]
            + s["streams_errored_total"] == 100
        )
        assert s["streams_errored_total"] == 0
        # TTL GC drains the result table too (no unbounded memory for
        # fire-and-forget clients)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with gw.scheduler._lock:
                left = len(gw.scheduler._streams)
            if left == 0:
                break
            time.sleep(0.1)
        assert left == 0, f"{left} stream records never GC'd"


# ---------------------------------------------------------------------------
# lah_top gateway rows
# ---------------------------------------------------------------------------


def test_lah_top_renders_gateway_columns():
    import importlib

    lah_top = importlib.import_module("tools.lah_top")

    def row(peer_id, gateway_section):
        return {
            "peer_id": peer_id, "role": "gateway",
            "endpoint": ("127.0.0.1", 1), "expires_at": 0.0,
            "snapshot": {"gateway": gateway_section, "metrics": {}},
        }

    rows = [
        row("gw-1", {"streams_active": 3, "streams_total": 41,
                     "slots": 8, "slots_in_use": 2, "shed_total": 7,
                     "kv_pages_total": 33, "kv_pages_used": 12,
                     "prefix_hits_total": 5}),
        # dense-layout gateway: slot columns fill, page columns dash
        row("gw-dense", {"streams_active": 1, "streams_total": 2,
                         "slots": 4, "slots_in_use": 1, "shed_total": 0}),
        {"peer_id": "srv-1", "role": "server",
         "endpoint": ("127.0.0.1", 2), "expires_at": 0.0, "snapshot": {}},
    ]
    out = lah_top.render(rows, "swarm", dead=set())
    assert "STREAMS" in out and "SLOTS" in out and "SHED" in out
    assert "PAGES" in out and "PFX-HIT" in out
    assert "3/41" in out and "2/8" in out and "12/33" in out
    gw_line = next(ln for ln in out.splitlines() if ln.startswith("gw-1"))
    assert gw_line.rstrip().endswith("5")  # PFX-HIT is the last column
    assert " 12/33 " in gw_line
    dense_line = next(
        ln for ln in out.splitlines() if ln.startswith("gw-dense")
    )
    assert dense_line.rstrip().endswith("-")  # no page pool to report
    assert " 1/4 " in dense_line
    # peers without a gateway section render dashes
    srv_line = next(ln for ln in out.splitlines() if ln.startswith("srv-1"))
    assert srv_line.rstrip().endswith("-")
    # malformed sections render dashes, never crash
    rows.append(row("gw-weird", {"slots": "eight", "shed_total": 1}))
    rows.append(row("gw-bool", {"slots": True}))
    rows.append(row("gw-badpages", {"slots": 2, "kv_pages_total": "many",
                                    "prefix_hits_total": 3}))
    out = lah_top.render(rows, "swarm", dead=set())
    for peer in ("gw-weird", "gw-bool", "gw-badpages"):
        line = next(ln for ln in out.splitlines() if ln.startswith(peer))
        assert line.rstrip().endswith("-")
