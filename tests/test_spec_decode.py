"""Counter-based sampling RNG + self-speculative decoding (ISSUE 17).

The contracts under test:

- **counter-based sampling determinism**: a sampled token depends only
  on ``(logits, stream_seed, absolute position)`` — its parity contracts
  are ``tests/test_spec_decode_sampling.py``'s;
- **exact speculation**: with ``spec_k > 0`` the gateway verifies k
  drafted tokens per batched round and commits exactly the longest
  matched prefix plus the bonus sample — output token-identical to the
  non-speculative decoder for greedy AND sampled streams, with KV pages
  rolled back past the first rejection (refcount-clean, audit-enforced);
- **hostile sampling fields**: malformed gen_submit sampling values are
  well-formed error frames, never decoder state.
"""

import contextlib
import time

import jax
import jax.numpy as jnp
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.gateway import Gateway, GatewayClient
from learning_at_home_tpu.models.drafter import (
    NGramDrafter,
    TruncatedTrunkDrafter,
)
from learning_at_home_tpu.models.kv_pages import PagedKVCache
from learning_at_home_tpu.models.sampling import SamplingParams
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

SEEDS = [7, 19, 1234]  # the ">= 3 sampling seeds" acceptance bar


def _cfg(**overrides):
    base = dict(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=4,
        seq_len=SEQ, grid_size=(2,), k_best=2, k_min=2, uid_prefix="ffn",
        timeout_after_k_min=30.0,
        forward_timeout=60.0, backward_timeout=60.0,
        wire_codec="none", routing_cost_weight=0,
    )
    base.update(overrides)
    return SwarmTransformerConfig(**base)


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


def _sp(seed, **kw):
    base = dict(seed=seed, temperature=0.9, top_p=0.95, top_k=8)
    base.update(kw)
    return SamplingParams(**base)


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


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------


def test_ngram_drafter_suffix_match_and_fallback():
    d = NGramDrafter(max_suffix=4)
    # repeating context: the suffix recurs, so it proposes the loop
    ctx = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    assert d.propose(ctx, 3) == [3, 4, 1]
    # nothing recurs → empty proposal (plain decode fallback)
    assert d.propose([1, 2, 3, 4, 5, 6], 3) == []
    assert d.propose([5], 3) == []
    assert d.propose(ctx, 0) == []


def test_truncated_trunk_drafter_shapes_and_determinism(swarm):
    model, params = swarm
    d = TruncatedTrunkDrafter(model, params, draft_layers=1, window=8)
    ctx = [3, 1, 4, 1, 5]
    out1 = d.propose(ctx, 4)
    out2 = d.propose(ctx, 4)
    assert out1 == out2
    assert len(out1) == 4
    assert all(0 <= t < VOCAB for t in out1)
    # never drafts past the position table
    long_ctx = list(range(1, SEQ))  # len SEQ-1
    assert len(d.propose(long_ctx, 8)) <= 1


# ---------------------------------------------------------------------------
# verify_step: longest-prefix acceptance + KV rollback refcounts
# ---------------------------------------------------------------------------


def _reference_tokens(model, params, prompt, n, sampling=None):
    return SwarmKVDecoder(model, params, max_slots=1).generate(
        [prompt], max_new_tokens=n, sampling=[sampling]
    )[0]


@pytest.mark.parametrize("sampling", [None, "sampled"])
def test_verify_step_accepts_longest_prefix_exactly(swarm, sampling):
    model, params = swarm
    sp = _sp(SEEDS[1]) if sampling else None
    prompt = [3, 1, 4, 1, 5]
    ref = _reference_tokens(model, params, prompt, 6, sp)
    dec = SwarmKVDecoder(
        model, params, max_slots=2, kv_layout="paged", page_len=2,
        prefix_cache=False,
    )
    first = dec.prefill_into_slot(0, prompt, stream_id="s", sampling=sp)
    assert first == ref[0]
    # draft the TRUE continuation with one poisoned position: the round
    # must accept exactly up to the poison, then the bonus sample
    drafts = [ref[1], ref[2], (ref[3] + 1) % VOCAB, ref[4]]
    assert dec.ensure_decode_pages() == []
    assert dec.ensure_lookahead_pages(0, len(drafts)) == len(drafts)
    res = dec.verify_step({0: drafts})[0]
    assert res["accepted"] == 2
    assert res["proposed"] == 4
    assert res["tokens"] == ref[1:4]  # 2 accepted drafts + bonus
    assert int(dec.pos[0]) == len(prompt) + 3
    assert int(dec.last_tok[0]) == ref[3]
    # rolled-back lookahead pages are refcount-clean
    assert dec.kv.audit() == []
    assert dec.kv.rollback_pages_total >= 1
    # a fully-correct draft round accepts everything + bonus
    drafts2 = [ref[4], ref[5]]
    assert dec.ensure_lookahead_pages(0, len(drafts2)) == len(drafts2)
    res2 = dec.verify_step({0: drafts2})[0]
    assert res2["accepted"] == 2
    assert res2["tokens"][:2] == ref[4:6]
    assert dec.kv.audit() == []
    # an empty proposal is a plain decode row
    res3 = dec.verify_step({0: []})[0]
    assert res3["accepted"] == 0 and res3["proposed"] == 0
    assert len(res3["tokens"]) == 1
    dec.evict(0)
    assert dec.kv.pages_used() - dec.kv.pages_reclaimable() <= 0


def test_verify_step_batches_multiple_streams_one_round(swarm):
    """Two streams with different draft depths verify in ONE call/round
    and each commits its own longest prefix — tokens identical to solo
    non-speculative decode."""
    model, params = swarm
    prompts = [[1, 2, 3], [9, 8]]
    refs = [_reference_tokens(model, params, p, 5) for p in prompts]
    dec = SwarmKVDecoder(
        model, params, max_slots=2, kv_layout="paged", page_len=4,
        prefix_cache=False,
    )
    for i, p in enumerate(prompts):
        assert dec.prefill_into_slot(i, p, stream_id=i) == refs[i][0]
    drafts = {
        0: [refs[0][1], (refs[0][2] + 1) % VOCAB],  # accept 1
        1: [refs[1][1], refs[1][2], refs[1][3]],    # accept all
    }
    assert dec.ensure_decode_pages() == []
    for s, d in drafts.items():
        assert dec.ensure_lookahead_pages(s, len(d)) == len(d)
    rounds0 = dec.verify_rounds_total
    res = dec.verify_step(drafts)
    assert dec.verify_rounds_total == rounds0 + 1
    assert res[0]["accepted"] == 1 and res[0]["tokens"] == refs[0][1:3]
    assert res[1]["accepted"] == 3 and res[1]["tokens"] == refs[1][1:5]
    assert dec.kv.audit() == []


def test_rollback_refcounts_and_shared_page_guard():
    kv = PagedKVCache(
        n_layers=1, n_heads=2, head_dim=4, dtype=jnp.float32,
        max_slots=2, seq_len=16, page_len=4, num_pages=8,
    )
    # private lookahead pages roll back cleanly
    for _ in range(4):
        kv.alloc_slot_page(0)
    assert kv.pages_used() == 4
    released = kv.truncate_slot(0, 6)  # keep ceil(6/4) = 2 pages
    assert released == 2
    assert int(kv.alloc_count[0]) == 2
    assert kv.pages_used() == 2
    assert kv.rollback_pages_total == 2
    assert kv.audit() == []
    # truncating into a prefix-cache-held page is a refcounting bug and
    # must raise, not silently free shared state
    assert kv.register_prefix(0, [1, 2, 3, 4, 5, 6, 7, 8]) == 2
    with pytest.raises(AssertionError, match="rollback_private_only"):
        kv.truncate_slot(0, 2)
    assert kv.audit() == []


def test_ensure_lookahead_pages_clamps_under_pressure(swarm):
    model, params = swarm
    dec = SwarmKVDecoder(
        model, params, max_slots=1, kv_layout="paged", page_len=2,
        num_pages=4, prefix_cache=False,  # 3 usable pages
    )
    dec.prefill_into_slot(0, [1, 2, 3], stream_id="s")  # pos 3, 2 pages
    assert dec.ensure_decode_pages() == []
    # pos 3 needs page 1 (held); lookahead 4 would need pages up to
    # logical 3 — only one free page remains, so the clamp bites
    k = dec.ensure_lookahead_pages(0, 4)
    assert k == 2  # pages 0..2 cover positions 0..5 → pos+2 max
    assert dec.kv.audit() == []


# ---------------------------------------------------------------------------
# gateway end-to-end: spec on == spec off, token for token
# ---------------------------------------------------------------------------

# a prompt whose continuation revisits itself so the n-gram drafter has
# something to copy (tiny random-init models loop under greedy anyway)
REPETITIVE = [5, 6, 7, 5, 6, 7, 5, 6]


def test_gateway_spec_decode_token_identical_greedy(swarm):
    model, params = swarm
    prompts = [REPETITIVE, [1, 2, 1, 2, 1], [9, 8, 9, 8]]
    results = {}
    for label, k in (("spec", 4), ("plain", 0)):
        with Gateway(
            model, params, max_slots=4, spec_k=k, spec_drafter="ngram"
        ) as gw:
            client = GatewayClient(gw.endpoint)
            outs = [client.generate(p, 6) for p in prompts]
            assert all(
                not o.get("shed") and not o.get("error") for o in outs
            )
            results[label] = [o["tokens"] for o in outs]
            if k:
                s = gw.scheduler.stats()
                assert s["spec_rounds_total"] >= 1
                assert s["spec_tokens_total"] >= 1
                assert gw.scheduler.audit() == []
    assert results["spec"] == results["plain"]


@pytest.mark.parametrize("seed", SEEDS)
def test_gateway_spec_decode_token_identical_sampled(swarm, seed):
    model, params = swarm
    prompts = [REPETITIVE, [1, 2, 1, 2, 1]]
    results = {}
    for label, k in (("spec", 3), ("plain", 0)):
        with Gateway(
            model, params, max_slots=4, spec_k=k, spec_drafter="ngram"
        ) as gw:
            client = GatewayClient(gw.endpoint)
            outs = [
                client.generate(
                    p, 6, seed=seed + i, temperature=0.8, top_k=6
                )
                for i, p in enumerate(prompts)
            ]
            assert all(
                not o.get("shed") and not o.get("error") for o in outs
            )
            results[label] = [o["tokens"] for o in outs]
            if k:
                assert gw.scheduler.audit() == []
    assert results["spec"] == results["plain"]


def test_gateway_spec_decode_trunk_drafter_token_identical(swarm):
    model, params = swarm
    prompts = [REPETITIVE, [4, 5, 6]]
    results = {}
    for label, k in (("spec", 3), ("plain", 0)):
        with Gateway(
            model, params, max_slots=4, spec_k=k, spec_drafter="trunk"
        ) as gw:
            client = GatewayClient(gw.endpoint)
            outs = [client.generate(p, 6) for p in prompts]
            assert all(
                not o.get("shed") and not o.get("error") for o in outs
            )
            results[label] = [o["tokens"] for o in outs]
    assert results["spec"] == results["plain"]


def test_gateway_spec_acceptance_counters_make_sense(swarm):
    model, params = swarm
    with Gateway(
        model, params, max_slots=2, spec_k=4, spec_drafter="ngram"
    ) as gw:
        client = GatewayClient(gw.endpoint)
        out = client.generate(REPETITIVE, 7)
        assert not out.get("error") and len(out["tokens"]) == 7
        s = gw.scheduler.stats()
        assert s["spec_k"] == 4
        assert 0 <= s["spec_accepted_total"] <= s["spec_proposed_total"]
        assert s["spec_tokens_total"] >= s["spec_accepted_total"]
        # the whole point: fewer rounds than tokens on a repetitive
        # stream the drafter can copy
        assert s["spec_rounds_total"] < s["spec_tokens_total"]
        assert 0.0 <= s["spec_acceptance_rate"] <= 1.0
        assert s["spec_effective_k"] >= 1.0


def test_gen_submit_rejects_hostile_sampling_fields(swarm):
    model, params = swarm
    from learning_at_home_tpu.utils.connection import RemoteCallError

    with Gateway(model, params, max_slots=2) as gw:
        client = GatewayClient(gw.endpoint)
        for bad in (
            {"temperature": float("nan")},
            {"temperature": -1.0},
            {"temperature": True},
            {"top_p": 0.0},
            {"top_p": 2.0},
            {"top_k": -3},
            {"top_k": 1.5},
            {"seed": -7},
            {"seed": "abc"},
        ):
            meta = {"prompt": [1, 2, 3], "max_new_tokens": 2, **bad}
            with pytest.raises(RemoteCallError):
                client._rpc("gen_submit", meta)
        # a clean sampled stream still serves after the rejects
        out = client.generate([1, 2, 3], 3, seed=5, temperature=0.7)
        assert not out.get("error") and len(out["tokens"]) == 3
        assert gw.scheduler.streams_errored_total == 0


# ---------------------------------------------------------------------------
# lah_top speculation panel
# ---------------------------------------------------------------------------


def test_lah_top_speculation_panel():
    import importlib

    lah_top = importlib.import_module("tools.lah_top")

    def row(peer_id, gateway_section):
        return {
            "peer_id": peer_id, "role": "gateway",
            "endpoint": ("127.0.0.1", 1), "expires_at": 0.0,
            "snapshot": {"gateway": gateway_section, "metrics": {}},
        }

    rows = [
        row("gw-spec", {
            "streams_active": 1, "streams_total": 9, "slots": 4,
            "slots_in_use": 2, "shed_total": 0, "spec_k": 4,
            "spec_acceptance_rate": 0.71, "spec_effective_k": 2.9,
            "spec_rounds_total": 55, "spec_draft_seconds_total": 0.2,
            "spec_verify_seconds_total": 1.8,
        }),
        # spec-off and malformed gateways get NO panel row, never a crash
        row("gw-off", {"slots": 4, "spec_k": 0}),
        row("gw-bool", {"slots": 4, "spec_k": True}),
        row("gw-junk", {"slots": 4, "spec_k": "four"}),
    ]
    out = lah_top.render(rows, "swarm", dead=set())
    assert "SPECULATION" in out
    panel = out.split("SPECULATION")[1]
    line = next(
        ln for ln in panel.splitlines() if ln.strip().startswith("gw-spec")
    )
    assert "71.0%" in line and "2.90" in line and "55" in line
    assert "10.0%" in line  # draft share: 0.2 / (0.2 + 1.8)
    for peer in ("gw-off", "gw-bool", "gw-junk"):
        assert peer not in panel
    # no speculative gateway anywhere -> no panel at all
    out = lah_top.render(rows[1:], "swarm", dead=set())
    assert "SPECULATION" not in out
