"""Counter-based sampling RNG (ISSUE 17): a sampled token depends only on
``(logits, stream_seed, absolute position)``, so paged == dense, any prefill
chunk size, recompute-after-preemption and coalesced vs solo execution all
reproduce identical sampled streams (the PR 13 parity contracts extended
past greedy).  A module apart from ``tests/test_spec_decode.py`` (the
speculation): each parity case compiles its decoders anew, and under ``--dist
loadfile`` a file is one worker's.
"""

import numpy as np
import pytest

from test_spec_decode import (  # noqa: F401  (``swarm`` is a fixture)
    SEEDS,
    SEQ,
    VOCAB,
    _poll_done,
    _sp,
    swarm,
)
from learning_at_home_tpu.gateway import Gateway, GatewayClient
from learning_at_home_tpu.models.sampling import SamplingParams, sample_token
from learning_at_home_tpu.models.swarm_decoder import SwarmKVDecoder


# ---------------------------------------------------------------------------
# the sampling primitive itself (no swarm)
# ---------------------------------------------------------------------------


def test_sampling_params_reject_hostile_values():
    for bad in (
        dict(temperature=-0.5),
        dict(temperature=float("nan")),
        dict(temperature=float("inf")),
        dict(top_p=0.0),
        dict(top_p=1.5),
        dict(top_p=float("nan")),
        dict(top_k=-1),
        dict(seed=-1),
        dict(seed=2 ** 63),
    ):
        with pytest.raises(ValueError):
            SamplingParams(**bad)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.7).greedy


def test_sample_token_is_a_pure_function_of_seed_and_position():
    rng = np.random.RandomState(0)
    logits = rng.randn(VOCAB).astype(np.float32)
    sp = _sp(seed=3)
    draws = [sample_token(logits, sp, pos) for pos in range(32)]
    # deterministic under replay, regardless of call order
    for pos in reversed(range(32)):
        assert sample_token(logits, sp, pos) == draws[pos]
    # the counter actually matters: positions do not all collide
    assert len(set(draws)) > 1
    # a different stream seed is a different sequence
    other = [sample_token(logits, _sp(seed=4), pos) for pos in range(32)]
    assert draws != other


def test_sample_token_greedy_and_mask_limits():
    rng = np.random.RandomState(1)
    logits = rng.randn(VOCAB).astype(np.float32)
    argmax = int(np.argmax(logits))
    # temperature 0 / params None are bitwise argmax
    assert sample_token(logits, None, 5) == argmax
    assert sample_token(logits, SamplingParams(), 5) == argmax
    # top_k=1 collapses every draw onto the argmax
    sp1 = SamplingParams(seed=9, temperature=1.3, top_k=1)
    assert all(
        sample_token(logits, sp1, pos) == argmax for pos in range(16)
    )
    # a tiny nucleus still always keeps the top token
    spp = SamplingParams(seed=9, temperature=1.3, top_p=1e-6)
    assert all(
        sample_token(logits, spp, pos) == argmax for pos in range(16)
    )
    # top_k masks: every draw is one of the k largest logits
    spk = SamplingParams(seed=11, temperature=2.0, top_k=4)
    top4 = set(np.argsort(-logits)[:4].tolist())
    assert all(
        sample_token(logits, spk, pos) in top4 for pos in range(32)
    )


# ---------------------------------------------------------------------------
# PR 13 parity contracts, extended to sampled streams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_sampled_paged_vs_dense_token_parity(swarm, seed):
    model, params = swarm
    prompts = [[1, 2, 3], [4, 5], [7, 8, 9, 10, 11]]
    sampling = [_sp(seed + i) for i in range(len(prompts))]
    dense = SwarmKVDecoder(model, params, max_slots=3)
    paged = SwarmKVDecoder(
        model, params, max_slots=3, kv_layout="paged", page_len=4
    )
    out_d = dense.generate(prompts, max_new_tokens=6, sampling=sampling)
    out_p = paged.generate(prompts, max_new_tokens=6, sampling=sampling)
    assert out_d == out_p


@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_sampled_chunked_prefill_token_equal_any_chunk_size(swarm, chunk):
    model, params = swarm
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    sp = _sp(SEEDS[0])
    ref = SwarmKVDecoder(model, params, max_slots=1).generate(
        [prompt], max_new_tokens=4, sampling=[sp]
    )[0]
    dec = SwarmKVDecoder(
        model, params, max_slots=1, kv_layout="paged", page_len=4,
        prefix_cache=False,
    )
    dec.begin_prefill(0, prompt, stream_id="s", sampling=sp)
    toks = []
    tok = None
    while tok is None:
        _consumed, tok = dec.prefill_step(0, chunk)
    toks.append(tok)
    while len(toks) < 4:
        assert dec.ensure_decode_pages() == []
        toks.append(int(dec.decode_step()[0]))
    assert toks == ref


@pytest.mark.parametrize("seed", SEEDS)
def test_sampled_recompute_after_preemption_token_identical(swarm, seed):
    """The pool is too small for both streams' full depth, so one gets
    preempted and recomputed — with the counter-based RNG the sampled
    continuation is identical to an uncontended run (the contract greedy
    streams always had)."""
    model, params = swarm
    prompts = [[1, 2], [9, 8]]
    n_new = SEQ - 2
    sampling = {tuple(p): _sp(seed + i) for i, p in enumerate(prompts)}
    ref = {}
    for p in prompts:
        ref[tuple(p)] = SwarmKVDecoder(model, params, max_slots=1).generate(
            [p], max_new_tokens=n_new, sampling=[sampling[tuple(p)]]
        )[0]
    with Gateway(
        model, params, max_slots=2, max_pending=64,
        page_len=2, num_pages=10,  # 9 usable < 2 streams × 8 pages
        prefix_cache=False, prefill_chunk_tokens=4,
    ) as gw:
        client = GatewayClient(gw.endpoint)
        sids = [
            gw.scheduler.submit(p, n_new, sampling=sampling[tuple(p)])
            for p in prompts
        ]
        for p, sid in zip(prompts, sids):
            out = _poll_done(client, sid)
            assert out.get("error") is None, out
            assert out["tokens"] == ref[tuple(p)]
        assert gw.scheduler.preemptions_total >= 1
        assert gw.scheduler.streams_errored_total == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_sampled_coalesced_vs_solo_parity(swarm, seed):
    """Coalescing groups expert fan-outs across streams; with sampling
    on, the grouped and ungrouped gateways must still emit identical
    per-stream tokens (bitwise logits + counter-keyed draws)."""
    model, params = swarm
    prompts = [[1, 2, 3], [4, 5, 6, 7], [7, 8]]
    results = {}
    for label, coalesce in (("grouped", True), ("solo", False)):
        with Gateway(model, params, max_slots=4, coalesce=coalesce) as gw:
            client = GatewayClient(gw.endpoint)
            outs = [
                client.generate(
                    p, 4, seed=seed + i, temperature=0.9,
                    top_p=0.95, top_k=8,
                )
                for i, p in enumerate(prompts)
            ]
            assert all(
                not o.get("shed") and not o.get("error") for o in outs
            )
            results[label] = [o["tokens"] for o in outs]
    assert results["grouped"] == results["solo"]
