"""Decentralized parameter averaging: matchmaking, butterfly parity,
mid-round death, late joiners, chaos-dropped frames (ISSUE 3).

All tests run real averager peers — own loops, TCP endpoints, and a real
in-process DHT for rendezvous — at tiny tree sizes, so they exercise the
full wire path (v2 mux frames, held replies) in tier-1 time."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.averaging import (
    AveragingConfig,
    AveragingFailed,
    DecentralizedAverager,
)
from learning_at_home_tpu.averaging.partitioning import (
    chunk_ranges,
    flatten_tree,
    partition_bounds,
    unflatten_tree,
    weighted_mean,
)
from learning_at_home_tpu.dht import DHT


# ---------------------------------------------------------------------------
# pure partitioning helpers
# ---------------------------------------------------------------------------


def test_flatten_roundtrip_mixed_dtypes():
    tree = {
        "w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": jnp.ones((4,), jnp.bfloat16),
        "nested": [jnp.float32(3.5), jnp.zeros((2, 2), jnp.float32)],
    }
    vec, treedef, specs = flatten_tree(tree)
    assert vec.dtype == np.float32
    back = unflatten_tree(vec, treedef, specs)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partition_bounds_cover_and_chunk_ranges():
    bounds = partition_bounds(10, 4)
    assert bounds == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert partition_bounds(2, 4) == [(0, 1), (1, 2), (2, 2), (2, 2)]
    assert chunk_ranges(10, 4) == [(0, 4), (4, 4), (8, 2)]
    assert chunk_ranges(0, 4) == [(0, 0)]  # empty partition still framed


def test_weighted_mean_matches_tree_map_mean_bitwise():
    rs = np.random.RandomState(0)
    vecs = [rs.randn(37).astype(np.float32) for _ in range(4)]
    got = weighted_mean(
        [(f"p{i}", 1.0, v) for i, v in enumerate(vecs)]
    )
    want = np.asarray(sum(vecs) / 4)
    np.testing.assert_array_equal(got, want)  # atol=0: same order, f32


# ---------------------------------------------------------------------------
# multi-peer rounds over the real stack
# ---------------------------------------------------------------------------


def _make_tree(seed: int, d: int = 17):
    rs = np.random.RandomState(seed)
    return {
        "embed": jnp.asarray(rs.randn(3, d).astype(np.float32)),
        "gate": {"w": jnp.asarray(rs.randn(d).astype(np.float32))},
    }


def _run_rounds(averagers, trees, matchmaking_timeout=20.0):
    """step_round on every averager concurrently; returns results list
    aligned with ``averagers`` (None entries for peers that raised)."""
    results = [None] * len(averagers)
    errors = []

    def run(i):
        try:
            results[i] = averagers[i].step_round(
                trees[i], matchmaking_timeout=matchmaking_timeout
            )
        except BaseException as e:
            errors.append((i, e))

    threads = [
        threading.Thread(target=run, args=(i,), daemon=True)
        for i in range(len(averagers))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "averaging round hung"
    return results, errors


@pytest.fixture
def dht():
    d = DHT()
    yield d
    d.shutdown()


def _spawn(dht, n, cfg=None, chaos=None, peer_ids=None):
    cfg = cfg or AveragingConfig()
    out = []
    for i in range(n):
        out.append(
            DecentralizedAverager(
                dht, config=cfg,
                peer_id=(peer_ids[i] if peer_ids else f"peer{i:02d}"),
                chaos=(chaos[i] if chaos else None),
            )
        )
    return out


def test_two_peer_round_bitwise_identical(dht):
    cfg = AveragingConfig(min_group_size=2, max_group_size=2,
                          part_timeout=3.0)
    a, b = _spawn(dht, 2, cfg)
    trees = [_make_tree(0), _make_tree(1)]
    try:
        results, errors = _run_rounds([a, b], trees)
        assert not errors, errors
        (tree_a, info_a), (tree_b, info_b) = results
        assert info_a["gid"] == info_b["gid"]
        assert not info_a["degraded"] and not info_b["degraded"]
        for la, lb in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        # and the value IS the mean of the inputs
        want = jax.tree.map(lambda x, y: (x + y) / 2, *trees)
        for la, lw in zip(jax.tree.leaves(tree_a), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lw))
        assert a.stats()["rounds"] == 1 and b.stats()["rounds"] == 1
        assert a.stats()["bytes_sent"] > 0 and b.stats()["bytes_sent"] > 0
    finally:
        a.shutdown()
        b.shutdown()


def test_four_peer_butterfly_parity_with_local_mean(dht):
    # chunk_elems=7 forces multi-chunk partitions: the chunked wire path
    # must reassemble exactly
    cfg = AveragingConfig(min_group_size=4, max_group_size=4,
                          part_timeout=5.0, chunk_elems=7)
    avgs = _spawn(dht, 4, cfg)
    trees = [_make_tree(i) for i in range(4)]
    try:
        results, errors = _run_rounds(avgs, trees)
        assert not errors, errors
        # peers are sorted by peer_id == spawn order == trees order, so
        # the local reference accumulates in the same order
        want = jax.tree.map(lambda *xs: sum(xs) / 4, *trees)
        for tree_i, info in results:
            assert not info["degraded"], info
            assert info["group_size"] == 4
            for li, lw in zip(
                jax.tree.leaves(tree_i), jax.tree.leaves(want)
            ):
                np.testing.assert_array_equal(  # atol=0 parity
                    np.asarray(li), np.asarray(lw)
                )
    finally:
        for av in avgs:
            av.shutdown()


def test_member_death_mid_round_degrades_not_hangs(dht):
    part_timeout = 1.5
    cfg = AveragingConfig(
        min_group_size=3, max_group_size=3, part_timeout=part_timeout
    )
    avgs = _spawn(dht, 3, cfg)
    dead = avgs[2]  # a FOLLOWER (leader is the smallest peer id)
    dead.debug_die_after_match = True  # joins, then sends/serves nothing
    trees = [_make_tree(i) for i in range(3)]
    try:
        t0 = time.monotonic()
        results, errors = _run_rounds(avgs, trees)
        elapsed = time.monotonic() - t0
        assert not errors, errors
        # the configured bound: survivors must finish within the round
        # timeout, not hang on the dead peer
        assert elapsed < cfg.resolved_round_timeout() + 10
        (tree_a, info_a), (tree_b, info_b), (tree_c, info_c) = results
        assert tree_c is None and info_c.get("died_after_match")
        assert info_a["degraded"] and info_b["degraded"]
        assert avgs[0].stats()["degraded_rounds"] == 1
        assert avgs[1].stats()["degraded_rounds"] == 1
        # survivors' OWN partitions are the re-weighted mean over the two
        # survivors; the dead member's partition kept local values
        vecs = [flatten_tree(t)[0] for t in trees]
        bounds = partition_bounds(vecs[0].size, 3)
        got_a = flatten_tree(tree_a)[0]
        got_b = flatten_tree(tree_b)[0]
        for lo, hi in bounds[:2]:  # partitions owned by survivors
            want = (vecs[0][lo:hi] + vecs[1][lo:hi]) / np.float32(2.0)
            np.testing.assert_array_equal(got_a[lo:hi], want)
            np.testing.assert_array_equal(got_b[lo:hi], want)
        lo, hi = bounds[2]  # dead member's partition: local values kept
        np.testing.assert_array_equal(got_a[lo:hi], vecs[0][lo:hi])
        np.testing.assert_array_equal(got_b[lo:hi], vecs[1][lo:hi])
        assert 2 in info_a["failed_parts"] and 2 in info_b["failed_parts"]
    finally:
        for av in avgs:
            av.shutdown()


def test_late_joiner_waits_for_next_epoch(dht):
    from learning_at_home_tpu.server.chaos import ChaosConfig

    # follower bb's avg_part replies are chaos-delayed 1.5 s, so the
    # LEADER aa (whom cc will knock at) stays visibly mid-round waiting
    # for its bb-owned partition — a deterministic wait window for cc
    slow = ChaosConfig(averaging_base_latency=1.5, seed=0).make()
    cfg = AveragingConfig(
        min_group_size=2, max_group_size=3, part_timeout=6.0,
        gather_timeout=4.0,
    )
    a, b = _spawn(dht, 2, cfg, peer_ids=["aa", "bb"], chaos=[None, slow])
    late = DecentralizedAverager(dht, config=cfg, peer_id="cc")
    trees = [_make_tree(0), _make_tree(1)]
    try:
        round1 = {}

        def run_first(av, key, tree):
            round1[key] = av.step_round(tree, matchmaking_timeout=20.0)

        ta = threading.Thread(target=run_first, args=(a, "a", trees[0]),
                              daemon=True)
        tb = threading.Thread(target=run_first, args=(b, "b", trees[1]),
                              daemon=True)
        ta.start()
        tb.start()
        # wait until the leader froze the group and is mid-round, THEN
        # knock: cc must be told to wait for the next epoch, never break
        # into the running round
        deadline = time.monotonic() + 15
        while not a._round_active and time.monotonic() < deadline:
            time.sleep(0.02)
        assert a._round_active, "round 1 never became active"
        late_result = {}

        def run_late():
            late_result["r"] = late.step_round(
                _make_tree(2), matchmaking_timeout=40.0
            )

        tl = threading.Thread(target=run_late, daemon=True)
        tl.start()
        ta.join(timeout=45)
        tb.join(timeout=45)
        assert not ta.is_alive() and not tb.is_alive()
        epoch1 = round1["a"][1]["epoch"]
        assert round1["a"][1]["members"] == ["aa", "bb"]
        # round 2: aa and bb go again; cc (still retrying) joins this one
        results, errors = _run_rounds(
            [a, b], trees, matchmaking_timeout=30.0
        )
        assert not errors, errors
        tl.join(timeout=60)
        assert not tl.is_alive(), "late joiner hung"
        assert "r" in late_result
        _, late_info = late_result["r"]
        assert late_info["epoch"] > epoch1
        assert "cc" in late_info["members"]
        assert late.stats()["late_join_waits"] >= 1
    finally:
        a.shutdown()
        b.shutdown()
        late.shutdown()


def test_chaos_dropped_frames_trigger_timeout_path(dht):
    from learning_at_home_tpu.server.chaos import ChaosConfig

    # peer1's handler drops every avg_part REPLY: peer0's sends to it
    # time out → peer0 completes degraded; the data still reached peer1,
    # so peer1's own partition reduces fully
    chaos = ChaosConfig(averaging_drop_prob=1.0, seed=0).make()
    cfg = AveragingConfig(
        min_group_size=2, max_group_size=2, part_timeout=1.0,
        sender_timeout=2.0, round_timeout=6.0,
    )
    a, b = _spawn(dht, 2, cfg, chaos=[None, chaos])
    try:
        t0 = time.monotonic()
        results, errors = _run_rounds(
            [a, b], [_make_tree(0), _make_tree(1)]
        )
        assert not errors, errors
        assert time.monotonic() - t0 < 30
        (_, info_a), (_, info_b) = results
        assert info_a["degraded"], info_a  # the dropped-reply partition
        assert 1 in info_a["failed_parts"]
        assert chaos.injected_averaging_drops >= 1
        assert a.stats()["degraded_rounds"] == 1
    finally:
        a.shutdown()
        b.shutdown()


def test_chunk_cap_prevents_held_reply_starvation(dht):
    """chunk_elems=1 on a ~500-element tree would mean ~250 held-reply
    chunk RPCs per partition — far over the mux in-flight limit (64),
    which deadlocks-until-timeout because reduction needs ALL chunks
    admitted before ANY reply resolves.  The MAX_CHUNKS_PER_PART cap
    widens chunks instead; the round must complete cleanly."""
    cfg = AveragingConfig(min_group_size=2, max_group_size=2,
                          part_timeout=3.0, chunk_elems=1)
    a, b = _spawn(dht, 2, cfg)
    trees = [_make_tree(i, d=29) for i in range(2)]  # 3*29 + 29 = 116/leafset
    try:
        results, errors = _run_rounds([a, b], trees)
        assert not errors, errors
        (tree_a, info_a), (tree_b, _) = results
        assert not info_a["degraded"], info_a
        want = jax.tree.map(lambda x, y: (x + y) / 2, *trees)
        for la, lw in zip(jax.tree.leaves(tree_a), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lw))
    finally:
        a.shutdown()
        b.shutdown()


def test_matchmaking_times_out_alone(dht):
    cfg = AveragingConfig(min_group_size=2, poll=0.1)
    av = _spawn(dht, 1, cfg)[0]
    try:
        with pytest.raises(AveragingFailed):
            av.step_round(_make_tree(0), matchmaking_timeout=1.5)
        assert av.stats()["matchmaking_failures"] == 1
    finally:
        av.shutdown()


def test_weighted_degraded_mean_reweights(dht):
    """Unequal weights: the survivors' mean uses THEIR weights only."""
    cfg_a = AveragingConfig(min_group_size=3, max_group_size=3,
                            part_timeout=1.5, weight=1.0)
    cfg_b = AveragingConfig(min_group_size=3, max_group_size=3,
                            part_timeout=1.5, weight=3.0)
    cfg_dead = AveragingConfig(min_group_size=3, max_group_size=3,
                               part_timeout=1.5)
    a = DecentralizedAverager(dht, config=cfg_a, peer_id="pa")
    b = DecentralizedAverager(dht, config=cfg_b, peer_id="pb")
    dead = DecentralizedAverager(dht, config=cfg_dead, peer_id="pz")
    dead.debug_die_after_match = True
    trees = [_make_tree(0), _make_tree(1), _make_tree(2)]
    try:
        results, errors = _run_rounds([a, b, dead], trees)
        assert not errors, errors
        (tree_a, info_a), _, _ = results
        assert info_a["degraded"]
        vecs = [flatten_tree(t)[0] for t in trees]
        bounds = partition_bounds(vecs[0].size, 3)
        lo, hi = bounds[0]  # partition owned by pa (sorted first)
        want = (
            vecs[0][lo:hi] * np.float32(1.0)
            + vecs[1][lo:hi] * np.float32(3.0)
        ) / np.float32(4.0)
        got = flatten_tree(tree_a)[0][lo:hi]
        np.testing.assert_array_equal(got, want)
    finally:
        for av in (a, b, dead):
            av.shutdown()


def test_session_background_delta_apply(dht):
    """Background mode (PipelinedSwarmTrainer's shape): notify_step kicks
    a round off-thread; the group delta is applied through apply_fn.
    With no steps taken during the round, delta-apply == group mean."""
    from learning_at_home_tpu.averaging import AveragingSession

    cfg = AveragingConfig(min_group_size=2, max_group_size=2,
                          part_timeout=3.0)
    a, b = _spawn(dht, 2, cfg)
    sa = AveragingSession(a, every_steps=1)
    sb = AveragingSession(b, every_steps=1)
    params = [_make_tree(0), _make_tree(1)]
    snap0 = [params[0], params[1]]
    locks = [threading.Lock(), threading.Lock()]

    def wire(i, session):
        def snapshot():
            with locks[i]:
                return params[i]

        def apply_fn(transform):
            with locks[i]:
                params[i] = transform(params[i])

        session.attach_trainer(snapshot, apply_fn)

    try:
        wire(0, sa)
        wire(1, sb)
        sa.notify_step(1)
        sb.notify_step(1)
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline:
            if sa.rounds_applied >= 1 and sb.rounds_applied >= 1:
                break
            time.sleep(0.05)
        assert sa.rounds_applied == 1 and sb.rounds_applied == 1, (
            sa.averaging_stats(), sb.averaging_stats()
        )
        want = jax.tree.map(lambda x, y: (x + y) / 2, snap0[0], snap0[1])
        for i in range(2):
            for leaf, lw in zip(
                jax.tree.leaves(params[i]), jax.tree.leaves(want)
            ):
                np.testing.assert_allclose(
                    np.asarray(leaf), np.asarray(lw), atol=1e-6
                )
    finally:
        sa.shutdown()
        sb.shutdown()


def test_session_blocking_round_and_stats(dht):
    from learning_at_home_tpu.averaging import AveragingSession

    cfg = AveragingConfig(min_group_size=2, max_group_size=2,
                          part_timeout=3.0)
    a, b = _spawn(dht, 2, cfg)
    sa, sb = AveragingSession(a), AveragingSession(b)
    trees = [_make_tree(0), _make_tree(1)]
    out = [None, None]
    try:
        threads = [
            threading.Thread(
                target=lambda i, s: out.__setitem__(
                    i, s.blocking_round(trees[i], matchmaking_timeout=20.0)
                ),
                args=(i, s), daemon=True,
            )
            for i, s in enumerate((sa, sb))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        for la, lb in zip(jax.tree.leaves(out[0]), jax.tree.leaves(out[1])):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        stats = sa.averaging_stats()
        assert stats["rounds"] == 1 and stats["rounds_applied"] == 1
        assert stats["round_p50_ms"] is not None
        # a lone failed round is counted, not raised
        lone = sa.blocking_round(trees[0], matchmaking_timeout=0.5)
        assert lone is trees[0]
        assert sa.averaging_stats()["rounds_skipped"] == 1
    finally:
        sa.shutdown()
        sb.shutdown()


# ---------------------------------------------------------------------------
# quantized wire chunks (ISSUE 5): only the wire compresses — the f32
# sorted-peer reduction and the bitwise-equality contract are untouched
# ---------------------------------------------------------------------------


def test_quantized_wire_keeps_members_bitwise_identical(dht):
    """With blockq8 chunks, every member must still end with IDENTICAL
    bytes per reduced partition (replies stay raw f32 — one exact result
    distribution), within quantization error of the true mean, with the
    contribute direction actually quantized (counter + bytes)."""
    cfg = AveragingConfig(min_group_size=3, max_group_size=3,
                          part_timeout=3.0, chunk_elems=1 << 10,
                          wire_codec="blockq8")
    avs = _spawn(dht, 3, cfg)
    trees = [_make_tree(i, d=997) for i in range(3)]
    try:
        results, errors = _run_rounds(avs, trees)
        assert not errors, errors
        outs = [r[0] for r in results]
        for r in results:
            assert not r[1]["degraded"], r[1]
        for other in outs[1:]:
            for la, lb in zip(jax.tree.leaves(outs[0]),
                              jax.tree.leaves(other)):
                np.testing.assert_array_equal(
                    np.asarray(la), np.asarray(lb)
                )
        exact = jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees)
        for la, le in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(exact)):
            err = float(np.abs(np.asarray(la) - np.asarray(le)).max())
            assert err < 0.1, err  # quantization-bounded, not exact
        stats = [av.stats() for av in avs]
        assert all(s["quantized_chunks"] > 0 for s in stats), stats
        assert all(s["wire_codec"] == "blockq8" for s in stats)
        # contribute direction really shrank: quantized bytes received
        # are well under the raw-f32 volume a ``none`` round would move
        raw_per_owner = sum(t.size for t in jax.tree.leaves(trees[0])) * 4
        for s in stats:
            assert s["bytes_received"] < raw_per_owner, (
                s["bytes_received"], raw_per_owner,
            )
    finally:
        for av in avs:
            av.shutdown()


def test_quantized_wire_falls_back_against_no_codec_owner(dht, monkeypatch):
    """An owner whose hello does not advertise ``codec`` (old build) must
    transparently receive raw f32 chunks — the round still completes and
    stays exact."""
    from learning_at_home_tpu.averaging import handler as avg_handler

    monkeypatch.setattr(avg_handler, "AVERAGING_FEATURES", ("mux",))
    cfg = AveragingConfig(min_group_size=2, max_group_size=2,
                          part_timeout=3.0, wire_codec="u8")
    a, b = _spawn(dht, 2, cfg)
    trees = [_make_tree(0), _make_tree(1)]
    try:
        results, errors = _run_rounds([a, b], trees)
        assert not errors, errors
        (tree_a, info_a), (tree_b, _) = results
        assert not info_a["degraded"]
        want = jax.tree.map(lambda x, y: (x + y) / 2, *trees)
        for la, lw in zip(jax.tree.leaves(tree_a), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lw))
        # nothing arrived quantized: the senders saw no codec feature
        assert a.stats()["quantized_chunks"] == 0
        assert b.stats()["quantized_chunks"] == 0
    finally:
        a.shutdown()
        b.shutdown()
