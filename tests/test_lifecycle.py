"""Elastic swarm lifecycle tests (ISSUE 9): graceful drain, live expert
migration (bitwise params + optimizer state), checkpoint fallback,
restart-from-checkpoint rejoin, and the zero-disruption drain contract."""

import time

import jax
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.dht import DHT
from learning_at_home_tpu.server import lifecycle
from learning_at_home_tpu.server.server import Server


@pytest.fixture(autouse=True)
def _reset_rpc():
    yield
    reset_client_rpc()


def _state_leaves(state: dict) -> list:
    return [
        np.asarray(leaf)
        for leaf in jax.tree_util.tree_leaves(
            {"params": state["params"], "opt_state": state["opt_state"]}
        )
    ]


def assert_state_bitwise(a: dict, b: dict):
    la, lb = _state_leaves(a), _state_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# pure wire helpers
# ---------------------------------------------------------------------------


def test_split_parts_respects_cap_and_keeps_order():
    leaves = [np.zeros(n, np.float32) for n in (10, 10, 1000, 10)]
    parts = lifecycle.split_parts(leaves, part_bytes=100)
    # order preserved, every index exactly once
    assert [i for part in parts for i in part] == [0, 1, 2, 3]
    # the 4000-byte leaf exceeds the cap: it travels alone
    assert [2] in parts
    for part in parts:
        if part != [2]:
            assert sum(leaves[i].nbytes for i in part) <= 100
    # zero leaves still yields one (empty) part: the RPC sequence exists
    assert lifecycle.split_parts([], part_bytes=100) == [[]]


def test_verify_manifest_catches_any_bit_flip():
    leaves, manifest = lifecycle.flatten_state(
        {"params": {"w": np.arange(8, dtype=np.float32)},
         "opt_state": {"c": np.ones((2, 3), np.int32)}}
    )
    assert lifecycle.verify_manifest(leaves, manifest)
    f32_idx = next(
        i for i, l in enumerate(leaves) if l.dtype == np.float32
    )
    flipped = list(leaves)
    flipped[f32_idx] = leaves[f32_idx].copy()
    flipped[f32_idx][3] = np.nextafter(
        flipped[f32_idx][3], np.float32(np.inf), dtype=np.float32
    )  # exactly one ULP
    assert not lifecycle.verify_manifest(flipped, manifest)
    # shape/dtype/count mismatches are refusals, not crashes
    assert not lifecycle.verify_manifest(leaves[:1], manifest)
    cast = list(leaves)
    cast[f32_idx] = leaves[f32_idx].astype(np.float64)
    assert not lifecycle.verify_manifest(cast, manifest)
    reshaped = list(leaves)
    reshaped[f32_idx] = leaves[f32_idx].reshape(2, 4)
    assert not lifecycle.verify_manifest(reshaped, manifest)


# ---------------------------------------------------------------------------
# drain state machine + heartbeat steering
# ---------------------------------------------------------------------------


def test_drain_flips_state_and_stops_expert_heartbeat():
    boot = DHT()
    d_a = DHT(initial_peers=[boot.endpoint])
    d_c = DHT(initial_peers=[boot.endpoint])
    srv = Server.create(
        expert_uids=["dr.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.01), dht=d_a, update_period=0.4,
    )
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            if d_c._loop.run(d_c._get_alive("dr")):
                break
            time.sleep(0.1)
        assert d_c._loop.run(d_c._get_alive("dr")), "never declared"
        assert srv.lifecycle_state == lifecycle.SERVING
        # no successor, no checkpoint root: the drain just steers away
        summary = srv.drain(grace=0.0, quiesce_timeout=2.0, handoff=False)
        assert srv.lifecycle_state == lifecycle.DRAINED
        assert summary["handed_off"] == []
        assert srv.wait_drained(timeout=1.0)
        # expert records expire (one TTL = 2 x update_period) because the
        # DRAINING/DRAINED server no longer re-declares them
        deadline = time.time() + 10
        while time.time() < deadline:
            if not d_c._loop.run(d_c._get_alive("dr")):
                break
            time.sleep(0.2)
        assert not d_c._loop.run(d_c._get_alive("dr")), (
            "expert records survived the drain"
        )
        # draining twice is an error, not a second drain
        with pytest.raises(RuntimeError):
            srv.drain(grace=0.0)
        info = srv.lifecycle_info()
        assert info["state"] == lifecycle.DRAINED
        assert info["restarts"] == 0
        assert info["uptime_s"] >= 0
    finally:
        srv.shutdown()
        for d in (d_a, d_c, boot):
            d.shutdown()


# ---------------------------------------------------------------------------
# live migration: bitwise params + optimizer state
# ---------------------------------------------------------------------------


def test_handoff_bitwise_params_and_opt_state():
    boot = DHT()
    d_a = DHT(initial_peers=[boot.endpoint])
    d_b = DHT(initial_peers=[boot.endpoint])
    d_c = DHT(initial_peers=[boot.endpoint])
    srv_a = Server.create(
        expert_uids=["mig.0", "mig.1"], hidden_dim=16, host="127.0.0.1",
        optimizer=optax.adam(1e-3), dht=d_a, update_period=0.5,
    )
    srv_b = Server.create(
        num_experts=0, hidden_dim=16, host="127.0.0.1",
        optimizer=optax.adam(1e-3), dht=d_b, update_period=0.5,
    )
    try:
        # async updates make opt_state non-trivial (adam moments + count)
        x = np.random.RandomState(0).randn(4, 16).astype(np.float32)
        g = np.ones((4, 16), np.float32)
        srv_a.experts["mig.0"].backward([x], [g])
        srv_a.experts["mig.0"].backward([x], [g])
        want = {uid: b.state_dict() for uid, b in srv_a.experts.items()}
        fwd_before = np.asarray(srv_a.experts["mig.0"].forward([x])[0])

        summary = srv_a.drain(
            successor=srv_b.endpoint, grace=0.0, quiesce_timeout=3.0
        )
        assert summary["handed_off"] == ["mig.0", "mig.1"]
        assert summary["failed"] == []
        # the drained server no longer hosts (or serves) the experts
        assert not srv_a.experts
        # MIGRATION CORRECTNESS (acceptance): params AND optimizer state
        # bitwise-equal on the successor, update_count carried
        for uid, state in want.items():
            got = srv_b.experts[uid].state_dict()
            assert_state_bitwise(state, got)
            assert got["update_count"] == state["update_count"]
        assert srv_b.migrated_in == {"mig.0", "mig.1"}
        assert srv_b.handoff.received == 2
        # the migrated expert SERVES the same function bitwise
        fwd_after = np.asarray(srv_b.experts["mig.0"].forward([x])[0])
        np.testing.assert_array_equal(fwd_before, fwd_after)
        # and the successor declared the uids (discoverable via DHT)
        deadline = time.time() + 10
        alive = {}
        while time.time() < deadline:
            alive = d_c._loop.run(d_c._get_alive("mig"))
            if "mig.0" in alive and "mig.1" in alive:
                break
            time.sleep(0.2)
        assert "mig.0" in alive and "mig.1" in alive
    finally:
        for srv in (srv_a, srv_b):
            srv.shutdown()
        for d in (d_a, d_b, d_c, boot):
            d.shutdown()


def test_handoff_overwrites_existing_replica_bitwise():
    """A successor already hosting the uid (as a replica) receives the
    migrated — more-trained — state in place of its own copy."""
    srv_a = Server.create(
        expert_uids=["ow.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.05), dht=None,
    )
    srv_b = Server.create(
        expert_uids=["ow.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.05), dht=None,
    )
    try:
        x = np.random.RandomState(1).randn(2, 8).astype(np.float32)
        g = np.ones((2, 8), np.float32)
        srv_a.experts["ow.0"].backward([x], [g])  # A diverges from B
        want = srv_a.experts["ow.0"].state_dict()
        summary = srv_a.drain(
            successor=srv_b.endpoint, grace=0.0, quiesce_timeout=2.0
        )
        assert summary["handed_off"] == ["ow.0"]
        got = srv_b.experts["ow.0"].state_dict()
        assert_state_bitwise(want, got)
        assert got["update_count"] == 1
        # overwrite path: not re-registered as a replica, but counted in
        assert "ow.0" in srv_b.migrated_in
    finally:
        srv_a.shutdown()
        srv_b.shutdown()


def test_handoff_refused_without_recipe_falls_back_to_checkpoint(tmp_path):
    """A successor that cannot build the expert (no replica recipe)
    refuses the migration; the drain falls back to a checkpoint save the
    restarted server recovers from — bitwise."""
    from learning_at_home_tpu.utils.checkpoint import latest_step

    root = str(tmp_path / "fallback")
    srv_a = Server.create(
        expert_uids=["fb.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.adam(1e-3), dht=None,
    )
    srv_a.replica_checkpoint_root = root
    # a bare Server (no .create) has no recipe to rebuild experts from
    srv_b = Server({}, host="127.0.0.1", dht=None)
    srv_b.run_in_background()
    try:
        x = np.random.RandomState(2).randn(2, 8).astype(np.float32)
        srv_a.experts["fb.0"].backward([x], [np.ones((2, 8), np.float32)])
        want = srv_a.experts["fb.0"].state_dict()
        summary = srv_a.drain(
            successor=srv_b.endpoint, grace=0.0, quiesce_timeout=2.0
        )
        assert summary["handed_off"] == []
        assert summary["failed"] == ["fb.0"]
        assert summary["checkpointed"] == ["fb.0"]
        assert "fb.0" not in srv_b.experts
        assert srv_b.handoff.received == 0
        step = latest_step(root)
        assert step == summary["checkpoint_step"]
        # a restarted server recovers the checkpointed state bitwise
        srv_c = Server.create(
            expert_uids=["fb.0"], hidden_dim=8, host="127.0.0.1",
            optimizer=optax.adam(1e-3), dht=None, start=False,
        )
        srv_c.load_checkpoint(root)
        assert_state_bitwise(want, srv_c.experts["fb.0"].state_dict())
    finally:
        srv_a.shutdown()
        srv_b.shutdown()


def test_draining_server_refuses_inbound_handoff():
    """Drains must not chain: a draining successor refuses migrations
    (the sender picks another successor or checkpoints)."""
    from learning_at_home_tpu.server.lifecycle import (
        HandoffError,
        send_expert_handoff,
    )

    srv_a = Server.create(
        expert_uids=["ch.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.0), dht=None,
    )
    srv_b = Server.create(
        num_experts=0, hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.0), dht=None,
    )
    try:
        srv_b.drain(grace=0.0, quiesce_timeout=1.0, handoff=False)
        with pytest.raises(HandoffError, match="DRAINED"):
            send_expert_handoff(
                srv_b.endpoint, "ch.0",
                srv_a.experts["ch.0"].state_dict(), timeout=10.0,
            )
        assert "ch.0" not in srv_b.experts
    finally:
        srv_a.shutdown()
        srv_b.shutdown()


def test_handoff_hostile_meta_rejected():
    """Peer-supplied handoff meta is validated structurally: bad
    sessions, out-of-order parts, manifest mismatches and wire-coded
    payloads are error replies — never installs, never crashes."""
    from learning_at_home_tpu.client.rpc import client_loop, pool_registry
    from learning_at_home_tpu.utils.connection import RemoteCallError

    srv = Server.create(
        num_experts=0, hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.0), dht=None,
    )
    pool = pool_registry().get(srv.endpoint)

    def rpc(meta, tensors=()):
        return client_loop().run(
            pool.rpc("handoff", tensors, meta, timeout=10.0)
        )

    # the pinned ORDERED battery (tests/fuzz_corpus, ISSUE 15): the ok
    # entry opens session s3 that later entries kill and re-probe
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fuzz_corpus",
                        "handoff_meta.json")
    with open(path) as fh:
        corpus = json.load(fh)
    assert corpus["format"] == "lah-fuzz-battery-v1"
    arr = np.ones(3, np.float32)
    manifest = [{"shape": [3], "dtype": "float32",
                 "crc": lifecycle._leaf_crc(arr)}]
    try:
        for case in corpus["cases"]:
            meta = {k: manifest if v == "$MANIFEST" else v
                    for k, v in case["meta"].items()}
            tensors = (arr,) * case["tensors"]
            if case["expect"] == "ok":
                _, reply = rpc(meta, tensors)
                assert reply["ok"] is True, case["name"]
            else:
                with pytest.raises(RemoteCallError, match=case["match"]):
                    rpc(meta, tensors)
                    raise AssertionError(
                        f"hostile handoff meta accepted: {case['name']}"
                    )
        # NO partial install survived any of it
        assert "h.0" not in srv.experts
        assert srv.handoff._sessions == {}
        assert srv.handoff.received == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# drain RPC (control plane) + stats surface
# ---------------------------------------------------------------------------


def test_drain_rpc_migrates_and_reports_state():
    from learning_at_home_tpu.client.rpc import client_loop, pool_registry

    srv_a = Server.create(
        expert_uids=["rp.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.01), dht=None,
    )
    srv_b = Server.create(
        num_experts=0, hidden_dim=8, host="127.0.0.1",
        optimizer=optax.sgd(0.01), dht=None,
    )
    try:
        pool = pool_registry().get(srv_a.endpoint)
        _, meta = client_loop().run(
            pool.rpc(
                "drain", (),
                {"successor": [srv_b.endpoint[0], srv_b.endpoint[1]],
                 "grace": 0.0},
                timeout=10.0,
            )
        )
        assert meta["draining"] is True and meta["started"] is True
        assert srv_a.wait_drained(timeout=20.0)
        # stats RPC surfaces the lifecycle section (lah_top's source)
        _, stats = client_loop().run(
            pool.rpc("stats", (), {}, timeout=10.0)
        )
        lc = stats["lifecycle"]
        assert lc["state"] == lifecycle.DRAINED
        assert lc["drain_summary"]["handed_off"] == ["rp.0"]
        assert "rp.0" in srv_b.experts
        # a second drain RPC is a no-op (started=False), not an error
        _, meta2 = client_loop().run(
            pool.rpc("drain", (), {}, timeout=10.0)
        )
        assert meta2["started"] is False
    finally:
        srv_a.shutdown()
        srv_b.shutdown()


# ---------------------------------------------------------------------------
# graceful drain during ACTIVE training: zero quorum failures, zero drops
# ---------------------------------------------------------------------------


def test_drain_during_active_dispatch_zero_failures():
    """The acceptance contract: draining one of two servers while a
    trainer keeps stepping causes ZERO quorum failures and ZERO dropped
    samples — dispatch steers to the successor, which serves the
    migrated experts."""
    import jax.numpy as jnp

    from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts

    boot = DHT()
    d_a = DHT(initial_peers=[boot.endpoint])
    d_b = DHT(initial_peers=[boot.endpoint])
    d_c = DHT(initial_peers=[boot.endpoint])
    # a liveness that is a COUNT of heartbeats: a record lives two periods,
    # so at 2 s a period the successor has to miss 4 s of its heartbeats
    # before its experts read dead (at 0.4 s, under six loaded workers, one
    # late pass of its loop was enough, and with the other server draining
    # no expert was alive)
    period = 2.0
    srv_a = Server.create(
        expert_uids=["lc.0", "lc.1"], hidden_dim=16, host="127.0.0.1",
        optimizer=optax.adam(1e-3), dht=d_a, update_period=period,
    )
    srv_b = Server.create(
        expert_uids=["lc.2", "lc.3"], hidden_dim=16, host="127.0.0.1",
        optimizer=optax.adam(1e-3), dht=d_b, update_period=period,
    )
    moe = None
    try:
        moe = RemoteMixtureOfExperts(
            in_features=16, grid_size=(4,), uid_prefix="lc", source=d_c,
            k_best=3, k_min=1, timeout_after_k_min=0.5,
            forward_timeout=20.0, backward_timeout=20.0, alive_ttl=0.4,
        )
        deadline = time.time() + 60
        while len(d_c._loop.run(d_c._get_alive("lc"))) < 4:
            assert time.time() < deadline, "the four experts never read alive"
            time.sleep(0.2)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        opt = optax.adam(1e-2)
        opt_state = opt.init(gate)
        rs = np.random.RandomState(0)
        X = rs.randn(64, 16).astype(np.float32)
        Y = np.roll(X, 1, axis=1)

        def loss_fn(gate, x, y):
            return jnp.mean((moe(x, gate) - y) ** 2)

        # the trainer steps for as long as the drain takes and eight steps
        # more (while the records the drained server left still name it),
        # however many steps that is on this machine
        failures = steps = steps_after = 0
        drained = False
        deadline = time.time() + 120
        while steps_after < 8 and time.time() < deadline:
            if steps == 8:
                assert srv_a.start_drain(
                    successor=srv_b.endpoint, grace=0.5,
                    quiesce_timeout=5.0,
                )
            if not drained and srv_a.wait_drained(timeout=0.0):
                drained = True
                srv_a.shutdown()  # the drained process exits
            idx = rs.randint(0, len(X), 8)
            x, y = jnp.asarray(X[idx]), jnp.asarray(Y[idx])
            try:
                loss, grads = jax.value_and_grad(loss_fn)(gate, x, y)
                updates, opt_state = opt.update(grads, opt_state)
                gate = optax.apply_updates(gate, updates)
            except Exception:
                failures += 1
            steps += 1
            steps_after += drained
        assert drained, f"drain never finished ({steps} steps)"
        assert failures == 0, f"{failures} quorum failures during drain"
        assert moe.samples_dropped == 0
        assert moe.backward_samples_dropped == 0
        # the successor took over the migrated experts
        assert {"lc.0", "lc.1"} <= set(srv_b.experts)
        assert srv_b.handoff.received == 2
    finally:
        if moe is not None:
            del moe
        for srv in (srv_a, srv_b):
            try:
                srv.shutdown()
            except Exception:
                pass
        for d in (d_a, d_b, d_c, boot):
            d.shutdown()


# ---------------------------------------------------------------------------
# restart-from-checkpoint: a hard-killed server rejoins from its last step
# ---------------------------------------------------------------------------


def test_restart_from_checkpoint_rejoins_and_counts_restart(tmp_path):
    """Hard-kill recovery: periodic snapshots via CheckpointManager, a
    fresh process restores the latest complete step BITWISE (identical
    params+opt state ⇒ within < 1 step of quality by construction),
    rejoins the DHT, and the restart counter increments."""
    from learning_at_home_tpu.utils.checkpoint import CheckpointManager

    root = str(tmp_path / "ckpt")
    boot = DHT()
    d_1 = DHT(initial_peers=[boot.endpoint])
    d_2 = DHT(initial_peers=[boot.endpoint])
    d_c = DHT(initial_peers=[boot.endpoint])
    srv1 = Server.create(
        expert_uids=["rs.0"], hidden_dim=8, host="127.0.0.1",
        optimizer=optax.adam(1e-3), dht=d_1, update_period=0.4,
    )
    srv2 = None
    try:
        x = np.random.RandomState(3).randn(2, 8).astype(np.float32)
        srv1.experts["rs.0"].backward([x], [np.ones((2, 8), np.float32)])
        mgr = CheckpointManager(root, keep_last=2)
        step = mgr.save_now(lambda s: srv1.save_checkpoint(root, s))
        assert step == 1
        want = srv1.experts["rs.0"].state_dict()
        fwd_before = np.asarray(srv1.experts["rs.0"].forward([x])[0])
        # hard kill: no drain, no final checkpoint
        srv1.shutdown()
        d_1.shutdown()

        # the relaunched process: fresh params, restore, rejoin, count
        srv2 = Server.create(
            expert_uids=["rs.0"], hidden_dim=8, host="127.0.0.1",
            optimizer=optax.adam(1e-3), dht=d_2, update_period=0.4,
        )
        restored_step = srv2.load_checkpoint(root)
        mgr2 = CheckpointManager(root, keep_last=2)
        srv2.restarts = mgr2.record_restart()
        assert restored_step == 1
        assert srv2.restarts == 1
        got = srv2.experts["rs.0"].state_dict()
        assert_state_bitwise(want, got)
        np.testing.assert_array_equal(
            fwd_before, np.asarray(srv2.experts["rs.0"].forward([x])[0])
        )
        assert srv2.lifecycle_info()["restarts"] == 1
        # rejoined: discoverable through the DHT again
        deadline = time.time() + 10
        alive = {}
        while time.time() < deadline:
            alive = d_c._loop.run(d_c._get_alive("rs"))
            if "rs.0" in alive:
                break
            time.sleep(0.2)
        assert "rs.0" in alive
        # a second restart keeps counting
        assert CheckpointManager(root).record_restart() == 2
    finally:
        if srv2 is not None:
            srv2.shutdown()
        for d in (d_2, d_c, boot):
            d.shutdown()


# ---------------------------------------------------------------------------
# stale-while-revalidate alive cache (the dispatch path must never block
# on a discovery lookup under churn)
# ---------------------------------------------------------------------------


def test_alive_cache_stale_while_revalidate():
    import asyncio

    from learning_at_home_tpu.client.routing import CachedAliveSet
    from learning_at_home_tpu.utils.asyncio_utils import BackgroundLoop

    class Source:
        def __init__(self):
            self.calls = 0
            self.delay = 0.0
            self.fail = False
            self.result = {"a.0": ("h", 1)}

        async def get_alive_experts(self, prefix):
            self.calls += 1
            if self.delay:
                await asyncio.sleep(self.delay)
            if self.fail:
                raise RuntimeError("lookup stalled out")
            return dict(self.result)

    src = Source()
    cache = CachedAliveSet(src, "a", ttl=0.05, swr=True)
    loop = BackgroundLoop(name="test-swr")
    try:
        # first discovery has nothing to serve stale: it blocks
        assert loop.run(cache.get()) == {"a.0": ("h", 1)}
        assert src.calls == 1
        time.sleep(0.08)  # expire the window
        src.result = {"a.1": ("h", 2)}
        src.delay = 0.5
        # stale window + slow lookup: get() must return the STALE set
        # immediately, NOT block for the 500 ms lookup
        t0 = time.monotonic()
        got = loop.run(cache.get())
        assert time.monotonic() - t0 < 0.25, "swr get blocked on the lookup"
        assert got == {"a.0": ("h", 1)}
        assert cache.stale_serves == 1
        # the background refresh lands the new set
        deadline = time.time() + 5
        while time.time() < deadline:
            if loop.run(cache.get()) == {"a.1": ("h", 2)}:
                break
            time.sleep(0.05)
        assert loop.run(cache.get()) == {"a.1": ("h", 2)}
        # a FAILED background refresh keeps the stale set and counts
        time.sleep(0.08)
        src.delay, src.fail = 0.0, True
        assert loop.run(cache.get()) == {"a.1": ("h", 2)}
        deadline = time.time() + 5
        while time.time() < deadline and cache.refresh_failures == 0:
            time.sleep(0.02)
        assert cache.refresh_failures >= 1
        assert loop.run(cache.get()) == {"a.1": ("h", 2)}
        # force_refresh still blocks for an authoritative read
        src.fail = False
        src.result = {"a.2": ("h", 3)}
        assert loop.run(cache.get(force_refresh=True)) == {"a.2": ("h", 3)}
    finally:
        loop.shutdown()

    # swr defaults ON since ISSUE 11 (cheap refreshes); pinning
    # swr=False restores the historical blocking-refresh semantics
    # chaos tests that reason about kill visibility rely on
    assert CachedAliveSet(Source(), "a", ttl=0.05).swr is True
    src2 = Source()
    cache2 = CachedAliveSet(src2, "a", ttl=0.05, swr=False)
    assert cache2.swr is False
    loop2 = BackgroundLoop(name="test-noswr")
    try:
        assert loop2.run(cache2.get()) == {"a.0": ("h", 1)}
        time.sleep(0.08)
        src2.result = {"a.1": ("h", 2)}
        assert loop2.run(cache2.get()) == {"a.1": ("h", 2)}  # blocked+fresh
        assert cache2.stale_serves == 0
    finally:
        loop2.shutdown()


# ---------------------------------------------------------------------------
# lah_top lifecycle rendering (pure)
# ---------------------------------------------------------------------------


def test_lah_top_renders_lifecycle_columns():
    import importlib

    lah_top = importlib.import_module("tools.lah_top")

    def row(peer_id, lifecycle_section):
        return {
            "peer_id": peer_id, "role": "server",
            "endpoint": ("127.0.0.1", 1), "expires_at": 0.0,
            "snapshot": {"lifecycle": lifecycle_section, "metrics": {}},
        }

    rows = [
        row("srv-serving", {"state": "SERVING", "uptime_s": 12.3,
                            "restarts": 2}),
        row("srv-draining", {"state": "DRAINING", "uptime_s": 5.0,
                             "restarts": 0}),
        {"peer_id": "trainer-1", "role": "trainer",
         "endpoint": ("127.0.0.1", 2), "expires_at": 0.0, "snapshot": {}},
    ]
    out = lah_top.render(rows, "swarm", dead={"srv-gone"})
    assert "STATE" in out and "UPTIME" in out and "RST" in out
    assert "SERVING" in out and "DRAINING" in out
    assert "12s" in out  # uptime rendered in seconds
    assert "DEAD" in out and "record expired" in out
    # malformed lifecycle sections render dashes, never crash
    rows.append(row("srv-weird", {"state": 42}))
    assert "srv-weird" in lah_top.render(rows, "swarm", dead=set())
