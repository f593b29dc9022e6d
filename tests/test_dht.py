"""M2 tests: Kademlia DHT — unit tier (routing table) + localhost swarm
integration (store/get across nodes, expiry, expert declare/discover),
mirroring the reference's test_dht.py strategy (SURVEY.md §4)."""

import asyncio
import time

import pytest

from learning_at_home_tpu.dht import DHT, DHTNode, uid_prefixes
from learning_at_home_tpu.dht.protocol import DHTRecordStorage, PLAIN_SUBKEY
from learning_at_home_tpu.dht.routing import DHTID, KBucket, RoutingTable
from learning_at_home_tpu.utils.timed_storage import get_dht_time


# ---------------- unit tier ----------------


def test_dhtid():
    a, b = DHTID.generate(), DHTID.generate()
    assert a != b
    assert a.xor_distance(a) == 0
    assert a.xor_distance(b) == b.xor_distance(a)
    assert DHTID.from_bytes(a.to_bytes()) == a
    assert DHTID.from_key("expert.1") == DHTID.from_key("expert.1")
    assert DHTID.from_key("expert.1") != DHTID.from_key("expert.2")


def test_kbucket_lru_and_replacement():
    bucket = KBucket(0, 2**160, k=3)
    ids = [DHTID(i + 1) for i in range(5)]
    for i, nid in enumerate(ids[:3]):
        assert bucket.add_or_update(nid, ("h", i))
    assert not bucket.add_or_update(ids[3], ("h", 3))  # full → replacement
    assert ids[3] in bucket.replacement
    # refresh moves to LRU tail
    bucket.add_or_update(ids[0], ("h", 0))
    assert bucket.oldest[0] == ids[1]
    # removal promotes from replacement
    bucket.remove(ids[1])
    assert ids[3] in bucket.peers and ids[1] not in bucket.peers
    # removing a REPLACEMENT node must not promote anything (esp. itself)
    assert bucket.add_or_update(ids[4], ("h", 4)) is False
    bucket.remove(ids[4])
    assert ids[4] not in bucket.peers and ids[4] not in bucket.replacement
    assert len(bucket.peers) == 3


def test_routing_table_split_and_nearest():
    own = DHTID(2**159)  # sits in the upper half
    table = RoutingTable(own, bucket_size=4)
    for i in range(64):
        table.add_or_update_node(DHTID.from_key(f"n{i}"), ("h", i))
    assert len(table.buckets) > 1
    assert len(table) > 4
    target = DHTID.from_key("target")
    nearest = table.nearest_neighbors(target, 5)
    assert len(nearest) == 5
    dists = [int(nid) ^ int(target) for nid, _ in nearest]
    assert dists == sorted(dists)
    # exhaustive check: these really are the closest known
    all_dists = sorted(
        int(nid) ^ int(target) for b in table.buckets for nid in b.peers
    )
    assert dists == all_dists[:5]


def test_record_storage_subkeys(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(
        "learning_at_home_tpu.utils.timed_storage.get_dht_time", lambda: now[0]
    )
    st = DHTRecordStorage()
    assert st.store(b"k", "a", 1, 110.0)
    assert st.store(b"k", "b", 2, 120.0)
    assert not st.store(b"k", "a", 0, 105.0)  # older expiration loses
    assert st.get(b"k") == {"a": (1, 110.0), "b": (2, 120.0)}
    now[0] = 115.0
    assert st.get(b"k") == {"b": (2, 120.0)}  # 'a' expired individually


def test_uid_prefixes():
    assert uid_prefixes("ffn.4.17") == ["ffn", "ffn.4"]
    assert uid_prefixes("expert.3") == ["expert"]


# ---------------- swarm tier (real protocol traffic on localhost) ----------------


def run(coro):
    return asyncio.run(coro)


async def make_swarm(n, **kwargs):
    first = await DHTNode.create(**kwargs)
    nodes = [first]
    for _ in range(n - 1):
        nodes.append(
            await DHTNode.create(initial_peers=[first.endpoint], **kwargs)
        )
    return nodes


async def teardown(nodes):
    await asyncio.gather(*(n.shutdown() for n in nodes))


def test_swarm_store_get_across_nodes(monkeypatch):
    # every peer here is alive: a call waits as long as only a dead peer
    # would make it (the adaptive timeout, 4 x a loopback RTT with a floor of
    # 50 ms, was met by LIVE peers under six loaded workers, and two strikes
    # evict: the lookup then found no holder of the key)
    from learning_at_home_tpu.dht import protocol

    monkeypatch.setattr(protocol, "ADAPTIVE_TIMEOUT_FLOOR", 30.0)

    async def main():
        nodes = await make_swarm(8, bucket_size=4, rpc_timeout=30.0)
        try:
            ok = await nodes[2].store("the-key", [1, 2, 3], get_dht_time() + 30)
            assert ok
            # a DIFFERENT node must find the value via iterative lookup
            rec = await nodes[7].get("the-key")
            assert rec[PLAIN_SUBKEY][0] == [1, 2, 3]
            # a key nobody stored is absent
            assert await nodes[5].get("missing-key") == {}
        finally:
            await teardown(nodes)

    run(main())


def test_swarm_expiration_is_failure_detection():
    async def main():
        nodes = await make_swarm(4, bucket_size=4)
        try:
            await nodes[0].store("ephemeral", "v", get_dht_time() + 0.5)
            assert (await nodes[3].get("ephemeral"))[PLAIN_SUBKEY][0] == "v"
            await asyncio.sleep(0.6)
            assert await nodes[3].get("ephemeral") == {}  # gone ⇒ 'dead'
        finally:
            await teardown(nodes)

    run(main())


def test_swarm_subkey_merge_from_different_writers():
    """Two servers declare under one prefix key; readers see both."""

    async def main():
        nodes = await make_swarm(5, bucket_size=4)
        try:
            exp = get_dht_time() + 30
            await nodes[1].store("ffn", ["hostA", 1], exp, subkey="ffn.0")
            await nodes[2].store("ffn", ["hostB", 2], exp, subkey="ffn.1")
            rec = await nodes[4].get("ffn")
            assert rec["ffn.0"][0] == ["hostA", 1]
            assert rec["ffn.1"][0] == ["hostB", 2]
        finally:
            await teardown(nodes)

    run(main())


def test_maintenance_evicts_dead_peer_and_refreshes():
    async def main():
        a = await DHTNode.create(bucket_size=4, maintenance_period=None)
        b = await DHTNode.create(
            initial_peers=[a.endpoint], bucket_size=4, maintenance_period=None
        )
        c = await DHTNode.create(
            initial_peers=[a.endpoint], bucket_size=4, maintenance_period=None
        )
        try:
            assert len(b.routing_table) >= 2
            await c.shutdown()  # c dies
            b.start_maintenance(period=0.3)
            deadline = asyncio.get_running_loop().time() + 10
            while asyncio.get_running_loop().time() < deadline:
                if b.routing_table.get_endpoint(c.node_id) is None:
                    break
                await asyncio.sleep(0.2)
            assert b.routing_table.get_endpoint(c.node_id) is None, (
                "dead peer never evicted by maintenance"
            )
            assert b.routing_table.get_endpoint(a.node_id) is not None
        finally:
            await teardown([a, b])

    run(main())


def test_node_failure_lookup_still_works():
    async def main():
        nodes = await make_swarm(6, bucket_size=4)
        try:
            await nodes[0].store("durable", 42, get_dht_time() + 30)
            # kill two nodes; replication across k closest should survive
            await nodes[1].shutdown()
            await nodes[2].shutdown()
            rec = await nodes[5].get("durable")
            assert rec and rec[PLAIN_SUBKEY][0] == 42
        finally:
            await teardown([nodes[0], *nodes[3:]])

    run(main())


# ---------------- DHT facade (thread-bridged) ----------------


def test_dht_facade_declare_and_discover():
    dht1 = DHT()
    dht2 = DHT(initial_peers=[dht1.endpoint])
    try:
        n = dht1.declare_experts_sync(
            ["ffn.0.0", "ffn.0.1", "ffn.1.1"], ("10.0.0.1", 9000), expiration=30
        )
        assert n == 3
        # full-uid resolution from the OTHER node
        eps = dht2.get_experts_sync(["ffn.0.1", "ffn.9.9"])
        assert eps["ffn.0.1"] == ("10.0.0.1", 9000)
        assert eps["ffn.9.9"] is None
        # enumeration via top-level prefix record
        alive = dht2._loop.run(dht2._get_alive("ffn"))
        assert set(alive) == {"ffn.0.0", "ffn.0.1", "ffn.1.1"}
        # beam-search primitive: which sub-prefixes are active
        active = dht2._loop.run(dht2._first_k_active(["ffn.0", "ffn.7", "ffn.1"], 2))
        assert active["ffn.0"] is True
        assert active["ffn.7"] is False
        assert active["ffn.1"] is True
    finally:
        dht2.shutdown()
        dht1.shutdown()


def test_dht_facade_bridge_from_foreign_loop():
    """The async API must work when awaited from a different event loop."""
    dht = DHT()
    try:
        async def foreign():
            await dht.declare_experts(["e.0"], ("1.2.3.4", 5), expiration=10)
            return await dht.get_experts(["e.0"])

        result = asyncio.run(foreign())
        assert result["e.0"] == ("1.2.3.4", 5)
    finally:
        dht.shutdown()


def test_record_storage_bounded():
    """Both storage tiers are capped: a flood of keys or subkeys evicts
    instead of growing without bound (the swarm is a trust boundary)."""
    st = DHTRecordStorage(maxsize=4, max_subkeys=3)
    exp = get_dht_time() + 30
    stored = [st.store(f"k{i}".encode(), PLAIN_SUBKEY, [i], exp) for i in range(10)]
    assert all(stored[:4])  # in-bounds stores succeed and say so
    assert len(st) <= 4
    for i in range(10):
        st.store(b"one", f"sk{i}", [i], exp)
    assert len(st.get(b"one")) <= 3


def test_store_rpc_rejects_absurd_keys():
    """Oversized keys/subkeys in a store RPC are refused, not stored."""
    node = asyncio.run(DHTNode.create(maintenance_period=None))
    try:
        meta = {
            "from": DHTID.generate().to_bytes(),
            "port": 1,
            "items": [
                [b"x" * 10_000, PLAIN_SUBKEY, [1], get_dht_time() + 30],
                [b"fine", "s" * 10_000, [1], get_dht_time() + 30],
                [b"fine", "ok", [1], get_dht_time() + 30],
            ],
        }
        reply = node.protocol._serve("store", meta, "127.0.0.1")
        assert reply["ok"]["ok"] is True
        assert sum(bool(v) for v in reply["ok"].values()) == 1
        assert len(node.storage.get(b"fine")) == 1
    finally:
        asyncio.run(node.shutdown())


@pytest.mark.slow
def test_join_covers_distant_regions_at_scale():
    """Regression for the 128-node hit-rate bug, guarded at BOTH layers.

    Mechanism: a node must learn from every peer it HEARS FROM in its own
    lookups (textbook Kademlia), plus the paper's full join (refresh every
    other bucket range).  Before the fixes a late joiner's table held
    exactly ONE peer (the bootstrap node): its own lookups taught it
    nothing, tables stayed neighborhood-thin, and iterative lookups
    converged on local clusters — store() placed records at XOR-ranks
    34-74 of 128 and hit rate fell to 0.973.

    Behavior: at 48 nodes every stored key must be retrievable via a
    cross-node lookup with placement tight around the true closest set
    (post-fix cold-join margins measured at 128 nodes: worst min rank 0,
    worst per-key median 4 — the bounds below have >3x headroom)."""

    async def main():
        import numpy as np

        nodes = await make_swarm(48, bucket_size=8, maintenance_period=None)
        try:
            # --- mechanism: the LAST joiner heard from many peers during
            # its join lookups and must have learned them (pre-fix: 1)
            late_table = len(nodes[-1].routing_table)
            assert late_table >= 8, late_table
            own = int(nodes[-1].node_id)
            prefix_depths = {
                (own ^ int(nid)).bit_length()
                for b in nodes[-1].routing_table.buckets
                for nid in b.peers
            }
            assert len(prefix_depths) >= 3, prefix_depths  # spans regions

            # --- behavior: store/get + placement
            rs = np.random.RandomState(0)
            n_keys = 40
            storer_idx = {}
            for i in range(n_keys):
                storer_idx[i] = rs.randint(48)
                ok = await nodes[storer_idx[i]].store(
                    f"scale-key-{i}", i, get_dht_time() + 60
                )
                assert ok
            for i in range(n_keys):
                # getter must DIFFER from the storer: get() merges local
                # storage, so a same-node draw would bypass the iterative
                # lookup this test regresses
                getter = (storer_idx[i] + 1 + rs.randint(47)) % 48
                rec = await nodes[getter].get(f"scale-key-{i}")
                if not (rec and rec[PLAIN_SUBKEY][0] == i):
                    # one transient RPC timeout under 1-core load can cost
                    # a lookup; a real client retries, so does the test —
                    # the BUG was a deterministic routing failure no retry
                    # could fix
                    await asyncio.sleep(0.5)
                    rec = await nodes[getter].get(f"scale-key-{i}")
                assert rec and rec[PLAIN_SUBKEY][0] == i, f"miss scale-key-{i}"
            bad_placement = []
            for i in range(n_keys):
                target = DHTID.from_key(f"scale-key-{i}")
                ranked = sorted(
                    nodes, key=lambda n: int(n.node_id) ^ int(target)
                )
                holder_ranks = [
                    r for r, n in enumerate(ranked)
                    if n.storage.get(target.to_bytes())
                ]
                if not holder_ranks or min(holder_ranks) >= 4 or (
                    float(np.median(holder_ranks)) >= 12
                ):
                    bad_placement.append((i, holder_ranks))
            # the bug class misplaced essentially every affected key's
            # WHOLE replica set (min rank >= 13); tolerate at most 2 of
            # 40 load-transient outliers, and ONLY near-miss ones — any
            # key whose best replica sits past rank 8 is true
            # misplacement and fails hard regardless of the count
            assert len(bad_placement) <= 2, bad_placement
            for i, hr in bad_placement:
                assert hr and min(hr) < 8, (i, hr)
        finally:
            await teardown(nodes)

    run(main())


def test_lookup_strike_eviction_requires_distinct_lookups():
    """Two-strike lookup eviction: two timeouts from ONE logical event
    (concurrent lookups whose RPCs were in flight during the same pause)
    must not evict; a strike from a distinct, later lookup must."""
    node = DHTNode(node_id=DHTID(2**80))
    peer = DHTID(2**81)
    node.routing_table.add_or_update_node(peer, ("127.0.0.1", 1))

    # lookups A and B issued their RPC waves before either strike landed —
    # one GC pause, two timeouts, ONE logical event: no eviction
    wave_started = time.monotonic()
    node._record_lookup_timeout(peer, lookup_id=1, wave_started=wave_started)
    node._record_lookup_timeout(peer, lookup_id=2, wave_started=wave_started)
    assert node.routing_table.get_endpoint(peer) is not None
    assert peer in node._lookup_strikes

    # a later lookup whose wave went out AFTER the strike was recorded
    # gives the peer a fresh chance; failing it is the real second strike
    node._record_lookup_timeout(
        peer, lookup_id=3, wave_started=time.monotonic()
    )
    assert node.routing_table.get_endpoint(peer) is None
    assert peer not in node._lookup_strikes  # eviction cleared the strike


def test_lookup_strike_same_lookup_never_evicts():
    node = DHTNode(node_id=DHTID(2**80))
    peer = DHTID(2**81)
    node.routing_table.add_or_update_node(peer, ("127.0.0.1", 1))
    node._record_lookup_timeout(peer, lookup_id=7, wave_started=time.monotonic())
    node._record_lookup_timeout(peer, lookup_id=7, wave_started=time.monotonic())
    assert node.routing_table.get_endpoint(peer) is not None


def test_lookup_strikes_cleared_when_node_leaves_table():
    """A peer that times out once and then leaves the table by ANY path
    (e.g. maintenance eviction) must not leak its strike entry."""
    node = DHTNode(node_id=DHTID(2**80))
    peer = DHTID(2**81)
    node.routing_table.add_or_update_node(peer, ("127.0.0.1", 1))
    node._record_lookup_timeout(peer, lookup_id=1, wave_started=time.monotonic())
    assert peer in node._lookup_strikes
    node.routing_table.remove_node(peer)  # the maintenance path
    assert peer not in node._lookup_strikes


# ---------------- routing-record cache (ISSUE 11) ----------------


def test_record_cache_honors_record_expiry():
    """A cached entry is filtered by each RECORD's own expiration: an
    expired subkey never comes out of the cache mid-TTL-window, so DHT
    expiry (the swarm's failure detector) is never blunted by caching."""
    from learning_at_home_tpu.dht import _RecordCache

    cache = _RecordCache(ttl=30.0)
    now = get_dht_time()
    cache.put("k", {"soon": (1, now + 0.2), "later": (2, now + 30)})
    assert set(cache.get("k")) == {"soon", "later"}
    time.sleep(0.25)
    assert set(cache.get("k")) == {"later"}  # 'soon' expired mid-window


def test_record_cache_all_expired_is_miss():
    """When EVERY cached record expires, the entry drops entirely — the
    next read re-resolves instead of serving an empty view for the rest
    of the TTL window."""
    from learning_at_home_tpu.dht import _RecordCache

    cache = _RecordCache(ttl=30.0)
    cache.put("k", {"a": (1, get_dht_time() + 0.1)})
    time.sleep(0.15)
    assert cache.get("k") is None
    assert cache.misses == 1


def test_record_cache_negative_caching_and_ttl():
    """An EMPTY lookup result is cached too (one lookup per window for a
    miss storm on a dead prefix), and ages out at the TTL like any entry."""
    from learning_at_home_tpu.dht import _RecordCache

    cache = _RecordCache(ttl=0.2)
    cache.put("missing", {})
    assert cache.get("missing") == {}  # negative hit: no lookup needed
    assert cache.hits == 1
    time.sleep(0.25)
    assert cache.get("missing") is None  # window over: re-resolve


def test_record_cache_invalidate_matches_wire_key():
    """Cache keys are the DHT wire form (DHTID digest): protocol
    ``on_store_observed`` only ever sees wire keys, so an inbound store
    must invalidate the entry cached under the PLAINTEXT key."""
    from learning_at_home_tpu.dht import _RecordCache

    cache = _RecordCache(ttl=30.0)
    cache.put("ffn", {"x": (1, get_dht_time() + 30)})
    cache.invalidate(DHTID.from_key("ffn").to_bytes())  # wire-form key
    assert cache.get("ffn") is None
    assert cache.invalidations == 1


def test_dht_cache_hit_serves_without_rpcs_and_bypass_forces_lookup():
    dht1 = DHT()
    dht2 = DHT(initial_peers=[dht1.endpoint])
    try:
        dht1.declare_experts_sync(
            ["ffn.0.0"], ("10.0.0.1", 9000), expiration=30
        )
        first = dht2.get_sync("ffn.0.0")
        assert "@10.0.0.1:9000" in first
        sent = sum(dht2.node.protocol.rpcs_sent.values())
        assert dht2.get_sync("ffn.0.0") == first
        assert sum(dht2.node.protocol.rpcs_sent.values()) == sent, (
            "second read within the TTL window must be served from cache"
        )
        assert dht2.get_sync("ffn.0.0", bypass_cache=True) == first
        assert sum(dht2.node.protocol.rpcs_sent.values()) > sent, (
            "bypass_cache must run a real iterative lookup"
        )
    finally:
        dht2.shutdown()
        dht1.shutdown()


def test_dht_cache_invalidated_by_own_store():
    """Read-your-writes: this handle's own declare invalidates its cached
    read, so the next read sees the new expert mid-TTL-window."""
    dht1 = DHT(cache_ttl=30.0)
    dht2 = DHT(initial_peers=[dht1.endpoint], cache_ttl=30.0)
    try:
        dht1.declare_experts_sync(
            ["ffn.0.0"], ("10.0.0.1", 9000), expiration=30
        )
        assert set(dht2._loop.run(dht2._get_alive("ffn"))) == {"ffn.0.0"}
        dht2.declare_experts_sync(
            ["ffn.0.1"], ("10.0.0.2", 9000), expiration=30
        )
        alive = dht2._loop.run(dht2._get_alive("ffn"))
        assert set(alive) == {"ffn.0.0", "ffn.0.1"}
    finally:
        dht2.shutdown()
        dht1.shutdown()


def test_dht_cache_invalidated_by_inbound_store():
    """In a 2-node swarm both nodes hold every record, so a declare on
    one lands an inbound store RPC on the other — whose cached read of
    the prefix must invalidate (``on_store_observed``), not serve the
    stale roster for the rest of a 30 s window."""
    dht1 = DHT(cache_ttl=30.0)
    dht2 = DHT(initial_peers=[dht1.endpoint], cache_ttl=30.0)
    try:
        dht1.declare_experts_sync(
            ["ffn.0.0"], ("10.0.0.1", 9000), expiration=30
        )
        assert set(dht2._loop.run(dht2._get_alive("ffn"))) == {"ffn.0.0"}
        dht1.declare_experts_sync(
            ["ffn.0.1"], ("10.0.0.1", 9001), expiration=30
        )
        alive = dht2._loop.run(dht2._get_alive("ffn"))
        assert set(alive) == {"ffn.0.0", "ffn.0.1"}, (
            "inbound store did not invalidate the cached prefix read"
        )
    finally:
        dht2.shutdown()
        dht1.shutdown()


# ---------------- batched multi-key store (ISSUE 11) ----------------


def test_store_many_wire_parity_with_per_key_stores():
    """The coalesced multi-key store bundle must land byte-for-byte the
    same records (values AND expirations) as the per-key path — while
    spending fewer store RPCs than one per (key, subkey)."""

    async def main():
        nodes = await make_swarm(6, bucket_size=4)
        try:
            exp = get_dht_time() + 30
            batched = [
                (f"bk.{i}", f"s{j}", [i, j], exp)
                for i in range(3)
                for j in range(2)
            ]
            sent0 = nodes[1].protocol.rpcs_sent.get("store", 0)
            acks = await nodes[1].store_many(batched)
            batched_rpcs = nodes[1].protocol.rpcs_sent.get("store", 0) - sent0
            assert all(acks), acks

            sent0 = nodes[2].protocol.rpcs_sent.get("store", 0)
            for i in range(3):
                for j in range(2):
                    ok = await nodes[2].store(
                        f"pk.{i}", [i, j], exp, subkey=f"s{j}"
                    )
                    assert ok
            per_key_rpcs = nodes[2].protocol.rpcs_sent.get("store", 0) - sent0

            for i in range(3):
                b = await nodes[5].get(f"bk.{i}")
                p = await nodes[5].get(f"pk.{i}")
                assert set(b) == set(p) == {"s0", "s1"}, (i, b, p)
                for j in range(2):
                    assert b[f"s{j}"][0] == p[f"s{j}"][0] == [i, j]
                    assert b[f"s{j}"][1] == p[f"s{j}"][1] == exp
            # 6 per-(key,subkey) calls each fan to ~k peers; the bundle
            # pays at most one store RPC per destination peer
            assert batched_rpcs <= len(nodes) < per_key_rpcs, (
                batched_rpcs, per_key_rpcs,
            )
        finally:
            await teardown(nodes)

    run(main())


def test_dead_peer_alive_refresh_bounded_by_adaptive_ceiling():
    """A cache-bypassing alive refresh with dead-but-not-yet-evicted DHT
    peers must finish within ~one adaptive-timeout ceiling: each dead
    contact costs at most ``rpc_timeout`` and a lookup wave contacts
    alpha peers in parallel, so dead-peer stalls do not stack."""
    dht1 = DHT(rpc_timeout=0.4)
    dead = [
        DHT(initial_peers=[dht1.endpoint], rpc_timeout=0.4) for _ in range(2)
    ]
    client = DHT(initial_peers=[dht1.endpoint], rpc_timeout=0.4)
    try:
        dht1.declare_experts_sync(
            ["ffn.0.0", "ffn.1.0"], ("10.0.0.1", 9000), expiration=30
        )
        client.get_sync("ffn")  # lookups learn the soon-dead peers
        for d in dead:
            d.shutdown()
        t0 = time.monotonic()
        alive = client._loop.run(
            client._get_alive("ffn", bypass_cache=True), timeout=30
        )
        elapsed = time.monotonic() - t0
        assert set(alive) == {"ffn.0.0", "ffn.1.0"}
        assert elapsed < 1.0, (
            f"fresh alive refresh stalled {elapsed:.2f}s — dead peers must "
            f"cost at most the adaptive ceiling (0.4s), paid in parallel"
        )
    finally:
        client.shutdown()
        dht1.shutdown()
