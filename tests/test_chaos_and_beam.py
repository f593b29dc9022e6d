"""M4 tests: beam-search routing on larger grids + chaos (latency,
stragglers, drops) against the k-of-n quorum — [BJ] config 4 scaled to CI."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import StaticExpertSource, beam_search_alive
from learning_at_home_tpu.dht import DHT
from learning_at_home_tpu.server import ChaosConfig, background_server

HID = 16


def test_beam_search_alive_static_source():
    import asyncio

    # 4x4 grid, only some rows alive
    experts = {f"g.{i}.{j}": ("h", 1) for i in (0, 2) for j in range(4)}
    source = StaticExpertSource(experts)
    logits0 = np.zeros((2, 4), np.float32)
    logits0[0, 2] = 5.0  # sample 0 prefers row 2
    logits0[1, 0] = 5.0  # sample 1 prefers row 0
    logits1 = np.zeros((2, 4), np.float32)
    alive = asyncio.run(
        beam_search_alive(source, "g", [logits0, logits1], (4, 4), beam_size=1)
    )
    assert set(alive) == {f"g.{i}.{j}" for i in (0, 2) for j in range(4)}


class _CountingSource:
    """ExpertSource wrapper counting DHT record reads (one per prefix key)."""

    def __init__(self, inner):
        self.inner = inner
        self.reads = 0

    async def get_alive_experts(self, prefix):
        self.reads += 1
        return await self.inner.get_alive_experts(prefix)

    async def first_k_active(self, prefixes, k):
        self.reads += len(prefixes)
        return await self.inner.first_k_active(prefixes, k)


def test_beam_search_3d_walks_dimensions():
    """Deep grids are walked dimension-by-dimension with per-level pruning:
    record reads stay O(beam·dims) on a 16x16x16 grid (4096 experts),
    and an expert whose prefix chain tops every level is always found."""
    import asyncio

    rs = np.random.RandomState(3)
    grid = (16, 16, 16)
    # 40 alive experts at random coords + one at the known argmax chain
    experts = {
        f"d3.{rs.randint(16)}.{rs.randint(16)}.{rs.randint(16)}": ("h", 1)
        for _ in range(40)
    }
    experts["d3.5.9.13"] = ("h", 2)
    source = _CountingSource(StaticExpertSource(experts))

    batch, beam = 4, 4
    logits = [rs.randn(batch, g).astype(np.float32) for g in grid]
    logits[0][0, 5] = 10.0  # sample 0's chain tops every level
    logits[1][0, 9] = 10.0
    logits[2][0, 13] = 10.0
    alive = asyncio.run(beam_search_alive(source, "d3", logits, grid, beam))

    assert "d3.5.9.13" in alive and alive["d3.5.9.13"] == ("h", 2)
    assert set(alive) <= set(experts)  # never invents uids
    # per-level budget: union_cap = 4*beam candidates at each of the two
    # walked levels (first_k_active at depth 1, row fetches at depth 2)
    assert source.reads <= 2 * 4 * beam + batch * beam, source.reads
    # far below enumerating the 256 depth-2 rows or 4096 uids
    assert source.reads < 64


def test_beam_search_2d_dead_top_rows_reroutes():
    """2-D grids reroute too: dead leaf rows trigger the one-shot capped
    retry over the remaining first-dimension rows."""
    import asyncio

    grid = (8, 4)
    experts = {"r2.6.1": ("h", 9)}  # only row 6 has anything alive
    source = _CountingSource(StaticExpertSource(experts))
    logits = [np.zeros((2, g), np.float32) for g in grid]
    logits[0][:, 0] = 10.0  # both samples prefer (dead) row 0
    logits[0][:, 6] = -5.0
    alive = asyncio.run(beam_search_alive(source, "r2", logits, grid, beam_size=2))
    assert set(alive) == {"r2.6.1"}


def test_beam_search_3d_dead_top_rows_reroutes():
    """If every top-scoring first-dimension row is dead, the walk rescans
    dimension 0 instead of returning empty (dead rows divert, not end)."""
    import asyncio

    grid = (8, 4, 4)
    experts = {"r.6.1.2": ("h", 9)}  # only row 6 has anything alive
    source = _CountingSource(StaticExpertSource(experts))
    batch = 2
    logits = [np.zeros((batch, g), np.float32) for g in grid]
    logits[0][:, 0] = 10.0  # both samples prefer (dead) row 0
    logits[0][:, 6] = -5.0  # alive row scores worst
    alive = asyncio.run(beam_search_alive(source, "r", logits, grid, beam_size=2))
    assert set(alive) == {"r.6.1.2"}


def test_beam_routing_matches_enumeration_on_dht():
    """With all rows alive and beam covering them, beam == enumerate."""
    dht = DHT()
    try:
        # 2-D grid of 8 experts on one server
        import optax

        from learning_at_home_tpu.models import make_expert
        from learning_at_home_tpu.server import ExpertBackend, Server

        experts = {}
        for i in range(4):
            for j in range(2):
                uid = f"grid.{i}.{j}"
                apply_fn, params = make_expert(
                    "ffn", HID, jax.random.PRNGKey(i * 2 + j), jnp.zeros((2, HID))
                )
                experts[uid] = ExpertBackend(uid, apply_fn, params, optax.sgd(0.01))
        server = Server(experts, host="127.0.0.1", dht=dht, update_period=0.5)
        server.run_in_background()
        try:
            deadline = time.time() + 10
            while time.time() < deadline:
                alive = dht._loop.run(dht._get_alive("grid"))
                if len(alive) == 8:
                    break
                time.sleep(0.1)
            assert len(alive) == 8

            x = jnp.asarray(np.random.RandomState(0).randn(4, HID).astype(np.float32))
            outs = {}
            for routing in ("enumerate", "beam"):
                moe = RemoteMixtureOfExperts(
                    in_features=HID, grid_size=(4, 2), uid_prefix="grid",
                    source=dht, k_best=2, k_min=1, routing=routing, beam_size=4,
                )
                gate = moe.init_gate_params(jax.random.PRNGKey(7))
                outs[routing] = np.asarray(moe(x, gate))
            np.testing.assert_allclose(
                outs["beam"], outs["enumerate"], atol=1e-5, rtol=1e-5
            )
        finally:
            server.shutdown()
    finally:
        dht.shutdown()
        reset_client_rpc()


def test_beam_routing_1d_grid_on_dht():
    """1-D grids have no intermediate prefix level: beam search queries the
    full-uid records directly (regression: used to find zero alive)."""
    dht = DHT()
    try:
        dht.declare_experts_sync(
            ["solo.0", "solo.1", "solo.2"], ("10.0.0.9", 1234), expiration=30
        )
        import asyncio

        logits = [np.asarray([[3.0, 1.0, 2.0]], np.float32)]
        alive = asyncio.run(
            beam_search_alive(dht, "solo", logits, (3,), beam_size=2)
        )
        # top-2 rows for the one sample are uids 0 and 2
        assert set(alive) == {"solo.0", "solo.2"}
        assert alive["solo.0"] == ("10.0.0.9", 1234)
    finally:
        dht.shutdown()


def test_partial_checkpoint_invisible(tmp_path):
    """A crash mid-save must not surface a partial step as 'latest'."""
    from learning_at_home_tpu.utils.checkpoint import (
        latest_step,
        mark_step_complete,
        save_pytree,
    )
    import jax.numpy as jnp

    root = str(tmp_path / "ck")
    save_pytree(root, 5, "params", {"a": jnp.ones(2)})
    # no marker: the "crash" happened before opt_state was written
    assert latest_step(root) is None
    mark_step_complete(root, 5)
    assert latest_step(root) == 5


def test_quorum_under_latency_and_stragglers():
    """Injected jitter + a straggler: the quorum returns on the replies of
    the peer that answers (grace timeout) and drops the straggler.  Said in
    counters, not in seconds: two of each sample's four experts sit on a
    peer that holds EVERY reply back for 60 s, its first ``hello`` among
    them; the dispatch comes back with every sample at quorum, and not one
    byte has come from the straggler when it does."""
    from learning_at_home_tpu.client.rpc import pool_registry

    fast = ChaosConfig(base_latency=0.01, jitter=0.02, seed=42)
    slow = ChaosConfig(straggler_prob=1.0, straggler_delay=60.0, seed=42)
    with background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=5, chaos=fast
    ) as (ep_fast, srv_fast), background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="ffn", expert_offset=2,
        seed=5, chaos=slow,
    ) as (ep_slow, srv_slow):
        experts = {uid: ep_fast for uid in srv_fast.experts}
        experts.update({uid: ep_slow for uid in srv_slow.experts})
        assert sorted(experts) == ["ffn.0", "ffn.1", "ffn.2", "ffn.3"]
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn",
            source=StaticExpertSource(experts), k_best=4, k_min=1,
            timeout_after_k_min=0.15, forward_timeout=120.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(1).randn(4, HID).astype(np.float32))
        out = np.asarray(moe(x, gate))
        assert np.isfinite(out).all() and np.abs(out).max() > 0
        # quorum reached: every sample had its k_min replies, none masked
        assert (moe.samples_total, moe.samples_dropped) == (4, 0)
        # the straggler dropped, not awaited: the peer that answers has
        # answered, the other's held reply never arrived
        assert pool_registry().get(ep_fast).bytes_received > 0
        assert pool_registry().get(ep_slow).bytes_received == 0
        assert srv_fast.chaos.injected_delays > 0
        for _ in range(2000):  # the straggler's own loop counts it: wait for it
            if srv_slow.chaos.injected_stragglers:
                break
            time.sleep(0.005)
        assert srv_slow.chaos.injected_stragglers > 0
    reset_client_rpc()


def test_quorum_under_drops():
    """Reply drops look like timeouts; k_min=1 still succeeds eventually:
    the merged call's reply is dropped (the seed's first draw), its four
    experts are asked again one by one, and the replies that are not dropped
    carry every sample to quorum.  A reply is waited for 5 s, not 1: on a
    loaded machine an answer that was NOT dropped took longer than a second,
    and every call counted as lost."""
    chaos = ChaosConfig(drop_prob=0.4, seed=7)
    with background_server(
        num_experts=4, hidden_dim=HID, expert_prefix="ffn", seed=6, chaos=chaos
    ) as (endpoint, srv):
        source = StaticExpertSource({uid: endpoint for uid in srv.experts})
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn", source=source,
            k_best=4, k_min=1, timeout_after_k_min=0.1, forward_timeout=5.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(2).randn(3, HID).astype(np.float32))
        out = np.asarray(moe(x, gate))
        assert np.isfinite(out).all() and np.abs(out).max() > 0
        assert srv.chaos.injected_drops > 0
        assert (moe.samples_total, moe.samples_dropped) == (3, 0)
    reset_client_rpc()
