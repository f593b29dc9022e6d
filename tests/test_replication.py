"""Hedged replica dispatch + replica lifecycle (ISSUE 8).

Real localhost servers, fake (static) expert sources: two servers host
the SAME uid (``Server.create(expert_uids=...)`` crc32-seeds identical
params on both), the alive map carries a replica SET, and the dispatch
fan-out must (a) hedge only past the RTT-EMA-derived deadline, (b) take
the first successful reply and cancel the loser with the right marker
semantics (straggler-marked primary folds its EMA; race-losing backup
never does), (c) never hedge a backward, and (d) survive a primary kill
mid-training with zero dropped samples and < 1 round of quality cost.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import (
    DEFAULT_COST_WEIGHT,
    StaticExpertSource,
)
from learning_at_home_tpu.client.rpc import pool_registry
from learning_at_home_tpu.server import ChaosConfig
from learning_at_home_tpu.server.server import background_server

HID = 16


@pytest.fixture(autouse=True)
def _fresh_client_state():
    """Every test seeds pool RTT EMAs by hand — never leak them."""
    yield
    reset_client_rpc()


def _replicated_moe(ep_a, ep_b, **kw):
    """One expert ``hdg.0`` hosted by BOTH endpoints (a is listed first;
    the cost model re-orders by predicted cost at dispatch time)."""
    source = StaticExpertSource({"hdg.0": (ep_a, ep_b)})
    kw.setdefault("forward_timeout", 20.0)
    kw.setdefault("hedge_floor_s", 0.05)
    return RemoteMixtureOfExperts(
        in_features=HID, grid_size=(1,), uid_prefix="hdg", source=source,
        k_best=1, k_min=1, **kw,
    )


def _seed_rtt(ep, rtt):
    pool = pool_registry().get(ep)
    pool.rtt_ema = rtt
    return pool


def _x(rows=4, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(rows, HID).astype(np.float32)
    )


def _replica_pair(chaos_a=None, chaos_b=None):
    return (
        background_server(
            hidden_dim=HID, expert_uids=["hdg.0"], optimizer=optax.sgd(0.0),
            chaos=chaos_a,
        ),
        background_server(
            hidden_dim=HID, expert_uids=["hdg.0"], optimizer=optax.sgd(0.0),
            chaos=chaos_b,
        ),
    )


def test_hedge_fires_only_past_deadline():
    """Fast primary, generous deadline (3 × seeded 1 s EMA): the hedge
    never arms, and the replica set is still visible in the stats."""
    ctx_a, ctx_b = _replica_pair()
    with ctx_a as (ep_a, _), ctx_b as (ep_b, _):
        moe = _replicated_moe(ep_a, ep_b)
        _seed_rtt(ep_a, 1.0)   # deadline max(3 × 1.0, 0.05) = 3 s
        _seed_rtt(ep_b, 2.0)   # orders second → a is the primary
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        for i in range(3):
            jax.block_until_ready(moe(_x(seed=i), gate))
        routing = moe.dispatch_stats()["routing"]
        assert routing["hedge_fires"] == 0, routing
        assert routing["hedge_wins"] == 0
        assert moe.samples_dropped == 0
        assert routing["replica_counts"] == {"hdg.0": 2}
        assert moe._headline_metrics()["lah_client_replicas_max"] == 2


def test_dead_primary_fast_failure_failover_wins():
    """The primary dies while still listed in the alive set: its calls
    fail fast, the backup replica is fired immediately (no deadline wait
    needed for a hard failure), and the reply is bitwise the healthy
    twin's — zero dropped samples, hedge-win counter > 0."""
    ctx_a, ctx_b = _replica_pair()
    with ctx_a as (ep_a, srv_a), ctx_b as (ep_b, _):
        moe = _replicated_moe(ep_a, ep_b)
        _seed_rtt(ep_a, 0.001)  # cheapest → stays primary after death
        _seed_rtt(ep_b, 0.5)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        y0 = np.asarray(moe(_x(), gate))  # both alive (sgd(0.0): frozen)
        srv_a.shutdown()
        y1 = np.asarray(moe(_x(), gate))
        np.testing.assert_allclose(y1, y0, atol=1e-5)
        routing = moe.dispatch_stats()["routing"]
        assert routing["hedge_fires"] >= 1, routing
        assert routing["hedge_wins"] >= 1, routing
        assert moe.samples_dropped == 0


def test_hedge_win_cancels_straggler_primary_marked():
    """A SLOW (not dead) primary: the backup wins the race past the
    50 ms deadline, and the loser primary's cancel carries the straggler
    marker — its elapsed wait folds into its RTT EMA (the pool learns
    the slowness), per the QUORUM_STRAGGLER_CANCEL contract."""
    # the primary holds every reply back for a minute: the backup wins
    # however long a loaded machine takes over its first (compiling) call
    ctx_a, ctx_b = _replica_pair(
        chaos_a=ChaosConfig(base_latency=60.0, seed=0)
    )
    with ctx_a as (ep_a, _), ctx_b as (ep_b, _):
        moe = _replicated_moe(ep_a, ep_b, forward_timeout=120.0)
        pool_a = _seed_rtt(ep_a, 0.001)  # deadline = floor = 0.05 s
        pool_b = _seed_rtt(ep_b, 0.4)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        jax.block_until_ready(moe(_x(), gate))
        routing = moe.dispatch_stats()["routing"]
        assert routing["hedge_fires"] == 1, routing
        assert routing["hedge_wins"] == 1, routing
        assert moe.samples_dropped == 0
        # marked cancel folded the ≥50 ms elapsed wait into the EMA:
        # 0.8 × 0.001 + 0.2 × ≥0.05 ≥ 0.0108 ≫ the seeded 0.001
        assert pool_a.rtt_ema > 0.005, pool_a.rtt_ema
        # the dispatch did not wait for the primary: it is done, and every
        # byte that came back came from the backup
        assert pool_a.bytes_received == 0 and pool_b.bytes_received > 0


def test_hedge_loser_backup_ema_never_poisoned():
    """The primary answers AFTER the hedge fired but before the backup:
    the backup's cancel is UNMARKED, so its RTT EMA stays exactly the
    seeded value — a lost race is evidence about the race, not the
    peer."""
    ctx_a, ctx_b = _replica_pair(
        chaos_a=ChaosConfig(base_latency=0.15, seed=0),
        chaos_b=ChaosConfig(base_latency=5.0, seed=0),
    )
    with ctx_a as (ep_a, _), ctx_b as (ep_b, _):
        moe = _replicated_moe(ep_a, ep_b)
        _seed_rtt(ep_a, 0.001)  # deadline 0.05 s < the 0.15 s latency
        pool_b = _seed_rtt(ep_b, 0.4)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        y = np.asarray(moe(_x(), gate))
        assert np.isfinite(y).all()
        routing = moe.dispatch_stats()["routing"]
        assert routing["hedge_fires"] == 1, routing
        assert routing["hedge_wins"] == 0, routing  # the primary won
        assert pool_b.rtt_ema == 0.4, pool_b.rtt_ema  # bitwise untouched
        assert moe.samples_dropped == 0


def test_hedge_mult_zero_disables_hedging():
    ctx_a, ctx_b = _replica_pair(
        chaos_a=ChaosConfig(base_latency=0.2, seed=0)
    )
    with ctx_a as (ep_a, _), ctx_b as (ep_b, _):
        moe = _replicated_moe(ep_a, ep_b, hedge_mult=0.0)
        _seed_rtt(ep_a, 0.001)
        _seed_rtt(ep_b, 0.4)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        jax.block_until_ready(moe(_x(), gate))
        routing = moe.dispatch_stats()["routing"]
        assert routing["hedge_fires"] == 0, routing
        # it really waited out the slow primary instead of hedging
        assert moe.dispatch_times[-1] >= 0.15, list(moe.dispatch_times)


def test_backward_never_hedges():
    """Gradient fan-outs must not hedge — the server-side optimizer step
    is a side effect a duplicate request would apply twice.  The slow
    primary that makes every FORWARD hedge leaves the backward counters
    untouched (backups only ride the forward path)."""
    ctx_a, ctx_b = _replica_pair(
        chaos_a=ChaosConfig(base_latency=0.2, seed=0)
    )
    with ctx_a as (ep_a, _), ctx_b as (ep_b, _):
        moe = _replicated_moe(ep_a, ep_b)
        _seed_rtt(ep_a, 0.001)
        _seed_rtt(ep_b, 0.4)
        gate = moe.init_gate_params(jax.random.PRNGKey(0))

        def loss(g, x):
            return jnp.sum(moe(x, g) ** 2)

        g = jax.grad(loss)(gate, _x())
        assert all(np.isfinite(v).all() for v in jax.tree_util.tree_leaves(g))
        fires_after_step = moe.hedge_fires
        assert fires_after_step >= 1  # the forward hedged (slow primary)
        # the backward ran against the WINNER endpoint with no backup
        # armed: had it hedged, fires would exceed the forward's count
        assert moe.hedge_fires == fires_after_step
        assert moe.samples_dropped == 0


def test_replica_kill_mid_training_costs_less_than_one_round():
    """Tier-1 chaos variant of the churn scenario: kill the hot expert's
    primary replica mid-training.  Every post-kill step must succeed via
    the hedged fallback (zero failed dispatches, zero dropped samples)
    and the loss curve keeps improving — the kill costs < 1 round of
    quality, not a divergence."""
    with background_server(
        hidden_dim=HID, expert_uids=["hdg.0"], optimizer=optax.sgd(5e-2),
    ) as (ep_a, srv_a):
        with background_server(
            hidden_dim=HID, expert_uids=["hdg.0", "hdg.1"],
            optimizer=optax.sgd(5e-2),
        ) as (ep_b, _):
            # the hot expert hdg.0 is replicated; hdg.1 lives only on b
            source = StaticExpertSource(
                {"hdg.0": (ep_a, ep_b), "hdg.1": ep_b}
            )
            moe = RemoteMixtureOfExperts(
                in_features=HID, grid_size=(2,), uid_prefix="hdg",
                source=source, k_best=2, k_min=1, forward_timeout=20.0,
                hedge_floor_s=0.05,
            )
            _seed_rtt(ep_a, 0.001)  # the doomed primary for hdg.0
            _seed_rtt(ep_b, 0.4)
            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            opt = optax.adam(5e-2)
            opt_state = opt.init(gate)
            rs = np.random.RandomState(0)
            x = jnp.asarray(rs.randn(8, HID).astype(np.float32))
            y = jnp.asarray(rs.randn(8, HID).astype(np.float32) * 0.1)

            def loss(g):
                return jnp.mean((moe(x, g) - y) ** 2)

            losses = []
            for step in range(8):
                if step == 4:
                    srv_a.shutdown()  # kill the primary mid-training
                val, grads = jax.value_and_grad(loss)(gate)
                updates, opt_state = opt.update(grads, opt_state)
                gate = optax.apply_updates(gate, updates)
                losses.append(float(val))
            routing = moe.dispatch_stats()["routing"]
            assert moe.samples_dropped == 0, (losses, routing)
            assert routing["hedge_wins"] >= 1, routing
            # quality: the step right after the kill regresses by no
            # more than one step's usual movement (the backup replica
            # missed the primary's last gradient steps — that gap IS
            # the < 1 round cost), and the curve still ends below its
            # pre-kill level
            pre_kill, post_kill = losses[3], losses[4]
            step_move = max(
                abs(losses[2] - losses[3]), abs(losses[1] - losses[2])
            )
            assert post_kill <= pre_kill + step_move + 1e-3, losses
            assert losses[-1] < losses[3], losses


def test_loss_parity_cost_model_bias_vs_blind(monkeypatch):
    """Decode-gap guard (ROADMAP standing item): the cost-model bias at
    DEFAULT strength must not measurably degrade the smoke loss curve vs
    the bias=0 blind gate.  Two identical 4-expert swarms (same seeds →
    identical expert params), same data, same gate init; only the
    routing_cost_weight differs.  The bias is a function of the pools' RTT
    EMAs, so they are PINNED at what two loopback peers read on a quiet
    machine (1.0 and 1.2 ms): on a loaded one a peer's first exchange (a
    compile on its server) reads a second, and a bias of that size is
    another routing, not the near-tie breaker this guards."""
    from learning_at_home_tpu.utils.connection import ConnectionPool

    monkeypatch.setattr(ConnectionPool, "_update_rtt", lambda self, dt: None)

    def run(weight):
        with background_server(
            num_experts=2, hidden_dim=HID, expert_prefix="par", seed=3,
            optimizer=optax.sgd(1e-2),
        ) as (ep_a, srv_a):
            with background_server(
                num_experts=2, hidden_dim=HID, expert_prefix="par",
                expert_offset=2, seed=3, optimizer=optax.sgd(1e-2),
            ) as (ep_b, srv_b):
                experts = {uid: ep_a for uid in srv_a.experts}
                experts.update({uid: ep_b for uid in srv_b.experts})
                _seed_rtt(ep_a, 1.0e-3)
                _seed_rtt(ep_b, 1.2e-3)
                moe = RemoteMixtureOfExperts(
                    in_features=HID, grid_size=(4,), uid_prefix="par",
                    source=StaticExpertSource(experts), k_best=2, k_min=1,
                    timeout_after_k_min=2.0, routing_cost_weight=weight,
                )
                gate = moe.init_gate_params(jax.random.PRNGKey(1))
                opt = optax.adam(5e-2)
                opt_state = opt.init(gate)
                rs = np.random.RandomState(7)
                x = jnp.asarray(rs.randn(8, HID).astype(np.float32))
                y = jnp.asarray(rs.randn(8, HID).astype(np.float32) * 0.1)

                def loss(g):
                    return jnp.mean((moe(x, g) - y) ** 2)

                losses = []
                for _ in range(6):
                    val, grads = jax.value_and_grad(loss)(gate)
                    updates, opt_state = opt.update(grads, opt_state)
                    gate = optax.apply_updates(gate, updates)
                    losses.append(float(val))
                applied = moe.dispatch_stats()["routing"]["bias_applied"]
        reset_client_rpc()
        return losses, applied

    blind, blind_applied = run(0.0)
    cost, cost_applied = run(DEFAULT_COST_WEIGHT)
    assert blind_applied == 0  # the A/B arm really is the blind gate
    assert cost_applied > 0    # and the cost arm really biased selection
    # parity: two different routings are compared, so a tolerance: the
    # biased arm's final loss within a tenth of the blind arm's (loopback
    # peers are near-identical, so the bias should only resolve near-ties,
    # never distort the mixture measurably)
    assert cost[-1] <= blind[-1] + max(0.1 * abs(blind[-1]), 0.02), (
        blind, cost,
    )
    # both curves actually trained
    assert cost[-1] < cost[0] and blind[-1] < blind[0]


def test_add_replica_builds_identical_backend_and_serves():
    """Server-side lifecycle: an (initially empty) server grows a
    replica of a uid it never hosted via ``add_replica`` — the crc32-uid
    seeding means the replica's params are BITWISE the original
    hoster's, so a dispatch answered by either replica is the same
    mixture."""
    from learning_at_home_tpu.server.server import Server

    with background_server(
        hidden_dim=HID, expert_uids=["ar.0"], optimizer=optax.sgd(0.0),
    ) as (ep_a, srv_a):
        srv_b = Server.create(
            num_experts=0, hidden_dim=HID, host="127.0.0.1",
            optimizer=optax.sgd(0.0),
        )
        try:
            assert srv_b.add_replica("ar.0") is True
            assert srv_b.add_replica("ar.0") is False  # idempotent
            assert srv_b.replica_uids == {"ar.0"}
            assert srv_b._telemetry_extra()["replicas"] == ["ar.0"]
            pa = srv_a.experts["ar.0"].state_dict()["params"]
            pb = srv_b.experts["ar.0"].state_dict()["params"]
            for a, b in zip(
                jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)
            ):
                np.testing.assert_array_equal(a, b)
            # and the replica actually serves: dispatch pinned to it
            moe = RemoteMixtureOfExperts(
                in_features=HID, grid_size=(1,), uid_prefix="ar",
                source=StaticExpertSource({"ar.0": srv_b.endpoint}),
                k_best=1, k_min=1, forward_timeout=20.0,
            )
            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            y = np.asarray(moe(_x(), gate))
            assert np.isfinite(y).all()
            assert moe.samples_dropped == 0
        finally:
            srv_b.shutdown()


def test_replica_sync_converges_diverged_replicas():
    """Replicas of a TRAINING expert stay in sync through the existing
    averaging machinery (ReplicaSync → DecentralizedAverager butterfly
    all-reduce): two hosters whose params were deliberately diverged end
    a sync round with the group mean on BOTH sides."""
    import time

    from learning_at_home_tpu.dht import DHT
    from learning_at_home_tpu.server.server import Server

    boot = DHT()
    d_a = DHT(initial_peers=[boot.endpoint])
    d_b = DHT(initial_peers=[boot.endpoint])
    srv_a = srv_b = None
    try:
        srv_a = Server.create(
            expert_uids=["rs.0"], hidden_dim=HID, host="127.0.0.1",
            optimizer=optax.sgd(0.0), dht=d_a, update_period=1.0,
        )
        srv_b = Server.create(
            expert_uids=["rs.0"], hidden_dim=HID, host="127.0.0.1",
            optimizer=optax.sgd(0.0), dht=d_b, update_period=1.0,
        )
        # diverge b's copy: +1 on every leaf (as if it missed updates)
        b_backend = srv_b.experts["rs.0"]
        pa = srv_a.experts["rs.0"].state_dict()["params"]
        b_backend.replace_params(
            jax.tree_util.tree_map(
                lambda t: t + 1.0, b_backend.state_dict()["params"]
            )
        )
        sync_a = srv_a.enable_replica_sync("rs.0", period=0.5)
        sync_b = srv_b.enable_replica_sync("rs.0", period=0.5)
        assert srv_a.enable_replica_sync("rs.0") is sync_a  # idempotent
        deadline = time.time() + 30
        while time.time() < deadline:
            if sync_a.rounds >= 1 and sync_b.rounds >= 1:
                break
            time.sleep(0.2)
        assert sync_a.rounds >= 1 and sync_b.rounds >= 1, (
            sync_a.stats(), sync_b.stats(),
        )
        mean = jax.tree_util.tree_map(lambda t: t + 0.5, pa)
        got_a = srv_a.experts["rs.0"].state_dict()["params"]
        got_b = srv_b.experts["rs.0"].state_dict()["params"]
        for m, a, b in zip(
            jax.tree_util.tree_leaves(mean),
            jax.tree_util.tree_leaves(got_a),
            jax.tree_util.tree_leaves(got_b),
        ):
            # members end bitwise-equal per partition (PR 3 contract);
            # vs the analytic mean allow float tolerance
            np.testing.assert_array_equal(a, b)
            np.testing.assert_allclose(a, m, atol=1e-5)
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                srv.shutdown()
        reset_client_rpc()
        for d in (d_a, d_b, boot):
            d.shutdown()


def test_sole_endpoint_rescue_fresh_lookup_retry():
    """A NON-replicated uid whose only endpoint dies mid-record-TTL
    (ISSUE 11): no hedge backup exists, so the dispatch must do ONE
    cache-bypassing alive refresh, re-resolve the uid (simulating a
    migrated host that re-declared within a heartbeat), and retry the
    same prepared payload at the fresh endpoint — zero dropped samples,
    bitwise-identical reply (both servers crc32-seed ``hdg.0``)."""
    ctx_a, ctx_b = _replica_pair()
    with ctx_a as (ep_a, srv_a), ctx_b as (ep_b, _):
        source = StaticExpertSource({"hdg.0": ep_a})  # sole endpoint
        # short forward_timeout: the half-open connection to the killed
        # server HANGS (no RST) and only fails at the rpc timeout — the
        # rescue triggers on that failure, not on a magic fast error
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(1,), uid_prefix="hdg",
            source=source, k_best=1, k_min=1, forward_timeout=2.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        y0 = np.asarray(moe(_x(), gate))
        srv_a.shutdown()
        source.experts["hdg.0"] = ep_b  # the 'migrated' re-declaration
        y1 = np.asarray(moe(_x(), gate))
        np.testing.assert_allclose(y1, y0, atol=1e-5)
        routing = moe.dispatch_stats()["routing"]
        assert routing["fresh_retries"] >= 1, routing
        assert routing["fresh_retry_wins"] >= 1, routing
        assert moe.samples_dropped == 0


def test_sole_endpoint_rescue_no_replacement_degrades():
    """The rescue fires at most once per uid and, when the fresh lookup
    finds no replacement (the static table still points at the corpse),
    the sample degrades through the normal quorum path instead of
    retrying forever."""
    ctx_a = background_server(
        hidden_dim=HID, expert_uids=["hdg.0"], optimizer=optax.sgd(0.0)
    )
    ctx_b = background_server(
        hidden_dim=HID, expert_uids=["hdg.1"], optimizer=optax.sgd(0.0)
    )
    with ctx_a as (ep_a, srv_a), ctx_b as (ep_b, _):
        source = StaticExpertSource({"hdg.0": ep_a, "hdg.1": ep_b})
        # grace (timeout_after_k_min) must outlive forward_timeout here:
        # hdg.1's fast reply meets the quorum and arms the grace period,
        # and the hung call to the corpse only fails at forward_timeout —
        # the rescue needs to fire inside that window to be observable
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(2,), uid_prefix="hdg",
            source=source, k_best=2, k_min=1, forward_timeout=2.0,
            timeout_after_k_min=5.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        jax.block_until_ready(moe(_x(), gate))
        srv_a.shutdown()
        jax.block_until_ready(moe(_x(), gate))  # hdg.1 alone meets k_min=1
        routing = moe.dispatch_stats()["routing"]
        assert routing["fresh_retries"] >= 1, routing
        assert routing["fresh_retry_wins"] == 0, routing
        assert moe.samples_dropped == 0
