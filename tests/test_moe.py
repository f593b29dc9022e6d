"""M1 tests (BASELINE config 2): RemoteMixtureOfExperts, 4 FFN experts,
top-2 gating, single host — plus the k-of-n fault-tolerance path."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.moe import MoEDispatchError, RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import (
    StaticExpertSource,
    make_uid,
    select_top_k,
    split_uid,
)
from learning_at_home_tpu.server.server import background_server

HID = 16


def test_uid_helpers():
    assert make_uid("ffn", (4, 17)) == "ffn.4.17"
    assert split_uid("ffn.4.17") == ("ffn", (4, 17))
    assert split_uid("expert.3") == ("expert", (3,))


def test_select_top_k():
    rs = np.random.RandomState(0)
    logits = [rs.randn(5, 4).astype(np.float32), rs.randn(5, 3).astype(np.float32)]
    uids = [make_uid("g", (i, j)) for i in range(4) for j in range(3)]
    sel, coords = select_top_k(logits, uids, k=3)
    assert sel.shape == (5, 3)
    # brute force check
    for b in range(5):
        scores = np.array([logits[0][b, i] + logits[1][b, j] for i, j in coords])
        best = np.argsort(-scores)[:3]
        np.testing.assert_array_equal(np.sort(scores[sel[b]]), np.sort(scores[best]))
        assert scores[sel[b, 0]] >= scores[sel[b, 1]] >= scores[sel[b, 2]]


@pytest.fixture(scope="module")
def moe_server():
    with background_server(
        num_experts=4, hidden_dim=HID, expert_prefix="ffn", seed=7
    ) as (endpoint, srv):
        source = StaticExpertSource({uid: endpoint for uid in srv.experts})
        yield endpoint, srv, source
    reset_client_rpc()


def _local_outputs(srv, x):
    """Each expert's output under its live server-side params."""
    outs = {}
    for uid, backend in srv.experts.items():
        params = backend.state_dict()["params"]
        outs[uid] = np.asarray(backend.apply_fn(params, x))
    return outs


def test_moe_forward_full_mixture(moe_server):
    """k_best = all experts: output must equal the full softmax mixture."""
    endpoint, srv, source = moe_server
    moe = RemoteMixtureOfExperts(
        in_features=HID, grid_size=(4,), uid_prefix="ffn", source=source,
        k_best=4, k_min=4,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(0))
    x = np.random.RandomState(1).randn(6, HID).astype(np.float32)

    local = _local_outputs(srv, x)
    out = np.asarray(moe(jnp.asarray(x), gate))

    logits = np.concatenate(
        [np.asarray(x @ gate["w0"])], axis=1
    )  # [6, 4], one grid dim
    w = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    expected = np.zeros_like(x)
    for i in range(4):
        expected += np.asarray(w[:, i : i + 1]) * local[f"ffn.{i}"]
    np.testing.assert_allclose(out, expected, atol=1e-4, rtol=1e-4)


def test_moe_top2_selects_best(moe_server):
    endpoint, srv, source = moe_server
    moe = RemoteMixtureOfExperts(
        in_features=HID, grid_size=(4,), uid_prefix="ffn", source=source,
        k_best=2, k_min=2,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(3))
    x = np.random.RandomState(2).randn(5, HID).astype(np.float32)
    local = _local_outputs(srv, x)

    out = np.asarray(moe(jnp.asarray(x), gate))

    logits = np.asarray(x @ np.asarray(gate["w0"]))  # [5, 4]
    expected = np.zeros_like(x)
    for b in range(5):
        top2 = np.argsort(-logits[b])[:2]
        w = jax.nn.softmax(jnp.asarray(logits[b, top2]))
        for wi, i in zip(np.asarray(w), top2):
            expected[b] += wi * local[f"ffn.{i}"][b]
    np.testing.assert_allclose(out, expected, atol=1e-4, rtol=1e-4)


def test_moe_grad_flows_and_updates_experts(moe_server):
    endpoint, srv, source = moe_server
    # generous grace: the update_count assertion below needs EVERY backward
    # RPC to land, so slow-box stragglers must not be cancelled mid-test
    moe = RemoteMixtureOfExperts(
        in_features=HID, grid_size=(4,), uid_prefix="ffn", source=source,
        k_best=4, k_min=4, backward_k_min=1, timeout_after_k_min=15.0,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(5))
    x = jnp.asarray(np.random.RandomState(3).randn(4, HID).astype(np.float32))

    updates_before = {uid: b.update_count for uid, b in srv.experts.items()}

    def loss(gate, x):
        return jnp.sum(moe(x, gate) ** 2)

    ggate, gx = jax.grad(loss, argnums=(0, 1))(gate, x)
    # gate grads are nonzero (gradient through softmax-weighted mixture)
    assert float(jnp.abs(ggate["w0"]).sum()) > 0
    # x grads are nonzero (gradient through the backward RPCs)
    assert float(jnp.abs(gx).sum()) > 0
    # every expert participated → every expert applied its async update
    for uid, b in srv.experts.items():
        assert b.update_count == updates_before[uid] + 1, uid


def test_moe_under_jit(moe_server):
    endpoint, srv, source = moe_server
    moe = RemoteMixtureOfExperts(
        in_features=HID, grid_size=(4,), uid_prefix="ffn", source=source,
        k_best=2, k_min=1,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(6))

    @jax.jit
    def step(x, gate):
        return moe(x, gate).sum()

    x = jnp.ones((3, HID), jnp.float32)
    v1 = step(x, gate)
    v2 = step(x, gate)  # compiled-cache path
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)


def test_moe_fault_tolerance_dead_server():
    """One of two servers dies; k_min=1 dispatch must still return."""
    with background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=11
    ) as (ep_alive, srv_alive):
        with background_server(
            num_experts=2, hidden_dim=HID, expert_prefix="dead", seed=12
        ) as (ep_dead, _):
            pass  # exits immediately → server is down, port is stale
        source = StaticExpertSource(
            {
                "ffn.0": ep_alive,
                "ffn.1": ep_alive,
                # grid coords 2,3 point at the dead endpoint
                "ffn.2": ep_dead,
                "ffn.3": ep_dead,
            }
        )
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn", source=source,
            k_best=4, k_min=1, timeout_after_k_min=0.2, forward_timeout=2.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = np.random.RandomState(5).randn(3, HID).astype(np.float32)
        out = np.asarray(moe(jnp.asarray(x), gate))
        # mixture must equal softmax over the ALIVE experts only
        local = _local_outputs(srv_alive, x)
        logits = np.asarray(x @ np.asarray(gate["w0"]))[:, :2]  # alive coords 0,1
        w = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        expected = w[:, 0:1] * local["ffn.0"] + w[:, 1:2] * local["ffn.1"]
        np.testing.assert_allclose(out, expected, atol=1e-4, rtol=1e-4)
    reset_client_rpc()


def test_moe_per_sample_quorum_degradation():
    """A sample whose only chosen expert is dead is masked to ZERO
    contribution and counted — the step survives; gradients stay finite
    (per-sample degradation, not step-killing; VERDICT r1 item 6)."""
    with background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="ffn", seed=13
    ) as (ep_alive, srv):
        with background_server(
            num_experts=1, hidden_dim=HID, expert_prefix="dead", seed=14
        ) as (ep_dead, _):
            pass  # exits immediately → dead endpoint
        source = StaticExpertSource({"ffn.0": ep_alive, "ffn.1": ep_dead})
        # the dead endpoint refuses the connection at once; the alive
        # one's first call (a compile on its server) took more than the
        # 1.5 s this waited under six loaded workers, and BOTH samples
        # dropped: the wait is one that only a peer that is gone meets
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(2,), uid_prefix="ffn", source=source,
            k_best=1, k_min=1, forward_timeout=60.0, backward_timeout=60.0,
        )
        # deterministic routing: sample 0 → expert 0 (alive),
        # sample 1 → expert 1 (dead)
        w0 = np.zeros((HID, 2), np.float32)
        w0[0, 0] = w0[1, 1] = 10.0
        gate = {"w0": jnp.asarray(w0)}
        x = np.zeros((2, HID), np.float32)
        x[0, 0] = x[1, 1] = 1.0

        out = np.asarray(moe(jnp.asarray(x), gate))
        local = _local_outputs(srv, x)
        np.testing.assert_allclose(out[0], local["ffn.0"][0], atol=1e-4)
        np.testing.assert_allclose(out[1], 0.0)  # dropped, not poisoned
        assert moe.samples_total == 2 and moe.samples_dropped == 1

        # gradients survive too: dead sample contributes zero input-grad
        def loss(gate, x):
            return moe(jnp.asarray(x), gate).sum()

        g = jax.grad(loss)(gate, x)
        assert np.isfinite(np.asarray(g["w0"])).all()
        # the forward-dropped sample's missing grads are EXPECTED — they
        # must not be double-counted as a backward failure
        assert moe.backward_samples_dropped == 0
    reset_client_rpc()


def test_moe_quorum_failure_raises():
    """All experts dead → MoEDispatchError, not a hang or silent zero."""
    with background_server(num_experts=1, hidden_dim=HID, expert_prefix="x") as (
        ep,
        _,
    ):
        pass  # dead now
    source = StaticExpertSource({"x.0": ep})
    moe = RemoteMixtureOfExperts(
        in_features=HID, grid_size=(1,), uid_prefix="x", source=source,
        k_best=1, k_min=1, forward_timeout=1.5,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(0))
    with pytest.raises(Exception):  # XLA wraps the MoEDispatchError
        np.asarray(moe(jnp.ones((2, HID), jnp.float32), gate))
    reset_client_rpc()


class TestWireDtype:
    """bf16 wire compression (round-3 verdict task 4): payloads downcast
    on the wire both directions, math still f32 on both ends."""

    def test_forward_parity_and_backward_runs(self, moe_server):
        endpoint, srv, source = moe_server
        kw = dict(
            in_features=HID, grid_size=(4,), uid_prefix="ffn",
            source=source, k_best=2, k_min=2,
        )
        moe32 = RemoteMixtureOfExperts(**kw)
        moe16 = RemoteMixtureOfExperts(**kw, wire_dtype="bfloat16")
        gate = moe32.init_gate_params(jax.random.PRNGKey(0))
        x = np.random.RandomState(3).randn(8, HID).astype(np.float32)

        y32 = np.asarray(moe32(jnp.asarray(x), gate))
        y16 = np.asarray(moe16(jnp.asarray(x), gate))
        # bf16 keeps ~8 mantissa bits: outputs agree to bf16 resolution
        np.testing.assert_allclose(y16, y32, rtol=0.05, atol=0.05)
        assert not np.allclose(y16, 0.0)

        # backward (fires the server's async optimizer step) must run and
        # produce finite input-grads through the compressed wire
        def loss(gate, x):
            return jnp.sum(moe16(x, gate) ** 2)

        g = jax.grad(loss, argnums=1)(gate, jnp.asarray(x))
        assert np.isfinite(np.asarray(g)).all()

    def test_remote_expert_wire_dtype(self, moe_server):
        from learning_at_home_tpu.client import RemoteExpert

        endpoint, srv, source = moe_server
        uid = sorted(srv.experts)[0]
        e32 = RemoteExpert(uid, endpoint)
        e16 = RemoteExpert(uid, endpoint, wire_dtype="bfloat16")
        x = np.random.RandomState(0).randn(4, HID).astype(np.float32)
        y32 = np.asarray(e32.forward_blocking([x])[0])
        reply = e16.forward_blocking([x])[0]
        # server downcasts its reply to the wire dtype
        assert reply.dtype == np.dtype("bfloat16")
        np.testing.assert_allclose(
            np.asarray(reply, np.float32), y32, rtol=0.05, atol=0.05
        )

    def test_bad_wire_dtype_rejected(self, moe_server):
        endpoint, srv, source = moe_server
        with pytest.raises(ValueError, match="wire_dtype"):
            RemoteMixtureOfExperts(
                in_features=HID, grid_size=(4,), uid_prefix="ffn",
                source=source, wire_dtype="float64",
            )


def test_select_top_k_bias_steers_selection():
    """Selection bias (latency-aware routing) flips near-ties without
    touching the caller's score space."""
    logits = [np.tile([0.2, 0.0, 0.0, 0.5], (3, 1)).astype(np.float32)]
    uids = [make_uid("b", (i,)) for i in range(4)]
    sel0, _ = select_top_k(logits, uids, k=1)
    assert (sel0 == 3).all()  # best gate score wins unbiased
    bias = np.asarray([0.0, 0.0, 0.0, -1.0], np.float32)  # slow peer
    sel, _ = select_top_k(logits, uids, k=1, bias=bias)
    assert (sel == 0).all()  # the penalty outweighs the 0.3 gate edge


class TestLatencyAwareRouting:
    """latency_weight: selection learns to avoid a slow peer (cf. the
    topology-/placement-aware MoE serving literature)."""

    HELD_S = 0.25  # what the slow peer's chaos holds every reply

    def _run(self, latency_weight: float, patch) -> list:
        """The selections of eight dispatches.  What a pool's EMA learns is
        what its peer HOLDS (the slow one ``HELD_S``, the fast one a
        loopback's millisecond), not what this machine's clock read: under
        six loaded workers the fast peer's first exchange (a compile on its
        server) read longer than the slow peer's 0.25 s, and the selection
        learned to avoid the wrong one."""
        from learning_at_home_tpu.server import ChaosConfig
        from learning_at_home_tpu.utils.connection import ConnectionPool

        slow_chaos = ChaosConfig(base_latency=self.HELD_S, seed=0)
        with background_server(
            num_experts=2, hidden_dim=HID, expert_prefix="lat", seed=1
        ) as (fast_ep, fast_srv):
            with background_server(
                num_experts=2, hidden_dim=HID, expert_prefix="lat",
                expert_offset=2, seed=2, chaos=slow_chaos,
            ) as (slow_ep, slow_srv):
                learn = ConnectionPool._update_rtt
                patch.setattr(
                    ConnectionPool, "_update_rtt", lambda pool, dt: learn(
                        pool, self.HELD_S if pool.endpoint == slow_ep else 1e-3))
                experts = {uid: fast_ep for uid in fast_srv.experts}
                experts.update({uid: slow_ep for uid in slow_srv.experts})
                moe = RemoteMixtureOfExperts(
                    in_features=HID, grid_size=(4,), uid_prefix="lat",
                    source=StaticExpertSource(experts), k_best=2, k_min=1,
                    timeout_after_k_min=2.0,
                    latency_weight=latency_weight,
                )
                gate = moe.init_gate_params(jax.random.PRNGKey(0))
                rs = np.random.RandomState(0)
                for _ in range(8):
                    x = jnp.asarray(rs.randn(6, HID).astype(np.float32))
                    moe(x, gate)
                selections = list(moe.selection_log)
        reset_client_rpc()
        return selections

    SLOW_UIDS = frozenset({"lat.2", "lat.3"})

    def test_latency_weight_learns_to_avoid_slow_peer(self, monkeypatch):
        with monkeypatch.context() as patch:
            aware_sel = self._run(20.0, patch)
        with monkeypatch.context() as patch:
            blind_sel = self._run(0.0, patch)
        # THE MECHANISM: the first dispatches probe both peers (EMA
        # warmup); once the slow peer's 0.25 s EMA is learned its
        # selection score drops by ~5, so the LAST dispatches must not
        # select its experts at all — while the unbiased control keeps
        # picking them (the gate's scores alone are topology-blind)
        late_aware = frozenset().union(*aware_sel[-3:])
        late_blind = frozenset().union(*blind_sel[-3:])
        assert not (late_aware & self.SLOW_UIDS), (aware_sel, late_aware)
        assert late_blind & self.SLOW_UIDS, (blind_sel, late_blind)
        # what routing around the slow peer saves, as a count: it is asked
        # in fewer dispatches than the control asks it (each pays HELD_S)
        asked = [sum(1 for chosen in log if chosen & self.SLOW_UIDS)
                 for log in (aware_sel, blind_sel)]
        assert asked[0] < asked[1], (aware_sel, blind_sel)
