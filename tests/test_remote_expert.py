"""M1 integration tests: real Server on localhost + RemoteExpert stubs.

Mirrors the reference's test_moe.py-style integration tier (SURVEY.md §4):
remote forward/backward must match an identical local module numerically,
including gradient flow through the custom-vjp network boundary."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
from learning_at_home_tpu.models import make_expert
from learning_at_home_tpu.server import ExpertBackend
from learning_at_home_tpu.server.server import Server, background_server

HID = 32


@pytest.fixture(scope="module")
def server():
    with background_server(num_experts=2, hidden_dim=HID, seed=42) as (endpoint, srv):
        yield endpoint, srv
    reset_client_rpc()


def local_twin(seed, uid_index):
    rng = jax.random.PRNGKey(seed + uid_index)
    return make_expert("ffn", HID, rng, jnp.zeros((2, HID)))


def test_remote_forward_matches_local(server):
    endpoint, _ = server
    expert = RemoteExpert("expert.0", endpoint)
    apply_fn, params = local_twin(42, 0)
    x = np.random.RandomState(0).randn(8, HID).astype(np.float32)
    out = expert(x)
    expected = apply_fn(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)


def test_remote_forward_under_jit(server):
    endpoint, _ = server
    expert = RemoteExpert("expert.1", endpoint)
    apply_fn, params = local_twin(42, 1)
    x = np.random.RandomState(1).randn(4, HID).astype(np.float32)

    @jax.jit
    def step(x):
        return expert(x) * 2.0

    out = step(x)
    expected = apply_fn(params, x) * 2.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)


def test_remote_grad_matches_local_and_updates_server(server):
    endpoint, srv = server
    expert = RemoteExpert("expert.0", endpoint)
    apply_fn, params = local_twin(42, 0)
    x = np.random.RandomState(2).randn(4, HID).astype(np.float32)

    # server params may already have been updated by other tests in this
    # module — read the live state for the expectation instead
    live_params = srv.experts["expert.0"].state_dict()["params"]

    def local_loss(x):
        return jnp.sum(apply_fn(live_params, x) ** 2)

    def remote_loss(x):
        return jnp.sum(expert(x) ** 2)

    expected_grad = jax.grad(local_loss)(jnp.asarray(x))
    before = srv.experts["expert.0"].update_count
    got_grad = jax.grad(remote_loss)(jnp.asarray(x))
    np.testing.assert_allclose(
        np.asarray(got_grad), np.asarray(expected_grad), atol=1e-3, rtol=1e-3
    )
    # the backward RPC must have applied the server-side async optimizer step
    assert srv.experts["expert.0"].update_count == before + 1


def test_info_rpc(server):
    endpoint, _ = server
    info = RemoteExpert("expert.1", endpoint).info()
    assert info["name"] == "expert.1"
    assert info["num_params"] > 0


def test_unknown_expert_raises(server):
    endpoint, _ = server
    from learning_at_home_tpu.utils.connection import RemoteCallError

    with pytest.raises(RemoteCallError, match="unknown expert"):
        RemoteExpert("nonexistent.99", endpoint).forward_blocking(
            [np.zeros((1, HID), np.float32)]
        )


def test_cross_client_batching(server, monkeypatch):
    """Many concurrent remote calls get batched into few device batches.
    The pool is held open until the sixteenth request is in (its window
    closes on the row count, 16 x 2, not on its 2 ms timer: under load the
    requests arrive further apart than that), so the count is exact: 16
    requests, ONE batch."""
    endpoint, srv = server
    expert = RemoteExpert("expert.1", endpoint)
    pool = srv.forward_pools["expert.1"]
    monkeypatch.setattr(pool, "batch_timeout", 60.0)
    monkeypatch.setattr(pool, "max_batch_size", 16 * 2)
    formed_before = pool.batches_formed

    import concurrent.futures as cf

    xs = [np.random.randn(2, HID).astype(np.float32) for _ in range(16)]
    with cf.ThreadPoolExecutor(16) as ex:
        outs = list(ex.map(lambda x: expert.forward_blocking([x])[0], xs))
    apply_fn, params = local_twin(42, 1)
    live = srv.experts["expert.1"].state_dict()["params"]
    for x, out in zip(xs, outs):
        np.testing.assert_allclose(
            out, np.asarray(apply_fn(live, x)), atol=1e-4, rtol=1e-4
        )
    # if batching broke, every request would form its own batch
    assert pool.batches_formed - formed_before == 1
    assert pool.bucket_batches.get(32, 0) >= 1


def test_server_create_classmethod():
    """Server.create: zoo-built experts, optional warmup, full serve cycle."""
    from learning_at_home_tpu.server import Server

    srv = Server.create(
        num_experts=2, hidden_dim=16, expert_prefix="zoo", host="127.0.0.1",
        warmup=False,
    )
    try:
        e = RemoteExpert("zoo.0", srv.endpoint)
        x = np.random.RandomState(0).randn(2, 16).astype(np.float32)
        (out,) = e.forward_blocking([x])
        assert out.shape == (2, 16)
        assert e.info()["name"] == "zoo.0"
    finally:
        srv.shutdown()


def test_server_stats_op():
    """The server-wide `stats` op: one round trip returns update counts
    and pool/padding counters for every hosted expert (docs/PROTOCOL.md)."""
    import numpy as np

    from learning_at_home_tpu.client.rpc import client_loop, pool_registry
    from learning_at_home_tpu.server.server import background_server

    with background_server(
        num_experts=2, hidden_dim=8, expert_prefix="st", seed=0
    ) as (endpoint, srv):
        from learning_at_home_tpu.client import RemoteExpert

        e = RemoteExpert("st.0", endpoint)
        x = np.random.RandomState(0).randn(3, 8).astype(np.float32)
        e.forward_blocking([x])
        e.backward_blocking([x], [x])  # applies one async update

        async def stats():
            _, meta = await pool_registry().get(endpoint).rpc(
                "stats", (), {}, timeout=10.0
            )
            return meta

        s = client_loop().run(stats())
    assert s["n_experts"] == 2
    assert s["update_count_total"] == 1
    assert s["update_count"]["st.0"] == 1 and s["update_count"]["st.1"] == 0
    assert s["pools"]["forward"]["batches_formed"] >= 1
    assert s["pools"]["forward"]["rows"] >= 3
    assert 0.0 <= s["pools"]["forward"]["padding_waste"] < 1.0
