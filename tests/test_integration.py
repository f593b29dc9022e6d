"""Full-stack integration: Server ⇄ DHT ⇄ RemoteMixtureOfExperts.

The complete call stack of SURVEY.md §3.1/§3.3: server declares its experts
to the DHT (heartbeat), client discovers alive experts via the DHT and
routes batches; record expiry drops dead servers from routing."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.moe import MoEDispatchError, RemoteMixtureOfExperts
from learning_at_home_tpu.dht import DHT
from learning_at_home_tpu.server.server import background_server

HID = 16


def test_server_dht_moe_end_to_end():
    bootstrap = DHT()
    client_dht = DHT(initial_peers=[bootstrap.endpoint])
    try:
        with background_server(
            num_experts=4,
            hidden_dim=HID,
            expert_prefix="ffn",
            seed=3,
            dht=DHT(initial_peers=[bootstrap.endpoint]),
            update_period=0.5,
        ) as (endpoint, srv):
            # wait for the first heartbeat to land
            deadline = time.time() + 10
            while time.time() < deadline:
                alive = client_dht._loop.run(client_dht._get_alive("ffn"))
                if len(alive) == 4:
                    break
                time.sleep(0.1)
            assert len(alive) == 4, f"experts never appeared in DHT: {alive}"
            assert all(ep == endpoint for ep in alive.values())

            # route a real batch through DHT discovery
            moe = RemoteMixtureOfExperts(
                in_features=HID, grid_size=(4,), uid_prefix="ffn",
                source=client_dht, k_best=2, k_min=1, alive_ttl=0.2,
            )
            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            x = jnp.asarray(np.random.RandomState(0).randn(3, HID).astype(np.float32))
            out = moe(x, gate)
            assert out.shape == (3, HID)
            assert np.isfinite(np.asarray(out)).all()

            # gradients flow end-to-end through DHT-discovered experts
            g = jax.grad(lambda gp, x: jnp.sum(moe(x, gp) ** 2))(gate, x)
            assert float(jnp.abs(g["w0"]).sum()) > 0
            srv.dht.shutdown()

        # server down: records expire (TTL = 2*update_period = 1s)
        deadline = time.time() + 15
        while time.time() < deadline:
            alive = client_dht._loop.run(client_dht._get_alive("ffn"))
            if not alive:
                break
            time.sleep(0.2)
        assert alive == {}, f"dead server's records never expired: {alive}"

        # routing now fails loudly, not silently
        moe2 = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn",
            source=client_dht, k_best=2, k_min=1, alive_ttl=0.0,
        )
        gate2 = moe2.init_gate_params(jax.random.PRNGKey(1))
        with pytest.raises(Exception):
            np.asarray(moe2(jnp.ones((2, HID), jnp.float32), gate2))
    finally:
        client_dht.shutdown()
        bootstrap.shutdown()
        reset_client_rpc()
