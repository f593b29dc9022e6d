"""Swarm-mode DMoE-Transformer integration (the reference's headline
trainer) + the async data-parallel contract: several independent trainers
sharing one expert pool, each expert updating asynchronously."""

import concurrent.futures as cf

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.models import make_expert
from learning_at_home_tpu.models.transformer_swarm import (
    SwarmDMoETransformerLM,
    SwarmTransformerConfig,
)
from learning_at_home_tpu.server import ExpertBackend, Server

D = 16
VOCAB = 64


@pytest.fixture(scope="module")
def swarm():
    """Expert server in a SEPARATE process (the real deployment topology).

    In-process client+server share one XLA CPU runtime: a trainer's
    io_callback blocks an execution slot while waiting for the reply, and
    the server's jitted backward needs a slot from the same pool — under
    enough concurrency that converges to a stall (observed via
    faulthandler stack dumps).  Cross-process, each side owns its runtime.
    """
    import os
    import subprocess
    import sys
    import tempfile
    import time

    from learning_at_home_tpu.client import RemoteExpert

    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = clean_jax_subprocess_env(repo, platform="cpu")
    port = 43311
    # a file, not a pipe: nobody reads while the server runs, and a full
    # pipe stops it (XLA's loader writes 2 KB a cached program on some hosts)
    log = tempfile.TemporaryFile(mode="w+")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "learning_at_home_tpu.server",
            "--num-experts", "2", "--hidden-dim", str(D),
            "--expert-prefix", "ffn0", "--port", str(port), "--no-dht",
            "--optimizer", "adam", "--lr", "1e-3",
            "--max-batch-size", "2048", "--warmup", "32", "64",
        ],
        env=env, stdout=log, stderr=subprocess.STDOUT, text=True,
    )
    uids = ["ffn0.0", "ffn0.1"]
    endpoint = ("127.0.0.1", port)
    probe = RemoteExpert(uids[0], endpoint, timeout=10.0)
    deadline = time.time() + 120
    up = False
    while time.time() < deadline:
        if proc.poll() is not None:
            log.seek(0)
            raise AssertionError(f"server died: {log.read()[-2000:]}")
        try:
            probe.info()
            up = True
            break
        except Exception:
            time.sleep(1.0)
    assert up, "server never came up"
    source = StaticExpertSource({uid: endpoint for uid in uids})

    class ExpertView:
        """update-count telemetry via the info RPC (server is remote now)."""

        @property
        def experts(self):
            return {
                uid: RemoteExpert(uid, endpoint, timeout=10.0).info()
                for uid in uids
            }

    yield ExpertView(), source
    proc.terminate()
    proc.wait(timeout=30)
    reset_client_rpc()


def _model(source):
    # deliberately tiny: every first-time XLA compile happens inside an RPC
    # window on a 1-core box, so the compile budget must stay small
    cfg = SwarmTransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=1, n_heads=4, seq_len=8,
        grid_size=(2,), k_best=2,
        # 1-core CI: concurrent trainers serialize many first-time compiles
        # through one runtime thread
        forward_timeout=240.0, backward_timeout=240.0,
    )
    return SwarmDMoETransformerLM(cfg, source)


def test_swarm_transformer_trains(swarm):
    server, source = swarm
    model = _model(source)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)
    step = model.make_train_step(opt)

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, VOCAB, (4, 8)))
    tgt = jnp.asarray(rs.randint(0, VOCAB, (4, 8)))
    losses = []
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, ids, tgt)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # expert-side async updates happened (one per layer-MoE backward per step)
    total_updates = sum(i["update_count"] for i in server.experts.values())
    assert total_updates > 0


def test_async_dp_multiple_trainers(swarm):
    """SURVEY §2.2 DP contract: independent trainers, no barrier, shared
    experts updating on arrival.  Both trainers must complete and the
    expert pool must absorb updates from both."""
    server, source = swarm
    before = sum(i["update_count"] for i in server.experts.values())

    class Trainer:
        def __init__(self, seed):
            self.model = _model(source)
            self.params = self.model.init_params(jax.random.PRNGKey(seed))
            self.opt = optax.adamw(1e-3)
            self.opt_state = self.opt.init(self.params)
            self.step = self.model.make_train_step(self.opt)
            self.rs = np.random.RandomState(seed)
            self.losses = []

        def run_steps(self, n):
            for _ in range(n):
                # same shape as test 1 → server buckets already compiled
                ids = jnp.asarray(self.rs.randint(0, VOCAB, (4, 8)))
                tgt = jnp.asarray(self.rs.randint(0, VOCAB, (4, 8)))
                self.params, self.opt_state, loss = self.step(
                    self.params, self.opt_state, ids, tgt
                )
                self.losses.append(float(loss))

    trainers = [Trainer(1), Trainer(2)]
    # warm each trainer's own trace serially (1-core CI: concurrent
    # first-time traces + server compiles starve the RPC deadlines;
    # the CONTRACT under test is concurrent steady-state training)
    for t in trainers:
        t.run_steps(1)
    with cf.ThreadPoolExecutor(2) as pool:
        list(pool.map(lambda t: t.run_steps(2), trainers))
    for t in trainers:
        assert len(t.losses) == 3 and np.isfinite(t.losses).all()
    after = sum(i["update_count"] for i in server.experts.values())
    # 2 trainers x 3 steps x 1 layer, each backward updating >= 1 expert
    assert after - before >= 6


def test_pipelined_trainer_converges_and_counts():
    """PipelinedSwarmTrainer: concurrent workers consume exactly `steps`
    micro-batches, updates land under the apply lock, loss decreases.
    (Network-free: a local quadratic model stands in for the swarm LM.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learning_at_home_tpu.client.trainer import PipelinedSwarmTrainer

    class Toy:
        def loss_fn(self, params, x, y):
            return ((x @ params["w"] - y) ** 2).mean()

    rs = np.random.RandomState(0)
    w_true = rs.randn(8, 1).astype(np.float32)
    params = {"w": jnp.zeros((8, 1))}
    xs = rs.randn(64, 8).astype(np.float32)
    ys = xs @ w_true

    def batches():
        while True:
            i = rs.randint(0, 48)
            yield jnp.asarray(xs[i : i + 16]), jnp.asarray(ys[i : i + 16])

    trainer = PipelinedSwarmTrainer(Toy(), optax.sgd(0.05), params, n_workers=3)
    summary = trainer.train(batches(), steps=40, tokens_per_batch=16)
    assert trainer.step_count == 40
    assert summary["steps"] == 40
    assert summary["final_loss"] < trainer.losses[0] * 0.1
    assert np.isfinite(np.asarray(trainer.params["w"])).all()
