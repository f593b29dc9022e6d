"""Future-based async dispatch + communication/compute overlap (ISSUE 7).

Covers the four contracts of the fire/join refactor:

- bitwise parity: the serial and overlapped schedules of the shortcut
  swarm step run the SAME primitive ops, so outputs, losses, gradients
  and updated params must be bit-identical (twin servers — per-uid
  crc32 param seeding makes two processes host identical experts, so
  each arm's backward updates can't contaminate the other's);
- measured overlap: with injected per-pool chaos latency, the overlapped
  schedule hides trunk compute inside the in-flight RPC window and the
  layer's ``overlap_fraction`` observable goes positive (and stays ~0 in
  the serial schedule);
- backward reuse: the backward fan-out resends the forward's
  already-encoded session rows (pack-once contract survives the split);
- clean failure: a stalled pool under the future-based path makes the
  join TIME OUT with a diagnosable error — the ROUND5 io_callback-hang
  class retired by construction.
"""

import asyncio
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.moe import (
    MoEDispatchError,
    RemoteMixtureOfExperts,
)
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.client.rpc import DispatchJoinTimeout
from learning_at_home_tpu.models.transformer_swarm import (
    SwarmDMoETransformerLM,
    SwarmTransformerConfig,
)
from learning_at_home_tpu.server import ChaosConfig
from learning_at_home_tpu.server.server import background_server

D = 16
VOCAB = 32
SEQ = 8
LAYERS = 2
UIDS = [f"ffn{layer}.{e}" for layer in range(LAYERS) for e in range(2)]


def _cfg(**overrides):
    base = dict(
        vocab_size=VOCAB, d_model=D, n_layers=LAYERS, n_heads=4,
        seq_len=SEQ, grid_size=(2,), k_best=2, k_min=1, uid_prefix="ffn",
        # generous quorum grace: determinism here requires that no honest
        # straggler is ever cancelled (all replies must land in both arms)
        timeout_after_k_min=30.0,
        forward_timeout=120.0, backward_timeout=120.0,
        # pin the codec: the adaptive selector reads per-pool RTT EMAs,
        # and the twin arms' EMAs differ — a per-arm escalation would
        # change wire precision and break bitwise parity by design
        wire_codec="none",
    )
    base.update(overrides)
    return SwarmTransformerConfig(**base)


@pytest.fixture()
def twin_swarms():
    """Two in-process servers hosting IDENTICAL experts (explicit
    ``expert_uids`` → per-uid crc32 seeding) behind ~50 ms injected
    chaos reply latency — one per schedule arm."""
    with contextlib.ExitStack() as stack:
        arms = []
        for _ in range(2):
            endpoint, srv = stack.enter_context(
                background_server(
                    expert_uids=UIDS, hidden_dim=D, seed=0,
                    chaos=ChaosConfig(base_latency=0.05),
                )
            )
            arms.append(
                (StaticExpertSource({u: endpoint for u in UIDS}), srv)
            )
        yield arms
    reset_client_rpc()


def _tree_equal(a, b) -> bool:
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b)
    )


def test_serial_overlapped_bitwise_parity(twin_swarms):
    """Acceptance: serial and overlapped modes produce bitwise-identical
    forward outputs AND gradients (hence identical updated params),
    across steps that include server-side expert updates."""
    (src_serial, _), (src_overlap, _) = twin_swarms
    model_s = SwarmDMoETransformerLM(_cfg(), src_serial)
    model_o = SwarmDMoETransformerLM(_cfg(), src_overlap)
    params = model_s.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    step_s = model_s.make_overlapped_train_step(opt, overlap=False)
    step_o = model_o.make_overlapped_train_step(opt, overlap=True)
    ps, po = params, params
    ss, so = opt.init(params), opt.init(params)
    rs = np.random.RandomState(0)
    for step in range(2):
        ids = jnp.asarray(rs.randint(0, VOCAB, (2, SEQ)))
        tgt = jnp.asarray(rs.randint(0, VOCAB, (2, SEQ)))
        ps, ss, loss_s = step_s(ps, ss, ids, tgt)
        po, so, loss_o = step_o(po, so, ids, tgt)
        assert np.array_equal(np.asarray(loss_s), np.asarray(loss_o)), (
            f"step {step}: losses diverged"
        )
    assert _tree_equal(ps, po), "updated params diverged between schedules"
    # forward-only parity on ONE arm (no updates): the two schedules of
    # the same model instance agree bitwise
    ids = jnp.asarray(rs.randint(0, VOCAB, (2, SEQ)))
    out_s = model_s.apply_overlapped(ps, ids, overlap=False)
    out_o = model_s.apply_overlapped(ps, ids, overlap=True)
    assert np.array_equal(np.asarray(out_s), np.asarray(out_o))


def test_overlap_fraction_positive_under_delay(twin_swarms):
    """With ~50 ms injected reply latency, the overlapped schedule hides
    trunk compute inside the in-flight window: overlap_fraction > 0 and
    above the serial arm's."""
    (src_serial, _), (src_overlap, _) = twin_swarms
    model_s = SwarmDMoETransformerLM(_cfg(), src_serial)
    model_o = SwarmDMoETransformerLM(_cfg(), src_overlap)
    params = model_s.init_params(jax.random.PRNGKey(1))
    rs = np.random.RandomState(1)
    ids = jnp.asarray(rs.randint(0, VOCAB, (2, SEQ)))
    for _ in range(2):
        jax.block_until_ready(
            model_s.apply_overlapped(params, ids, overlap=False)
        )
        jax.block_until_ready(
            model_o.apply_overlapped(params, ids, overlap=True)
        )

    def frac(model):
        stats = [m.dispatch_stats() for m in model.moes]
        assert all(s["inflight_dispatches"] == 0 for s in stats)
        return max(s["overlap_fraction"] for s in stats)

    serial_frac, overlap_frac = frac(model_s), frac(model_o)
    assert overlap_frac > 0.005, (
        f"overlapped schedule hid no in-flight time: {overlap_frac}"
    )
    assert overlap_frac > serial_frac, (serial_frac, overlap_frac)


def test_backward_reuses_forward_session_rows():
    """The backward fan-out resends the forward's already-encoded session
    rows — `pack_once_bytes_saved` must grow at backward time, and the
    stored session payload is the wire-encoded (downcast) array."""
    import ml_dtypes

    with background_server(
        num_experts=2, hidden_dim=D, expert_prefix="ffn", seed=3
    ) as (endpoint, srv):
        source = StaticExpertSource({u: endpoint for u in srv.experts})
        moe = RemoteMixtureOfExperts(
            in_features=D, grid_size=(2,), uid_prefix="ffn", source=source,
            k_best=2, k_min=1, timeout_after_k_min=30.0,
            wire_dtype="bfloat16",
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        rs = np.random.RandomState(0)
        x = rs.randn(4, D).astype(np.float32)
        lc = x @ np.asarray(gate["w0"])
        fut = moe.dispatch_async(x, lc)  # fire
        y, idx, mask, cid = fut.join()
        assert int(cid) >= 0
        saved_after_fwd = moe.pack_bytes_saved
        with moe._sessions_lock:
            session, _, _ = moe._sessions[int(cid)]
        assert session, "no experts answered"
        for _uid, (_ep, x_rows, _rows, _slots) in session.items():
            assert np.asarray(x_rows).dtype == ml_dtypes.bfloat16, (
                "session must store the wire-encoded rows, not f32"
            )
        gy = np.ones((4, moe.k_best, D), np.float32)
        gx = moe._host_backward(np.int32(cid), gy)
        assert gx.shape == (4, D)
        assert moe.pack_bytes_saved > saved_after_fwd, (
            "backward did not reuse the forward's encoded session rows"
        )
    reset_client_rpc()


@pytest.mark.parametrize("kind", ["forward", "backward"])
def test_joined_dispatch_is_freed_by_reference_count(kind):
    """A joined dispatch leaves no cycle behind: its future, and with it
    the replies and the inputs its finalizer holds, go when the caller
    drops it, not at the collector's next full pass (a trainer that fires
    2048-row dispatches back to back would otherwise hold 33 MB more for
    every dispatch until then)."""
    import gc
    import weakref

    with background_server(
        num_experts=2, hidden_dim=D, expert_prefix="ffn", seed=3
    ) as (endpoint, srv):
        source = StaticExpertSource({u: endpoint for u in srv.experts})
        moe = RemoteMixtureOfExperts(
            in_features=D, grid_size=(2,), uid_prefix="ffn", source=source,
            k_best=2, k_min=1, timeout_after_k_min=30.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).randn(4, D).astype(np.float32)
        lc = x @ np.asarray(gate["w0"])
        gc.collect()
        gc.disable()
        try:
            fut = moe.dispatch_async(x, lc)
            _, _, _, cid = fut.join()
            if kind == "backward":
                with moe._sessions_lock:
                    session, fwd_dropped, trace = moe._sessions.pop(int(cid))
                gy = np.ones((4, moe.k_best, D), np.float32)
                fut = moe.backward_async(session, fwd_dropped, trace, gy)
                assert fut.join().shape == (4, D)
            ref = weakref.ref(fut)
            del fut
            assert ref() is None, "a joined dispatch is held by a cycle"
        finally:
            gc.enable()
    reset_client_rpc()


def test_stalled_pool_join_times_out_cleanly(monkeypatch):
    """ISSUE 7 satellite: a stalled pool (accepts, never replies, ignores
    its own RPC timeout) under the future-based path must make the join
    time out with a diagnosable error — never hang."""
    from learning_at_home_tpu.utils import connection

    async def _stall(self, *args, **kwargs):
        await asyncio.Event().wait()  # black hole: ignores timeout=

    monkeypatch.setattr(connection.ConnectionPool, "rpc", _stall)
    monkeypatch.setattr(connection.ConnectionPool, "rpc_prepared", _stall)
    monkeypatch.setattr(
        connection.ConnectionPool, "ensure_negotiated",
        lambda self, timeout=None: _stall(self),
    )
    source = StaticExpertSource({"ffn.0": ("127.0.0.1", 1)})
    moe = RemoteMixtureOfExperts(
        in_features=8, grid_size=(1,), uid_prefix="ffn", source=source,
        k_best=1, k_min=1, forward_timeout=0.2, timeout_after_k_min=0.1,
    )
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    lc = np.zeros((2, 1), np.float32)
    fut = moe.dispatch_async(x, lc)
    t0 = time.monotonic()
    with pytest.raises(DispatchJoinTimeout) as excinfo:
        fut.join(timeout=1.0)
    assert time.monotonic() - t0 < 10.0, "join did not respect its deadline"
    assert "stalled" in str(excinfo.value)
    # the fan-out task was cancelled — the loop is left clean, and the
    # in-flight gauge returns to zero (on_join_exit ran)
    deadline = time.monotonic() + 5.0
    while not fut._cf.done() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert fut._cf.done()
    assert moe.dispatch_stats()["inflight_dispatches"] == 0
    reset_client_rpc()


def test_evicted_ticket_drains_inflight_gauge(monkeypatch):
    """A fired-but-never-joined ticket evicted past max_sessions must
    cancel its fan-out AND drain the inflight_dispatches gauge (the
    join-exit hook runs from cancel), so lah_top never shows phantom
    in-flight dispatches after an eviction."""
    from learning_at_home_tpu.utils import connection

    async def _stall(self, *args, **kwargs):
        await asyncio.Event().wait()  # keep every fan-out pending

    monkeypatch.setattr(connection.ConnectionPool, "rpc", _stall)
    monkeypatch.setattr(connection.ConnectionPool, "rpc_prepared", _stall)
    monkeypatch.setattr(
        connection.ConnectionPool, "ensure_negotiated",
        lambda self, timeout=None: _stall(self),
    )
    source = StaticExpertSource({"ffn.0": ("127.0.0.1", 1)})
    moe = RemoteMixtureOfExperts(
        in_features=8, grid_size=(1,), uid_prefix="ffn", source=source,
        k_best=1, k_min=1, forward_timeout=0.2, timeout_after_k_min=0.1,
        max_sessions=1,
    )
    x = np.zeros((2, 8), np.float32)
    lc = np.zeros((2, 1), np.float32)
    h1 = moe._host_fire(x, lc, store_session=False)
    h2 = moe._host_fire(x, lc, store_session=False)  # evicts ticket h1
    assert moe.dispatch_stats()["inflight_dispatches"] == 1
    with pytest.raises(MoEDispatchError):
        moe._host_join(h1)  # evicted: a diagnosable error, never a hang
    with moe._sessions_lock:
        fut = moe._pending.pop(int(h2))
    with pytest.raises(DispatchJoinTimeout):
        fut.join(timeout=0.5)
    assert moe.dispatch_stats()["inflight_dispatches"] == 0
    # discard(): the error-path cleanup apply_overlapped uses when a
    # raise lands between fire and join — cancels + drains, idempotent
    h3 = moe._host_fire(x, lc, store_session=False)
    assert moe.dispatch_stats()["inflight_dispatches"] == 1
    moe.discard(None, h3)
    assert moe.dispatch_stats()["inflight_dispatches"] == 0
    moe.discard(None, h3)  # already discarded: no-op
    reset_client_rpc()


def test_join_deadline_is_a_number_above_the_rpc_bounds():
    """Every join has a hard deadline, longer than the fan-out's own
    bounds (``forward_timeout + timeout_after_k_min``)."""
    source = StaticExpertSource({"ffn.0": ("127.0.0.1", 1)})
    moe = RemoteMixtureOfExperts(
        in_features=8, grid_size=(1,), uid_prefix="ffn", source=source,
        k_best=1, k_min=1, forward_timeout=1.0, timeout_after_k_min=0.5,
    )
    assert isinstance(moe._join_timeout("forward"), float)
    assert moe._join_timeout("forward") > 1.5


def test_fire_join_under_jit(twin_swarms):
    """The fire/join custom-vjp pair compiles and runs under jit (the
    handle chain keeps the callbacks ordered: every join finds its own
    fire's dispatch, and none is left in flight), and agrees with the
    eager serial schedule.  The heavyweight 2048-row repro of the
    retired ROUND5 hazard lives in test_jitted_client_regression."""
    (src, _), _ = twin_swarms
    model = SwarmDMoETransformerLM(_cfg(), src)
    params = model.init_params(jax.random.PRNGKey(2))
    rs = np.random.RandomState(2)
    ids = jnp.asarray(rs.randint(0, VOCAB, (2, SEQ)))
    eager = np.asarray(model.apply_overlapped(params, ids, overlap=False))
    jitted = jax.jit(
        lambda p, i: model.apply_overlapped(p, i, overlap=True)
    )
    out = np.asarray(jitted(params, ids))
    assert all(
        m.dispatch_stats()["inflight_dispatches"] == 0 for m in model.moes
    )
    # two compiled programs of one float32 computation: jit fuses what the
    # eager call runs op by op, and XLA reassociates the fused sums, so the
    # logits agree to a few ulp of their scale, not bit for bit (the
    # bitwise contract is between the two SCHEDULES of one program:
    # test_serial_overlapped_bitwise_parity).  A join that read another
    # dispatch's reply would be off by the scale itself.
    ulp = np.finfo(np.float32).eps * np.abs(eager).max()
    np.testing.assert_allclose(out, eager, rtol=0, atol=16 * ulp)  # 1.07 seen


@pytest.mark.slow
def test_jitted_client_regression():
    """Pinned repro of the ROUND5 jitted-client io_callback deadlock
    hazard, retired by the future-based dispatch core: a jitted client
    step at the 2048-row production shape against a SEPARATE-process
    server (the historical trigger: a blocking callback on the 1-core
    XLA:CPU pool while producer thunks queue behind it — intermittent
    ~50% of runs pre-refactor).  The fire callback no longer blocks on
    the network and the single join point carries a hard deadline, so
    this must now complete (or fail loudly) within the subprocess
    timeout instead of hanging."""
    import os
    import subprocess
    import sys

    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = r"""
import faulthandler
faulthandler.dump_traceback_later(240, exit=True)
import numpy as np
from learning_at_home_tpu.utils.subproc import (
    shutdown_procs, spawn_expert_servers,
)

repo = %(repo)r
# ONE real-ffn server (the shared PDEATHSIG spawn+probe helper), warmed
# at the production batch shape so the jitted steps hit a hot bucket
procs, ports = spawn_expert_servers(
    repo, "jreg", (0.0,), d_model=64, expert_cls="ffn",
    extra_args=("--warmup", "2048"), platform="cpu",
)
port = ports[0]
try:
    import jax, jax.numpy as jnp
    from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
    from learning_at_home_tpu.client.routing import StaticExpertSource

    source = StaticExpertSource(
        {f"jreg0.{i}": ("127.0.0.1", port) for i in range(2)}
    )
    moe = RemoteMixtureOfExperts(
        in_features=64, grid_size=(2,), uid_prefix="jreg0", source=source,
        k_best=2, k_min=2, forward_timeout=120.0, timeout_after_k_min=60.0,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(0))

    @jax.jit
    def step(x, g):
        token, handle, lc = moe.fire(x, g)
        # trunk work the schedule can hide behind the in-flight RPCs
        trunk = jnp.tanh(x) @ jnp.eye(64, dtype=x.dtype)
        return moe.join(token, handle, lc) + trunk[:, None, :1] * 0.0

    rs = np.random.RandomState(0)
    for i in range(3):
        x = jnp.asarray(rs.randn(2048, 64).astype(np.float32))
        jax.block_until_ready(step(x, gate))
        print(f"iter {i} ok", flush=True)
    print("JIT_REGRESSION_OK", flush=True)
finally:
    shutdown_procs(procs)
""" % {"repo": repo}
    env = clean_jax_subprocess_env(repo, platform="cpu")
    r = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=420, cwd=repo,
    )
    assert "JIT_REGRESSION_OK" in r.stdout, (
        f"jitted-client regression failed/hung:\nstdout: {r.stdout[-2000:]}"
        f"\nstderr: {r.stderr[-2000:]}"
    )
