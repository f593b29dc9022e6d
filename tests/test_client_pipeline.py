"""The pipelined client dispatch path (PR 2): off-loop serialization,
pack-once fan-out byte parity, protocol-v2 multiplexing, v1 fallback, and
the explicit quorum-straggler cancel marker.

Mirrors PR 1's server-side no-stacking-on-loop regression pattern on the
client: the ``lah-client`` event loop must only ever write pre-serialized
buffers — every wire cast and spec/blob walk happens on the caller's host
thread."""

import asyncio
import threading

import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.client.rpc import client_loop, pool_registry
from learning_at_home_tpu.server import background_server
from learning_at_home_tpu.utils import serialization as ser
from learning_at_home_tpu.utils.connection import (
    QUORUM_STRAGGLER_CANCEL,
    ConnectionPool,
    PoolRegistry,
)
from learning_at_home_tpu.utils.serialization import (
    WireTensors,
    frame_nbytes,
    pack_frames,
    pack_message,
    peek_header,
    recv_frame,
    send_frame,
    send_frame_parts,
    unpack_message,
    wire_cast,
)

HID = 16


# ---------------------------------------------------------------------------
# wire-format building blocks
# ---------------------------------------------------------------------------


def test_pack_frames_byte_parity_with_pack_message():
    """The vectored path must put EXACTLY the v1 bytes on the wire: a
    joined pack_frames frame equals the outer length prefix +
    pack_message payload, for every tensor mix."""
    tensors = [
        np.random.RandomState(0).randn(4, 8).astype(np.float32),
        np.arange(6, dtype=np.int32),
        np.array(2.5, dtype=np.float64),
    ]
    meta = {"uid": "ffn.3", "k": 2}
    payload = pack_message("forward", tensors, meta)
    joined = b"".join(
        bytes(p) for p in pack_frames("forward", WireTensors.prepare(tensors), meta)
    )
    import struct

    assert joined == struct.pack("<I", len(payload)) + payload


def test_pack_once_fanout_byte_parity():
    """Per-uid payloads sliced from ONE whole-batch wire cast must be
    byte-identical to per-call casting of each uid's rows (the legacy
    path) — pack-once changes where the work happens, never the bytes."""
    rs = np.random.RandomState(1)
    x = rs.randn(16, HID).astype(np.float32)
    jobs = {"a": np.array([0, 3, 5]), "b": np.array([3, 5, 9, 15])}
    for wd in (None, "bfloat16", "float16"):
        x_wire = wire_cast([x], wd)[0]  # pack-once: one batch downcast
        for rows in jobs.values():
            once = b"".join(
                bytes(p)
                for p in pack_frames(
                    "forward", WireTensors.prepare([x_wire[rows]]), {"u": 1}
                )
            )
            per_call = b"".join(
                bytes(p)
                for p in pack_frames(
                    "forward",
                    WireTensors.prepare(wire_cast([x[rows]], wd)),
                    {"u": 1},
                )
            )
            assert once == per_call


def test_wiretensors_concat_shares_blobs():
    """The merged multi request is a reference concat: no tensor bytes
    are copied when k per-uid payloads combine into one frame."""
    a = WireTensors.prepare([np.ones((2, 4), np.float32)])
    b = WireTensors.prepare([np.zeros((3, 4), np.float32)])
    merged = WireTensors.concat([a, b])
    assert merged.blobs[0] is a.blobs[0]
    assert merged.blobs[1] is b.blobs[0]
    assert merged.nbytes == a.nbytes + b.nbytes


def test_rid_tagged_frame_roundtrip():
    """v2 frames carry a request id in the header; unpack ignores it and
    peek_header surfaces it."""
    t = np.random.RandomState(0).randn(3, 4).astype(np.float32)
    parts = pack_frames("forward", WireTensors.prepare([t]), {"uid": "x"}, rid=77)
    payload = b"".join(bytes(p) for p in parts)[4:]
    assert peek_header(payload) == ("forward", 77)
    msg_type, tensors, meta = unpack_message(payload)
    assert msg_type == "forward" and meta == {"uid": "x"}
    np.testing.assert_array_equal(tensors[0], t)
    # v1 frames have no rid
    v1 = pack_message("forward", [t], {"uid": "x"})
    assert peek_header(v1) == ("forward", None)


def test_send_frame_parts_wire_parity():
    """writelines and write put identical bytes on the socket."""

    async def run():
        got = []

        async def handler(reader, writer):
            got.append(await recv_frame(reader))
            got.append(await recv_frame(reader))
            writer.close()

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        t = np.arange(12, dtype=np.float32).reshape(3, 4)
        payload = pack_message("fwd", [t], {"a": 1})
        await send_frame(writer, payload)
        await send_frame_parts(
            writer, pack_frames("fwd", WireTensors.prepare([t]), {"a": 1})
        )
        await asyncio.sleep(0.1)
        writer.close()
        server.close()
        assert got[0] == got[1] == payload

    asyncio.run(run())


# ---------------------------------------------------------------------------
# no serialization on the client event loop (PR 1's pattern, client side)
# ---------------------------------------------------------------------------


def test_no_serialization_on_client_event_loop():
    """Regression: in pipelined mode, neither the wire downcast nor the
    spec/blob walk may run on the ``lah-client`` loop thread — payloads
    arrive at the loop pre-serialized.

    The old version monkeypatched ``ser.wire_cast``/``_tensor_to_wire``
    to track thread names; the invariant now lives in the sanitizer's
    ``@runs_on("host")`` assertions on ``moe._prepare_payloads`` and
    ``EncodedBatch.encode`` — any on-loop serialization is a recorded
    violation the shared conftest guard turns into a failure, and the
    site stats prove the host thread really did the packing."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.utils import sanitizer

    if not sanitizer.enabled():
        pytest.skip("sanitizer disabled (LAH_SANITIZE=0)")
    before = sanitizer.site_stats()

    with background_server(
        num_experts=4, hidden_dim=HID, expert_prefix="ffn", seed=0
    ) as (endpoint, srv):
        source = StaticExpertSource({uid: endpoint for uid in srv.experts})
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn",
            source=source, k_best=2, k_min=2, wire_dtype="bfloat16",
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = jnp.asarray(
            np.random.RandomState(0).randn(6, HID).astype(np.float32)
        )

        def loss(g, x):
            return jnp.sum(moe(x, g) ** 2)

        jax.grad(loss)(gate, x)  # forward + backward fan-out
        after = sanitizer.site_stats()

        def delta(site, cls):
            return after.get(site, {}).get(cls, 0) - before.get(
                site, {}
            ).get(cls, 0)

        # the pack really ran, and on a host thread (the io_callback
        # thread is unnamed → class "host"; the lah-client loop would
        # class as "lah-client" AND record a violation)
        assert delta("moe._prepare_payloads", "host") >= 2  # fwd + bwd
        assert delta("EncodedBatch.encode", "host") > 0
        assert delta("moe._prepare_payloads", "lah-client") == 0
        assert delta("EncodedBatch.encode", "lah-client") == 0
        assert moe.pack_bytes > 0
        assert moe.pack_bytes_saved > 0  # k=2 shares one downcast
        assert len(moe.pack_times) >= 2 and len(moe.wait_times) >= 2
    reset_client_rpc()


# ---------------------------------------------------------------------------
# protocol v2 multiplexing
# ---------------------------------------------------------------------------


def test_mux_concurrent_rpcs_share_one_socket():
    """Many concurrent RPCs on one pool must negotiate v2, interleave on
    a single connection, and each get ITS OWN reply back."""
    connections = []

    with background_server(
        num_experts=2, hidden_dim=HID, expert_prefix="nop", seed=0,
        expert_cls="nop", optimizer=optax.sgd(0.0),
    ) as (endpoint, srv):
        async def hammer():
            pool = pool_registry().get(endpoint)
            rs = np.random.RandomState(0)
            xs = [rs.randn(2, HID).astype(np.float32) for _ in range(24)]

            async def one(x):
                out, _ = await pool.rpc(
                    "forward", [x], {"uid": "nop.0"}, timeout=30.0
                )
                return out[0]

            outs = await asyncio.gather(*(one(x) for x in xs))
            return pool, xs, outs

        pool, xs, outs = client_loop().run(hammer())
        # a nop expert echoes its input: reply↔request binding is exact
        for x, out in zip(xs, outs):
            np.testing.assert_allclose(out, x, atol=1e-6)
        assert pool._proto == 2
        assert pool.inflight_max > 1  # RPCs really overlapped on the mux
    reset_client_rpc()


def test_mux_interleaved_out_of_order_replies():
    """The client must match replies by request id even when the server
    completes them in REVERSE arrival order."""

    async def run():
        async def handler(reader, writer):
            wlock = asyncio.Lock()
            batch = []

            async def reply_reversed():
                for payload in reversed(batch):
                    _, tensors, meta = unpack_message(payload)
                    _, rid = peek_header(payload)
                    parts = pack_frames(
                        "result", WireTensors.prepare(tensors),
                        {"echo": meta.get("i")}, rid=rid,
                    )
                    async with wlock:
                        await send_frame_parts(writer, parts)

            while True:
                try:
                    payload = await recv_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionResetError):
                    break
                msg_type, rid = peek_header(payload)
                if msg_type == "hello":
                    await send_frame_parts(
                        writer,
                        pack_frames(
                            "hello_ok", WireTensors.prepare(),
                            {"features": ["mux"]}, rid=rid,
                        ),
                    )
                    continue
                batch.append(payload)
                if len(batch) == 4:  # hold replies until all 4 arrived
                    await reply_reversed()
                    batch = []

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        ep = server.sockets[0].getsockname()[:2]
        pool = ConnectionPool(ep)

        async def one(i):
            x = np.full((2, 2), i, np.float32)
            out, meta = await pool.rpc("forward", [x], {"i": i}, timeout=10)
            return i, out[0], meta["echo"]

        results = await asyncio.gather(*(one(i) for i in range(4)))
        for i, out, echo in results:
            assert echo == i
            np.testing.assert_array_equal(out, np.full((2, 2), i, np.float32))
        assert pool._proto == 2
        pool.close()
        server.close()

    asyncio.run(run())


def test_v1_fallback_against_old_protocol_server():
    """A pre-v2 server answers ``hello`` with an error frame; the pool
    must pin v1, REUSE the probe socket, and serve RPCs normally."""

    async def run():
        n_connections = [0]

        async def old_server(reader, writer):
            # the old build's handler: framed v1, no hello in its table
            n_connections[0] += 1
            while True:
                try:
                    payload = await recv_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionResetError):
                    break
                msg_type, tensors, meta = unpack_message(payload)
                if msg_type == "forward":
                    await send_frame(
                        writer, pack_message("result", [tensors[0] * 2])
                    )
                else:
                    await send_frame(
                        writer,
                        pack_message(
                            "error",
                            meta={"message": f"unknown message type {msg_type!r}"},
                        ),
                    )

        server = await asyncio.start_server(old_server, "127.0.0.1", 0)
        ep = server.sockets[0].getsockname()[:2]
        pool = ConnectionPool(ep)
        x = np.ones((2, 3), np.float32)
        for _ in range(3):
            out, _ = await pool.rpc("forward", [x], {"uid": "e"}, timeout=10)
            np.testing.assert_array_equal(out[0], x * 2)
        assert pool._proto == 1
        # fallback reused the hello probe's socket: ONE connection total
        assert n_connections[0] == 1
        pool.close()
        server.close()

    asyncio.run(run())


def test_moe_output_matches_per_expert_reference():
    """The mixture's output against a plain reference built here: each
    row's two best experts by gate logit, each expert asked for its rows
    alone through ``RemoteExpert.forward_blocking``, the replies combined
    by the softmax of the chosen logits in numpy (frozen server params)."""
    import jax
    import jax.numpy as jnp

    with background_server(
        num_experts=4, hidden_dim=HID, expert_prefix="ffn", seed=0,
        optimizer=optax.sgd(0.0),
    ) as (endpoint, srv):
        source = StaticExpertSource({uid: endpoint for uid in srv.experts})
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn",
            source=source, k_best=2, k_min=2, wire_dtype="bfloat16",
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = np.random.RandomState(2).randn(5, HID).astype(np.float32)
        y = np.asarray(moe(jnp.asarray(x), gate))

        logits = x @ np.asarray(gate["w0"])
        chosen = np.argsort(-logits, axis=1)[:, :2]
        scores = np.take_along_axis(logits, chosen, axis=1)
        weights = np.exp(scores - scores.max(axis=1, keepdims=True))
        weights /= weights.sum(axis=1, keepdims=True)
        want = np.zeros_like(x)
        for e in range(4):
            rows, slots = np.nonzero(chosen == e)
            if len(rows):
                expert = RemoteExpert(f"ffn.{e}", endpoint,
                                      wire_dtype="bfloat16")
                (out,) = expert.forward_blocking([x[rows]])
                want[rows] += (
                    weights[rows, slots, None] * np.asarray(out, np.float32)
                )
        # replies travel as bfloat16: a reply computed in another batch
        # may round to the neighbouring value, one part in 2**8
        np.testing.assert_allclose(y, want, rtol=2.0 ** -7, atol=1e-5)
    reset_client_rpc()


# ---------------------------------------------------------------------------
# quorum-straggler cancel marker
# ---------------------------------------------------------------------------


class TestStragglerCancelMarker:
    def _black_hole(self):
        async def handler(reader, writer):
            await asyncio.sleep(60)

        return handler

    def test_marked_cancel_folds_ema_below_old_floor(self):
        """A quorum straggler cancelled FASTER than the old 0.05 s floor
        must still fold its wait into the EMA — the floor is gone; the
        marker is the signal (timeout_after_k_min < 50 ms works now)."""

        async def run():
            server = await asyncio.start_server(
                self._black_hole(), "127.0.0.1", 0
            )
            ep = server.sockets[0].getsockname()[:2]
            pool = ConnectionPool(ep, negotiate_v2=False)
            task = asyncio.ensure_future(
                pool.rpc("forward", (), {"uid": "x"}, timeout=30)
            )
            await asyncio.sleep(0.02)  # well under the old 50 ms floor
            task.cancel(msg=QUORUM_STRAGGLER_CANCEL)
            with pytest.raises(asyncio.CancelledError):
                await task
            assert pool.rtt_ema is not None and pool.rtt_ema < 0.05
            pool.close()
            server.close()

        asyncio.run(run())

    def test_unmarked_teardown_cancel_never_folds(self):
        """A teardown cancellation (no marker) says nothing about the
        peer — even when it lands long after the old floor."""

        async def run():
            server = await asyncio.start_server(
                self._black_hole(), "127.0.0.1", 0
            )
            ep = server.sockets[0].getsockname()[:2]
            pool = ConnectionPool(ep, negotiate_v2=False)
            task = asyncio.ensure_future(
                pool.rpc("forward", (), {"uid": "x"}, timeout=30)
            )
            await asyncio.sleep(0.08)  # above the old floor
            task.cancel()  # plain teardown-style cancel
            with pytest.raises(asyncio.CancelledError):
                await task
            assert pool.rtt_ema is None
            pool.close()
            server.close()

        asyncio.run(run())


# ---------------------------------------------------------------------------
# registry creation race (satellite)
# ---------------------------------------------------------------------------


def test_pool_registry_get_is_race_free_across_threads():
    """Host threads and the loop thread may race first contact; exactly
    one pool per endpoint must ever exist (EMA updates would otherwise
    land on an orphan)."""
    reg = PoolRegistry()
    ep = ("127.0.0.1", 4242)
    barrier = threading.Barrier(8)
    pools = []

    def grab():
        barrier.wait()
        pools.append(reg.get(ep))

    threads = [threading.Thread(target=grab) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(pools) == 8
    assert all(p is pools[0] for p in pools)
    assert len(reg._pools) == 1


def test_frame_nbytes_and_cap():
    parts = pack_frames(
        "fwd", WireTensors.prepare([np.zeros(10, np.float32)]), {}
    )
    joined = b"".join(bytes(p) for p in parts)
    assert frame_nbytes(parts) == len(joined)
    with pytest.raises(ValueError, match="MAX_FRAME_BYTES"):
        big = WireTensors([["uint8", [2 << 30], 2 << 30]], [])
        big.nbytes = 2 << 30  # spoof: construction of the real thing OOMs
        pack_frames("fwd", big, {})
