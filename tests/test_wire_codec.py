"""Quantized wire codecs (ISSUE 5): round-trip fuzz, pack-once slice
parity, decode-into-staging, adaptive selection, hello negotiation
fallback, off-loop encode regression, and the gradient-quality gate.

The contract under test (docs/PROTOCOL.md "Wire codecs"):

- ``none`` stays byte-identical to the pre-codec wire;
- ``u8``/``blockq8`` quantize to 8 bits with per-tensor headers that
  slice together with the payload (blockq8 blocks never cross the
  trailing axis), are validated as hostile input on receipt, and decode
  directly into the consumer's buffer (the server's staging path);
- quantized payloads are only OFFERED to peers whose ``hello`` echoed
  the ``codec`` feature — v1 peers and old builds transparently get the
  wire_dtype base;
- quality is measured, not asserted: backward gradient cosine ≥ 0.99
  under ``blockq8``.
"""

import asyncio
import threading
import time

import numpy as np
import optax
import pytest

from learning_at_home_tpu.client import reset_client_rpc
from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import StaticExpertSource
from learning_at_home_tpu.client.rpc import pool_registry
from learning_at_home_tpu.server import background_server
from learning_at_home_tpu.utils import serialization as ser
from learning_at_home_tpu.utils.serialization import (
    BLOCKQ8_BLOCK,
    BLOCKQ8_CLIP,
    EncodedBatch,
    LazyDecode,
    QUANTIZED_CODECS,
    WIRE_CODECS,
    WireTensors,
    decode_wire_tensors,
    encode_wire_tensors,
    pack_frames,
    select_wire_codec,
    unpack_message,
    wire_codec_name,
)

HID = 16

SHAPES = [(0, 8), (1,), (), (5, 1), (3, 1024), (2, 1500), (7, 3, 64),
          (2048,), (16, 2, 256), (4, 1)]
FLOAT_DTYPES = [np.float32, np.float64, "bfloat16", np.float16]


# ---------------------------------------------------------------------------
# round-trip fuzz: all codecs x dtypes x shapes (incl. 0-row, 1-element)
# ---------------------------------------------------------------------------


def _tolerance(codec: str, x32: np.ndarray) -> float:
    if x32.size == 0:
        return 0.0
    if codec == "u8":
        # half a quantization step over the per-tensor range
        return float(x32.max() - x32.min()) / 255 * 0.51 + 1e-6
    # blockq8: half a step of the WORST block's scale; values beyond
    # ±CLIP sigma clip, but randn data never reaches 6 sigma here
    nvec, last, _nb = ser._blockq8_geometry(x32.shape, BLOCKQ8_BLOCK)
    flat = x32.reshape(nvec, last)
    worst_std = 0.0
    for v in range(nvec):
        for off in range(0, last, BLOCKQ8_BLOCK):
            blk = flat[v, off: off + BLOCKQ8_BLOCK]
            worst_std = max(worst_std, float(blk.std()))
    return max(worst_std, 1.0) * (BLOCKQ8_CLIP / 127.0) * 0.51 + 1e-5


def test_codec_roundtrip_fuzz_all_dtypes_and_shapes():
    rs = np.random.RandomState(0)
    for codec in QUANTIZED_CODECS:
        for dtype in FLOAT_DTYPES:
            for shape in SHAPES:
                x = np.asarray(rs.randn(*shape) * 3 + 1, dtype=dtype)
                x32 = np.asarray(x, np.float32)
                wire, header = EncodedBatch.encode(x, codec).full()
                assert wire.shape == x.shape
                y = LazyDecode(wire, header).decode()
                assert y.shape == x.shape and y.dtype == np.float32
                if x.size:
                    # tolerance vs the f32 view the encoder actually saw
                    tol = _tolerance(codec, x32) + float(
                        np.abs(x32 - np.asarray(x, np.float32)).max()
                    )
                    assert float(np.abs(y - x32).max()) <= tol, (
                        codec, dtype, shape,
                    )


def test_codec_roundtrip_through_msgpack_frames():
    """Headers must survive the real wire (msgpack bin fields), and
    integer tensors pass through raw under every codec."""
    rs = np.random.RandomState(1)
    tensors = [
        rs.randn(4, 1025).astype(np.float32),
        np.arange(6, dtype=np.int32),
        rs.randn(3).astype(np.float32),
    ]
    for codec in WIRE_CODECS:
        wire_tensors, wmeta = encode_wire_tensors(tensors, codec)
        parts = pack_frames(
            "forward", WireTensors.prepare(wire_tensors),
            {"uid": "x", "wire": wmeta} if wmeta is not None else {"uid": "x"},
        )
        payload = b"".join(bytes(p) for p in parts)[4:]
        _, rx_tensors, rx_meta = unpack_message(payload)
        out = decode_wire_tensors(rx_tensors, rx_meta.get("wire"), lazy=False)
        np.testing.assert_array_equal(np.asarray(out[1]), tensors[1])
        assert np.asarray(out[1]).dtype == np.int32
        for got, want in zip((out[0], out[2]), (tensors[0], tensors[2])):
            got = np.asarray(got, np.float32)
            if codec == "none":
                np.testing.assert_array_equal(got, want)
            else:
                tol = 0.2 if codec in QUANTIZED_CODECS else 0.1
                assert float(np.abs(got - want).max()) <= tol


def test_codec_none_is_byte_identical_to_raw_wire():
    """The default codec must not change a single wire byte."""
    rs = np.random.RandomState(2)
    tensors = [rs.randn(8, 32).astype(np.float32),
               np.arange(5, dtype=np.int64)]
    meta = {"uid": "ffn.3"}
    base = b"".join(
        bytes(p)
        for p in pack_frames("forward", WireTensors.prepare(tensors), meta)
    )
    wire_tensors, wmeta = encode_wire_tensors(tensors, "none")
    assert wmeta is None
    assert all(w is t for w, t in zip(wire_tensors, tensors))
    again = b"".join(
        bytes(p)
        for p in pack_frames(
            "forward", WireTensors.prepare(wire_tensors), meta
        )
    )
    assert base == again


def test_codec_fuzz_mutated_frames_parse_or_raise():
    """Random mutations of a codec-framed payload must decode cleanly or
    raise ValueError — never crash, hang, or return tensors inconsistent
    with their headers (the serialization fuzz harness, codec flavor)."""
    import random

    rng = random.Random(0)
    rs = np.random.RandomState(0)
    tensors = [rs.randn(4, 700).astype(np.float32)]
    for codec in QUANTIZED_CODECS:
        wire_tensors, wmeta = encode_wire_tensors(tensors, codec)
        base = b"".join(
            bytes(p)
            for p in pack_frames(
                "forward", WireTensors.prepare(wire_tensors),
                {"wire": wmeta},
            )
        )[4:]
        for _ in range(150):
            buf = bytearray(base)
            for _ in range(rng.randint(1, 8)):
                buf[rng.randrange(len(buf))] = rng.randrange(256)
            try:
                _, rx, meta = unpack_message(bytes(buf))
                out = decode_wire_tensors(rx, meta.get("wire"), lazy=False)
            except Exception:
                continue  # clean rejection is the contract
            for t in out:
                arr = np.asarray(t)
                assert arr.dtype == np.float32 or arr is t


# ---------------------------------------------------------------------------
# pack-once slice parity + decode-into-staging
# ---------------------------------------------------------------------------


def test_encoded_batch_slice_parity():
    """A row (or row+slot) gather of ONE batch encode must decode to the
    same values as gathering the full decode — the pack-once contract."""
    rs = np.random.RandomState(3)
    x = rs.randn(32, 300).astype(np.float32)
    rows = np.array([0, 3, 3, 31, 7])
    gy = rs.randn(16, 2, 64).astype(np.float32)
    rws, slots = np.array([0, 5, 9]), np.array([1, 0, 1])
    for codec in QUANTIZED_CODECS:
        eb = EncodedBatch.encode(x, codec)
        w, h = eb.take(rows)
        wf, hf = eb.full()
        np.testing.assert_array_equal(
            LazyDecode(w, h).decode(), LazyDecode(wf, hf).decode()[rows]
        )
        ebg = EncodedBatch.encode(gy, codec)
        w, h = ebg.take((rws, slots))
        wf, hf = ebg.full()
        np.testing.assert_array_equal(
            LazyDecode(w, h).decode(),
            LazyDecode(wf, hf).decode()[rws, slots],
        )


def test_lazydecode_into_staging_buffer():
    """BatchJob.stack must land quantized task rows directly in the
    staging buffer (pad rows still re-zeroed), identical to an eager
    decode — the server-side no-f32-on-the-loop contract."""
    from learning_at_home_tpu.server.staging import StagingBuffers
    from learning_at_home_tpu.server.task_pool import BatchJob, TaskPool

    rs = np.random.RandomState(4)
    a = rs.randn(3, 128).astype(np.float32)
    b = rs.randn(2, 128).astype(np.float32)
    lazy_a = LazyDecode(*EncodedBatch.encode(a, "blockq8").full())
    lazy_b = LazyDecode(*EncodedBatch.encode(b, "u8").full())
    job = BatchJob(
        priority=0.0, seq=0, pool=None,
        task_tensors=[(lazy_a,), (lazy_b,)],
        row_spans=[(None, 0, 3), (None, 3, 5)],
        n_rows=5, target_rows=8,
        dtypes=[np.dtype(np.float32)],
    )
    staging = StagingBuffers()
    # dirty the recycled buffer to prove pad rows are re-zeroed
    dirty = staging.acquire((8, 128), np.float32)
    dirty[:] = 7.0
    staging.release([dirty])
    inputs, buffers = job.stack(staging)
    assert len(buffers) == 1 and inputs[0] is buffers[0]
    np.testing.assert_array_equal(inputs[0][:3], lazy_a.decode())
    np.testing.assert_array_equal(inputs[0][3:5], lazy_b.decode())
    np.testing.assert_array_equal(inputs[0][5:], 0.0)
    # single-task full-bucket path decodes too (zero-copy is impossible
    # for a quantized payload, but it must still happen off-loop here)
    solo = BatchJob(
        priority=0.0, seq=1, pool=None, task_tensors=[(lazy_a,)],
        row_spans=[(None, 0, 3)], n_rows=3, target_rows=3,
    )
    inputs, buffers = solo.stack(staging)
    assert buffers == []
    np.testing.assert_array_equal(inputs[0], lazy_a.decode())


def _load_header_battery():
    """Pinned hostile-header corpus (tests/fuzz_corpus, ISSUE 15) —
    the regression battery lives as data so ``lah_fuzz`` replays and
    this test drive the SAME cases."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fuzz_corpus",
                        "wire_headers.json")
    with open(path) as fh:
        corpus = json.load(fh)
    assert corpus["format"] == "lah-fuzz-battery-v1"

    def resolve(v):
        if isinstance(v, dict) and "$bytes_hex" in v:
            return bytes.fromhex(v["$bytes_hex"])
        if v == "$NAN":
            return float("nan")
        if v == "$BLOCKQ8_BLOCK":
            return BLOCKQ8_BLOCK
        if isinstance(v, dict):
            return {k: resolve(x) for k, x in v.items()}
        return v

    return [(c["name"], c["target"], c["data"], resolve(c["wire"]),
             c["match"]) for c in corpus["cases"]]


def test_lazydecode_validates_hostile_headers():
    for name, target, data, wire, match in _load_header_battery():
        payload = np.zeros(tuple(data["shape"]), np.dtype(data["dtype"]))
        with pytest.raises(ValueError, match=match):
            if target == "lazy":
                LazyDecode(payload, wire)
            else:
                decode_wire_tensors([payload], wire)
            raise AssertionError(f"hostile header accepted: {name}")


# ---------------------------------------------------------------------------
# adaptive per-pool selection
# ---------------------------------------------------------------------------


class TestAdaptiveSelection:
    def test_escalation_ladder(self):
        MB = 1 << 20
        # unmeasured or fast pools never escalate
        assert select_wire_codec("forward", 10 * MB, None, None) == "none"
        assert select_wire_codec("forward", 10 * MB, 0.001, 1e6) == "none"
        # slow pool, small payload (est ≤ 100 ms): stay raw
        assert select_wire_codec("forward", 50_000, 0.2, 1e6) == "none"
        # mid payload (est ≤ 300 ms): bf16
        assert select_wire_codec("forward", 200_000, 0.2, 1e6) == "bf16"
        # big payload: quantize — activations u8, gradients blockq8
        assert select_wire_codec("forward", 10 * MB, 0.2, 1e6) == "u8"
        assert select_wire_codec("backward", 10 * MB, 0.2, 1e6) == "blockq8"
        # configured bf16 base is kept where "none" would be
        assert select_wire_codec("forward", 50_000, 0.2, 1e6,
                                 base="bf16") == "bf16"

    def test_moe_override_wins_and_requires_support(self):
        with background_server(
            num_experts=2, hidden_dim=HID, expert_prefix="sel", seed=0,
            optimizer=optax.sgd(0.0),
        ) as (endpoint, srv):
            source = StaticExpertSource(
                {uid: endpoint for uid in srv.experts}
            )
            moe = RemoteMixtureOfExperts(
                in_features=HID, grid_size=(2,), uid_prefix="sel",
                source=source, k_best=2, k_min=2, wire_codec="u8",
            )
            import jax

            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            x = np.random.RandomState(0).randn(4, HID).astype(np.float32)
            # FIRST dispatch: the pool has never negotiated, so the
            # quantized override must fall back to the base codec
            import jax.numpy as jnp

            moe(jnp.asarray(x), gate)
            assert moe.codec_counts.get("none", 0) > 0
            # negotiation done → the pin takes effect
            moe(jnp.asarray(x), gate)
            assert moe.codec_counts.get("u8", 0) > 0
            pool = pool_registry().peek(endpoint)
            assert pool.supports("codec")
        reset_client_rpc()

    def test_adaptive_escalates_on_synthetic_slow_pool(self):
        """Force RTT/bandwidth EMAs to WAN-like values: the adaptive
        path (wire_codec=None) must quantize the large dispatch."""
        with background_server(
            num_experts=2, hidden_dim=256, expert_prefix="ad", seed=0,
            optimizer=optax.sgd(0.0), max_batch_size=2048,
        ) as (endpoint, srv):
            source = StaticExpertSource(
                {uid: endpoint for uid in srv.experts}
            )
            moe = RemoteMixtureOfExperts(
                in_features=256, grid_size=(2,), uid_prefix="ad",
                source=source, k_best=2, k_min=2,
            )
            import jax
            import jax.numpy as jnp

            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            x = jnp.asarray(
                np.random.RandomState(0).randn(512, 256).astype(np.float32)
            )
            moe(x, gate)  # negotiate + measure
            pool = pool_registry().peek(endpoint)
            pool.rtt_ema, pool.bw_ema = 0.3, 2e6  # 2 MB/s WAN-ish link
            moe(x, gate)
            # 512 rows x 256 f32 x 2 experts / 2e6 B/s >> 80 ms → u8
            assert moe.codec_counts.get("u8", 0) > 0, moe.codec_counts
        reset_client_rpc()

    def test_adaptive_drift_between_forward_and_backward(self):
        """Backward payloads are ~2x forward, so the selector may
        escalate backward while the forward went raw — the session's f32
        rows must then travel in a form the server accepts (regression:
        an unconverted f32 input under a 'bfloat16' declaration was
        rejected by the all-floats-compressed contract).

        Sizing note: 50 rows keeps every FORWARD exchange below
        BW_MIN_SAMPLE_BYTES, so the pinned EMAs below cannot be diluted
        by a real loopback measurement between the pin and the backward
        selection — the old 512-row version re-sampled ~1.5 MB forward
        exchanges, and a fast (warm-cache) box could drag the pinned
        bandwidth above the escalation threshold: flaky by construction."""
        with background_server(
            num_experts=2, hidden_dim=256, expert_prefix="dr", seed=0,
            optimizer=optax.sgd(0.0), max_batch_size=2048,
        ) as (endpoint, srv):
            source = StaticExpertSource(
                {uid: endpoint for uid in srv.experts}
            )
            moe = RemoteMixtureOfExperts(
                in_features=256, grid_size=(2,), uid_prefix="dr",
                source=source, k_best=2, k_min=2,
            )
            import jax
            import jax.numpy as jnp

            gate = moe.init_gate_params(jax.random.PRNGKey(0))
            x = jnp.asarray(
                np.random.RandomState(0).randn(50, 256).astype(np.float32)
            )

            def loss(xx):
                return jnp.sum(moe(xx, gate) ** 2)

            jax.grad(loss)(x)  # negotiate + measure
            pool = pool_registry().peek(endpoint)
            # fwd ≈ 100 KB → ~68 ms (stays raw); bwd ≈ 200 KB → ~137 ms
            # (bf16, below the 300 ms 8-bit bar)
            pool.rtt_ema, pool.bw_ema = 0.3, 1.5e6
            gx = np.asarray(jax.grad(loss)(x))
            assert np.isfinite(gx).all() and np.abs(gx).sum() > 0
            assert moe.codec_counts.get("bf16", 0) > 0, moe.codec_counts
            assert moe.backward_samples_dropped == 0
            assert moe.samples_dropped == 0
        reset_client_rpc()


# ---------------------------------------------------------------------------
# negotiation fallback + codec-mismatch rejection
# ---------------------------------------------------------------------------


def test_v1_peer_never_offered_quantized_codec():
    """Against an old-protocol (no hello) server, a u8-pinned MoE must
    transparently serve raw payloads — and still be numerically right."""

    async def old_server(reader, writer):
        from learning_at_home_tpu.utils.serialization import (
            pack_message,
            recv_frame,
            send_frame,
        )

        while True:
            try:
                payload = await recv_frame(reader)
            except (asyncio.IncompleteReadError, ConnectionResetError):
                break
            msg_type, tensors, meta = unpack_message(payload)
            if msg_type == "multi":
                # old-build multi: echo each part's tensors doubled
                parts = [
                    {"uid": p["uid"], "ok": True, "n_tensors": 1}
                    for p in meta["parts"]
                ]
                await send_frame(
                    writer,
                    pack_message(
                        "result", [t * 2 for t in tensors],
                        {"parts": parts},
                    ),
                )
            elif msg_type == "forward":
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

    loop = asyncio.new_event_loop()
    server_box = {}

    def run_loop():
        async def start():
            server_box["server"] = await asyncio.start_server(
                old_server, "127.0.0.1", 0
            )
            server_box["ep"] = server_box["server"].sockets[0].getsockname()[:2]
        loop.run_until_complete(start())
        loop.run_forever()

    t = threading.Thread(target=run_loop, daemon=True)
    t.start()
    for _ in range(100):
        if "ep" in server_box:
            break
        time.sleep(0.05)
    ep = tuple(server_box["ep"])
    try:
        source = StaticExpertSource({"old.0": ep, "old.1": ep})
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(2,), uid_prefix="old",
            source=source, k_best=2, k_min=2, wire_codec="blockq8",
        )
        import jax
        import jax.numpy as jnp

        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).randn(4, HID).astype(np.float32)
        moe(jnp.asarray(x), gate)  # first: pool unknown → raw
        moe(jnp.asarray(x), gate)  # pool pinned v1 → still raw
        assert moe.codec_counts.get("blockq8", 0) == 0
        assert moe.codec_counts.get("none", 0) > 0
        pool = pool_registry().peek(ep)
        assert pool._proto == 1 and not pool.supports("codec")
    finally:
        loop.call_soon_threadsafe(loop.stop)
        reset_client_rpc()


def test_server_rejects_unknown_codec_and_mismatched_payload():
    from learning_at_home_tpu.client.rpc import client_loop
    from learning_at_home_tpu.utils.connection import RemoteCallError

    with background_server(
        num_experts=1, hidden_dim=HID, expert_prefix="rej", seed=0,
    ) as (endpoint, _srv):
        pool = pool_registry().get(endpoint)
        x = np.random.RandomState(0).randn(2, HID).astype(np.float32)

        async def call(meta, tensors):
            return await pool.rpc("forward", tensors, meta, timeout=15)

        with pytest.raises(RemoteCallError, match="unsupported wire codec"):
            client_loop().run(
                call({"uid": "rej.0", "wire": {"c": "zstd", "h": [None]}},
                     [x])
            )
        # declared u8 but payload is f32: validation must reject, not
        # silently launder
        with pytest.raises(RemoteCallError, match="uint8"):
            client_loop().run(
                call(
                    {"uid": "rej.0",
                     "wire": {"c": "u8",
                              "h": [{"c": "u8", "lo": 0.0, "sc": 1.0}]}},
                    [x],
                )
            )
    reset_client_rpc()


# ---------------------------------------------------------------------------
# off-loop encode regression (PR 2 thread-tracking pattern)
# ---------------------------------------------------------------------------


def test_no_quantize_on_client_event_loop():
    """In pipelined mode the 8-bit encode must run on the caller's host
    thread — never on the ``lah-client`` loop (and decode of quantized
    replies must not run there either).

    The old version monkeypatched ``_encode_blockq8``/``_encode_u8``/
    ``_decode_quant_into`` to track thread names; the sanitizer's
    ``runs_on("host")`` assertions on ``EncodedBatch.encode`` and
    ``LazyDecode.decode`` now carry the invariant (the conftest guard
    fails the test on any violation), and the site stats prove the
    encode/decode really happened, off-loop."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.utils import sanitizer

    if not sanitizer.enabled():
        pytest.skip("sanitizer disabled (LAH_SANITIZE=0)")
    before = sanitizer.site_stats()

    with background_server(
        num_experts=4, hidden_dim=HID, expert_prefix="ffn", seed=0,
    ) as (endpoint, srv):
        source = StaticExpertSource({uid: endpoint for uid in srv.experts})
        moe = RemoteMixtureOfExperts(
            in_features=HID, grid_size=(4,), uid_prefix="ffn",
            source=source, k_best=2, k_min=2, wire_codec="blockq8",
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))
        x = jnp.asarray(
            np.random.RandomState(0).randn(6, HID).astype(np.float32)
        )

        def loss(g, x):
            return jnp.sum(moe(x, g) ** 2)

        jax.grad(loss)(gate, x)  # negotiation dispatch (raw)
        jax.grad(loss)(gate, x)  # quantized forward + backward
        after = sanitizer.site_stats()

        def delta(site, cls):
            return after.get(site, {}).get(cls, 0) - before.get(
                site, {}
            ).get(cls, 0)

        # client-side encode happened on the host (io_callback) thread,
        # never on the lah-client loop; the server side may legitimately
        # add "runtime" (staging decode) and scoped serving-loop counts
        assert delta("EncodedBatch.encode", "host") > 0, (
            "blockq8 encode never ran on a host thread"
        )
        assert delta("EncodedBatch.encode", "lah-client") == 0
        assert delta("LazyDecode.decode", "lah-client") == 0
        decode_total = sum(
            after.get("LazyDecode.decode", {}).values()
        ) - sum(before.get("LazyDecode.decode", {}).values())
        assert decode_total > 0, "quantized payloads never decoded"
        assert moe.codec_counts.get("blockq8", 0) > 0
    reset_client_rpc()


# ---------------------------------------------------------------------------
# quality gate: backward gradient cosine under blockq8
# ---------------------------------------------------------------------------


def test_backward_gradient_cosine_blockq8():
    """Per-expert backward input-gradient cosine ≥ 0.99 vs the
    uncompressed run (frozen optimizer so both runs see one model)."""
    import jax
    import jax.numpy as jnp

    with background_server(
        num_experts=4, hidden_dim=64, expert_prefix="q", seed=0,
        optimizer=optax.sgd(0.0),
    ) as (endpoint, srv):
        source = StaticExpertSource({uid: endpoint for uid in srv.experts})
        x = jnp.asarray(
            np.random.RandomState(0).randn(32, 64).astype(np.float32)
        )
        grads = {}
        for codec in ("none", "blockq8"):
            moe = RemoteMixtureOfExperts(
                in_features=64, grid_size=(4,), uid_prefix="q",
                source=source, k_best=2, k_min=2, wire_codec=codec,
            )
            gate = moe.init_gate_params(jax.random.PRNGKey(0))

            def loss(xx):
                return jnp.sum(moe(xx, gate) ** 2)

            jax.grad(loss)(x)  # negotiation warm-up
            grads[codec] = np.asarray(jax.grad(loss)(x))
        g0, g1 = grads["none"], grads["blockq8"]
        cos = float(
            (g0 * g1).sum()
            / (np.linalg.norm(g0) * np.linalg.norm(g1) + 1e-12)
        )
        assert cos >= 0.99, f"gradient cosine {cos:.4f} < 0.99"
    reset_client_rpc()


# ---------------------------------------------------------------------------
# misc contract details
# ---------------------------------------------------------------------------


def test_wire_codec_name_labels():
    assert wire_codec_name(None) == "none"
    assert wire_codec_name("bfloat16") == "bf16"
    assert wire_codec_name("float16") == "f16"
    assert wire_codec_name({"c": "u8", "h": []}) == "u8"


def test_moe_rejects_unknown_codec():
    with pytest.raises(ValueError, match="wire codec"):
        RemoteMixtureOfExperts(
            in_features=4, grid_size=(2,), uid_prefix="x",
            source=StaticExpertSource({}), wire_codec="zstd",
        )
