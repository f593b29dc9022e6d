"""Scale tests: 4096-expert grid routing ([BJ] config 4 dimensions) and a
true multi-process server (SURVEY §4: multi-process-on-localhost)."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from learning_at_home_tpu.client.routing import (
    StaticExpertSource,
    beam_search_alive,
    make_uid,
    select_top_k,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_select_top_k_4096_experts():
    """Full-enumeration selection stays fast and exact at the 4096 grid."""
    rs = np.random.RandomState(0)
    grid = (64, 64)
    uids = [make_uid("big", (i, j)) for i in range(64) for j in range(64)]
    logits = [rs.randn(32, 64).astype(np.float32) for _ in range(2)]
    t0 = time.monotonic()
    sel, coords = select_top_k(logits, uids, k=4)
    elapsed = time.monotonic() - t0
    assert sel.shape == (32, 4)
    assert elapsed < 5.0, f"selection took {elapsed:.2f}s for 4096 experts"
    # exact: verify one sample against brute force
    scores = logits[0][7][:, None] + logits[1][7][None, :]
    best = np.argsort(-scores.ravel())[:4]
    got = {tuple(coords[s]) for s in sel[7]}
    want = {(b // 64, b % 64) for b in best}
    assert got == want


def test_beam_search_4096_reads_few_records():
    """Beam routing touches only beam_size prefix records, not the grid."""
    import asyncio

    experts = {
        make_uid("big", (i, j)): ("h", 1) for i in range(64) for j in range(64)
    }

    class CountingSource(StaticExpertSource):
        def __init__(self, experts):
            super().__init__(experts)
            self.reads = 0

        async def get_alive_experts(self, prefix):
            self.reads += 1
            return await super().get_alive_experts(prefix)

    source = CountingSource(experts)
    rs = np.random.RandomState(1)
    logits = [rs.randn(16, 64).astype(np.float32), rs.randn(16, 64).astype(np.float32)]
    alive = asyncio.run(
        beam_search_alive(source, "big", logits, (64, 64), beam_size=4)
    )
    assert source.reads <= 4 * 16  # ≤ beam_size rows per sample, deduped
    assert 64 <= len(alive) <= 4 * 16 * 64  # plausible candidate set
    for uid in alive:
        assert uid.startswith("big.")


@pytest.mark.slow
def test_multiprocess_server_roundtrip(tmp_path):
    """Launch the server CLI as a REAL separate process and call it."""
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    env = clean_jax_subprocess_env(REPO, platform="cpu")
    # a cache of its own: every entry loaded from the checkout's warm cache
    # prints XLA:CPU's loader warning, and enough of them fill the unread
    # pipe below before the server listens
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    port = 43219
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "learning_at_home_tpu.server",
            "--num-experts", "1", "--hidden-dim", "8",
            "--port", str(port), "--no-dht",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
        from learning_at_home_tpu.utils.connection import RemoteCallError

        deadline = time.time() + 60
        out = None
        expert = RemoteExpert("expert.0", ("127.0.0.1", port), timeout=10.0)
        while time.time() < deadline:
            try:
                out = expert.forward_blocking([np.ones((2, 8), np.float32)])
                break
            except (OSError, RemoteCallError):
                if proc.poll() is not None:
                    raise AssertionError(
                        f"server died: {proc.stdout.read()[-2000:]}"
                    )
                time.sleep(1.0)
        assert out is not None and out[0].shape == (2, 8)
        reset_client_rpc()
    finally:
        proc.terminate()
        proc.wait(timeout=30)
