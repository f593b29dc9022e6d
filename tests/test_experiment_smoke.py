"""Benchmarks-as-tests (SURVEY §4): each experiment CLI must run end to end
at tiny scale and emit its JSON — guards the scripts against bitrot."""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(args, timeout=240):
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    env = clean_jax_subprocess_env(REPO, platform="cpu")
    proc = subprocess.run(
        [sys.executable, *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    return [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]


@pytest.mark.slow
def test_benchmark_dht_smoke():
    (out,) = run_script(
        ["experiments/benchmark_dht.py", "--nodes", "3", "--ops", "8"]
    )
    assert out["hit_rate"] == 1.0
    assert out["store_ops_per_sec"] > 0


@pytest.mark.slow
def test_benchmark_throughput_smoke():
    (out,) = run_script(
        [
            "experiments/benchmark_throughput.py",
            "--num-experts", "1", "--clients", "2", "--requests", "2",
            "--hidden-dim", "16", "--rows", "4",
        ]
    )
    assert out["samples_per_sec"] > 0
    assert out["batches_formed"] >= 1


@pytest.mark.slow
def test_mnist_expert_smoke():
    lines = run_script(
        [
            "experiments/mnist_expert.py",
            "--steps", "6", "--hidden-dim", "32", "--batch-size", "32",
        ]
    )
    assert lines[-1]["updates_applied"] == 6
    assert lines[-1]["steps_per_sec"] > 0


@pytest.mark.slow
def test_train_lm_pod_smoke():
    lines = run_script(
        [
            "experiments/train_lm.py", "--mode", "pod", "--steps", "3",
            "--num-experts", "8", "--batch-size", "8", "--d-model", "32",
            "--seq-len", "16", "--log-every", "2",
        ],
        timeout=300,
    )
    assert lines and all("loss" in l for l in lines)


@pytest.mark.slow
def test_train_lm_swarm_subprocess_smoke():
    """The headline decentralized trainer, against REAL server processes."""
    lines = run_script(
        [
            "experiments/train_lm.py", "--mode", "swarm",
            "--subprocess-servers", "--steps", "3",
            "--experts-per-layer", "2", "--n-servers", "1",
            "--n-layers", "1", "--batch-size", "2", "--d-model", "16",
            "--seq-len", "8", "--log-every", "2",
            # no --base-port: servers bind ephemeral ports and publish the
            # real endpoint via the DHT (fixed ports collided with orphans
            # from killed prior runs)
        ],
        timeout=420,
    )
    assert lines and all("loss" in l for l in lines)


@pytest.mark.slow
def test_train_lm_overlap_loss_parity_smoke():
    """ISSUE 9 satellite (the PR 7 leftover): ``--overlap`` drives the
    ScMoE shortcut schedule in train_lm; its loss curve must match the
    serial arm (``--overlap-serial`` — same primitive ops, join-early
    scheduling) on identical seeds/servers/data.  The schedules are
    bitwise-comparable in one process (tests/test_overlap.py); across
    two fresh swarm runs the curves must still agree to float tolerance
    (each run's servers start from the same crc32-seeded experts)."""
    common = [
        "experiments/train_lm.py", "--mode", "swarm",
        "--steps", "4", "--experts-per-layer", "2", "--n-servers", "1",
        "--n-layers", "1", "--batch-size", "2", "--d-model", "16",
        "--seq-len", "8", "--log-every", "1", "--seed", "3",
    ]
    losses = {}
    for arm in ("--overlap", "--overlap-serial"):
        lines = run_script(common + [arm], timeout=420)
        losses[arm] = [l["loss"] for l in lines if "loss" in l]
    assert losses["--overlap"], "overlapped arm produced no loss curve"
    assert len(losses["--overlap"]) == len(losses["--overlap-serial"])
    import numpy as np

    np.testing.assert_allclose(
        losses["--overlap"], losses["--overlap-serial"], atol=1e-4,
    )


@pytest.mark.slow
def test_generate_lm_smoke():
    outs = run_script(
        ["experiments/generate_lm.py", "--no-checkpoint",
         "--num-experts", "8", "--d-model", "64", "--seq-len", "64",
         "--prompt", "ab", "--max-new-tokens", "6", "--bench", "8"],
        timeout=300,
    )
    comp = next(o for o in outs if "completion" in o)
    bench = next(o for o in outs if "decode_steps_per_sec" in o)
    assert comp["completion"].startswith("ab")
    assert bench["decode_steps_per_sec"] > 0 and bench["use_cache"]


@pytest.mark.slow
def test_train_lm_multi_trainer_averaging_convergence(tmp_path):
    """ISSUE 3 acceptance: with ``--averaging`` on, a 2-trainer swarm
    smoke ends with trunk+gate parameters EQUAL across trainers.

    Each trainer runs one blocking mid-run round (step 3) plus the final
    round after its last step, then dumps its final params to
    ``avg_final_params.npz``; the trees must agree to atol=1e-6 (they are
    in fact bitwise equal: the final partition bytes come from one
    reduction per partition, distributed verbatim)."""
    import numpy as np

    lines = run_script(
        [
            "experiments/train_lm.py", "--mode", "swarm",
            "--n-trainers", "2", "--steps", "6",
            "--experts-per-layer", "2", "--n-servers", "1",
            "--n-layers", "1", "--batch-size", "2", "--d-model", "16",
            "--seq-len", "8", "--log-every", "2", "--lr", "0.005",
            "--averaging", "--averaging-every", "3",
            "--checkpoint-dir", str(tmp_path),
        ],
        timeout=600,
    )
    summary = next(l for l in lines if "n_trainers" in l)
    for t in summary["trainers"]:
        assert t["averaging_rounds"] == 2, t  # step-3 round + final round
        assert t["averaging_degraded_rounds"] == 0, t
    a = np.load(tmp_path / "t0" / "avg_final_params.npz")
    b = np.load(tmp_path / "t1" / "avg_final_params.npz")
    assert set(a.files) == set(b.files) and len(a.files) > 0
    for key in a.files:
        np.testing.assert_allclose(a[key], b[key], atol=1e-6)


@pytest.mark.slow
def test_train_lm_multi_trainer_async_dp():
    """Concurrent multi-trainer async DP (SURVEY §2.2 DP: "many independent
    trainers" against one shared expert pool; round-4 verdict task 3).

    Two trainer PROCESSES — own trunks/gates/optimizers, disjoint corpus
    shards — train against the same subprocess expert servers + DHT.  The
    contract under true write contention: both loss curves fall, numerics
    stay finite, and the client/server ledger closes — the servers' summed
    ``update_count`` cannot exceed the trainers' total SENT backward RPCs
    (each update executes ≥1 sent task; pools may merge concurrent
    trainers' rows into one padded batch = one optimizer step; acked is
    NOT the bound — a post-quorum straggler cancelled client-side still
    executes server-side) yet must exceed what either trainer alone sent
    (both trainers' gradients were applied)."""
    lines = run_script(
        [
            "experiments/train_lm.py", "--mode", "swarm",
            "--n-trainers", "2", "--steps", "16",
            "--experts-per-layer", "4", "--n-servers", "2",
            "--n-layers", "1", "--batch-size", "2", "--d-model", "32",
            "--seq-len", "16", "--log-every", "1", "--lr", "0.005",
            # no --base-port: ephemeral server ports (the port-collision
            # flake this test was known for)
        ],
        timeout=600,
    )
    summary = next(l for l in lines if "n_trainers" in l)
    assert summary["n_trainers"] == 2
    for t in summary["trainers"]:
        # measured drop is ~2.3 nats in 16 steps; 0.5 leaves 4x margin for
        # async-interleaving nondeterminism (no wall-clock dependence)
        assert t["final_loss"] < t["first_loss"] - 0.5, t
        assert math.isfinite(t["final_loss"]), t
        assert t["backward_rpcs_ok"] > 0, t
        assert t["backward_rpcs_sent"] >= t["backward_rpcs_ok"], t
    total_sent = summary["backward_rpcs_sent_total"]
    updates = summary["server_updates_total"]
    max_single = max(t["backward_rpcs_sent"] for t in summary["trainers"])
    assert 0 < updates <= total_sent, summary
    assert updates > max_single, summary  # both trainers' work was applied
    assert summary["experts_updated"] >= 3, summary  # load spread over grid


@pytest.mark.slow
def test_train_lm_swarm_blockq8_loss_parity():
    """Quality parity gate (ISSUE 5): a short swarm run whose dispatch
    wire is pinned to ``blockq8`` must track the uncompressed run's loss
    curve within the run-to-run band this smoke class tolerates (same
    seed, same data; async interleaving is the residual noise source).
    Guards against a quantizer that silently degrades training while
    every per-RPC check still passes."""
    base = [
        "experiments/train_lm.py", "--mode", "swarm",
        "--subprocess-servers", "--steps", "10",
        "--experts-per-layer", "2", "--n-servers", "1",
        "--n-layers", "1", "--batch-size", "2", "--d-model", "32",
        "--seq-len", "16", "--log-every", "1", "--lr", "0.005",
        "--seed", "0",
    ]
    curves = {}
    for codec in ("none", "blockq8"):
        lines = run_script(
            base + (["--wire-codec", codec] if codec != "none" else []),
            timeout=420,
        )
        losses = [l["loss"] for l in lines if "loss" in l]
        assert losses and all(math.isfinite(v) for v in losses), lines
        curves[codec] = losses
    # both curves fall, and the quantized endpoint sits inside the band
    # the async-dp smoke uses for run-to-run drift (0.5 nats)
    for codec, losses in curves.items():
        assert losses[-1] < losses[0], (codec, losses)
    assert abs(curves["blockq8"][-1] - curves["none"][-1]) <= 0.5, curves
