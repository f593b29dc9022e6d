"""Spawning framework subprocesses: environment, launch, teardown.

Every spawned server/trainer names the JAX platform it runs on — there is
no default, because a default is how a "chip-resident" server ends up on
the CPU without anyone asking.  An accelerator belongs to one process at
a time (a second process that needs it fails or hangs), so the launchers
here refuse to start chip children next to a parent, or next to each
other, that would hold the same chip.
"""

from __future__ import annotations

import os
import sys
from typing import Optional


def clean_jax_subprocess_env(
    repo_root: Optional[str], *, platform: str
) -> dict[str, str]:
    """The parent's environment with ``JAX_PLATFORMS`` set to ``platform``
    (``"cpu"``, ``"tpu"``, ...) and ``repo_root`` on ``PYTHONPATH``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    if repo_root:
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
    return env


def require_free_chip(platform: str, n_children: int, who: str) -> None:
    """Refuse a launch in which two processes would want one chip.

    ``platform`` is where the ``n_children`` about to be spawned will run.
    CPU children never contend.  Accelerator children do with each other
    (more than one) and with a parent whose own JAX backend is already an
    accelerator."""
    if platform == "cpu":
        return
    if n_children > 1:
        raise RuntimeError(
            f"{who}: {n_children} child processes on platform "
            f"{platform!r} would share one chip; a chip belongs to one "
            "process — run one server per chip, or the others on 'cpu'"
        )
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized() and (
            jax.default_backend() != "cpu"
        ):
            raise RuntimeError(
                f"{who}: this process already holds the "
                f"{jax.default_backend()!r} backend, so a child on "
                f"{platform!r} could not get the chip; start the child "
                "before touching JAX here, or pin this process to 'cpu'"
            )


# PDEATHSIG exec wrapper: the child re-execs python with prctl(PR_SET_
# PDEATHSIG, SIGKILL) armed, so a dying launcher can never orphan its
# servers (the exact failure find_orphan_servers exists to catch).
PDEATHSIG_WRAPPER = (
    "import ctypes, os, sys; "
    "ctypes.CDLL('libc.so.6').prctl(1, 9); "
    "os.execv(sys.executable, [sys.executable] + sys.argv[1:])"
)


def spawn_expert_servers(
    repo_root: str,
    prefix: str,
    latencies,
    *,
    d_model: int = 512,
    num_experts: int = 2,
    expert_cls: str = "nop",
    probe_timeout_s: float = 120.0,
    extra_args: tuple = (),
    platform: str,
):
    """Spawn one subprocess expert server per entry of ``latencies``
    (each with that injected chaos reply latency; 0 = none), under the
    PDEATHSIG wrapper, and block until every server answers a probe
    forward.  Every server runs on ``platform``.  Returns ``(procs,
    ports)``; on any boot failure every started server is killed before
    the error propagates.

    SUBPROCESS isolation is load-bearing for the collect-gate overlap
    smoke — an
    in-process server shares the client's GIL, and compute the client
    hides inside the in-flight RPC window starves the server's loops,
    growing the window by exactly the hidden time (observed 2026-08-04).
    ``nop`` experts keep the window pure latency."""
    import socket
    import subprocess
    import time

    import numpy as np

    require_free_chip(platform, len(latencies), "spawn_expert_servers")
    from learning_at_home_tpu.client import RemoteExpert
    from learning_at_home_tpu.utils.connection import RemoteCallError

    procs, ports = [], []
    try:
        for layer, delay in enumerate(latencies):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
            cmd = [
                sys.executable, "-c", PDEATHSIG_WRAPPER,
                "-m", "learning_at_home_tpu.server",
                "--expert-prefix", f"{prefix}{layer}",
                "--num-experts", str(num_experts),
                "--expert-cls", expert_cls, "--hidden-dim", str(d_model),
                "--port", str(ports[-1]), "--no-dht",
                "--max-batch-size", "4096",
                "--optimizer", "sgd", "--lr", "0",
                *extra_args,
            ]
            if delay:
                cmd += ["--chaos-latency", str(delay)]
            procs.append(subprocess.Popen(
                cmd,
                env=clean_jax_subprocess_env(repo_root, platform=platform),
                stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            ))
        deadline = time.time() + probe_timeout_s
        for layer, port in enumerate(ports):
            probe = RemoteExpert(
                f"{prefix}{layer}.0", ("127.0.0.1", port), timeout=10.0
            )
            while True:
                try:
                    probe.forward_blocking(
                        [np.ones((2, d_model), np.float32)]
                    )
                    break
                except (OSError, RemoteCallError):
                    if (
                        any(p.poll() is not None for p in procs)
                        or time.time() > deadline
                    ):
                        raise RuntimeError(
                            f"expert server {prefix}{layer} never came up"
                        )
                    time.sleep(1.0)
    except Exception:
        for p in procs:
            p.kill()
        for p in procs:  # reap: no <defunct> children in the launcher
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass  # unkillable (D-state): nothing more to do
        raise
    return procs, ports


def shutdown_procs(procs) -> None:
    """Terminate-then-kill-then-reap teardown for spawned servers."""
    import subprocess

    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=15)
        except subprocess.TimeoutExpired:
            p.kill()
            try:  # reap the kill too: no <defunct> children left behind
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass  # unkillable (D-state): nothing more to do


def spawn_overlap_swarm(
    repo_root: str, prefix: str, latencies, *, d_model: int = 512,
    seq: int = 64, platform: str,
):
    """One subprocess ``nop``-expert server per entry of ``latencies``
    (the per-pool fake-delay WAN proxies) + the matching multi-layer
    swarm source/config — the collect-gate overlap smoke's swarm.
    Returns ``(procs, source, cfg)``; tear down with
    :func:`shutdown_procs`."""
    from learning_at_home_tpu.client.routing import StaticExpertSource
    from learning_at_home_tpu.models.transformer_swarm import (
        SwarmTransformerConfig,
    )

    procs, ports = spawn_expert_servers(
        repo_root, prefix, latencies, d_model=d_model, platform=platform
    )
    source = StaticExpertSource({
        f"{prefix}{layer}.{e}": ("127.0.0.1", ports[layer])
        for layer in range(len(ports)) for e in range(2)
    })
    cfg = SwarmTransformerConfig(
        vocab_size=64, d_model=d_model, n_layers=len(ports), n_heads=8,
        seq_len=seq, grid_size=(2,), k_best=2, k_min=1, uid_prefix=prefix,
        timeout_after_k_min=30.0,
        forward_timeout=120.0, backward_timeout=120.0,
        # pin the codec: the adaptive selector reads per-pool RTT EMAs
        # and would change wire precision per schedule arm, breaking the
        # bitwise-parity contract between serial and overlapped
        wire_codec="none",
    )
    return procs, source, cfg


def find_orphan_servers(exclude_descendants_of: Optional[int] = None) -> list:
    """Scan /proc for ``learning_at_home_tpu.server`` processes left over
    from a PRIOR session.  Orphans silently load the (single) core and
    corrupt every absolute CPU timing taken while they live — three
    churn servers once ran ~6 h into the next session and invalidated its
    morning's numbers.  ``tools/collect_gate.py`` calls this BEFORE
    spawning anything, so every match is by definition not ours.

    Returns ``[(pid, age_seconds, cmdline), ...]``; empty off-Linux (no
    /proc) — the guard degrades to a no-op rather than guessing.
    ``exclude_descendants_of`` skips processes whose parent chain reaches
    that pid (a concurrently-running sibling launcher we own)."""
    import time

    out: list = []
    try:
        pids = [p for p in os.listdir("/proc") if p.isdigit()]
    except OSError:
        return out
    try:
        boot = time.time() - float(
            open("/proc/uptime").read().split()[0]
        )
        clock_tck = os.sysconf("SC_CLK_TCK")
    except Exception:
        boot, clock_tck = None, 100

    def parent_of(pid: int) -> Optional[int]:
        try:
            with open(f"/proc/{pid}/stat") as f:
                # field 4 (after the parenthesised comm, which may
                # contain spaces)
                rest = f.read().rsplit(")", 1)[1].split()
                return int(rest[1])
        except Exception:
            return None

    def is_descendant(pid: int, ancestor: int) -> bool:
        seen = 0
        while pid and pid != 1 and seen < 64:
            if pid == ancestor:
                return True
            pid = parent_of(pid) or 0
            seen += 1
        return False

    me = os.getpid()
    for pid_s in pids:
        pid = int(pid_s)
        if pid == me:
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = [
                    a.decode("utf-8", "replace")
                    for a in f.read().split(b"\0") if a
                ]
        except OSError:
            continue
        # exact argv token (the ``-m learning_at_home_tpu.server`` module
        # arg): a shell whose ONE-token script merely mentions the module
        # (this very scan, a grep) must not match
        if "learning_at_home_tpu.server" not in argv:
            continue
        cmdline = " ".join(argv).strip()
        if is_descendant(pid, me):
            continue  # our own child (a launcher scanning mid-run)
        if exclude_descendants_of and is_descendant(
            pid, exclude_descendants_of
        ):
            continue
        age = None
        if boot is not None:
            try:
                with open(f"/proc/{pid}/stat") as f:
                    start_ticks = int(
                        f.read().rsplit(")", 1)[1].split()[19]
                    )
                age = round(time.time() - (boot + start_ticks / clock_tck), 1)
            except Exception:
                age = None
        out.append((pid, age, cmdline[:200]))
    return out
