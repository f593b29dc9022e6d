#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

Drives both tensor paths once, through the entry points a user calls, at
the full width of the flagship (depth and weights are what they are at a
fresh start: 4 layers, random from a seed), and checks what comes out:

1. *Trainer (pod path).*  One process, one-device mesh, the 256-expert
   d_model-512 flagship under the one-chip recipe
   (``__graft_entry__.flagship_one_chip``): ``init_params`` →
   ``init_opt_state`` → ``make_train_step`` → a warm-up step plus a few
   steps on one seeded batch.  Pass: the platform is the expected one,
   every loss is finite, the loss on the repeated batch falls, nothing
   compiles after the first step, expert stacks are sharded over the
   mesh's expert axis and the optimizer state is laid out as
   ``opt_state_shardings`` says.
2. *Expert server (swarm path).*  ``python -m learning_at_home_tpu.server``
   with four 1024-wide FFN experts on the chip; then a client pinned to
   the CPU (client and server never share a process:
   models/transformer_swarm.py) runs jitted forward+grad dispatches of
   2048 rows through ``RemoteMixtureOfExperts`` over loopback.  Pass: the
   server says its parameters are on the expected platform, outputs and
   grads are finite, no sample was dropped, and the server's optimizer
   steps are positive and no more than the backward RPCs sent.

This process never imports jax and holds no chip.  Each phase runs in a
child, and a child has exited before the next one starts: a chip belongs
to one process at a time.  Any phase that fails, outlives its deadline or
finds another platform makes the script exit non-zero with no result
line.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "tpu"

# wall-clock bounds per child, compilation included; together under the
# driver's 1200 s even when every one of them is hit
TRAINER_DEADLINE_S = 540
SERVER_BOOT_DEADLINE_S = 240
CLIENT_DEADLINE_S = 240
RESULT_TAG = "PHASE_RESULT "


def _load_subproc():
    """utils/subproc.py by path: importing it through the package would
    import jax into this process (the package root imports its wire
    codecs), and the parent stays off jax."""
    spec = importlib.util.spec_from_file_location(
        "_lah_subproc",
        os.path.join(REPO, "learning_at_home_tpu", "utils", "subproc.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


subproc = _load_subproc()


class PhaseFailed(RuntimeError):
    pass


# --------------------------------------------------------------------------
# parent side: children, deadlines, teardown
# --------------------------------------------------------------------------


class _Child:
    """A child python process whose stdout is echoed line by line (so a
    hang leaves its last words in the log) and kept for parsing."""

    def __init__(self, tag: str, argv: list, platform: str):
        self.tag = tag
        self.lines: list[str] = []
        self.proc = subprocess.Popen(
            [sys.executable, "-c", subproc.PDEATHSIG_WRAPPER, *argv],
            env=subproc.clean_jax_subprocess_env(REPO, platform=platform),
            cwd=REPO, stdout=subprocess.PIPE, text=True, bufsize=1,
        )
        self._pump = threading.Thread(target=self._echo, daemon=True)
        self._pump.start()

    def _echo(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.lines.append(line)
            print(f"[{self.tag}] {line}", flush=True)

    def wait_for_line(self, pattern: str, deadline_s: float) -> str:
        """First stdout line matching ``pattern``; fails if the child
        exits or the deadline passes first."""
        t_end = time.monotonic() + deadline_s
        seen = 0
        while True:
            for line in self.lines[seen:]:
                seen += 1
                if re.search(pattern, line):
                    return line
            if self.proc.poll() is not None:
                raise PhaseFailed(
                    f"{self.tag} exited rc={self.proc.returncode} before "
                    f"printing /{pattern}/"
                )
            if time.monotonic() > t_end:
                raise PhaseFailed(
                    f"{self.tag} printed no /{pattern}/ within {deadline_s}s"
                )
            time.sleep(0.1)

    def wait_exit(self, deadline_s: float) -> int:
        try:
            rc = self.proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            self.stop()
            raise PhaseFailed(
                f"{self.tag} still running after {deadline_s}s; stopped"
            ) from None
        self._pump.join(timeout=10)
        return rc

    def stop(self) -> int:
        """SIGTERM, then SIGKILL, and reap: the child is gone on return."""
        subproc.shutdown_procs([self.proc])
        self._pump.join(timeout=10)
        return self.proc.returncode

    def result(self) -> dict:
        for line in reversed(self.lines):
            if line.startswith(RESULT_TAG):
                return json.loads(line[len(RESULT_TAG):])
        raise PhaseFailed(f"{self.tag} printed no result")


def _run_phase_child(tag: str, args: list, platform: str,
                     deadline_s: float) -> dict:
    child = _Child(tag, [os.path.abspath(__file__), *args], platform)
    rc = child.wait_exit(deadline_s)
    if rc != 0:
        raise PhaseFailed(f"{tag} exited rc={rc}")
    return child.result()


def trainer_phase(expect_platform: str, *, tiny: bool = False) -> dict:
    """Phase 1 in a child of its own; returns its report."""
    args = ["--trainer", expect_platform] + (["--tiny"] if tiny else [])
    return _run_phase_child(
        "trainer", args, expect_platform, TRAINER_DEADLINE_S
    )


def server_phase(expect_platform: str, *, tiny: bool = False) -> dict:
    """Phase 2: an expert server on ``expect_platform``, a CPU client, and
    the ledger between them.  The server is stopped and reaped before this
    returns, however it returns."""
    hid, rows = (64, 64) if tiny else (1024, 2048)
    # top-2 of 4 experts: an expert sees about rows/2, at most all rows
    buckets = [rows // 4, rows // 2, rows]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    t0 = time.monotonic()
    server = _Child(
        "server",
        ["-m", "learning_at_home_tpu.server",
         "--num-experts", "4", "--expert-cls", "ffn",
         "--hidden-dim", str(hid), "--host", "127.0.0.1",
         "--port", str(port), "--no-dht", "--max-batch-size", str(rows),
         "--warmup", *map(str, buckets)],
        expect_platform,
    )
    try:
        line = server.wait_for_line(r"^serving ", SERVER_BOOT_DEADLINE_S)
        boot_s = time.monotonic() - t0
        m = re.search(r"parameters on (\S+) \[(.*?)\]", line)
        if not m:
            raise PhaseFailed(f"server start-up line names no device: {line}")
        if m.group(1) != expect_platform:
            raise PhaseFailed(
                f"server parameters live on {m.group(1)!r} [{m.group(2)}], "
                f"expected {expect_platform!r}"
            )
        report = _run_phase_child(
            "client", ["--client", str(port), str(hid), str(rows)],
            "cpu", CLIENT_DEADLINE_S,
        )
    finally:
        rc = server.stop()
    if rc != 0:
        raise PhaseFailed(f"server exited rc={rc} after SIGTERM")
    report.update(
        server_platform=m.group(1), server_device_kind=m.group(2),
        server_boot_s=round(boot_s, 1),
    )
    return report


# --------------------------------------------------------------------------
# child side: the work (these import jax)
# --------------------------------------------------------------------------


def _device_report(expect_platform: str) -> dict:
    import jax

    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if report["platform"] != expect_platform:
        raise PhaseFailed(
            f"JAX runs on {report['platform']!r} [{report['kind']}], "
            f"expected {expect_platform!r}"
        )
    return report


def _check_layout(model, params, opt_state, optimizer, mesh) -> dict:
    """What a dry run on virtual devices cannot vouch for on real ones:
    every expert-stack leaf is split over the expert axis into
    ``total/ep``-byte shards on distinct devices, trunk leaves are whole
    on every device, and ``init_opt_state`` put the optimizer state where
    ``opt_state_shardings`` says."""
    import jax

    from learning_at_home_tpu.parallel.mesh import opt_state_shardings

    ep = mesh.shape["expert"]
    n_dev = mesh.devices.size
    expert_bytes = 0
    shardings = model.param_shardings(params)
    for (path, leaf), spec in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_leaves(shardings),
    ):
        name = jax.tree_util.keystr(path)
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != n_dev:
            raise PhaseFailed(f"{name}: {len(shards)} shards, {n_dev} devices")
        if not leaf.sharding.is_equivalent_to(spec, leaf.ndim):
            raise PhaseFailed(f"{name}: laid out as {leaf.sharding}, not {spec}")
        sharded = "expert" in jax.tree_util.tree_leaves(tuple(spec.spec))
        want = leaf.nbytes // ep if sharded else leaf.nbytes
        if any(s.data.nbytes != want for s in shards):
            raise PhaseFailed(
                f"{name}: shard bytes {[s.data.nbytes for s in shards]}, "
                f"expected {want} each"
            )
        if sharded:
            expert_bytes += leaf.nbytes
    if not expert_bytes:
        raise PhaseFailed("no expert-sharded parameter found")
    want_opt = opt_state_shardings(
        jax.eval_shape(optimizer.init, params), shardings, params, mesh
    )
    for (path, leaf), spec in zip(
        jax.tree_util.tree_flatten_with_path(opt_state)[0],
        jax.tree_util.tree_leaves(want_opt),
    ):
        if not leaf.sharding.is_equivalent_to(spec, leaf.ndim):
            raise PhaseFailed(
                f"opt_state{jax.tree_util.keystr(path)}: laid out as "
                f"{leaf.sharding}, not {spec}"
            )
    return {"expert_param_bytes": expert_bytes,
            "expert_param_bytes_per_device": expert_bytes // ep}


def run_trainer(expect_platform: str, *, tiny: bool = False,
                mesh_axes: dict | None = None, steps: int = 4) -> dict:
    """The trainer phase's work, in this process.  ``mesh_axes`` defaults
    to the one-device mesh; tools/chip_probe.py passes four-device ones."""
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax
    import jax.monitoring
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import flagship_one_chip
    from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh

    seen = {"compile_s": 0.0, "programs": 0, "hits": 0, "misses": 0}

    def on_duration(event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compile_s"] += duration
            seen["programs"] += 1

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    device = _device_report(expect_platform)
    mesh_axes = mesh_axes or {"expert": 1}
    n_dev = int(np.prod(list(mesh_axes.values())))
    mesh = make_mesh(mesh_axes, devices=jax.devices()[:n_dev])
    model, cfg, optimizer, batch = flagship_one_chip(mesh, tiny=tiny)

    def peaks() -> list:
        # the CPU backend keeps no memory statistics
        return [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in mesh.devices.flat
        ]

    t0 = time.perf_counter()
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = model.init_opt_state(optimizer, params)
    jax.block_until_ready((params, opt_state))
    init_s = time.perf_counter() - t0
    peak_after_init = peaks()
    layout = _check_layout(model, params, opt_state, optimizer, mesh)

    step = model.make_train_step(optimizer)
    rs = np.random.RandomState(0)
    ids, tgt = (
        jax.device_put(
            jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len)),
                        jnp.int32),
            batch_sharding(mesh),
        )
        for _ in range(2)
    )

    losses, step_ms = [], []
    for i in range(1 + steps):  # the first call compiles; no later one may
        t0 = time.perf_counter()
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        jax.block_until_ready((params, opt_state, loss))
        step_ms.append(round(1e3 * (time.perf_counter() - t0), 1))
        losses.append(float(loss))
        if i == 0:
            programs_after_first_step = seen["programs"]

    report = {
        "device": device,
        "mesh": mesh_axes,
        "model": f"{cfg.num_experts} experts, d_model {cfg.d_model}, "
                 f"{cfg.n_layers} layers, seq {cfg.seq_len}, vocab "
                 f"{cfg.vocab_size}, top-{cfg.k}, batch {batch}",
        "compile_cache_dir": cache_dir,
        "compile_s": round(seen["compile_s"], 1),
        "compile_cache_hits": seen["hits"],
        "compile_cache_misses": seen["misses"],
        "programs_compiled_in_later_steps": (
            seen["programs"] - programs_after_first_step
        ),
        "init_s": round(init_s, 1),
        "first_step_ms": step_ms[0],
        "step_ms": step_ms[1:],
        "losses": [round(l, 4) for l in losses],
        "dropped_fraction": round(float(metrics["dropped_fraction"]), 4),
        "peak_bytes_after_init": peak_after_init,
        "peak_bytes_in_use": peaks(),
        **layout,
    }
    print(RESULT_TAG + json.dumps(report), flush=True)  # then judged
    if not all(np.isfinite(losses)):
        raise PhaseFailed(f"non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise PhaseFailed(f"loss on the repeated batch did not fall: {losses}")
    if report["programs_compiled_in_later_steps"]:
        raise PhaseFailed(
            "the train step compiled again after its first call (its "
            "outputs came back under other shardings than its inputs?): "
            f"{report['programs_compiled_in_later_steps']} program(s)"
        )
    return report


def run_client(port: int, hid: int, rows: int, n_dispatch: int = 3) -> dict:
    """The CPU client of the server phase: jitted forward+grad dispatches
    against the server on ``port``, then the client/server ledger."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
    from learning_at_home_tpu.client.routing import StaticExpertSource
    from learning_at_home_tpu.client.rpc import (
        client_loop,
        pool_registry,
        reset_client_rpc,
    )

    _device_report("cpu")
    endpoint = ("127.0.0.1", port)
    moe = RemoteMixtureOfExperts(
        in_features=hid, grid_size=(4,), uid_prefix="expert", k_best=2,
        source=StaticExpertSource(
            {f"expert.{i}": endpoint for i in range(4)}
        ),
        forward_timeout=120.0, backward_timeout=120.0,
    )
    gate = moe.init_gate_params(jax.random.PRNGKey(0))

    @jax.jit
    def loss_and_grads(gate, x):
        def loss(gate, x):
            y = moe(x, gate)
            return jnp.mean(y * y), y

        (value, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True
        )(gate, x)
        return value, y, grads

    rs = np.random.RandomState(0)
    dispatch_ms = []
    for _ in range(n_dispatch):
        x = jnp.asarray(rs.randn(rows, hid).astype(np.float32))
        t0 = time.perf_counter()
        value, y, grads = jax.block_until_ready(loss_and_grads(gate, x))
        dispatch_ms.append(round(1e3 * (time.perf_counter() - t0), 1))
        leaves = [value, y, *jax.tree_util.tree_leaves(grads)]
        if not all(bool(jnp.all(jnp.isfinite(l))) for l in leaves):
            raise PhaseFailed("non-finite output or gradient from dispatch")
        if y.shape != (rows, hid) or not float(jnp.abs(grads[1]).max()) > 0:
            raise PhaseFailed(
                f"dispatch returned shape {y.shape} / an all-zero input grad"
            )

    async def server_stats():
        _, meta = await pool_registry().get(endpoint).rpc(
            "stats", (), {}, timeout=10.0
        )
        return meta

    updates = int(client_loop().run(server_stats())["update_count_total"])
    reset_client_rpc()  # close the pools: the server is stopped next
    report = {
        "rows": rows, "hidden": hid, "dispatches": n_dispatch,
        "dispatch_ms": dispatch_ms, "loss": round(float(value), 6),
        "samples_dropped": moe.samples_dropped,
        "backward_samples_dropped": moe.backward_samples_dropped,
        "backward_rpcs_sent": moe.backward_rpcs_sent,
        "server_updates": updates,
    }
    print(RESULT_TAG + json.dumps(report), flush=True)  # then judged
    if moe.samples_dropped or moe.backward_samples_dropped:
        raise PhaseFailed(f"samples were dropped: {report}")
    if not 0 < updates <= moe.backward_rpcs_sent:
        raise PhaseFailed(
            f"ledger broken: server optimizer steps {updates} vs backward "
            f"RPCs sent {moe.backward_rpcs_sent}"
        )
    return report


def _child_main(argv: list) -> int:
    import faulthandler

    sys.path.insert(0, REPO)
    if argv[0] == "--trainer":
        # a hang becomes a stack dump before the parent's deadline kills us
        faulthandler.dump_traceback_later(TRAINER_DEADLINE_S - 20, exit=True)
        run_trainer(argv[1], tiny="--tiny" in argv)
    else:
        faulthandler.dump_traceback_later(CLIENT_DEADLINE_S - 20, exit=True)
        run_client(*map(int, argv[1:4]))
    return 0


def main() -> int:
    trainer = trainer_phase(PLATFORM)
    server = server_phase(PLATFORM)
    device = trainer["device"]
    if (server["server_platform"], server["server_device_kind"]) != (
        device["platform"], device["kind"]
    ):
        raise PhaseFailed(
            f"phases ran on different devices: {device} vs {server}"
        )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1:
        sys.exit(_child_main(sys.argv[1:]))
    sys.exit(main())
