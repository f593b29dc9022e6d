"""Runner ``expert_server``: one expert server on the chip, in this process,
and its clients in one CPU-pinned child (``swarm_clients.py``).

The server lives here because this process holds the chip and only it can
trace the device; the clients live in a child because a client and a
server never share a process (``models/transformer_swarm.py``).  The child
is started first, so that its imports run while the server is built, and
has exited before the result is printed.

Set-up warms exactly the programs the window will run.  Where requests
meet by chance in an expert's pool (``numpy_threads``), each expert gets
one request of each size in the traffic file's ``warm_rows``.  Where the
routing is fixed by the traffic file (``jitted_mixture``: a fixed gate over a pool
of input batches), each pool batch is dispatched once, which runs every
(expert, bucket) pair the window can meet and no other.  The window is the
child's; rates and latencies are read on the child's clock, the device
trace is taken here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

import harness
from harness import BenchError

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD_DEADLINE_S = 300.0  # any one wait for the child, compiles included

# Error allowed between a hosted expert's reply and the plain f32 reference
# at "highest" matmul precision, as a share of the rms of the expert's own
# contribution (ref - x): the rms of the difference, and its largest
# element.  The server multiplies f32 operands at the MXU's default
# precision, one bf16 pass: each product carries a relative error near
# 2**-9, through two matmuls of depth 1024 and 4096.  On the chip that
# reads 4e-3 rms and 1.4e-2 to 1.8e-2 at the worst of 65,536 elements
# (PERF.md, PR 24); an f32 server on the CPU reads 1e-6.  bf16 activations
# or parameters (2**-8 on every stored value, not only inside the products)
# read two to three times the chip's figure, over the bar; a dropped term
# (bias, residual, LayerNorm) reads near 1.
REFERENCE_TOLERANCE_RMS = 8e-3
REFERENCE_TOLERANCE_MAX = 4e-2


def _load_subproc():
    """utils/subproc.py by path, as chip_smoke.py does: importing it
    through the package would import jax before the child is started."""
    spec = importlib.util.spec_from_file_location(
        "_lah_subproc",
        os.path.join(harness.ROOT, "learning_at_home_tpu", "utils",
                     "subproc.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _Child:
    """The client process; its stdout lines are kept and echoed."""

    def __init__(self, spec: dict):
        subproc = _load_subproc()
        self.proc = subprocess.Popen(
            [sys.executable, "-c", subproc.PDEATHSIG_WRAPPER,
             os.path.join(HERE, "swarm_clients.py"), json.dumps(spec)],
            env=subproc.clean_jax_subprocess_env(harness.ROOT, platform="cpu"),
            cwd=harness.ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1,
        )
        self._lines: list[str] = []
        self._cond = threading.Condition()
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            with self._cond:
                self._lines.append(line.rstrip("\n"))
                self._cond.notify_all()
        with self._cond:
            self._lines.append(None)  # end of stream
            self._cond.notify_all()

    def wait_for(self, tag: str):
        """The payload of the next line that starts with ``tag``."""
        deadline = time.monotonic() + CHILD_DEADLINE_S
        seen = 0
        with self._cond:
            while True:
                for line in self._lines[seen:]:
                    seen += 1
                    if line is None:
                        raise BenchError(
                            f"the client child ended before {tag!r} "
                            f"(rc={self.proc.poll()})"
                        )
                    if line == tag or line.startswith(tag + " "):
                        rest = line[len(tag):].strip()
                        return json.loads(rest) if rest else None
                left = deadline - time.monotonic()
                if left <= 0:
                    raise BenchError(f"no {tag!r} from the client child "
                                     f"in {CHILD_DEADLINE_S:.0f} s")
                self._cond.wait(timeout=left)

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def close(self) -> int:
        """The child is gone on return, however it got there."""
        if self.proc.returncode is not None:
            return self.proc.returncode
        try:
            self.proc.stdin.close()
            return self.proc.wait(timeout=30)
        except (subprocess.TimeoutExpired, OSError):
            self.proc.kill()
            return self.proc.wait()
        finally:
            self._pump.join(timeout=10)


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    words = harness.seed_words(args.seed, 5)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    scratch = tempfile.mkdtemp(prefix="bench_swarm_")
    child = _Child({
        **{k: traffic[k] for k in (
            "client", "clients", "rows", "backward", "warm_rows",
            "check_rows", "request_timeout_s")},
        "k_best": traffic.get("k_best"),
        "warm_dispatches": traffic.get("warm_dispatches", 0),
        "pool_batches": traffic.get("pool_batches", 0),
        "routing_seed": traffic.get("routing_seed"),
        "hidden_dim": config["hidden_dim"],
        "num_experts": config["num_experts"],
        "port": port, "seed_words": words[:4],
        "npz": os.path.join(scratch, "client.npz"),
    })
    clock.mark("client_start")
    try:
        result = _serve(cell, config, traffic, args, clock, child, port,
                        words[4], scratch)
    finally:
        rc = child.close()
        shutil.rmtree(scratch, ignore_errors=True)
    if rc != 0:
        raise BenchError(f"the client child exited rc={rc}")
    return result


def _serve(cell, config, traffic, args, clock, child, port, server_seed,
           scratch) -> dict:
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    cache_dir = enable_compile_cache()

    import optax

    from learning_at_home_tpu.server import Server

    clock.mark("import")
    counter = harness.CompileCounter()
    devices = harness.require_devices(config["platform"], cell["chips"])
    clock.mark("device_init")

    optimizers = {"adam": optax.adam, "sgd": optax.sgd, "adamw": optax.adamw}
    server = Server.create(
        num_experts=config["num_experts"], expert_cls=config["expert_cls"],
        hidden_dim=config["hidden_dim"], expert_prefix="expert",
        optimizer=optimizers[config["optimizer"]](config["learning_rate"]),
        max_batch_size=traffic["max_batch_size"], warmup=False,
        seed=server_seed % (1 << 30), host="127.0.0.1", port=port,
    )
    try:
        return _measure(cell, config, traffic, args, clock, child, server,
                        counter, devices, cache_dir, scratch)
    finally:
        child.close()  # its pools close before the server stops
        server.shutdown()


def _measure(cell, config, traffic, args, clock, child, server, counter,
             devices, cache_dir, scratch) -> dict:
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(
        next(iter(server.experts.values())).params
    )[0]
    if next(iter(leaf.devices())) != devices[0]:
        raise BenchError(f"expert parameters live on {leaf.devices()}")
    clock.mark("param_init")

    drawn = child.wait_for("DRAWN")
    clock.mark("client_import_wait")
    child.tell("SERVE")
    warm = child.wait_for("WARM")
    clock.mark("warmup_requests")
    setup = counter.snapshot()
    setup_s = clock.total()

    # ---- the window (the child's) ---------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    programs_at_start = counter.programs
    with harness.quiet_gc():
        child.tell(f"GO {args.seconds}")
        if trace_dir:
            time.sleep(harness.TRACE_START_S)
            harness.start_trace(trace_dir)
            time.sleep(harness.TRACE_SECONDS)
            jax.profiler.stop_trace()
        child.wait_for("WINDOW_DONE")
        compiled_in_window = counter.programs - programs_at_start
    child.tell("CHECK")
    reply = child.wait_for("RESULT")

    # ---- outside the window: correctness --------------------------------
    data = np.load(os.path.join(scratch, "client.npz"))
    inside = data["ends"] <= args.seconds
    ends = data["ends"][inside]
    ok = data["ok"][inside]
    latency_ms = 1e3 * (data["ends"] - data["starts"])[inside][ok]
    if len(latency_ms) < 2:
        raise BenchError(f"{len(latency_ms)} request(s) completed in the window")

    reference = harness.load_path(
        os.path.join(harness.ROOT, config["reference"])
    )
    errors_rms, errors_max = [], []
    for i in range(config["num_experts"]):  # the child's order
        want = np.asarray(reference.apply(
            server.experts[f"expert.{i}"].params, data["check_x"]
        ))
        scale = np.sqrt(np.mean((want - data["check_x"]) ** 2))
        diff = data["check_y"][i] - want
        errors_rms.append(float(np.sqrt(np.mean(diff ** 2)) / scale))
        errors_max.append(float(np.max(np.abs(diff)) / scale))
    counters = reply["counters"]
    problems = []
    if not (max(errors_rms) <= REFERENCE_TOLERANCE_RMS
            and max(errors_max) <= REFERENCE_TOLERANCE_MAX):  # NaN fails too
        problems.append(f"replies differ from the reference: rms "
                        f"{max(errors_rms)}, max {max(errors_max)}")
    if reply["samples_dropped"]:
        problems.append(f"{reply['samples_dropped']} samples dropped")
    if traffic["backward"]:
        sent = counters["backward_rpcs_sent"]
        if not 0 < counters["server_updates"] <= sent:
            problems.append(
                f"ledger: {counters['server_updates']} optimizer updates, "
                f"{sent} backward RPCs sent"
            )
    elif counters["server_updates"]:
        problems.append(f"{counters['server_updates']} optimizer updates in "
                        "a forward-only cell")
    if compiled_in_window:
        problems.append(f"{compiled_in_window} program(s) compiled in the window")
    failed = int(np.sum(~ok))

    gaps = harness.intervals(list(ends[ok]))
    print("INTERVALS " + json.dumps({
        "what": "seconds between request completions",
        **harness.five_numbers(gaps),
        "request_latency_ms": harness.five_numbers(list(latency_ms)),
    }), flush=True)
    print("SETUP " + json.dumps({
        "setup_s": setup_s, "phases": clock.phases, **setup,
        "client": {**drawn, **warm}, "compile_cache_dir": cache_dir,
        "reference_error_rms": [min(errors_rms), max(errors_rms)],
        "reference_error_max": [min(errors_max), max(errors_max)],
    }), flush=True)
    if problems:
        print("INCORRECT " + "; ".join(problems), file=sys.stderr, flush=True)

    observations = {
        "intervals_s": gaps,
        "counters": {
            **counters,
            "dispatch_ms_p50": harness.quantile(list(latency_ms), 0.50),
            **{k: v for k, v in reply.items()
               if k.startswith("client_") and v is not None},
        },
    }
    if trace_dir is not None:
        import trace_reduce

        try:
            # the runtime thread's five stages name a device gap by what
            # that thread was in at the gap's middle (PERF.md section 5)
            observations["trace"] = trace_reduce.reduce_dir(
                trace_dir,
                host_spans=("runtime.idle", "runtime.stack", "runtime.dispatch",
                            "runtime.materialize", "runtime.handoff"))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "correct": not problems,
        "attempted": int(len(ends)),
        "failed": failed,
        "end_to_end": {
            "swarm_samples_per_s": harness.rate_between_completions(
                list(ends[ok]), traffic["rows"]
            ),
            "dispatch_ms_p50": harness.quantile(list(latency_ms), 0.50),
            "dispatch_ms_p95": harness.quantile(list(latency_ms), 0.95),
            "setup_s": setup_s,
        },
        "observations": observations,
        "devices": devices,
    }
