"""The clients of the ``expert_server`` runner: one child process, pinned
to the CPU, that the runner starts, talks to over its standard streams,
and waits for.

    python3 swarm_clients.py '<spec as JSON>'

Two kinds of client, named by the traffic file:

- ``numpy_threads``: the loop of ``experiments/benchmark_throughput.py`` —
  closed-loop threads, each sending ``RemoteExpert.forward_blocking`` (and
  ``backward_blocking`` where the traffic says so) to an expert drawn from
  its seeded stream, the next request when the reply is in.
- ``jitted_mixture``: ``chip_smoke.run_client`` held for a window — ONE
  jitted forward+grad ``RemoteMixtureOfExperts`` trainer (several jitted
  clients as threads of one process can deadlock in ``io_callback`` on a
  small XLA:CPU pool: ``bench.py:dispatch_worker``).

Lines this process prints: ``DRAWN`` when its inputs exist (then it waits
for ``SERVE``), ``WARM {...}`` when every shape has been through the
server (then it waits for ``GO <seconds>``), ``WINDOW_DONE`` when the
window has closed (then it waits for ``CHECK``), ``RESULT {...}`` at the
end.  Arrays go to the ``.npz`` the spec names.
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import time

T0 = time.perf_counter()


def say(tag: str, payload=None) -> None:
    print(tag if payload is None else f"{tag} {json.dumps(payload)}",
          flush=True)


def expect(word: str) -> list:
    line = sys.stdin.readline().split()
    if not line or line[0] != word:
        raise SystemExit(f"expected {word!r} on stdin, got {line!r}")
    return line[1:]


def main() -> int:
    spec = json.loads(sys.argv[1])
    import numpy as np

    from learning_at_home_tpu.client import RemoteExpert
    from learning_at_home_tpu.client.rpc import (
        client_loop,
        pool_registry,
        reset_client_rpc,
    )

    t_import = time.perf_counter() - T0
    hid, rows = spec["hidden_dim"], spec["rows"]
    endpoint = ("127.0.0.1", spec["port"])
    uids = [f"expert.{i}" for i in range(spec["num_experts"])]
    experts = [
        RemoteExpert(uid, endpoint, timeout=spec["request_timeout_s"])
        for uid in uids
    ]
    rng = np.random.default_rng(spec["seed_words"])
    jitted = spec["client"] == "jitted_mixture"
    if jitted:
        import jax
        import jax.numpy as jnp

        from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
        from learning_at_home_tpu.client.routing import StaticExpertSource

        if jax.devices()[0].platform != "cpu":
            raise SystemExit("the client child must be pinned to the CPU")
        moe = RemoteMixtureOfExperts(
            in_features=hid, grid_size=(len(uids),), uid_prefix="expert",
            k_best=spec["k_best"],
            source=StaticExpertSource({uid: endpoint for uid in uids}),
            forward_timeout=spec["request_timeout_s"],
            backward_timeout=spec["request_timeout_s"],
        )
        # the gate and the pool of inputs fix how many rows each expert gets
        # in a dispatch: they come from the traffic file's own seed, so that
        # every run does the same work, and ``--seed`` only orders the pool
        fixed = np.random.default_rng(spec["routing_seed"])
        gate = moe.init_gate_params(
            jnp.asarray(fixed.integers(0, 1 << 32, 2, dtype=np.uint32))
        )

        @jax.jit
        def loss_and_grads(gate, x):
            def loss(gate, x):
                y = moe(x, gate)
                return jnp.mean(y * y), y

            (value, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True
            )(gate, x)
            return value, y, grads

        if spec["warm_dispatches"] < spec["pool_batches"]:
            raise SystemExit("every pool batch must be dispatched in warm-up")
        pool = [jnp.asarray(fixed.standard_normal((rows, hid), np.float32))
                for _ in range(spec["pool_batches"])]
        pool = [pool[i] for i in rng.permutation(len(pool))]
    else:
        # one input a thread, and its whole stream of expert choices
        pool = [rng.standard_normal((rows, hid), np.float32)
                for _ in range(spec["clients"])]
        choices = rng.integers(0, len(uids), (spec["clients"], 1 << 16))
        grad = rng.standard_normal((rows, hid), np.float32)
    check_x = rng.standard_normal((spec["check_rows"], hid), np.float32)
    say("DRAWN", {"import_s": t_import, "drawn_s": time.perf_counter() - T0})

    expect("SERVE")
    t = time.perf_counter()
    for expert in experts:  # every bucket of every expert, once
        for n in spec["warm_rows"]:
            x = np.zeros((n, hid), np.float32)
            expert.forward_blocking([x])
            if spec["backward"]:
                expert.backward_blocking([x], [x])
    t_buckets = time.perf_counter() - t
    t = time.perf_counter()
    first_ms = []
    if jitted:
        for i in range(spec["warm_dispatches"]):
            t1 = time.perf_counter()
            out = jax.block_until_ready(loss_and_grads(gate, pool[i % len(pool)]))
            first_ms.append(1e3 * (time.perf_counter() - t1))
        leaves = [out[0], out[1], *jax.tree_util.tree_leaves(out[2])]
        if not all(bool(jnp.all(jnp.isfinite(leaf))) for leaf in leaves):
            raise SystemExit("non-finite output or gradient from a dispatch")
    say("WARM", {"bucket_requests_s": t_buckets,
                 "client_dispatches_s": time.perf_counter() - t,
                 "client_dispatch_ms": first_ms})

    backward_calls = [0]  # by the numpy clients; the mixture counts its own

    async def server_stats():
        _, meta = await pool_registry().get(endpoint).rpc(
            "stats", (), {}, timeout=10.0
        )
        return meta

    def counters() -> dict:
        stats = client_loop().run(server_stats())
        kinds = stats["pools"].values()
        return {
            "pool_rows": sum(k["rows"] for k in kinds),
            "pool_padded_rows": sum(k["padded_rows"] for k in kinds),
            "pool_batches": sum(k["batches_formed"] for k in kinds),
            "server_updates": int(stats["update_count_total"]),
            "backward_rpcs_sent": (
                moe.backward_rpcs_sent if jitted else backward_calls[0]
            ),
        }

    seconds = float(expect("GO")[0])
    before = counters()
    records: list = []  # (start, end, ok) on this process's clock
    lock = threading.Lock()
    gc.collect()
    gc.freeze()
    gc.disable()
    t_start = time.perf_counter()
    t_end = t_start + seconds

    def numpy_client(wid: int) -> None:
        mine, x, stream = [], pool[wid], choices[wid]
        n = 0
        while True:
            t0 = time.perf_counter()
            if t0 >= t_end:
                break
            expert = experts[stream[n % len(stream)]]
            n += 1
            try:
                expert.forward_blocking([x])
                if spec["backward"]:
                    expert.backward_blocking([x], [grad])
                ok = True
            except Exception as e:  # a failed request is counted, not fatal
                print(f"request failed: {e!r}", file=sys.stderr, flush=True)
                ok = False
            mine.append((t0, time.perf_counter(), ok))
        with lock:
            records.extend(mine)
            backward_calls[0] += len(mine) if spec["backward"] else 0

    if jitted:
        n = 0
        while True:
            t0 = time.perf_counter()
            if t0 >= t_end:
                break
            jax.block_until_ready(loss_and_grads(gate, pool[n % len(pool)]))
            records.append((t0, time.perf_counter(), True))
            n += 1
    else:
        threads = [threading.Thread(target=numpy_client, args=(w,))
                   for w in range(spec["clients"])]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    gc.enable()
    say("WINDOW_DONE")
    expect("CHECK")  # the runner has read its compile counter
    after = counters()

    # outside the window: what each expert answers on a seeded sample, for
    # the runner to hold against the plain reference
    replies = [np.asarray(e.forward_blocking([check_x])[0]) for e in experts]
    records.sort(key=lambda r: r[1])
    np.savez(
        spec["npz"], check_x=check_x, check_y=np.stack(replies),
        starts=np.asarray([r[0] for r in records]) - t_start,
        ends=np.asarray([r[1] for r in records]) - t_start,
        ok=np.asarray([r[2] for r in records], bool),
    )
    result = {
        "counters": {k: after[k] - before[k] for k in after},
        "samples_dropped": (
            moe.samples_dropped + moe.backward_samples_dropped if jitted else 0
        ),
    }
    if jitted:
        stats = moe.dispatch_stats()
        result["client_pack_p50_ms"] = stats["pack_p50_ms"]
        result["client_wait_p50_ms"] = stats["wait_p50_ms"]
    reset_client_rpc()  # close the pools: the server is stopped next
    say("RESULT", result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
