#!/usr/bin/env python
"""Expert-server throughput benchmark (the reference's headline
measurement harness — SURVEY.md §2 'Experiment scripts').

Spins up one Server with N experts, hammers it with C concurrent client
workers issuing forward (or forward+backward) requests, and reports
samples/sec plus request-latency percentiles and batching telemetry.
``--chaos-*`` flags emulate WAN latency/stragglers/drops ([BJ] config 4).

Example:
  python experiments/benchmark_throughput.py --num-experts 16 \
      --clients 32 --requests 50 --backward
"""

import argparse
import concurrent.futures as cf
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-experts", type=int, default=16)
    p.add_argument("--expert-cls", default="ffn", choices=["ffn", "nop", "transformer", "swiglu"])
    p.add_argument("--hidden-dim", type=int, default=256)
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--requests", type=int, default=50, help="per client")
    p.add_argument("--rows", type=int, default=16, help="rows per request")
    p.add_argument("--backward", action="store_true", help="also run backward")
    p.add_argument("--max-batch-size", type=int, default=1024)
    p.add_argument("--chaos-latency", type=float, default=0.0)
    p.add_argument("--chaos-jitter", type=float, default=0.0)
    p.add_argument("--chaos-straggler-prob", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax
    import numpy as np

    from learning_at_home_tpu.client import RemoteExpert, reset_client_rpc
    from learning_at_home_tpu.server import ChaosConfig, background_server

    # the server's experts live on JAX's default device (the clients here
    # are numpy-over-sockets threads and use none); the result names it
    device = jax.devices()[0]

    chaos = None
    if args.chaos_latency or args.chaos_jitter or args.chaos_straggler_prob:
        chaos = ChaosConfig(
            base_latency=args.chaos_latency,
            jitter=args.chaos_jitter,
            straggler_prob=args.chaos_straggler_prob,
            straggler_delay=0.5,
            seed=args.seed,
        )

    with background_server(
        num_experts=args.num_experts,
        expert_cls=args.expert_cls,
        hidden_dim=args.hidden_dim,
        expert_prefix="bench",
        max_batch_size=args.max_batch_size,
        chaos=chaos,
        seed=args.seed,
    ) as (endpoint, srv):
        experts = [
            RemoteExpert(uid, endpoint, timeout=60.0) for uid in srv.experts
        ]
        rs = np.random.RandomState(args.seed)
        x = rs.randn(args.rows, args.hidden_dim).astype(np.float32)
        g = rs.randn(args.rows, args.hidden_dim).astype(np.float32)

        latencies = []

        def worker(wid: int):
            rs = np.random.RandomState(wid)
            times = []
            for r in range(args.requests):
                expert = experts[rs.randint(len(experts))]
                t0 = time.monotonic()
                expert.forward_blocking([x])
                if args.backward:
                    expert.backward_blocking([x], [g])
                times.append(time.monotonic() - t0)
            return times

        # warmup: compile every expert's forward/backward bucket once
        experts[0].forward_blocking([x])
        if args.backward:
            experts[0].backward_blocking([x], [g])

        t0 = time.monotonic()
        with cf.ThreadPoolExecutor(args.clients) as pool:
            for times in pool.map(worker, range(args.clients)):
                latencies.extend(times)
        elapsed = time.monotonic() - t0

        total_requests = args.clients * args.requests
        total_samples = total_requests * args.rows
        lat = np.asarray(latencies) * 1000
        fwd_pools = list(srv.forward_pools.values())
        result = {
            "metric": "expert server throughput"
            + (" (fwd+bwd)" if args.backward else " (fwd)"),
            "samples_per_sec": round(total_samples / elapsed, 1),
            "requests_per_sec": round(total_requests / elapsed, 1),
            "latency_ms": {
                "p50": round(float(np.percentile(lat, 50)), 2),
                "p99": round(float(np.percentile(lat, 99)), 2),
            },
            "batches_formed": sum(p.batches_formed for p in fwd_pools),
            "avg_batch_rows": round(
                sum(p.total_rows for p in fwd_pools)
                / max(1, sum(p.batches_formed for p in fwd_pools)),
                1,
            ),
            "padding_waste": round(
                sum(p.padded_rows for p in fwd_pools)
                / max(1, sum(p.total_rows + p.padded_rows for p in fwd_pools)),
                4,
            ),
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_time_s": round(srv.runtime.device_time, 2),
            "runtime": srv.runtime.stats(),
            "chaos": vars(chaos) if chaos else None,
        }
        # client dispatch hot path (PR 2): negotiated protocol, bytes
        # handed to the wire, and the multiplexed in-flight high-water
        # mark per endpoint pool
        from learning_at_home_tpu.client.rpc import pool_registry

        pools = pool_registry().pools()
        result["client"] = {
            "protocol": "v2" if any(p._proto == 2 for p in pools) else "v1",
            "bytes_sent": int(sum(p.bytes_sent for p in pools)),
            "inflight_depth_max": max(
                (p.inflight_max for p in pools), default=0
            ),
        }
        print(json.dumps(result))
    reset_client_rpc()


if __name__ == "__main__":
    main()
