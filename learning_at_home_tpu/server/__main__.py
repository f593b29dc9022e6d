"""Standalone expert server CLI — the reference's ``Server.create`` entry
point (SURVEY.md §3.3): start a peer hosting N experts, join the DHT swarm,
declare + heartbeat, serve until interrupted.

    python -m learning_at_home_tpu.server \
        --num-experts 4 --expert-cls ffn --hidden-dim 1024 \
        --expert-prefix ffn --port 31337 \
        --initial-peers 10.0.0.1:31338 \
        --checkpoint-dir ./ckpt --checkpoint-every 300
"""

from __future__ import annotations

import argparse
import signal
import threading


def parse_endpoint(s: str) -> tuple[str, int]:
    host, sep, port = s.rpartition(":")
    if not sep or not port.isdigit():
        raise SystemExit(
            f"--initial-peers entry {s!r} must be host:port (e.g. 10.0.0.1:31337)"
        )
    return (host, int(port))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-experts", type=int, default=4)
    p.add_argument("--expert-cls", default="ffn",
                   choices=["ffn", "transformer", "swiglu", "nop"])
    p.add_argument("--hidden-dim", type=int, default=1024)
    p.add_argument("--expert-prefix", default="expert")
    p.add_argument("--expert-offset", type=int, default=0,
                   help="first expert index (partition a grid across servers)")
    p.add_argument("--expert-uids", default=None,
                   help="comma-separated explicit uid list (e.g. "
                        "'ffn0.1,ffn1.3'); overrides prefix/offset/num; "
                        "params seeded stably per uid")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--dht-port", type=int, default=0)
    p.add_argument("--initial-peers", nargs="*", default=[],
                   help="host:port of existing DHT peers")
    p.add_argument("--no-dht", action="store_true")
    p.add_argument("--update-period", type=float, default=15.0)
    p.add_argument("--max-batch-size", type=int, default=1024)
    p.add_argument("--optimizer", default="adam", choices=["adam", "sgd", "adamw"])
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=float, default=0.0,
                   help="seconds between checkpoints (0 = only on shutdown)")
    p.add_argument("--checkpoint-keep-last", type=int, default=3,
                   help="complete checkpoint steps to retain (older ones "
                        "and crashed half-saves are pruned)")
    p.add_argument("--resume", action="store_true",
                   help="load the latest checkpoint before serving")
    p.add_argument("--drain-on-term", action="store_true",
                   help="graceful lifecycle (ISSUE 9): the first SIGTERM "
                        "DRAINS instead of exiting — stop heartbeating "
                        "(DHT expiry steers dispatch away), finish "
                        "in-flight batches, migrate every expert's params"
                        "+optimizer state to a successor (checkpoint "
                        "fallback), then exit.  A second SIGTERM forces "
                        "immediate shutdown")
    p.add_argument("--drain-grace", type=float, default=None,
                   help="seconds to keep serving after the drain starts "
                        "(default: the declared record TTL, 2 x "
                        "--update-period, so published records expire)")
    p.add_argument("--drain-successor", default=None,
                   help="host:port to migrate experts to on drain "
                        "(default: least-loaded peer from the load.* "
                        "DHT heartbeats)")
    p.add_argument("--warmup", type=int, nargs="*", default=None,
                   help="pre-compile fwd/bwd for these batch-bucket sizes "
                        "before serving (e.g. --warmup 64 256 1024); "
                        "no value = all power-of-2 buckets")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--telemetry-prefix", default="swarm",
                   help="DHT scope the metrics endpoint is advertised "
                        "under (telemetry.<prefix>); lah_top discovers "
                        "all peers sharing a prefix")
    p.add_argument("--chaos-latency", type=float, default=0.0,
                   help="inject WAN-like base latency (seconds) per request")
    p.add_argument("--chaos-jitter", type=float, default=0.0)
    p.add_argument("--chaos-straggler-prob", type=float, default=0.0)
    p.add_argument("--chaos-straggler-delay", type=float, default=1.5)
    p.add_argument("--chaos-bandwidth", type=float, default=0.0,
                   help="emulated link bandwidth in bytes/sec (0 = "
                        "unlimited); each reply delayed by payload/bw")
    args = p.parse_args()

    import logging

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
    )

    from learning_at_home_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # before the first jit: warm-up is all compiles

    import jax
    import optax

    from learning_at_home_tpu.dht import DHT
    from learning_at_home_tpu.server import ChaosConfig, Server

    optimizer = {
        "adam": optax.adam,
        "adamw": optax.adamw,
        "sgd": optax.sgd,
    }[args.optimizer](args.lr)

    dht = None
    if not args.no_dht:
        dht = DHT(
            initial_peers=[parse_endpoint(s) for s in args.initial_peers],
            port=args.dht_port,
        )
        print(f"DHT node at {dht.endpoint}", flush=True)

    if args.warmup is not None:
        # True = all power-of-two buckets; a list = exactly those sizes
        warmup = args.warmup if args.warmup else True
    else:
        warmup = False
    expert_uids = None
    if args.expert_uids is not None:
        expert_uids = [u.strip() for u in args.expert_uids.split(",") if u.strip()]
        if not expert_uids:
            raise SystemExit("--expert-uids given but empty")
    server = Server.create(
        num_experts=args.num_experts,
        expert_cls=args.expert_cls,
        hidden_dim=args.hidden_dim,
        expert_prefix=args.expert_prefix,
        expert_offset=args.expert_offset,
        expert_uids=expert_uids,
        optimizer=optimizer,
        max_batch_size=args.max_batch_size,
        warmup=warmup,
        seed=args.seed,
        start=False,
        host=args.host,
        port=args.port,
        dht=dht,
        update_period=args.update_period,
        telemetry_prefix=args.telemetry_prefix,
        chaos=(
            ChaosConfig(
                base_latency=args.chaos_latency,
                jitter=args.chaos_jitter,
                straggler_prob=args.chaos_straggler_prob,
                straggler_delay=args.chaos_straggler_delay,
                bandwidth_bps=args.chaos_bandwidth,
                seed=args.seed,
            )
            if args.chaos_latency or args.chaos_jitter
            or args.chaos_straggler_prob or args.chaos_bandwidth
            else None
        ),
    )
    experts = server.experts
    # where the parameters actually live, read off a hosted expert's own
    # arrays before serving can donate them (an empty replica host has
    # none yet: its default device)
    leaves = jax.tree_util.tree_leaves(
        [backend.params for backend in experts.values()]
    )
    device = next(iter(leaves[0].devices())) if leaves else jax.devices()[0]
    # replicas installed via the ``replica`` RPC and the drain fallback
    # restore from THIS server's checkpoint root (never peer-supplied)
    server.replica_checkpoint_root = args.checkpoint_dir
    server.run_in_background()
    ckpt_mgr = None
    if args.checkpoint_dir:
        from learning_at_home_tpu.utils.checkpoint import CheckpointManager

        ckpt_mgr = CheckpointManager(
            args.checkpoint_dir, keep_last=args.checkpoint_keep_last
        )
    if args.resume and ckpt_mgr is not None:
        try:
            step = server.load_checkpoint(args.checkpoint_dir)
            server.restarts = ckpt_mgr.record_restart()
            print(f"resumed from checkpoint step {step} "
                  f"(restart #{server.restarts})", flush=True)
        except FileNotFoundError:
            print("no checkpoint found; starting fresh", flush=True)
    if ckpt_mgr is not None and args.checkpoint_every > 0:
        ckpt_mgr.start_periodic(
            lambda step: server.save_checkpoint(args.checkpoint_dir, step),
            args.checkpoint_every,
        )
        server.checkpoint_manager = ckpt_mgr
    span = (
        f"({sorted(experts)[0]}..{sorted(experts)[-1]}) " if experts
        # a server may boot EMPTY and gain experts via replica RPCs
        else "(none yet — replica-host mode) "
    )
    print(
        f"serving {len(experts)} {args.expert_cls!r} experts "
        f"{span}on "
        f"{server.endpoint[0]}:{server.endpoint[1]}, parameters on "
        f"{device.platform} [{device.device_kind}] "
        f"(metrics http://{server.endpoint[0]}:{server.metrics_port}/metrics)",
        flush=True,
    )

    stop = threading.Event()
    drain_req = threading.Event()

    def on_term(*_):
        # first SIGTERM with --drain-on-term: graceful drain (handled by
        # the main loop — a signal handler must not block through the
        # whole sequence); second SIGTERM, or no drain flag: exit now
        if args.drain_on_term and not drain_req.is_set():
            drain_req.set()
        else:
            stop.set()

    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, on_term)
    successor = (
        parse_endpoint(args.drain_successor) if args.drain_successor else None
    )
    drained = False
    while not stop.wait(timeout=0.5):
        if drain_req.is_set() and not drained:
            drained = True
            print("SIGTERM: draining (migrate experts, then exit) ...",
                  flush=True)
            server.start_drain(successor=successor, grace=args.drain_grace)
        if drained and server.wait_drained(timeout=0.0):
            print(f"drain complete: {server.drain_summary}", flush=True)
            break
    if ckpt_mgr is not None and not drained:
        # a drain already checkpointed whatever it could not hand off;
        # the plain-shutdown path snapshots everything here instead.
        # Stop the periodic thread FIRST: racing it on next_step() could
        # mark a torn two-writer snapshot complete
        ckpt_mgr.stop()
        step = ckpt_mgr.save_now(
            lambda s: server.save_checkpoint(args.checkpoint_dir, s)
        )
        if step is None:
            print("final checkpoint FAILED (see log)", flush=True)
        else:
            print(f"final checkpoint saved @ step {step}", flush=True)
    server.shutdown()
    if dht is not None:
        dht.shutdown()
    print("server shut down", flush=True)


if __name__ == "__main__":
    main()
