#!/usr/bin/env python
"""DMoE-Transformer LM training — [BJ] config 3 (256-expert grid).

Two modes, one CLI:

- ``--mode pod``   : the TPU-native path — experts sharded over the device
  mesh, all_to_all dispatch, single jitted train step.
- ``--mode swarm`` : the reference's decentralized path — this process
  starts N expert servers + a DHT swarm on localhost, then trains a local
  trunk against DHT-discovered remote experts (async server-side SGD).

Data: ``--data /path/to/wikitext.txt`` (or .npy token file) reproduces the
reference setup; without it a synthetic Zipfian corpus is used (this
sandbox has no network egress — see models/data.py).

Examples:
  python experiments/train_lm.py --mode pod --steps 200
  python experiments/train_lm.py --mode swarm --experts-per-layer 16 \
      --n-servers 2 --steps 50
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=["pod", "swarm"], default="pod")
    p.add_argument("--data", default=None, help="local corpus (.txt/.npy)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--num-experts", type=int, default=256, help="pod mode")
    p.add_argument("--experts-per-layer", type=int, default=16, help="swarm mode")
    p.add_argument("--n-servers", type=int, default=2, help="swarm mode")
    p.add_argument("--subprocess-servers", action="store_true",
                   help="swarm mode: host experts in separate server "
                        "processes (the production topology; required for "
                        "heavy runs — a trainer must not share an XLA "
                        "runtime with its servers)")
    p.add_argument("--base-port", type=int, default=0,
                   help="swarm mode: fixed base port for spawned expert "
                        "servers (server s binds base+s). Default 0 = each "
                        "server binds an EPHEMERAL port and trainers "
                        "discover endpoints via the DHT — fixed defaults "
                        "made concurrent runs (or an orphan from a killed "
                        "prior run) collide on one box (the multi-trainer "
                        "port-collision flake)")
    p.add_argument("--initial-peers", default=None,
                   help="swarm mode: comma-separated host:port DHT peers of "
                        "an EXISTING swarm to join as a pure trainer (no "
                        "servers are spawned; the reference's many-trainer "
                        "deployment shape)")
    p.add_argument("--data-shard", default=None, metavar="I:N",
                   help="train on the I-th of N contiguous corpus shards "
                        "(disjoint data per trainer in multi-trainer runs)")
    p.add_argument("--n-trainers", type=int, default=1,
                   help="swarm mode: spawn this many INDEPENDENT trainer "
                        "processes (own trunk+gates, disjoint data shards) "
                        "against one shared expert swarm — the reference's "
                        "concurrent async-DP deployment (SURVEY §2.2 DP)")
    p.add_argument("--pipeline", type=int, default=1,
                   help="swarm mode: concurrent micro-batch steps in flight "
                        "(PipelinedSwarmTrainer; 1 = sequential). Overlaps "
                        "each step's RPC quorum waits with the next step's "
                        "trunk compute — delayed parameter updates.")
    p.add_argument("--overlap", action="store_true",
                   help="swarm mode: drive the ScMoE-style shortcut "
                        "schedule (ISSUE 7's fire/join dispatch — each "
                        "layer's expert fan-out flies while its attention "
                        "computes).  Opt-in: the shortcut WIRING differs "
                        "from the default apply, so loss curves are "
                        "comparable only against --overlap-serial (same "
                        "ops, serial schedule — the A/B parity arm)")
    p.add_argument("--overlap-serial", action="store_true",
                   help="swarm mode: the shortcut architecture with the "
                        "SERIAL schedule (join right after fire) — "
                        "bitwise the same math as --overlap, no "
                        "communication/compute overlap; the baseline arm "
                        "of the loss-parity smoke")
    p.add_argument("--chaos-bandwidth", type=float, default=0.0,
                   help="swarm mode: emulated server link bandwidth in "
                        "bytes/sec (0 = unlimited) — loopback hides "
                        "payload-size costs without it")
    p.add_argument("--chaos-latency", type=float, default=0.0,
                   help="swarm + --subprocess-servers: inject WAN-like "
                        "latency (s) on every server reply")
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument(
        "--optimizer", choices=("adamw", "adafactor"), default="adamw",
        help="pod mode: adafactor (factored, ~no state) fits the "
        "256-expert shape on one 16 GB chip where f32+AdamW cannot",
    )
    p.add_argument(
        "--param-dtype", choices=("f32", "bf16"), default="f32",
        help="pod mode: parameter storage dtype (bf16 halves HBM)",
    )
    p.add_argument(
        "--router-jitter", type=float, default=0.0,
        help="pod mode: multiplicative routing noise, selection-only "
        "(0 = off, matching DMoETransformerConfig and preserving zigzag/"
        "contiguous equivalence).  Byte-level batches hold ~84 unique "
        "tokens and collapse onto few experts at init; 0.1 with "
        "--aux-weight 5e-2 is the measured recipe (BASELINE.md)",
    )
    p.add_argument(
        "--aux-weight", type=float, default=1e-2,
        help="pod mode: load-balance auxiliary loss weight",
    )
    p.add_argument("--averaging", action="store_true",
                   help="swarm mode: decentralized trunk/gate parameter "
                        "averaging across trainers (DHT-matched group "
                        "all-reduce; learning_at_home_tpu/averaging). "
                        "Sequential trainers run a BLOCKING round every "
                        "--averaging-every steps (params replaced by the "
                        "group mean); pipelined trainers average in the "
                        "background and apply the group delta atomically. "
                        "A final blocking round runs after training, so "
                        "co-scheduled trainers end with identical trunks")
    p.add_argument("--averaging-every", type=int, default=10,
                   help="steps between averaging rounds")
    p.add_argument("--averaging-group-size", type=int, default=2,
                   help="minimum trainers per averaging round")
    p.add_argument("--averaging-timeout", type=float, default=30.0,
                   help="matchmaking budget per round (s); a round that "
                        "finds no group is skipped and counted, never "
                        "fatal")
    p.add_argument("--wire-dtype", default=None,
                   choices=["bfloat16", "float16"],
                   help="swarm mode: downcast activation/grad RPC payloads "
                        "on the wire (servers still compute in f32) — "
                        "halves DCN bytes per dispatch")
    p.add_argument("--wire-codec", default=None,
                   choices=["none", "bf16", "f16", "u8", "blockq8"],
                   help="swarm mode: pin the wire codec for dispatch "
                        "payloads (8-bit codecs quarter DCN bytes vs f32; "
                        "servers still compute in f32).  Default: adaptive "
                        "per-pool escalation; LAH_WIRE_CODEC also works")
    p.add_argument("--latency-weight", type=float, default=0.0,
                   help="swarm mode: debit expert selection scores by this "
                        "x endpoint RTT EMA (s) — route around slow peers")
    p.add_argument("--routing-cost-weight", type=float, default=None,
                   help="swarm mode: latency-aware routing cost model "
                        "weight (RTT EMA + advertised queue depth + "
                        "estimated transfer, min over replicas; ISSUE 8). "
                        "0 = off (bias=None, blind-gate selection); "
                        "default: fall back to --latency-weight")
    p.add_argument("--telemetry-prefix", default="swarm",
                   help="swarm mode: advertise this trainer's metrics "
                        "endpoint under telemetry.<prefix> in the DHT "
                        "(lah_top discovers it; utils/telemetry.py)")
    p.add_argument("--no-telemetry", action="store_true",
                   help="swarm mode: don't host/advertise a metrics "
                        "endpoint for this trainer")
    p.add_argument("--telemetry-host", default="127.0.0.1",
                   help="swarm mode: host the trainer's metrics endpoint "
                        "binds AND advertises in the DHT — set to this "
                        "machine's swarm-reachable address for "
                        "cross-machine deployments (loopback is only "
                        "correct for single-box swarms)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--checkpoint-dir", default=None,
                   help="trainer-side checkpoints (pod and swarm modes)")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="steps between checkpoints (0 = end of run only)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    if args.n_trainers > 1 and args.mode != "swarm":
        p.error("--n-trainers requires --mode swarm (pod mode is one "
                "jitted SPMD trainer; concurrency there is the mesh)")
    if args.averaging and args.mode != "swarm":
        p.error("--averaging requires --mode swarm (pod mode's trunk is "
                "one SPMD program — it cannot diverge)")
    if args.overlap and args.overlap_serial:
        p.error("--overlap and --overlap-serial are the two arms of one "
                "A/B — pick one")
    if (args.overlap or args.overlap_serial) and args.mode != "swarm":
        p.error("--overlap[-serial] requires --mode swarm (pod mode has "
                "no remote dispatch to overlap)")
    if (args.overlap or args.overlap_serial) and args.pipeline > 1:
        p.error("--overlap[-serial] drives the sequential step; "
                "--pipeline overlap is a different axis (pick one)")
    return args


def run_pod(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learning_at_home_tpu.models.data import VOCAB_SIZE, LMBatcher, load_corpus
    from learning_at_home_tpu.models.transformer import (
        DMoETransformerConfig,
        DMoETransformerLM,
    )
    from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh

    n_dev = len(jax.devices())
    dp = 2 if n_dev % 2 == 0 and n_dev > 2 else 1
    mesh = make_mesh({"data": dp, "expert": n_dev // dp})
    cfg = DMoETransformerConfig(
        vocab_size=VOCAB_SIZE,
        d_model=args.d_model,
        n_layers=args.n_layers,
        seq_len=args.seq_len,
        num_experts=args.num_experts,
        k=args.k,
        dtype=jnp.bfloat16 if jax.devices()[0].platform != "cpu" else jnp.float32,
        param_dtype=jnp.bfloat16 if args.param_dtype == "bf16" else jnp.float32,
        router_jitter=args.router_jitter,
        aux_loss_weight=args.aux_weight,
    )
    from learning_at_home_tpu.parallel.mesh import data_axes

    n_shards = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    if args.batch_size % n_shards:
        raise SystemExit(
            f"--batch-size {args.batch_size} must be divisible by the "
            f"{n_shards} batch shards of mesh {dict(mesh.shape)}"
        )
    model = DMoETransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    # adafactor + bf16 params is the single-chip recipe for the 256-expert
    # shape (f32+AdamW needs ~34 GB of state vs one v5e's 16 GB HBM)
    optimizer = (
        optax.adafactor(args.lr)
        if args.optimizer == "adafactor"
        else optax.adamw(args.lr)
    )
    opt_state = model.init_opt_state(optimizer, params)
    step_fn = model.make_train_step(optimizer)

    ckpt = None
    start_step = 0
    if args.checkpoint_dir:
        from learning_at_home_tpu.utils.checkpoint import TrainCheckpointer

        ckpt = TrainCheckpointer(args.checkpoint_dir)
        if args.resume:
            restored = ckpt.restore_latest(params, opt_state)
            if restored is not None:
                start_step, params, opt_state = restored
                print(f"# resumed from step {start_step}", flush=True)

    tokens = load_corpus(args.data, seed=args.seed)
    batches = LMBatcher(tokens, args.batch_size, args.seq_len, seed=args.seed)
    batches.skip(start_step)  # resume continues the data order, no replay
    sharding = batch_sharding(mesh)

    t0 = time.perf_counter()
    for step, (ids, tgt) in zip(range(start_step, args.steps), batches):
        ids = jax.device_put(jnp.asarray(ids), sharding)
        tgt = jax.device_put(jnp.asarray(tgt), sharding)
        params, opt_state, loss, metrics = step_fn(params, opt_state, ids, tgt)
        if ckpt and args.checkpoint_every and (step + 1) % args.checkpoint_every == 0:
            ckpt.save(step + 1, params, opt_state)
        if step % args.log_every == 0 or step == args.steps - 1:
            elapsed = time.perf_counter() - t0
            tps = (step + 1 - start_step) * args.batch_size * args.seq_len / elapsed
            print(
                json.dumps(
                    {
                        "step": step,
                        "loss": round(float(loss), 4),
                        "ce": round(float(metrics["ce"]), 4),
                        "dropped": round(float(metrics["dropped_fraction"]), 4),
                        "tokens_per_sec": round(tps, 1),
                    }
                ),
                flush=True,
            )
    if ckpt is not None:
        ckpt.save(args.steps, params, opt_state)
        print(f"# checkpointed final step {args.steps}", flush=True)


def _uids_for_server(args, s: int) -> list[str]:
    """Experts strided across servers: ffn{layer}.{i} for i ≡ s (mod n)."""
    return [
        f"ffn{layer}.{i}"
        for layer in range(args.n_layers)
        for i in range(args.experts_per_layer)
        if i % args.n_servers == s
    ]


def _spawn_servers(args, bootstrap_endpoint):
    """Launch the expert-server subprocesses of a swarm (shared by the
    single-trainer --subprocess-servers path and the --n-trainers
    orchestrator)."""
    import subprocess

    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # --n-servers processes on this one host cannot share a chip, and the
    # trainer that spawned them may hold it: these servers are CPU
    # processes.  A chip-resident server is started on its own
    # (``python -m learning_at_home_tpu.server``, one per chip) and joined
    # through --initial-peers.
    env = clean_jax_subprocess_env(repo, platform="cpu")
    procs = []
    for s in range(args.n_servers):
        uids = _uids_for_server(args, s)
        if not uids:
            continue  # more servers than experts: nothing to host
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "learning_at_home_tpu.server",
                    "--expert-uids", ",".join(uids),
                    "--hidden-dim", str(args.d_model),
                    # ephemeral by default: the kernel hands out a free
                    # port and the DHT heartbeat publishes the real
                    # endpoint, so nothing ever collides
                    "--port",
                    str(args.base_port + s) if args.base_port else "0",
                    "--initial-peers",
                    f"{bootstrap_endpoint[0]}:{bootstrap_endpoint[1]}",
                    "--update-period", "5.0",
                    "--optimizer", "adam", "--lr", str(args.lr),
                    "--max-batch-size", "4096",
                ]
                + (
                    ["--chaos-latency", str(args.chaos_latency)]
                    if args.chaos_latency
                    else []
                )
                + (
                    ["--chaos-bandwidth", str(args.chaos_bandwidth)]
                    if args.chaos_bandwidth
                    else []
                ),
                env=env,
            )
        )
    return procs


def _wait_for_experts(client_dht, procs, n_layers: int, want: int,
                      deadline_s: float = 30.0) -> int:
    """Poll the DHT until ``want`` experts are alive (or the deadline
    passes), failing fast if a server subprocess dies during startup.
    Returns the number found."""
    deadline = time.time() + deadline_s
    found = 0
    while time.time() < deadline:
        for proc in procs:
            if proc.poll() is not None:
                raise SystemExit(
                    f"server process exited with {proc.returncode} during "
                    "startup (port in use? see its log)"
                )
        found = sum(
            len(client_dht._loop.run(client_dht._get_alive(f"ffn{l}")))
            for l in range(n_layers)
        )
        if found >= want:
            break
        time.sleep(0.25)
    return found


def _rpc_server_stats(client_dht, n_layers: int) -> dict | None:
    """Merged server-wide ``stats`` over every alive peer: ONE RPC per
    endpoint (per-expert ``info`` queries would cost n_experts × RTT).
    Returns ``{"update_count_total": int, "update_count": {uid: int}}``
    or None — telemetry must never kill a training loop."""
    try:
        import asyncio

        from learning_at_home_tpu.client.rpc import client_loop, pool_registry

        alive_all: dict = {}
        for layer in range(n_layers):
            alive_all.update(
                client_dht._loop.run(client_dht._get_alive(f"ffn{layer}"))
            )
        endpoints = {tuple(ep) for ep in alive_all.values()}
        registry = pool_registry()

        async def gather():
            async def one(ep):
                _, meta = await registry.get(ep).rpc("stats", (), {},
                                                     timeout=5.0)
                return meta

            return await asyncio.gather(
                *(one(ep) for ep in endpoints), return_exceptions=True
            )

        merged = {"update_count_total": 0, "update_count": {}}
        for meta in client_loop().run(gather()):
            if isinstance(meta, dict):
                merged["update_count_total"] += int(
                    meta.get("update_count_total", 0)
                )
                merged["update_count"].update(meta.get("update_count", {}))
        return merged
    except Exception:
        return None


def run_swarm(args):
    import signal

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    # The trainer's trunk runs on JAX's default backend — the chip, where
    # there is one: the remote dispatch's io_callback-under-custom_vjp
    # runs from a TPU-resident jit (probed on a v5e, tools/chip_probe.py
    # client).  This process then holds that chip; servers it spawns
    # (--subprocess-servers) are CPU processes, see _spawn_servers.
    device = jax.devices()[0]
    print(f"# swarm trainer on {device.platform} [{device.device_kind}]",
          flush=True)

    # SIGTERM (e.g. `timeout`) must run the finally-block below, or the
    # spawned server subprocesses outlive us and eat the host's cores
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    from learning_at_home_tpu.client import reset_client_rpc
    from learning_at_home_tpu.dht import DHT
    from learning_at_home_tpu.models import make_expert
    from learning_at_home_tpu.models.data import VOCAB_SIZE, LMBatcher, load_corpus
    from learning_at_home_tpu.models.transformer_swarm import (
        SwarmDMoETransformerLM,
        SwarmTransformerConfig,
    )
    from learning_at_home_tpu.server import ExpertBackend, Server

    if args.pipeline > 1 and not args.subprocess_servers:
        print(
            "# WARNING: --pipeline > 1 with in-process servers is unreliable:"
            " each in-flight step parks a blocking host callback on an XLA"
            " CPU execution slot the co-hosted servers need, which can"
            " starve backward RPCs into total-failure timeouts. Use"
            " --subprocess-servers (the production topology).",
            flush=True,
        )
    # grid: experts_per_layer experts in one dimension per layer; experts
    # strided across servers
    grid = (args.experts_per_layer,)
    if args.initial_peers:
        # pure-trainer mode: join an existing swarm (the reference's
        # many-trainer topology — servers are someone else's processes)
        peers = [
            (host, int(port))
            for host, port in
            (e.rsplit(":", 1) for e in args.initial_peers.split(","))
        ]
        bootstrap = None
        servers, dhts, procs = [], [], []
    elif args.subprocess_servers:
        bootstrap = DHT()
        peers = [bootstrap.endpoint]
        servers, dhts = [], [bootstrap]
        procs = _spawn_servers(args, bootstrap.endpoint)
    else:
        bootstrap = DHT()
        peers = [bootstrap.endpoint]
        servers, dhts, procs = [], [bootstrap], []
        import zlib

        for s in range(args.n_servers):
            uids = _uids_for_server(args, s)
            if not uids:
                continue
            experts = {}
            for uid in uids:
                # crc32 seeding: deterministic across runs AND identical to
                # the subprocess path (hash() is salted per interpreter)
                key = jax.random.PRNGKey(zlib.crc32(uid.encode()) & 0x7FFFFFFF)
                apply_fn, params = make_expert(
                    "ffn", args.d_model, key, jnp.zeros((2, args.d_model))
                )
                experts[uid] = ExpertBackend(
                    uid, apply_fn, params, optax.adam(args.lr), max_batch_size=4096
                )
            dht = DHT(initial_peers=[bootstrap.endpoint])
            dhts.append(dht)
            server = Server(experts, host="127.0.0.1", dht=dht, update_period=5.0)
            server.run_in_background()
            servers.append(server)
    client_dht = DHT(initial_peers=peers)
    dhts.append(client_dht)

    # wait for all experts to appear in the DHT
    want = args.n_layers * args.experts_per_layer
    found = _wait_for_experts(client_dht, procs, args.n_layers, want)
    print(f"# discovered {found}/{want} experts via DHT", flush=True)

    cfg = SwarmTransformerConfig(
        vocab_size=VOCAB_SIZE,
        d_model=args.d_model,
        n_layers=args.n_layers,
        seq_len=args.seq_len,
        grid_size=grid,
        k_best=args.k,
        wire_dtype=args.wire_dtype,
        wire_codec=args.wire_codec,
        latency_weight=args.latency_weight,
        routing_cost_weight=args.routing_cost_weight,
        telemetry_prefix=args.telemetry_prefix,
    )
    model = SwarmDMoETransformerLM(cfg, client_dht)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    optimizer = optax.adamw(args.lr)
    opt_state = optimizer.init(params)
    if args.overlap or args.overlap_serial:
        # ScMoE shortcut schedule (ISSUE 7/9): fire the expert fan-out,
        # compute attention while the RPCs fly, join late.  The serial
        # arm runs the SAME primitive ops joined immediately — loss
        # curves between the two arms are the bitwise A/B contract the
        # parity smoke asserts (tests/test_experiment_smoke.py)
        step_fn = model.make_overlapped_train_step(
            optimizer, overlap=args.overlap
        )
        print(f"# shortcut schedule: "
              f"{'overlapped' if args.overlap else 'serial'}", flush=True)
    else:
        step_fn = model.make_train_step(optimizer)

    avg_session = None
    if args.averaging:
        from learning_at_home_tpu.averaging import (
            AveragingConfig,
            AveragingSession,
            DecentralizedAverager,
        )

        averager = DecentralizedAverager(
            client_dht,
            config=AveragingConfig(
                prefix="averaging.trunk",
                min_group_size=args.averaging_group_size,
                matchmaking_timeout=args.averaging_timeout,
            ),
        )
        avg_session = AveragingSession(
            averager, every_steps=args.averaging_every
        )
        print(f"# averaging peer {averager.peer_id} on "
              f"{averager.endpoint[0]}:{averager.endpoint[1]}", flush=True)

    telemetry = None
    if not args.no_telemetry:
        # the trainer is a swarm peer too: host a metrics endpoint and
        # heartbeat it under telemetry.<prefix> so lah_top aggregates
        # trainer dispatch/averaging stats next to the servers' (ISSUE 4)
        from learning_at_home_tpu.utils.telemetry import TelemetryPublisher

        def _trainer_extra():
            extra = {
                "dispatch": model.moes[0].dispatch_stats()
                if model.moes else {},
            }
            if avg_session is not None:
                extra["averaging"] = avg_session.averaging_stats()
            return extra

        try:
            telemetry = TelemetryPublisher(
                client_dht, prefix=args.telemetry_prefix, role="trainer",
                host=args.telemetry_host, extra_fn=_trainer_extra,
            ).start()
            print(f"# trainer metrics endpoint http://{telemetry.endpoint[0]}:"
                  f"{telemetry.port}/metrics (telemetry."
                  f"{args.telemetry_prefix})", flush=True)
        except Exception as e:  # telemetry must never kill training
            print(f"# telemetry endpoint failed to start: {e}", flush=True)
            telemetry = None

    # client-side recovery (§5.4): the trainer's trunk+gate params resume
    # from a checkpoint; expert params recover via the SERVER's per-expert
    # checkpoints (server --resume) — two halves of one contract
    ckpt = start_step = None
    if args.checkpoint_dir:
        from learning_at_home_tpu.utils.checkpoint import TrainCheckpointer

        ckpt = TrainCheckpointer(args.checkpoint_dir)
        start_step = 0
        if args.resume:
            restored = ckpt.restore_latest(params, opt_state)
            if restored is not None:
                start_step, params, opt_state = restored
                print(f"# resumed trainer from step {start_step}", flush=True)

    tokens = load_corpus(args.data, seed=args.seed)
    if args.data_shard:
        i, n = (int(x) for x in args.data_shard.split(":"))
        if not 0 <= i < n:
            raise SystemExit(f"--data-shard {args.data_shard}: need 0 <= I < N")
        lo, hi = i * len(tokens) // n, (i + 1) * len(tokens) // n
        tokens = tokens[lo:hi]
        print(f"# data shard {i}:{n} -> tokens [{lo}:{hi})", flush=True)
    batches = LMBatcher(tokens, args.batch_size, args.seq_len, seed=args.seed)
    if start_step:
        batches.skip(start_step)  # continue the data order, no replay

    def dispatch_p50() -> float | None:
        times = list(model.moes[0].dispatch_times)
        return float(np.median(times) * 1000) if times else None

    def backward_rpcs() -> tuple[int, int]:
        """Cumulative (sent, acked) backward RPCs across all MoE layers.
        ``sent`` is the count the servers' summed ``update_count`` is
        bounded by in multi-trainer runs (a cancelled straggler still
        executes server-side, so ``acked`` is NOT an upper bound)."""
        return (
            sum(m.backward_rpcs_sent for m in model.moes),
            sum(m.backward_rpcs_ok for m in model.moes),
        )

    def server_update_total() -> int | None:
        """Total async optimizer steps applied across all experts — the
        evidence the server-side SGD is running.  In-process servers are
        read directly; subprocess/remote servers via ONE server-wide
        ``stats`` RPC per peer, issued concurrently (per-expert queries
        would cost n_experts × RTT every log interval)."""
        if servers:
            return sum(
                b.update_count
                for srv in servers
                for b in srv.experts.values()
            )
        stats = _rpc_server_stats(client_dht, args.n_layers)
        return stats["update_count_total"] if stats else None

    try:
        if args.pipeline > 1:
            from learning_at_home_tpu.client import PipelinedSwarmTrainer

            trainer = PipelinedSwarmTrainer(
                model, optimizer, params, opt_state, n_workers=args.pipeline
            )
            if avg_session is not None:
                # background rounds: snapshot under the apply lock, apply
                # the group delta atomically (delayed-update tolerant)
                trainer.attach_averaging(avg_session)

            def on_log(entry):
                p50 = dispatch_p50()
                entry["dispatch_p50_ms"] = round(p50, 2) if p50 else None
                print(json.dumps(entry), flush=True)
                if (
                    ckpt is not None and args.checkpoint_every
                    and entry["step"] % args.checkpoint_every == 0
                ):
                    # consistent triple under the trainer's apply lock
                    p, o, done = trainer.snapshot()
                    ckpt.save((start_step or 0) + done, p, o)

            arrayified = (
                (jnp.asarray(ids), jnp.asarray(tgt)) for ids, tgt in batches
            )
            summary = trainer.train(
                arrayified, steps=args.steps - (start_step or 0),
                log_every=args.log_every, on_log=on_log,
                tokens_per_batch=args.batch_size * args.seq_len,
            )
            if avg_session is not None:
                # a background round may still be applying its delta to
                # trainer.params; read params only once it settled, or
                # the final blocking round would feed (and the
                # checkpoint would keep) the stale pre-delta copy
                avg_session.wait_idle()
            params, opt_state = trainer.params, trainer.opt_state
            p50 = dispatch_p50()
            sent, acked = backward_rpcs()
            summary_json = {
                "pipeline": args.pipeline,
                "tokens_per_sec": round(summary["tokens_per_sec"], 1),
                "final_loss": round(summary["final_loss"], 4),
                "dispatch_p50_ms": round(p50, 2) if p50 is not None else None,
                "server_updates": server_update_total(),
                "backward_rpcs_sent": sent,
                "backward_rpcs_ok": acked,
            }
            if avg_session is not None:
                summary_json["averaging"] = trainer.averaging_stats()
            print(json.dumps(summary_json), flush=True)
        else:
            t0 = time.perf_counter()
            for step, (ids, tgt) in zip(
                range(start_step or 0, args.steps), batches
            ):
                params, opt_state, loss = step_fn(
                    params, opt_state, jnp.asarray(ids), jnp.asarray(tgt)
                )
                if (
                    avg_session is not None
                    and (step + 1) % args.averaging_every == 0
                    and step + 1 < args.steps  # the final round follows
                ):
                    # BLOCKING round between steps: all co-scheduled
                    # sequential trainers rendezvous at the same step
                    # index and leave with the group mean (or skip when
                    # no group forms — a lone trainer keeps training)
                    params = avg_session.blocking_round(params)
                if (
                    ckpt is not None and args.checkpoint_every
                    and (step + 1) % args.checkpoint_every == 0
                ):
                    ckpt.save(step + 1, params, opt_state)
                if step % args.log_every == 0 or step == args.steps - 1:
                    elapsed = time.perf_counter() - t0
                    tps = (
                        (step + 1 - (start_step or 0))
                        * args.batch_size * args.seq_len / elapsed
                    )
                    p50 = dispatch_p50()
                    sent, acked = backward_rpcs()
                    print(
                        json.dumps(
                            {
                                "step": step,
                                "loss": round(float(loss), 4),
                                "tokens_per_sec": round(tps, 1),
                                "dispatch_p50_ms": round(p50, 2) if p50 else None,
                                "server_updates": server_update_total(),
                                "backward_rpcs_sent": sent,
                                "backward_rpcs_ok": acked,
                            }
                        ),
                        flush=True,
                    )
        if avg_session is not None:
            # final blocking round: co-scheduled trainers rendezvous once
            # more after their last step, so every participant ends with
            # IDENTICAL trunk+gate parameters (the convergence contract
            # tests/test_experiment_smoke.py asserts)
            avg_session.wait_idle()
            params = avg_session.blocking_round(
                params, matchmaking_timeout=args.averaging_timeout * 2
            )
            print(json.dumps(
                {"averaging": avg_session.averaging_stats()}
            ), flush=True)
            if args.checkpoint_dir:
                os.makedirs(args.checkpoint_dir, exist_ok=True)
                np.savez(
                    os.path.join(args.checkpoint_dir,
                                 "avg_final_params.npz"),
                    **{
                        f"p{i}": np.asarray(leaf)
                        for i, leaf in enumerate(jax.tree.leaves(params))
                    },
                )
        if ckpt is not None:
            ckpt.save(args.steps, params, opt_state)
            print(f"# checkpointed trainer at step {args.steps}", flush=True)
    finally:
        if telemetry is not None:
            telemetry.stop()
        if avg_session is not None:
            avg_session.shutdown()
        for server in servers:
            server.shutdown()
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except Exception:
                proc.kill()
                proc.wait(timeout=10)  # reap; no zombies
        for dht in dhts:
            dht.shutdown()
        reset_client_rpc()


def run_multi_trainer(args):
    """The reference's concurrent async-DP deployment (SURVEY §2.2 DP:
    "many independent trainers" sharing one expert pool): spawn the expert
    servers ONCE, then ``--n-trainers`` fully independent trainer
    processes — each with its own trunk+gate parameters, its own optimizer,
    and a disjoint contiguous shard of the corpus — all pushing forward and
    backward batches through the same experts, whose server-side optimizer
    steps interleave both trainers' gradients with no coordination (true
    write contention).

    Emits one summary JSON with per-trainer loss curves and the
    client-vs-server ledger: ``server_updates_total`` must not exceed
    ``backward_rpcs_ok_total`` (a task pool may merge concurrent trainers'
    rows into one padded batch = one optimizer step), and with both
    trainers making progress it must exceed what either trainer alone
    acked."""
    import signal
    import subprocess
    import threading

    import jax

    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    # host-only by design: the orchestrator polls the DHT and computes
    # nothing, so it must never take a chip
    jax.config.update("jax_platforms", "cpu")
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    from learning_at_home_tpu.client import reset_client_rpc
    from learning_at_home_tpu.dht import DHT

    if args.initial_peers:
        raise SystemExit("--n-trainers spawns its own swarm; "
                         "drop --initial-peers")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # --n-trainers processes on one host: CPU trainers, like their servers
    env = clean_jax_subprocess_env(repo, platform="cpu")
    bootstrap = DHT()
    procs = _spawn_servers(args, bootstrap.endpoint)
    client_dht = DHT(initial_peers=[bootstrap.endpoint])
    trainers: list[subprocess.Popen] = []
    logs: list[list[dict]] = [[] for _ in range(args.n_trainers)]
    try:
        # all experts discoverable BEFORE any trainer starts (children also
        # wait, but a shared healthy start keeps their clocks comparable)
        want = args.n_layers * args.experts_per_layer
        found = _wait_for_experts(client_dht, procs, args.n_layers, want)
        print(f"# orchestrator: {found}/{want} experts alive", flush=True)

        peers_arg = f"{bootstrap.endpoint[0]}:{bootstrap.endpoint[1]}"
        base = [
            sys.executable, os.path.abspath(__file__), "--mode", "swarm",
            "--initial-peers", peers_arg,
            "--steps", str(args.steps),
            "--batch-size", str(args.batch_size),
            "--seq-len", str(args.seq_len),
            "--d-model", str(args.d_model),
            "--n-layers", str(args.n_layers),
            "--experts-per-layer", str(args.experts_per_layer),
            "--n-servers", str(args.n_servers),
            "--k", str(args.k),
            "--lr", str(args.lr),
            "--log-every", str(args.log_every),
            "--pipeline", str(args.pipeline),
        ]
        if args.data:
            base += ["--data", args.data]
        if args.overlap:
            base += ["--overlap"]
        if args.overlap_serial:
            base += ["--overlap-serial"]
        if args.averaging:
            base += [
                "--averaging",
                "--averaging-every", str(args.averaging_every),
                "--averaging-group-size", str(args.averaging_group_size),
                "--averaging-timeout", str(args.averaging_timeout),
            ]
        if args.wire_dtype:
            base += ["--wire-dtype", args.wire_dtype]
        if args.wire_codec:
            base += ["--wire-codec", args.wire_codec]
        if args.latency_weight:
            base += ["--latency-weight", str(args.latency_weight)]
        if args.routing_cost_weight is not None:
            base += ["--routing-cost-weight", str(args.routing_cost_weight)]
        if args.checkpoint_every:
            base += ["--checkpoint-every", str(args.checkpoint_every)]
        for t in range(args.n_trainers):
            cmd = base + [
                "--seed", str(args.seed + t),
                "--data-shard", f"{t}:{args.n_trainers}",
            ]
            if args.checkpoint_dir:
                # each trainer owns its trunk/gate state: per-trainer dirs
                cmd += ["--checkpoint-dir",
                        os.path.join(args.checkpoint_dir, f"t{t}")]
                if args.resume:
                    cmd += ["--resume"]
            trainers.append(subprocess.Popen(
                cmd, env=env, text=True,
                stdout=subprocess.PIPE, stderr=sys.stderr,
            ))

        def pump(t: int, proc: subprocess.Popen) -> None:
            for line in proc.stdout:
                line = line.rstrip("\n")
                print(f"[t{t}] {line}", flush=True)
                if line.startswith("{"):
                    try:
                        logs[t].append(json.loads(line))
                    except json.JSONDecodeError:
                        pass

        pumps = [
            threading.Thread(target=pump, args=(t, p), daemon=True)
            for t, p in enumerate(trainers)
        ]
        for th in pumps:
            th.start()
        rcs = [p.wait() for p in trainers]
        for th in pumps:
            th.join(timeout=10)
        if any(rc != 0 for rc in rcs):
            raise SystemExit(f"trainer exit codes {rcs}")

        stats = _rpc_server_stats(client_dht, args.n_layers)
        per_trainer = []
        for t, entries in enumerate(logs):
            losses = [e["loss"] for e in entries if "loss" in e]

            def last(key: str) -> int:
                return max(
                    (e[key] for e in entries if e.get(key) is not None),
                    default=0,
                )

            avg_stats = [e["averaging"] for e in entries if "averaging" in e]
            per_trainer.append({
                "trainer": t,
                "first_loss": losses[0] if losses else None,
                "final_loss": losses[-1] if losses else None,
                "backward_rpcs_sent": last("backward_rpcs_sent"),
                "backward_rpcs_ok": last("backward_rpcs_ok"),
                "averaging_rounds": (
                    avg_stats[-1]["rounds"] if avg_stats else None
                ),
                "averaging_degraded_rounds": (
                    avg_stats[-1]["degraded_rounds"] if avg_stats else None
                ),
            })
        sent_total = sum(t["backward_rpcs_sent"] for t in per_trainer)
        ok_total = sum(t["backward_rpcs_ok"] for t in per_trainer)
        counts = list((stats or {}).get("update_count", {}).values())
        print(json.dumps({
            "n_trainers": args.n_trainers,
            "trainers": per_trainer,
            "backward_rpcs_sent_total": sent_total,
            "backward_rpcs_ok_total": ok_total,
            "server_updates_total":
                stats["update_count_total"] if stats else None,
            "experts_updated": sum(1 for c in counts if c > 0),
            "n_experts": len(counts),
        }), flush=True)
    finally:
        for proc in trainers:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            proc.terminate()
        for proc in [*trainers, *procs]:
            try:
                proc.wait(timeout=30)
            except Exception:
                proc.kill()
                proc.wait(timeout=10)  # reap; no zombies
        client_dht.shutdown()
        bootstrap.shutdown()
        reset_client_rpc()


def main():
    args = parse_args()
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()
    if args.mode == "pod":
        run_pod(args)
    elif args.n_trainers > 1:
        run_multi_trainer(args)
    else:
        run_swarm(args)


if __name__ == "__main__":
    main()
