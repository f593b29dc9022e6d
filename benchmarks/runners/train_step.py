"""Runner ``train_step``: a closed loop of the pod train step on a mesh.

The model, its optimizer and its batch are the program's own one-chip
recipe (``__graft_entry__.flagship_one_chip``); the configuration file
restates the sizes and the run fails where they differ.  The traffic file
gives the mesh, the rows a chip carries in a step and the pool of batches.

What a run does, in order: parameters by the program's own ``init_params``
from the seed; optimizer state; the layout check; a pool of batches on the
device; warm-up steps until a step hands no program to the backend; then the window, in
which the loop keeps one step enqueued ahead of the one it waits for, and
reads the host clock each time a step's loss is ready.
"""

from __future__ import annotations

import json
import shutil
import statistics
import sys
import tempfile
import time

import harness
from harness import BenchError

# sizes the configuration file restates -> the program's config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "d_model": "d_model", "n_layers": "n_layers",
    "n_heads": "n_heads", "seq_len": "seq_len", "num_experts": "num_experts",
    "experts_per_token": "k", "capacity_factor": "capacity_factor",
    "remat": "remat", "scan_layers": "scan_layers",
    "stack_layers": "stack_layers", "tie_embeddings": "tie_embeddings",
}
WARMUP_STEPS_MAX = 6


def _check_sizes(config: dict, cfg, model, mesh) -> None:
    import jax.numpy as jnp

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    if "num_experts_times" in config:
        # the recipe's tiny preset holds two experts a shard of this axis
        got["num_experts"] //= mesh.shape[config["num_experts_times"]]
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    got["ffn_mult"] = model.moe.ffn_dim // cfg.d_model
    wrong = {k: (config.get(k), v) for k, v in got.items()
             if config.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def _check_layout(model, params, opt_state, optimizer, mesh) -> dict:
    """chip_smoke._check_layout: every expert-stack leaf is split over the
    expert axis into total/ep-byte shards on distinct devices, trunk
    leaves are whole on every device, and the optimizer state lies where
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
            raise BenchError(f"{name}: {len(shards)} shards, {n_dev} devices")
        if not leaf.sharding.is_equivalent_to(spec, leaf.ndim):
            raise BenchError(f"{name}: laid out as {leaf.sharding}, not {spec}")
        sharded = "expert" in jax.tree_util.tree_leaves(tuple(spec.spec))
        want = leaf.nbytes // ep if sharded else leaf.nbytes
        if any(s.data.nbytes != want for s in shards):
            raise BenchError(
                f"{name}: shard bytes {[s.data.nbytes for s in shards]}, "
                f"expected {want} each"
            )
        if sharded:
            expert_bytes += leaf.nbytes
    if not expert_bytes:
        raise BenchError("no expert-sharded parameter found")
    want_opt = opt_state_shardings(
        jax.eval_shape(optimizer.init, params), shardings, params, mesh
    )
    for (path, leaf), spec in zip(
        jax.tree_util.tree_flatten_with_path(opt_state)[0],
        jax.tree_util.tree_leaves(want_opt),
    ):
        if not leaf.sharding.is_equivalent_to(spec, leaf.ndim):
            raise BenchError(
                f"opt_state{jax.tree_util.keystr(path)}: laid out as "
                f"{leaf.sharding}, not {spec}"
            )
    return {"expert_param_bytes": expert_bytes,
            "expert_param_bytes_per_device": expert_bytes // ep}


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import flagship_one_chip
    from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh

    clock.mark("import")
    counter = harness.CompileCounter()
    devices = harness.require_devices(config["platform"], cell["chips"])
    clock.mark("device_init")

    mesh_axes = traffic["mesh"]
    if int(np.prod(list(mesh_axes.values()))) != cell["chips"]:
        raise BenchError(f"mesh {mesh_axes} is not {cell['chips']} chip(s)")
    mesh = make_mesh(mesh_axes, devices=devices)
    tiny = config.get("recipe") == "tiny"
    model, cfg, optimizer, recipe_batch = flagship_one_chip(mesh, tiny=tiny)
    _check_sizes(config, cfg, model, mesh)
    rows = traffic["rows_per_chip"] * cell["chips"]
    if not tiny and traffic["rows_per_chip"] != recipe_batch:
        raise BenchError(
            f"traffic gives a chip {traffic['rows_per_chip']} rows, the "
            f"recipe {recipe_batch}"
        )
    tokens_per_step = rows * cfg.seq_len

    # parameters as the program's users make them (experiments/train_lm.py):
    # ``init_params`` called eagerly, leaf by leaf.  Wrapped in ``jax.jit``
    # with ``out_shardings`` it takes 24 s cold where this takes 44 s, and
    # no longer builds every leaf on device 0 (PERF.md, PR 24): the
    # program's to adopt, since its peak memory is part of what is measured
    words = harness.seed_words(args.seed, 4)
    key = jnp.asarray(words[:2], jnp.uint32)
    params = model.init_params(key)
    opt_state = model.init_opt_state(optimizer, params)
    jax.block_until_ready((params, opt_state))
    clock.mark("param_init")
    layout = _check_layout(model, params, opt_state, optimizer, mesh)

    rng = np.random.default_rng(words[2:])
    if traffic["token_ids"] != "uniform":
        raise BenchError(f"token_ids {traffic['token_ids']!r}: only 'uniform'")
    sharding = batch_sharding(mesh)
    pool = [
        tuple(
            jax.device_put(
                rng.integers(0, cfg.vocab_size, (rows, cfg.seq_len),
                             dtype=np.int32),
                sharding,
            )
            for _ in range(2)
        )
        for _ in range(traffic["pool_batches"])
    ]
    jax.block_until_ready(pool)
    clock.mark("batch_pool")

    step = model.make_train_step(optimizer)
    before = counter.programs
    ids, tgt = pool[0]
    params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
    loss_before = float(loss)  # on pool[0], before any update
    clock.mark("compile_or_cache_load")
    # until a step hands nothing to the backend (the second call retraces:
    # its inputs come back committed to their shardings)
    warm_steps = 1
    while True:
        seen = counter.programs
        ids, tgt = pool[warm_steps % len(pool)]
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        jax.block_until_ready(loss)
        warm_steps += 1
        if counter.programs == seen:
            break
        if warm_steps >= WARMUP_STEPS_MAX:
            raise BenchError(
                f"the train step still compiles after {warm_steps} calls"
            )
    step_programs = counter.programs - before
    clock.mark("warmup_steps")
    setup = counter.snapshot()
    setup_s = clock.total()

    # ---- the window -----------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    annotate = jax.profiler.TraceAnnotation
    completions: list[float] = []
    losses, dropped = [], []
    tracing = traced = False
    trace_t0 = trace_t1 = None
    programs_at_start = counter.programs
    with harness.quiet_gc():
        t0 = time.perf_counter()
        t_end = t0 + args.seconds
        i = warm_steps
        ids, tgt = pool[i % len(pool)]
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        pending = [(loss, metrics["dropped_fraction"])]
        while pending:
            now = time.perf_counter()
            if args.trace and not traced and not tracing and (
                now - t0 >= harness.TRACE_START_S
            ):
                harness.start_trace(trace_dir)
                tracing, trace_t0 = True, time.perf_counter()
            if now < t_end:  # one step ahead of the one waited for
                i += 1
                ids, tgt = pool[i % len(pool)]
                with annotate("step"):
                    params, opt_state, loss, metrics = step(
                        params, opt_state, ids, tgt
                    )
                pending.append((loss, metrics["dropped_fraction"]))
            with annotate("between_steps"):
                done = pending.pop(0)
                jax.block_until_ready(done[0])
                completions.append(time.perf_counter())
                losses.append(done[0])
                dropped.append(done[1])
            if tracing and completions[-1] - trace_t0 >= harness.TRACE_SECONDS:
                jax.profiler.stop_trace()
                tracing, traced = False, True
                trace_t1 = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            trace_t1 = time.perf_counter()
    compiled_in_window = counter.programs - programs_at_start

    # ---- outside the window: correctness --------------------------------
    inside = [t for t in completions if t <= t_end]
    losses = [float(x) for x in losses]
    dropped = [float(x) for x in dropped]
    ids, tgt = pool[0]
    params, opt_state, loss, _ = step(params, opt_state, ids, tgt)
    loss_after = float(loss)  # on pool[0] again, before this step's update
    failed = sum(1 for x in losses if not np.isfinite(x))
    problems = []
    if failed:
        problems.append(f"{failed} non-finite losses")
    if not loss_after < loss_before:
        problems.append(
            f"loss on the first pool batch did not fall: {loss_before} -> "
            f"{loss_after}"
        )
    if compiled_in_window:
        problems.append(f"{compiled_in_window} program(s) compiled in the window")
    if len(inside) < 2:
        raise BenchError(f"{len(inside)} step(s) completed in the window")

    step_s = harness.intervals(inside)
    rate = harness.rate_between_completions(inside, tokens_per_step)
    print("INTERVALS " + json.dumps({
        "what": "seconds between step completions",
        **harness.five_numbers(step_s),
    }), flush=True)
    print("SETUP " + json.dumps({
        "setup_s": setup_s, "phases": clock.phases, **setup,
        "step_programs": step_programs, "warmup_steps": warm_steps,
        "compile_cache_dir": cache_dir, **layout,
    }), flush=True)
    if problems:
        print("INCORRECT " + "; ".join(problems), file=sys.stderr, flush=True)

    observations = {
        "intervals_s": step_s,
        # from the median interval, not the window's rate: in a traced run
        # the profiler's start stalls the loop for seconds
        "tokens_per_s_per_chip": (
            tokens_per_step / statistics.median(step_s) / cell["chips"]
        ),
        "dropped_fraction": dropped,
        "chips": cell["chips"],
        "device_kind": devices[0].device_kind,
        "sizes": config,
        "memory_peak_bytes": [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
        ],
    }
    if trace_dir is not None:
        import trace_reduce

        try:
            observations["trace"] = trace_reduce.reduce_dir(
                trace_dir, host_spans=("step", "between_steps")
            )
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        observations["trace"]["window_s"] = trace_t1 - trace_t0
    return {
        "correct": not problems,
        "attempted": len(losses),
        "failed": failed,
        "end_to_end": {
            "train_tokens_per_s_per_chip": rate / cell["chips"],
            "setup_s": setup_s,
        },
        "observations": observations,
        "devices": devices,
    }
