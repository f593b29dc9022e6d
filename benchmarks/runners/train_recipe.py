"""Runner ``train_recipe``: a closed loop of the pod train step of a named
one-chip recipe of the program (``"recipe"`` in the configuration file, a
function of ``__graft_entry__`` that returns ``(model, cfg, optimizer,
batch)``), compared outside the window with the configuration's plain
reference.

It is ``train_step``'s run for another recipe: that module's layout check
is called as it is (loaded through ``harness``), and the window keeps its
rules: one step enqueued ahead of the one waited for, the host clock read
when a step's loss is ready, a rate between completions, no program
handed to the backend inside the window.  What differs: the configuration
file restates the sizes under the source's own key names; ``token_ids``
may be ``zipf``; every step's ``dropped_fraction`` must be 0 where the
configuration says nothing is dropped; the reference comparison; and, in
a traced run, the device time by scope of the program.

Reference comparison (published widths, one seeded sequence of ``seq_len``
tokens, the trained-for-a-window parameters): the program's ``apply`` and
``loss_fn`` against ``configs/<reference>`` on the float32 cast of the
same weights, a layer at a time.  Logits are compared by the rms of the
difference and by the 99.9th percentile of its absolute value, both over
the rms of the reference's logits; the loss relatively.  The limits and
their reasons are ``TOLERANCES`` below.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

import harness
from harness import BenchError

# sizes the configuration file restates (the source's key) -> the program's
# config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "seq_len": "seq_len", "num_experts": "num_experts",
    "num_experts_per_tok": "k", "intermediate_size": "expert_ffn_dim",
    "norm_topk_prob": "renormalize", "tie_word_embeddings": "tie_embeddings",
    "norm": "norm", "positions": "positions", "qk_norm": "qk_norm",
    "expert_kind": "expert_kind", "routing": "routing",
    "aux_loss_weight": "aux_loss_weight", "router_z_weight": "router_z_weight",
    "remat": "remat", "scan_layers": "scan_layers",
    "stack_layers": "stack_layers",
}
WARMUP_STEPS_MAX = 6

# The program computes in bf16 (activations and matmul operands rounded to
# 8 bits of mantissa, one MXU pass, float32 accumulation); the reference in
# float32 at "highest".  Over four layers and the head that is a relative
# rms of about 1e-2 of the logits.  A maximum is no measure here: where a
# token's 8th and 9th largest gates are nearly equal the two sides choose
# different experts and that token's logits differ by a whole expert's
# output, so the tail is taken at the 99.9th percentile.  The limits are
# set between two readings (PERF.md, PR 27): the largest the program gave
# over its seeds on the chip, and the reference itself with every matmul
# operand rounded to float8_e4m3 (the nearest precision below bf16), which
# must fail.
TOLERANCES = {"logits_rms": 2e-2, "logits_p999": 1.5e-1, "loss": 1e-3}


def zipf_batches(rng, vocab: int, rows: int, seq_len: int, count: int,
                 exponent: float = 1.0) -> list:
    """``count`` pairs (inputs, targets) of [rows, seq_len] int32: each row
    is seq_len + 1 independent draws from a Zipf law over ``vocab`` ids,
    rank mapped to id by a permutation drawn from ``rng``; inputs are the
    first seq_len, targets the last (next token).  No packing, no document
    boundaries."""
    import numpy as np

    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    p /= p.sum()
    id_of_rank = rng.permutation(vocab).astype(np.int32)
    out = []
    for _ in range(count):
        ranks = rng.choice(vocab, size=(rows, seq_len + 1), p=p)
        ids = id_of_rank[ranks]
        out.append((ids[:, :-1], ids[:, 1:]))
    return out


def uniform_batches(rng, vocab: int, rows: int, seq_len: int,
                    count: int) -> list:
    import numpy as np

    return [
        tuple(rng.integers(0, vocab, (rows, seq_len), dtype=np.int32)
              for _ in range(2))
        for _ in range(count)
    ]


def _check_sizes(config: dict, cfg) -> None:
    import jax.numpy as jnp

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    wrong = {k: (config.get(k), v) for k, v in got.items()
             if config.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None) -> dict:
    """The program against the reference on ``ids`` [1, S]: relative rms
    and 99.9th percentile of the logits' difference, relative difference of
    the loss.  With ``operand_dtype`` the REFERENCE at that precision takes
    the program's place (what a too-low precision would read)."""
    import jax
    import numpy as np

    sizes = dict(  # the reference is given the FILE's sizes, not the program's
        n_heads=config["num_attention_heads"],
        experts_per_token=config["num_experts_per_tok"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
    )

    def reference_run(operand_dtype):
        layer = jax.jit(
            lambda lp, x: reference.layer(lp, x, sizes, operand_dtype)
        )
        x = reference.embed(params, ids)
        aux = z = 0.0
        for lp in params["layers"]:  # a layer at a time: one float32 cast live
            x, a, zz = layer(lp, x)
            aux, z = aux + a, z + zz
        logits = jax.jit(
            lambda p, x: reference.head(p, x, sizes, operand_dtype)
        )({"ln_f": params["ln_f"], "lm_head": params["lm_head"]}, x)
        n = len(params["layers"])
        loss = (reference.ce_of_logits(logits, targets)
                + sizes["aux_loss_weight"] * aux / n
                + sizes["router_z_weight"] * z / n)
        return np.asarray(logits, np.float32), float(loss)

    want, want_loss = reference_run(None)
    if operand_dtype is None:
        logits, loss = jax.jit(
            lambda p, i, t: (model.apply(p, i)[0], model.loss_fn(p, i, t)[0])
        )(params, ids, targets)
        got, got_loss = np.asarray(logits, np.float32), float(loss)
    else:
        got, got_loss = reference_run(operand_dtype)
    return readings(got, got_loss, want, want_loss)


def readings(got, got_loss: float, want, want_loss: float) -> dict:
    """Logits [.., V] and a loss against the reference's: rms and 99.9th
    percentile of the absolute difference over the rms of the reference's
    logits, and the loss's relative difference."""
    import numpy as np

    want = np.asarray(want, np.float32)
    diff = np.abs(np.asarray(got, np.float32) - want).ravel()
    scale = float(np.sqrt(np.mean(np.square(want, dtype=np.float64))))
    return {
        "logits_rms": float(np.sqrt(np.mean(np.square(diff, dtype=np.float64)))) / scale,
        "logits_p999": float(np.quantile(diff, 0.999)) / scale,
        "loss": abs(float(got_loss) - want_loss) / abs(want_loss),
        "reference_loss": want_loss,
        "reference_logits_rms": scale,
    }


def over_tolerance(read: dict) -> list:
    return [f"{k} {read[k]:.3e} > {limit:g}"
            for k, limit in TOLERANCES.items() if not read[k] <= limit]


OP_NAME = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\""
)
# scope of an operation, from the named_scope path in its op_name
# (docs/OBSERVABILITY.md "Span names"): a path component, or the argument of
# the jvp(..) / transpose(jvp(..)) that differentiation wraps around a
# top-level one; first match wins
SCOPES = tuple(
    (name, re.compile(r"[/(]%s[/)]" % name))
    for name in ("experts", "router", "moe_sort", "moe_combine", "attention",
                 "ce", "optimizer", "embed")
)
# XLA's grouped matmul on the TPU is a kernel of its own whose call loses
# the scope path (op_name "ragged-dot-none"); the instruction keeps the
# name.  Every grouped matmul of this program is the expert layer's.
GROUPED_MATMUL = "ragged-dot"
GROUPED_MATMUL_LAYOUT = "ragged-dot-metadata"  # lays out the groups: no matmul


def scope_times(ops: list, hlo_text: str) -> dict:
    """Device self time of one device's traced operations (``(name, start,
    end)``) by scope of the program: each is an instruction of the compiled
    step, whose ``op_name`` metadata carries the scope path.  ``{"by_scope":
    {scope: s}, "total_s", "grouped_matmul_s", "grouped_matmul_calls"}``;
    what carries no path (copies, the step's own plumbing) is ``other``."""
    import trace_reduce

    op_name = {}
    for line in hlo_text.splitlines():
        m = OP_NAME.match(line)
        if m:
            op_name.setdefault(m.group(1), m.group(2))

    def is_matmul(name: str) -> bool:
        return name.startswith(GROUPED_MATMUL) and not name.startswith(
            GROUPED_MATMUL_LAYOUT)

    self_ns = trace_reduce.self_times(ops)
    by_scope: dict = {}
    for name, ns in self_ns.items():
        scope = "other"
        if name.startswith(GROUPED_MATMUL):
            scope = "experts"
        else:
            path = "/" + op_name.get(name, "") + "/"
            for candidate, pattern in SCOPES:
                if pattern.search(path):
                    scope = candidate
                    break
        by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
    return {
        "by_scope": by_scope,
        "total_s": sum(by_scope.values()),
        "grouped_matmul_s": sum(
            ns for name, ns in self_ns.items() if is_matmul(name)) / 1e9,
        "grouped_matmul_calls": sum(1 for name, _, _ in ops if is_matmul(name)),
    }


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__ as entry
    from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh

    recipe = getattr(entry, config["recipe"], None)
    if recipe is None:  # a program from before the recipe: no result
        raise BenchError(
            f"the program has no recipe {config['recipe']!r} in "
            "__graft_entry__"
        )
    manifest = harness.load_manifest(args.manifest)
    train_step = harness.load_module(manifest, "runners", "train_step")
    reference = harness.load_path(os.path.join(harness.ROOT, config["reference"]))

    clock.mark("import")
    counter = harness.CompileCounter()
    devices = harness.require_devices(config["platform"], cell["chips"])
    clock.mark("device_init")

    mesh_axes = traffic["mesh"]
    if int(np.prod(list(mesh_axes.values()))) != cell["chips"]:
        raise BenchError(f"mesh {mesh_axes} is not {cell['chips']} chip(s)")
    mesh = make_mesh(mesh_axes, devices=devices)
    tiny = bool(config.get("tiny"))
    model, cfg, optimizer, recipe_batch = recipe(mesh, tiny=tiny)
    _check_sizes(config, cfg)
    rows = traffic["rows_per_chip"] * cell["chips"]
    if not tiny and traffic["rows_per_chip"] != recipe_batch:
        raise BenchError(
            f"traffic gives a chip {traffic['rows_per_chip']} rows, the "
            f"recipe {recipe_batch}"
        )
    tokens_per_step = rows * cfg.seq_len

    words = harness.seed_words(args.seed, 4)
    key = jnp.asarray(words[:2], jnp.uint32)
    params = model.init_params(key)
    opt_state = model.init_opt_state(optimizer, params)
    jax.block_until_ready((params, opt_state))
    clock.mark("param_init")
    layout = train_step._check_layout(model, params, opt_state, optimizer, mesh)

    rng = np.random.default_rng(words[2:])
    if traffic["token_ids"] == "zipf":
        batches = zipf_batches(rng, cfg.vocab_size, rows, cfg.seq_len,
                               traffic["pool_batches"],
                               traffic.get("zipf_exponent", 1.0))
    elif traffic["token_ids"] == "uniform":
        batches = uniform_batches(rng, cfg.vocab_size, rows, cfg.seq_len,
                                  traffic["pool_batches"])
    else:
        raise BenchError(
            f"token_ids {traffic['token_ids']!r}: 'uniform' or 'zipf'"
        )
    sharding = batch_sharding(mesh)
    pool = [tuple(jax.device_put(a, sharding) for a in pair)
            for pair in batches]
    jax.block_until_ready(pool)
    clock.mark("batch_pool")

    step = model.make_train_step(optimizer)
    before = counter.programs
    ids, tgt = pool[0]
    params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
    loss_before = float(loss)  # on pool[0], before any update
    clock.mark("compile_or_cache_load")
    warm_steps = 1
    while True:  # until a step hands nothing to the backend
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
    step_counters = [k for k in ("dropped_fraction",
                                 "expert_load_max_over_mean") if k in metrics]
    completions: list[float] = []
    done_steps: list = []
    tracing = traced = False
    trace_t0 = trace_t1 = None
    programs_at_start = counter.programs
    with harness.quiet_gc():
        t0 = time.perf_counter()
        t_end = t0 + args.seconds
        i = warm_steps
        ids, tgt = pool[i % len(pool)]
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        pending = [(loss, [metrics[k] for k in step_counters])]
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
                pending.append((loss, [metrics[k] for k in step_counters]))
            with annotate("between_steps"):
                done = pending.pop(0)
                jax.block_until_ready(done[0])
                completions.append(time.perf_counter())
                done_steps.append(done)
            if tracing and completions[-1] - trace_t0 >= harness.TRACE_SECONDS:
                jax.profiler.stop_trace()
                tracing, traced = False, True
                trace_t1 = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            trace_t1 = time.perf_counter()
    compiled_in_window = counter.programs - programs_at_start
    memory_peak = [  # the training's, before the comparison allocates
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]

    # ---- outside the window: correctness --------------------------------
    inside = [t for t in completions if t <= t_end]
    losses = [float(d[0]) for d in done_steps]
    counters = {k: [float(d[1][j]) for d in done_steps]
                for j, k in enumerate(step_counters)}
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
    if config.get("routing") == "dropless" and any(
        x != 0.0 for x in counters.get("dropped_fraction", [1.0])
    ):
        problems.append("dropless routing reported a dropped assignment")
    if len(inside) < 2:
        raise BenchError(f"{len(inside)} step(s) completed in the window")

    one_row = batches[0][0][:1], batches[0][1][:1]
    read = compare_with_reference(
        model, params, reference, config, jnp.asarray(one_row[0]),
        jnp.asarray(one_row[1]),
    )
    problems += [f"reference: {p}" for p in over_tolerance(read)]
    print("REFERENCE " + json.dumps({**read, "limits": TOLERANCES}),
          flush=True)

    step_s = harness.intervals(inside)
    rate = harness.rate_between_completions(inside, tokens_per_step)
    print("INTERVALS " + json.dumps({
        "what": "seconds between step completions",
        **harness.five_numbers(step_s),
    }), flush=True)
    print("COUNTERS " + json.dumps({
        k: {"first": v[0], "last": v[-1], **harness.five_numbers(v)}
        for k, v in counters.items()
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
        **counters,
        "tokens_per_step_per_chip": tokens_per_step // cell["chips"],
        "chips": cell["chips"],
        "device_kind": devices[0].device_kind,
        "sizes": config,
        "memory_peak_bytes": memory_peak,
    }
    if trace_dir is not None:
        import trace_reduce

        try:
            events = trace_reduce.load_events(
                trace_reduce.find_xplane(trace_dir),
                host_spans=("step", "between_steps"),
            )
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        observations["trace"] = trace_reduce.reduce_events(events)
        observations["trace"]["window_s"] = trace_t1 - trace_t0
        used = [d for _, d in sorted(events["devices"].items()) if d["ops"]]
        if used:  # the compiled step's text names every traced operation
            hlo = step.lower(params, opt_state, ids, tgt).compile().as_text()
            observations["scopes"] = scope_times(used[0]["ops"], hlo)
            print("SCOPES " + json.dumps(observations["scopes"]), flush=True)
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
