"""Per-variant step timing + optional device trace for the flagship.

The MFU ladder tool: times the 256-expert flagship train step under
combinations of the model's perf knobs (scan vs unrolled layers, remat
policy, batch), each timed window ended by ``jax.block_until_ready``.
Every variant starts from the one-chip recipe
(``__graft_entry__.flagship_one_chip``: dtypes, remat, batch); the recipe
itself is ``--no-stack --optimizer fused``.

Reuses bench.py's analytic HBM sizing — extended with the extra
activation term of ``remat_policy="dots"`` (saved matmul outputs per
layer) — against the device's own memory limit, and refuses a variant
that does not fit rather than spend the run finding the allocator cliff.
Needs an accelerator: it fails on the CPU.

Usage (on the chip):
    python experiments/profile_step.py --batch 176 --no-scan
    python experiments/profile_step.py --batch 112 --remat-policy dots
    python experiments/profile_step.py --batch 176 --trace /tmp/trace
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def dots_extra_bytes(cfg, batch: int) -> int:
    """Extra live bytes of remat_policy='dots' vs 'full': per-layer saved
    matmul outputs (qkv, attention out, wo out, MoE h/ye, router logits)."""
    import jax.numpy as jnp
    import numpy as np

    s, d, L, E = cfg.seq_len, cfg.d_model, cfg.n_layers, cfg.num_experts
    tokens = batch * s
    cap = int(np.ceil(cfg.capacity_factor * cfg.k * tokens / E))
    act = jnp.dtype(cfg.dtype).itemsize
    per_layer = (
        tokens * d * act * 5  # q, k, v, attn-out, wo-out
        + E * cap * (4 * d) * act  # MoE hidden h [E, C, ffn]
        + E * cap * d * act  # MoE ye
        + tokens * E * 4  # router logits (f32)
    )
    return per_layer * L


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None,
                    help="default: the one-chip recipe's batch")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--no-scan", action="store_true",
                    help="unrolled layer loop (scan_layers=False)")
    ap.add_argument("--no-stack", action="store_true",
                    help="per-layer param tuple (implies --no-scan)")
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--optimizer", default="adafactor",
                    choices=["adafactor", "adamw", "fused"])
    ap.add_argument("--trace", default=None,
                    help="capture a jax.profiler trace of 3 steps here")
    ap.add_argument("--deadline", type=int, default=420)
    args = ap.parse_args()

    import faulthandler

    faulthandler.dump_traceback_later(args.deadline, exit=True)

    from learning_at_home_tpu.utils.chip import (
        enable_compile_cache,
        hbm_bytes,
        peak_bf16_flops,
    )

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from __graft_entry__ import flagship_one_chip
    from bench import (
        _activation_bytes,
        _model_flops_per_step,
        _static_state_bytes,
    )
    from learning_at_home_tpu.models.transformer import DMoETransformerLM
    from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh

    device = jax.devices()[0]
    if device.platform == "cpu":
        raise SystemExit("profile_step times a device step; JAX found only "
                         "the CPU")
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    _, cfg, optimizer, recipe_batch = flagship_one_chip(mesh)
    if args.batch is None:
        args.batch = recipe_batch
    cfg = dataclasses.replace(
        cfg,
        remat=not args.no_remat,
        remat_policy=args.remat_policy,
        scan_layers=not (args.no_scan or args.no_stack),
        stack_layers=not args.no_stack,
    )
    model = DMoETransformerLM(cfg, mesh)
    if args.optimizer == "adafactor":
        optimizer = optax.adafactor(1e-3)
    elif args.optimizer == "adamw":
        optimizer = optax.adamw(1e-3)

    budget = 0.75 * hbm_bytes(device)
    need = _static_state_bytes(model, optimizer) + _activation_bytes(
        cfg, args.batch
    )
    if cfg.remat and args.remat_policy == "dots":
        need += dots_extra_bytes(cfg, args.batch)
    if need > budget:
        print(
            f"REFUSED: estimated peak {need / 1e9:.1f} GB > budget "
            f"{budget / 1e9:.1f} GB",
            file=sys.stderr,
        )
        sys.exit(2)
    print(f"variant: batch={args.batch} scan={cfg.scan_layers} "
          f"remat={cfg.remat}/{cfg.remat_policy} opt={args.optimizer} "
          f"est_peak={need / 1e9:.1f} GB", file=sys.stderr)

    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = model.init_opt_state(optimizer, params)
    step = model.make_train_step(optimizer)
    sharding = batch_sharding(mesh)
    rs = np.random.RandomState(0)
    ids = jax.device_put(
        jnp.asarray(rs.randint(0, cfg.vocab_size, (args.batch, cfg.seq_len))),
        sharding,
    )
    tgt = jax.device_put(
        jnp.asarray(rs.randint(0, cfg.vocab_size, (args.batch, cfg.seq_len))),
        sharding,
    )

    t_c0 = time.perf_counter()
    params, opt_state, loss, _ = step(params, opt_state, ids, tgt)
    jax.block_until_ready((params, opt_state, loss))
    compile_s = time.perf_counter() - t_c0

    n = args.steps
    t0 = time.perf_counter()
    for _ in range(n):
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
    jax.block_until_ready((params, opt_state, loss))
    elapsed = time.perf_counter() - t0

    if args.trace:
        from learning_at_home_tpu.utils.profiling import device_trace

        with device_trace(args.trace):
            for _ in range(3):
                params, opt_state, loss, metrics = step(
                    params, opt_state, ids, tgt
                )
            jax.block_until_ready((params, opt_state, loss))

    step_s = elapsed / n
    tps = args.batch * cfg.seq_len / step_s
    out = {
        "batch": args.batch,
        "scan_layers": cfg.scan_layers,
        "remat": cfg.remat,
        "remat_policy": cfg.remat_policy,
        "optimizer": args.optimizer,
        "step_ms": round(1000 * step_s, 2),
        "tokens_per_sec": round(tps, 1),
        "compile_s": round(compile_s, 1),
        "loss": round(float(loss), 4),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "mfu": round(
            _model_flops_per_step(cfg, args.batch) / step_s
            / peak_bf16_flops(device), 4
        ),
        "hbm_peak_gb": round(
            device.memory_stats()["peak_bytes_in_use"] / 1e9, 2
        ),
    }
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
