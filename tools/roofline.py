"""Chip-independent HBM/MXU roofline for the flagship bench recipe.

Answers the round-4 review's question: is
the single-chip flagship at batch 176 bandwidth-bound on parameter
traffic — in which case the gradient-accumulation ladder can lift MFU
toward 0.25 — or is the param-traffic share already small enough that
accum cannot get there?

Method: exact state bytes come from ``jax.eval_shape`` on the REAL
flagship (same construction path as ``bench.py``: bf16 params, fused
Adafactor, remat, unstacked layers — nothing allocated, runs anywhere);
traversal counts are read off the train step's structure:

  per microbatch   forward reads every param once            1×P
                   remat recompute reads them again          1×P
                   backward dgrad matmuls read them again    1×P
                   gradient write (param dtype)              1×G
  accum>1 only     f32 accum buffer read-modify-write        2×A32 + 1×G
  per opt step     fused Adafactor: read params+grads, rw    2×P + 1×Gin
                   factored stats, write params (ONE fused       + 2×O
                   traversal, ops/fused_adafactor.py)

Compute floors use ``bench._model_flops_per_step`` (algorithmic, the MFU
numerator) and a 4/3 remat-recompute factor for *executed* FLOPs.

Public spec constants: v5e 819 GB/s HBM, 197 bf16 TFLOP/s.  Measured
anchor: 273.0 ms/step at batch 176 (BASELINE.md round-3 fused-recipe
row, re-used as the round-4 ``vs_baseline`` denominator).

Run: ``python tools/roofline.py`` (pins itself to the CPU: nothing here
executes on a device).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_GBPS = 819e9  # v5e spec (Google Cloud documentation, "TPU v5e")
CHIP = "TPU v5 lite"  # the device_kind this analysis is about


def main() -> None:
    # analysis only (eval_shape, nothing executes): host-only by design,
    # so it never takes a chip from a process that needs one
    import jax

    jax.config.update("jax_platforms", "cpu")

    from bench import BASELINE_TPS, _model_flops_per_step, _tree_bytes
    from learning_at_home_tpu.utils.chip import PEAK_BF16_FLOPS

    PEAK_BF16 = PEAK_BF16_FLOPS[CHIP]
    from __graft_entry__ import flagship_one_chip
    from learning_at_home_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    model, cfg, opt, BATCH = flagship_one_chip(mesh)

    # the measured anchor is the recorded round-3 best: 165,040 tok/s at
    # batch 176 × seq 256 (bench.BASELINE_TPS is the single source)
    MEASURED_STEP_S = BATCH * cfg.seq_len / BASELINE_TPS["tpu"]

    aparams = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    P = _tree_bytes(aparams)  # bf16 params
    G = P  # cotangents carry the param dtype
    A32 = 4 * sum(l.size for l in jax.tree_util.tree_leaves(aparams))
    O = _tree_bytes(jax.eval_shape(opt.init, aparams))
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(aparams))

    flops = _model_flops_per_step(cfg, BATCH)  # algorithmic (MFU numerator)
    t_alg = flops / PEAK_BF16
    t_exec = flops * (4.0 / 3.0) / PEAK_BF16  # remat recompute included

    def ms(nbytes: float) -> float:
        return nbytes / HBM_GBPS * 1e3

    fwd_bwd = 3 * P + G          # per microbatch, accum or not
    accum_rmw = 2 * A32 + G      # per microbatch, accum>1 only
    opt_pass = 2 * P + 2 * O + A32  # once per opt step (reads f32 sums when accum>1)
    opt_pass_a1 = 2 * P + 2 * O + G  # accum=1: reads the bf16 grad tree

    print(f"flagship: {n_params/1e9:.3f} B params | P(bf16) {P/1e9:.2f} GB | "
          f"opt state {O/1e9:.2f} GB | f32 accum buffer {A32/1e9:.2f} GB")
    print(f"algorithmic FLOPs/step (batch {BATCH}): {flops/1e12:.2f} TF "
          f"-> compute floor {t_alg*1e3:.1f} ms algorithmic, "
          f"{t_exec*1e3:.1f} ms executed (remat 4/3)")
    print(f"measured step: {MEASURED_STEP_S*1e3:.1f} ms "
          f"(MFU {flops/MEASURED_STEP_S/PEAK_BF16:.3f})")
    print()
    print("param-sized HBM traffic per optimizer step @ 819 GB/s:")
    residual = None
    for accum in (1, 2, 4):
        if accum == 1:
            traffic = fwd_bwd + opt_pass_a1
            step_ms = MEASURED_STEP_S * 1e3
        else:
            traffic = accum * (fwd_bwd + accum_rmw) + opt_pass
            # model: each micro costs the measured non-opt time plus the
            # accum RMW; the single opt pass replaces accum=1's per-step one
            micro_ms = (MEASURED_STEP_S * 1e3 - ms(opt_pass_a1)
                        + ms(accum_rmw))
            step_ms = accum * micro_ms + ms(opt_pass)
        tokens = accum * BATCH * cfg.seq_len
        mfu = accum * flops / (step_ms / 1e3) / PEAK_BF16
        print(f"  accum={accum}: traffic {traffic/1e9:6.1f} GB = "
              f"{ms(traffic):5.1f} ms floor | predicted step "
              f"{step_ms:6.1f} ms | tok/s {tokens/(step_ms/1e3)/1e3:6.1f}k | "
              f"MFU {mfu:.3f}")
        if accum == 1:
            residual = MEASURED_STEP_S * 1e3 - ms(traffic) - t_exec * 1e3
    print()
    print(f"decomposition of the measured 273 ms (accum=1): executed matmuls "
          f">= {t_exec*1e3:.1f} ms, param traffic >= {ms(fwd_bwd+opt_pass_a1):.1f} ms, "
          f"residual (activations, CE chunks, dispatch, non-matmul ops, "
          f"matmul inefficiency) ~= {residual:.1f} ms")
    share = ms(fwd_bwd + opt_pass_a1) / (MEASURED_STEP_S * 1e3)
    print(f"param-traffic share of the step: {share:.1%} -> the step is NOT "
          f"param-bandwidth-bound at batch {BATCH}")
    best_no_param = MEASURED_STEP_S * 1e3 - ms(opt_pass_a1)
    print(f"accum ceiling: even amortizing the optimizer pass to zero, "
          f"MFU <= {flops/(best_no_param/1e3)/PEAK_BF16:.3f}; the f32 accum "
          f"RMW ({ms(accum_rmw):.1f} ms/micro) exceeds the amortized "
          f"optimizer saving ({ms(opt_pass_a1):.1f} ms/step), so accum>1 is "
          f"predicted NET NEGATIVE at this shape")


if __name__ == "__main__":
    main()
