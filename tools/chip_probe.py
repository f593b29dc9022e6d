#!/usr/bin/env python3
"""Builder-run probes for questions only a chip can answer.

    python tools/chip_probe.py kernels [name-part]    # one chip
    python tools/chip_probe.py client     # one chip
    python tools/chip_probe.py fourchip   # one four-chip host

``chip_smoke.py`` is the check the driver repeats; these are the
one-off verdicts a bring-up records in CHANGES.md, kept as code so the
next toolchain can be asked the same questions the same way.  Each
subcommand is one process (a chip belongs to one process), prints one
``VERDICT`` line per question, and exits non-zero if any question got
the wrong answer or no TPU was found.

- ``kernels``: each Pallas path of the train step compiled by Mosaic
  (``interpret=False``) and compared with its XLA reference: today the
  blocked attention kernel at seq 8192, whose step-0 loss must sit
  within 2e-2 relative of the xla core's (bf16 operands).
- ``client``: can a process that HOLDS the TPU run
  ``RemoteMixtureOfExperts`` forward+grad under ``jit`` (``io_callback``
  inside ``custom_vjp``) against an expert server on the CPU?
- ``fourchip``: ``chip_smoke.run_trainer`` — layout checks included — on
  ``{"expert": 4}`` and ``{"data": 2, "expert": 2}``; the two layouts'
  step-0 losses must agree within 2e-2 relative (bf16 compute, different
  reduction orders across shards), and the four per-device peaks are
  printed after init and after the steps.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAILED: list[str] = []


def verdict(name: str, ok: bool, **facts) -> None:
    if not ok:
        FAILED.append(name)
    print("VERDICT " + json.dumps({"probe": name, "ok": ok, **facts}),
          flush=True)


def refused(name: str, exc: BaseException) -> None:
    """The compiler's (or runtime's) own words, first lines only."""
    text = f"{type(exc).__name__}: {exc}"
    verdict(name, False, refused=text[:1500])


def require_tpu():
    import jax

    d = jax.devices()[0]
    print(f"# device: {d.platform} [{d.device_kind}] x{len(jax.devices())} "
          f"jax {jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_probe needs a TPU, found {d.platform!r}")
    return jax


def _random_ids(rs, cfg, batch: int, mesh):
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.parallel.mesh import batch_sharding

    return jax.device_put(
        jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len)),
                    jnp.int32),
        batch_sharding(mesh),
    )


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def probe_flash() -> None:
    """The blocked attention kernel (what a one-chip model of this length
    resolves to) through one train step at seq 8192 on the 4-layer /
    64-expert variant BASELINE.md's long-context row used."""
    import dataclasses

    import jax
    import numpy as np

    from __graft_entry__ import flagship_one_chip
    from learning_at_home_tpu.models.transformer import DMoETransformerLM
    from learning_at_home_tpu.parallel.mesh import make_mesh

    name = "flash_attention[seq8192]"
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    _, cfg, optimizer, _ = flagship_one_chip(mesh)
    cfg = dataclasses.replace(cfg, seq_len=8192, num_experts=64)
    batch = 2
    try:
        model = DMoETransformerLM(cfg, mesh)
        assert model.attn_impl == "flash", model.attn_impl
        params = model.init_params(jax.random.PRNGKey(0))
        opt_state = model.init_opt_state(optimizer, params)
        step = model.make_train_step(optimizer)
        ids = _random_ids(np.random.RandomState(0), cfg, batch, mesh)
        losses = []
        for _ in range(2):
            params, opt_state, loss, _ = step(params, opt_state, ids, ids)
            losses.append(float(jax.block_until_ready(loss)))
        # the same forward through XLA attention, as the reference
        xla = DMoETransformerLM(cfg, mesh)
        xla.attn_impl = "xla"
        params = xla.init_params(jax.random.PRNGKey(0))
        l_xla = float(jax.jit(xla.loss_fn)(params, ids[:1], ids[:1])[0])
        l_flash = float(jax.jit(model.loss_fn)(params, ids[:1], ids[:1])[0])
    except Exception as e:
        refused(name, e)
        return
    verdict(
        name,
        bool(np.all(np.isfinite(losses)))
        and abs(l_flash - l_xla) <= 2e-2 * abs(l_xla),
        train_losses=losses, loss_flash_vs_xla=[l_flash, l_xla],
        shapes=f"batch {batch} seq {cfg.seq_len} experts {cfg.num_experts}",
    )


def kernels(only: str = "") -> None:
    """All kernel probes, or those whose name contains ``only``."""
    require_tpu()
    probes = {
        "flash": probe_flash,
    }
    for name, probe in probes.items():
        if only in name:
            probe()


# --------------------------------------------------------------------------
# client that holds the TPU
# --------------------------------------------------------------------------


def client() -> None:
    """The server is spawned (on the CPU) BEFORE this process touches JAX;
    then this process takes the chip and dispatches from it."""
    import faulthandler

    from learning_at_home_tpu.utils.subproc import (
        shutdown_procs,
        spawn_expert_servers,
    )

    hid, rows = 1024, 2048
    procs, ports = spawn_expert_servers(
        REPO, "probe", (0,), d_model=hid, num_experts=4, expert_cls="ffn",
        extra_args=("--warmup", "512", "1024", "2048"), platform="cpu",
        probe_timeout_s=240.0,
    )
    try:
        jax = require_tpu()
        import jax.numpy as jnp
        import numpy as np

        from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
        from learning_at_home_tpu.client.routing import StaticExpertSource

        # a hang is an answer too: dump every thread's stack and leave
        faulthandler.dump_traceback_later(180, exit=True)
        moe = RemoteMixtureOfExperts(
            in_features=hid, grid_size=(4,), uid_prefix="probe0", k_best=2,
            source=StaticExpertSource(
                {f"probe0.{i}": ("127.0.0.1", ports[0]) for i in range(4)}
            ),
            forward_timeout=60.0, backward_timeout=60.0,
        )
        gate = moe.init_gate_params(jax.random.PRNGKey(0))

        @jax.jit
        def loss_and_grads(gate, x):
            return jax.value_and_grad(
                lambda g, x: jnp.mean(moe(x, g) ** 2), argnums=(0, 1)
            )(gate, x)

        rs = np.random.RandomState(0)
        times = []
        try:
            for _ in range(3):
                x = jnp.asarray(rs.randn(rows, hid), jnp.float32)
                t0 = time.perf_counter()
                value, grads = jax.block_until_ready(loss_and_grads(gate, x))
                times.append(round(1e3 * (time.perf_counter() - t0), 1))
        except Exception as e:
            refused("tpu_resident_client", e)
            return
        finite = all(
            bool(jnp.all(jnp.isfinite(l)))
            for l in jax.tree_util.tree_leaves((value, grads))
        )
        verdict(
            "tpu_resident_client",
            finite and moe.samples_dropped == 0,
            client_devices=sorted(
                {str(d) for l in jax.tree_util.tree_leaves(grads)
                 for d in l.devices()}
            ),
            dispatch_ms=times, loss=float(value),
            samples_dropped=moe.samples_dropped,
            backward_rpcs_sent=moe.backward_rpcs_sent,
        )
        faulthandler.cancel_dump_traceback_later()
    finally:
        shutdown_procs(procs)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def fourchip_worker(axes_json: str) -> None:
    """One mesh layout in one process, so that its per-device peaks are
    its own (peak_bytes_in_use never falls within a process)."""
    jax = require_tpu()
    import chip_smoke

    axes = json.loads(axes_json)
    name = "fourchip[" + "x".join(f"{k}{v}" for k, v in axes.items()) + "]"
    try:
        r = chip_smoke.run_trainer("tpu", mesh_axes=axes)
    except Exception as e:
        refused(name, e)
        return
    verdict(
        name, True, losses=r["losses"], step_ms=r["step_ms"],
        dropped_fraction=r["dropped_fraction"],
        peak_bytes_after_init=r["peak_bytes_after_init"],
        peak_bytes_in_use=r["peak_bytes_in_use"],
        expert_param_bytes_per_device=r["expert_param_bytes_per_device"],
        hbm_bytes_limit=[
            (d.memory_stats() or {}).get("bytes_limit")
            for d in jax.devices()[:4]
        ],
        memory_stats_device0=jax.devices()[0].memory_stats(),
    )


def fourchip() -> None:
    """Stays off JAX; one worker process per layout, one after the other."""
    import subprocess

    first_losses = []
    for axes in ({"expert": 4}, {"data": 2, "expert": 2}):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "fourchip-worker",
             json.dumps(axes)],
            stdout=subprocess.PIPE, text=True, timeout=900,
        )
        sys.stdout.write(r.stdout)
        found = [
            json.loads(line[len("VERDICT "):])
            for line in r.stdout.splitlines() if line.startswith("VERDICT ")
        ]
        if r.returncode != 0 or not found or not found[-1]["ok"]:
            raise SystemExit(f"fourchip worker {axes} failed rc={r.returncode}")
        first_losses.append(found[-1]["losses"][0])
    a, b = first_losses
    verdict("fourchip[layouts agree at step 0]",
            abs(a - b) <= 2e-2 * abs(a), losses=[a, b], tolerance="2e-2 rel")


if __name__ == "__main__":
    {"kernels": kernels, "client": client, "fourchip": fourchip,
     "fourchip-worker": fourchip_worker}[sys.argv[1]](*sys.argv[2:])
    if FAILED:
        raise SystemExit(f"chip_probe: wrong answers from {FAILED}")
