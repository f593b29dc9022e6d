"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tier-1 is the CPU suite: it checks results, control flow and counts, and
the multi-device sharding tests need 8 devices, which only the CPU
backend can fake (SURVEY.md §4 "TPU-build implication").  The env vars
are set for any subprocess a test spawns and the live jax config is
updated for this process, so the suite runs the same on a machine that
has an accelerator.  What runs on a chip is ``chip_smoke.py``'s to check.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# Concurrency sanitizer (ISSUE 6) ON BY DEFAULT under pytest: every
# tier-1 dispatch runs with thread-identity assertions, the event-loop
# stall detector, and lock-order tracking armed.  Must be set before the
# package is imported (the arming decision is made at import time);
# LAH_SANITIZE=0 in the environment opts a run out.
os.environ.setdefault("LAH_SANITIZE", "1")
# The CPU's code generation at its cheapest (ISSUE 70): tier-1's programs run
# once on a few dozen tokens, and LLVM's optimisation of them was a third of
# the suite's seconds.  No CPU executable is a product of this repo; the
# flags reach the children a test spawns (``benchmark_cells.rehearse``) and
# never ``benchmarks/run.py`` on the chip.  A run that names a flag keeps its own.
xla_flags = os.environ.get("XLA_FLAGS", "")
for flag in ("--xla_force_host_platform_device_count=8",
             "--xla_backend_optimization_level=0",
             "--xla_llvm_disable_expensive_passes=true"):
    if flag.split("=")[0] not in xla_flags:
        xla_flags = f"{xla_flags} {flag}".strip()
os.environ["XLA_FLAGS"] = xla_flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# opt-in hang diagnosis: dump all thread stacks periodically
if os.environ.get("LAH_DUMP_STACKS"):
    import faulthandler

    faulthandler.dump_traceback_later(
        int(os.environ["LAH_DUMP_STACKS"]), repeat=True, exit=False
    )

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _sanitizer_guard():
    """The shared replacement for the old per-file thread-tracking
    monkeypatch fixtures (ISSUE 6): every test runs under the sanitizer's
    thread-identity checks, and any violation it records FAILS the test
    that caused it.  Seeded-violation tests drain their expected findings
    through ``sanitizer.expect_violations()`` so this guard stays green.
    """
    from learning_at_home_tpu.utils import sanitizer

    if not sanitizer.enabled():
        yield
        return
    before = sanitizer.violation_count()
    yield
    new = sanitizer.violations()[before:]
    if new:
        rendered = "\n".join(
            f"  [{v['kind']}] {v['site']} on thread {v['thread']}: "
            f"{v['detail']}"
            for v in new
        )
        pytest.fail(
            f"concurrency sanitizer recorded {len(new)} violation(s) "
            f"during this test:\n{rendered}"
        )


def pytest_sessionfinish(session, exitstatus):
    """Export the sanitizer roll-up into the gate output: printed on
    every run (the tier-1 log IS the gate artifact) and written as JSON
    when LAH_SANITIZE_SUMMARY names a path (tools/collect_gate)."""
    try:
        from learning_at_home_tpu.utils import sanitizer
    except Exception:
        return
    if not sanitizer.enabled():
        return
    import json

    summary = sanitizer.summary()
    stall = sanitizer.stall_stats()
    if stall.get("last"):
        # the live stack was already logged when the stall fired; the
        # one-line gate summary keeps only what/how-long
        stall["last"] = {
            k: v for k, v in stall["last"].items() if k != "stack"
        }
    summary.update(stall=stall)
    line = json.dumps(summary, sort_keys=True)
    print(f"\nLAH_SANITIZER_SUMMARY {line}")
    path = os.environ.get("LAH_SANITIZE_SUMMARY")
    if path:
        try:
            with open(path, "w") as fh:
                fh.write(line + "\n")
        except OSError:
            pass


@pytest.fixture(scope="session")
def llvm_optimised():
    """``llvm_optimised(fn)(*args)``: ``fn`` compiled with the code
    generation the two flags above turn off, for the few cases whose limit
    only that code's order of a float32 sum meets.  Each says beside its
    call what it read without (``CHANGES.md``, PR 70, lists them)."""
    options = {"xla_backend_optimization_level": 3,
               "xla_llvm_disable_expensive_passes": False}

    def compiled(fn):
        return lambda *args: jax.jit(fn).lower(*args).compile(
            compiler_options=options)(*args)

    return compiled


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described (not attached) ``v5e:2x2``, for AOT
    compiles at real sizes; the test is skipped where the TPU compiler is
    absent or its library is held by another process.  Asked for by name
    (never autouse), so the topology is described only once a test that
    needs it has started."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]
