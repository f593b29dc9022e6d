"""Worker for the 2-process jax.distributed smoke test (SURVEY.md §2.3
tier-a bring-up).  Launched by tests/test_multihost.py with a scrubbed CPU
env and 2 virtual devices per process; joins the coordinator, assembles a
global batch from host-local rows, runs one psum'd shard_map step and one
cross-process ShardedMixtureOfExperts forward, and prints a marker line
the parent asserts on.

Exit codes: 0 ok; 3 = environment cannot run jax.distributed on CPU
(parent skips); anything else = real failure.
"""

import sys

import faulthandler

faulthandler.dump_traceback_later(220, exit=True)

pid, nproc, addr = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

import jax

from learning_at_home_tpu.parallel.multihost import (
    host_local_array_to_global,
    initialize_multihost,
)

try:
    initialize_multihost(addr, num_processes=nproc, process_id=pid)
except Exception as e:  # unsupported runtime -> skip, not fail
    print(f"MULTIHOST_SKIP {type(e).__name__}: {e}", flush=True)
    sys.exit(3)

import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from learning_at_home_tpu.parallel import ShardedMixtureOfExperts, make_mesh

assert jax.process_count() == nproc, jax.process_count()
n_local = len(jax.local_devices())
n_global = len(jax.devices())
assert n_global == nproc * n_local, (n_global, nproc, n_local)

# batch-bearing axis first => process-major, as multihost.py documents
mesh = make_mesh({"data": nproc, "expert": n_local})

# 1) host-local rows -> one global array in the train step's layout
local = np.full((2, 4), float(pid + 1), np.float32)
g = host_local_array_to_global(local, mesh)
assert g.shape == (2 * nproc, 4), g.shape

# 2) one psum'd step across BOTH processes
def summed(x):
    return jax.lax.psum(jnp.sum(x), ("data", "expert"))

try:
    total = jax.jit(
        shard_map(
            summed, mesh=mesh, in_specs=P(("data", "expert")), out_specs=P()
        )
    )(g)
except Exception as e:
    # some jaxlib builds bring up jax.distributed but cannot EXECUTE
    # cross-process computations on CPU ("Multiprocess computations
    # aren't implemented on the CPU backend") — same environment class
    # as an initialize failure: skip, don't fail
    if "Multiprocess computations aren't implemented" in str(e):
        print(f"MULTIHOST_SKIP {type(e).__name__}: {e}", flush=True)
        sys.exit(3)
    raise
expect = sum(8.0 * (i + 1) for i in range(nproc))  # 2x4 rows of (pid+1)
assert abs(float(total) - expect) < 1e-5, (float(total), expect)

# 3) the expert-parallel MoE program spanning processes: experts live on
# the 'expert' axis (2 per process); the all_to_all crosses the
# process boundary exactly like ICI inside a pod slice
moe = ShardedMixtureOfExperts(
    mesh, hidden_dim=4, num_experts=2 * n_local, k=2,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
params = jax.jit(
    lambda k: moe.init_params(k, device_put=False),
    out_shardings=moe.param_shardings(),
)(jax.random.PRNGKey(0))
x = host_local_array_to_global(
    np.random.RandomState(0).randn(8, 4).astype(np.float32), mesh
)
y, aux = jax.jit(moe)(params, x)
y_norm = float(jnp.linalg.norm(y))  # replicated scalar: addressable
assert np.isfinite(y_norm) and np.isfinite(float(aux["aux_loss"]))

print(f"MULTIHOST_OK pid={pid} devices={n_global} moe_norm={y_norm:.4f}",
      flush=True)
