"""Device-mesh helpers for the ICI tier.

The TPU-native communication backend (SURVEY.md §2.3): intra-pod expert
parallelism rides XLA collectives over ICI inside ``shard_map`` programs;
everything off-slice goes through the DHT + RPC tier.  These helpers build
the meshes both tiers hang off.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axes: dict[str, int] | None = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a named mesh; axis sizes must multiply to the device count.

    Default: all devices on a single ``expert`` axis (pure expert
    parallelism — the reference's scaling dimension).  A typical pod-scale
    layout is ``{"data": 4, "expert": 8}``.
    """
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"expert": len(devices)}
    sizes = list(axes.values())
    if int(np.prod(sizes)) != len(devices):
        raise ValueError(
            f"mesh axes {axes} need {int(np.prod(sizes))} devices, "
            f"have {len(devices)}"
        )
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(axes))


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes a token batch is sharded over (everything but model axes)."""
    return tuple(a for a in mesh.axis_names if a in ("data", "expert"))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Tokens sharded across all data-bearing axes; with a ``seq`` axis the
    sequence dimension (axis 1) is context-parallel too."""
    if "seq" in mesh.axis_names:
        return NamedSharding(mesh, P(data_axes(mesh), "seq"))
    return NamedSharding(mesh, P(data_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def expert_sharding(mesh: Mesh) -> NamedSharding:
    """Stacked per-expert params: leading axis split over 'expert'."""
    return NamedSharding(mesh, P("expert"))


def opt_state_shardings(abstract_opt_state, param_shardings, params, mesh: Mesh):
    """Shardings for an optimizer state mirroring the param tree.

    Optimizer states (optax) embed sub-trees shaped like the params (mu/nu
    in Adam); those leaves inherit the matching param's sharding — found by
    matching each opt-state leaf's key-path SUFFIX against param key-paths.
    A leaf with the param's shape takes the param's spec.  A factored
    statistic (adafactor's ``v_row``/``v_col``: the param's shape with ONE
    axis reduced away) takes the param's spec with that axis dropped — so
    the statistics of an expert stack stay split over ``expert``, which is
    how the train step returns them: placed replicated instead, the second
    step saw new input shardings and compiled the whole step again (31–35
    s at the flagship on four v5e chips, PR 21).  When the reduced axis
    cannot be told (equal dims) and the candidates disagree, and for
    everything else (step counts, scalars, ``[1]`` sentinels), the leaf is
    replicated.  Needed because ``jit(opt.init)`` does not propagate
    NamedShardings to its outputs, and a checkpoint restored onto
    mismatched devices poisons the train step.

    LIMIT of the heuristic (round-2 advisor): the suffix+shape match is
    positional-blind — an optimizer whose state leaf coincidentally has
    the param's exact shape but different per-axis SEMANTICS (e.g. a
    transposed statistic) would silently inherit the param's spec.  The
    optimizers in use (adamw, adafactor, ops.fused_adafactor) are covered
    by tests; new optimizers with same-shape/different-semantics state
    need an explicit sharding override instead of this helper.
    """
    shard_map_ = {
        jax.tree_util.keystr(path): s
        for path, s in jax.tree_util.tree_flatten_with_path(param_shardings)[0]
    }
    shape_map = {
        jax.tree_util.keystr(path): tuple(p.shape)
        for path, p in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    if shard_map_.keys() != shape_map.keys():
        raise ValueError(
            "param_shardings and params trees disagree: "
            f"{sorted(shard_map_.keys() ^ shape_map.keys())[:4]} — a silent "
            "mispairing here would mis-shard the optimizer state"
        )
    param_map = {k: (shard_map_[k], shape_map[k]) for k in shard_map_}
    repl = NamedSharding(mesh, P())

    def reduced(sharding, shape, leaf_shape):
        """Spec of ``shape`` with one axis reduced away to ``leaf_shape``."""
        spec = tuple(sharding.spec) + (None,) * (len(shape) - len(sharding.spec))
        candidates = {
            spec[:axis] + spec[axis + 1:]
            for axis in range(len(shape))
            if shape[:axis] + shape[axis + 1:] == leaf_shape
        }
        if len(candidates) == 1:
            return NamedSharding(mesh, P(*candidates.pop()))
        return repl

    def assign(path, leaf):
        for i in range(len(path)):
            suffix = jax.tree_util.keystr(path[i:])
            if suffix in param_map:
                sharding, shape = param_map[suffix]
                if tuple(leaf.shape) == shape:
                    return sharding
                return reduced(sharding, shape, tuple(leaf.shape))
        return repl

    return jax.tree_util.tree_map_with_path(assign, abstract_opt_state)
