"""What the configurations' test files share, once: the tiny stack on one
device under weights that decide (``one_device_mesh``, ``decisive``,
``tiny_stack``, ``close``), a configuration's runner read against it
(``Limits``), and the comparison's programs compiled once a module
(``compiled_once``).

This module is no test module itself (``tests/benchmark_cells.py`` is the
precedent).  A configuration's file says
``limits = Limits(runner, reference, TINY_FILE)`` beside its ``tiny``
fixture, reads with ``limits.read(tiny, **how)`` and names what fell
outside with ``limits.outside(read)``.

``runner.compare_with_reference`` builds its jitted stages inside the call,
so each case of a file compiled the comparison anew: the stack as it is, then
the same stages again for every wrong step (48 to 79 s a case in
``tests/test_ling3_runner.py``, where a wrong forward took 4 to 9: PERF.md
section 8, PR 70).  The runners are the benchmark's and take no compiled
stage from outside, so the sharing is done where XLA's programs are keyed by
their text: a compile cache of the module's own, in a directory pytest makes
for this run and this module and that no other run reads.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from learning_at_home_tpu.parallel.mesh import make_mesh

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py)


def one_device_mesh():
    return make_mesh({"expert": 1}, devices=jax.devices()[:1])


def decisive(params, seed=7, spread=("['scale']",), drawn=None, scaled=None):
    """Seeded weights under which every part of a tiny stack decides (the
    program's init gives routers near-equal scores and norms a scale of 1,
    under which a wrong part hides inside any tolerance).  By the END of a
    leaf's path: ``spread`` times a factor drawn from 0.5 to 1.5, ``drawn``
    ``{ending: w}`` drawn anew from -w to w, ``scaled`` ``{ending: factor}``
    (the first that matches).  A configuration's file says which, and why."""
    rs = np.random.RandomState(seed)

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith(tuple(spread)):
            return a * jnp.asarray(rs.uniform(0.5, 1.5, a.shape), a.dtype)
        for ending, width in (drawn or {}).items():
            if name.endswith(ending):
                return jnp.asarray(rs.uniform(-width, width, a.shape), a.dtype)
        return a * next(
            (v for k, v in (scaled or {}).items() if name.endswith(k)), 1.0)

    return jax.tree_util.tree_map_with_path(leaf, params)


def tiny_stack(recipe, decide):
    """``(model, cfg, float32 params, ids, targets)`` of ``recipe`` at its
    tiny size on one device, the weights through ``decide``."""
    model, cfg, _, batch = recipe(one_device_mesh(), tiny=True)
    params = decide(model.init_params(jax.random.PRNGKey(11)))
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len + 1)))
    return model, cfg, params, ids[:, :-1], ids[:, 1:]


def close(got, want, tol=1e-4, **kw):
    """To ``tol`` of the reference's largest entry."""
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=0, atol=tol * np.abs(want).max(), **kw)


class Limits:
    """One configuration's runner, reference and tiny file."""

    def __init__(self, runner, reference, config, with_targets=True):
        self.runner, self.reference, self.config = runner, reference, config
        self.with_targets = with_targets  # sdar's comparison draws its own

    def read(self, tiny, model=None, reference=None, **how) -> dict:
        """``compare_with_reference`` on the first row of ``tiny``: the
        fixture's ``(model, cfg, params, ids, targets)``; ``model`` or
        ``reference`` another one in its place."""
        program, _, params, ids, targets = tiny
        rows = (ids[:1], targets[:1]) if self.with_targets else (ids[:1],)
        return self.runner.compare_with_reference(
            program if model is None else model, params,
            self.reference if reference is None else reference,
            self.config, *rows, **how)

    def reference_with(self, **changes):
        """A copy of the reference module with functions replaced."""
        broken = harness.load_path(self.reference.__file__)
        for name, value in changes.items():
            setattr(broken, name, value)
        return broken

    def outside(self, read, near_ties=False) -> list:
        """The limits ``read`` is not inside, by name; the share of near
        ties apart unless asked for (a few dozen positions tie by chance)."""
        return [name for name, limit in self.runner.TOLERANCES.items()
                if (near_ties or name != "near_tie_share")
                and not read[name] <= limit]

    def inside(self, read, *names) -> bool:
        return all(read[name] <= self.runner.TOLERANCES[name] for name in names)

    def none_inside(self, read, *names) -> bool:
        return not any(
            read[name] <= self.runner.TOLERANCES[name] for name in names)


@pytest.fixture(scope="module")
def compiled_once(tmp_path_factory):
    """For the module's duration XLA's programs are kept by their text in a
    directory of this module's own, so a stage that a later case builds again
    (another call of ``compare_with_reference``, the same stack) is read and
    not compiled.  The runners keep their backward programs out of the chip
    machine's capped cache by raising the least compile time worth keeping
    (``_kept_out_of_the_compile_cache``); here the cache is the module's and
    holding them is the point, so that one setting is held at nought."""
    least = "jax_persistent_cache_min_compile_time_secs"
    was = {name: getattr(jax.config, name)
           for name in ("jax_compilation_cache_dir", least)}
    update = jax.config.update
    with pytest.MonkeyPatch.context() as patch:
        update("jax_compilation_cache_dir",
               str(tmp_path_factory.mktemp("compiled_once")))
        update(least, 0.0)
        compilation_cache.reset_cache()
        patch.setattr(jax.config, "update", lambda name, value: (
            None if name == least else update(name, value)))
        yield
    for name, value in was.items():
        update(name, value)
    compilation_cache.reset_cache()
