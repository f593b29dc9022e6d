"""What the delta rule's forward kernel keeps for its backward
(``ops/delta_rule.py``, PR 58): ONE float32 state a grid step, from which
``delta_chunk_bwd`` rebuilds the chunks' entering states in VMEM, and the
name ``DELTA_RESIDUALS`` on it and on ``o`` for a remat policy to keep.
Under ``interpret`` on the CPU; a module apart from
``tests/test_delta_rule_kernel.py`` (its inputs and its loss), so that no
file's cases sum past tier-1's 150 s."""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_delta_rule_kernel import DK, NAMES, SHAPES, _everything, _inputs
from learning_at_home_tpu.ops import delta_rule

_spec = importlib.util.spec_from_file_location("probe", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools", "smallthinker_probe.py"))
probe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(probe)  # ``_equations``: a jaxpr's equations by primitive


@pytest.mark.parametrize("shape, residual", [
    ("2-chunks", (2, 1, 2, DK, 64)), ("6-chunks", (1, 3, 1, DK, 128)),
    ("4-chunks", (1, 1, 1, DK, 128)), ("8-chunks", (1, 2, 1, DK, 128))])
def test_the_backwards_residual_is_one_state_a_grid_step(shape, residual):
    """What the differentiated forward hands the backward besides its own
    inputs: the float32 state entering each GRID STEP ``[B H / hg, S / t,
    hg, dk, dv]`` (``_grid``'s ``t``: two frames where they come out even),
    not each chunk; a call that is not differentiated writes none."""
    bsz, s, chunk, h, dv = SHAPES[shape]
    q, k, v, g, beta = _inputs(s, h, jnp.bfloat16, dv=dv, bsz=bsz)

    def heads_first(a):
        return jnp.moveaxis(a, 2, 1).reshape(bsz * h, s, *a.shape[3:])

    args = (heads_first(q), heads_first(k), v.reshape(bsz, s, h * dv),
            heads_first(g), heads_first(beta))
    hg, t = delta_rule._grid(h, s, chunk, DK, dv)
    assert residual == (bsz * h // hg, s // t, hg, DK, dv)
    (o, final), kept = jax.eval_shape(
        lambda *a: delta_rule._rule_fwd(*a, chunk, False, True), *args)
    assert [a.shape for a in kept[:5]] == [a.shape for a in args]
    assert kept[5].shape == residual and kept[5].dtype == jnp.float32
    assert len(jax.eval_shape(
        lambda *a: delta_rule._rule(*a, chunk, False, True), *args)) == 2
    assert o.shape == args[2].shape and final.shape == (bsz * h, DK, dv)


@pytest.mark.parametrize("chunk, h, dv", [(128, 1, 128), (64, 2, 64)])
def test_the_rebuilt_states_are_the_ones_the_forward_carried(
        chunk, h, dv, monkeypatch):
    """The five gradients at a grid step of two frames (the backward kernel
    chains the step's chunks forward in VMEM from the one state kept)
    against a grid step of one: to the bit.  At chunks of 128 a step of one
    frame IS one chunk, every entering state the forward kernel's own
    write, as in the parent's kernel (which kept a state a chunk; its
    gradients at this file's shapes, both ``unit``s, are these to the bit:
    PERF.md section 6, PR 58)."""
    args = _inputs(256, h, jnp.bfloat16, seed=5, dv=dv, raw=True)

    def kernel(*a):
        return delta_rule.gated_delta_kernel(*a, chunk, interpret=True, unit=True)

    assert delta_rule._grid(h, 256, chunk, DK, dv) == (h, 256)
    two_frames = _everything(kernel, args)
    monkeypatch.setattr(delta_rule, "_grid", lambda *a: (h, delta_rule.FRAME))
    one_frame = _everything(kernel, args)
    for name, got, want in zip(("o", "state", *NAMES), (*two_frames[:2], *two_frames[2]),
                               (*one_frame[:2], *one_frame[2])):
        assert np.array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)), name


def test_a_checkpoint_that_keeps_the_residuals_runs_the_forward_kernel_once():
    """Under ``jax.checkpoint`` with ``save_only_these_names(
    DELTA_RESIDUALS)`` the gradient's program holds ONE ``delta_chunk_fwd``
    (the recompute holds none: ``o`` and the step states are kept) beside
    the one ``delta_chunk_bwd``; under a checkpoint that keeps nothing, two;
    the gradients are the same to the bit either way, and those of no
    checkpoint at all."""
    args = _inputs(256, 1, jnp.bfloat16, seed=7, dv=128, raw=True)
    weigh = jnp.asarray(np.random.RandomState(8).randn(1, 256, 1, 128), jnp.float32)

    def layer(*a):  # a mixer's core: what follows reads ``o``
        o, _ = delta_rule.gated_delta_kernel(*a, 64, interpret=True, unit=True)
        return jnp.tanh(o.astype(jnp.float32)) * weigh

    def grads(wrap):
        fn = jax.grad(lambda *a: jnp.sum(wrap(layer)(*a)), argnums=(0, 1, 2, 3, 4))
        kernels = [eqn.params["name"] for _, eqn in probe._equations(
            jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")]
        return kernels, jax.jit(fn)(*args)

    kept_calls, kept = grads(lambda f: jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            delta_rule.DELTA_RESIDUALS)))
    bare_calls, bare = grads(lambda f: jax.checkpoint(
        f, policy=jax.checkpoint_policies.nothing_saveable))
    plain_calls, plain = grads(lambda f: f)
    assert sorted(kept_calls) == sorted(plain_calls) == [
        "delta_chunk_bwd", "delta_chunk_fwd"]
    assert sorted(bare_calls) == [
        "delta_chunk_bwd", "delta_chunk_fwd", "delta_chunk_fwd"]
    for name, a, b, c in zip(NAMES, kept, bare, plain):
        for other in (b, c):
            assert np.array_equal(
                np.asarray(a, np.float32), np.asarray(other, np.float32)), name


# sha256 of ``jax.jit(gated_delta_kernel under interpret).lower(..).as_text()``
# of a call that is not differentiated: keeping a state a grid step and
# naming the residuals changes the differentiated forward alone.  Taken on
# PR 58's PARENT (793a7a5) and unmoved until PR 62 rewrote the frame's
# inverse (``_unit_lower_inverse``: the same bits from other operations,
# ``tests/test_delta_rule_kernel.py``), which moved the text; taken again there
FORWARD_ONLY_SHA256 = {
    False: "3e6eb09e676e24b6569ee366f919b1eeb35e70075d86eb944cd162e23d1b7e12",
    True: "1cabe0f9f578fa8c65a06cd296149c690e919686120b3d3fdf099346a276c463",
}


@pytest.mark.parametrize("unit", [False, True])
def test_a_call_that_is_not_differentiated_lowers_to_the_parents_text(unit):
    args = _inputs(256, 2, jnp.bfloat16, raw=unit)
    text = jax.jit(lambda *a: delta_rule.gated_delta_kernel(
        *a, 64, interpret=True, unit=unit)).lower(*args).as_text()
    assert "delta_rule_residuals" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == FORWARD_ONLY_SHA256[unit]
