"""The benchmark runner's comparison of Nemotron-Labs-TwoTower's tiny stack
with its reference, and what must fail it: a wrong stack, a ``_hidden`` that
composes another stack, a lower precision.  A module apart from
``tests/test_nemotron_hybrid.py`` (each case compiles the comparison's
programs anew), so that ``--dist loadfile`` can spread the two.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runner_limits import Limits, compiled_once  # noqa: F401  (a fixture)
from test_nemotron_hybrid import (  # noqa: F401  (``tiny`` is a fixture)
    TINY_FILE,
    _one_device_mesh,
    reference,
    runner,
    tiny,
)
from learning_at_home_tpu.models.transformer import DMoETransformerLM

pytestmark = pytest.mark.usefixtures("compiled_once")
limits = Limits(runner, reference, TINY_FILE)


# ---- (b) the negatives: the comparison can fail ----


def _norm_then_gate(y, z, scale, groups, eps):
    b, s, d_inner = y.shape
    normed = reference.rms(
        y.reshape(b, s, groups, d_inner // groups), 1.0, eps)
    return normed.reshape(b, s, d_inner) * scale * jax.nn.silu(z)


NEGATIVES = {
    "a_scan_without_the_d_x_term": (
        dict(skip_term=lambda d, x: 0.0 * x), ("ssm_rms", "layers_rms")),
    "a_gate_applied_after_the_norm": (
        dict(gated_norm=_norm_then_gate), ("ssm_rms", "layers_rms")),
    "a_decay_taken_from_dt_without_softplus": (
        dict(step_sizes=lambda dt, dt_bias: jnp.abs(dt + dt_bias)),
        ("ssm_rms", "ssm_state_rms")),
    "an_expert_with_relu_in_place_of_its_square": (
        dict(activation=jax.nn.relu), ("layers_rms",)),
}


def test_the_stack_as_it_is_reads_inside_the_runner_tolerances(tiny):
    read = limits.read(tiny)
    assert limits.outside(read) == []
    assert len(read["embed_and_layers_rms"]) == 10  # the embedding, nine layers
    assert len(read["ssm_layers_rms"]) == len(read["ssm_states_rms"]) == 4
    assert len(read["near_tie_shares"]) == 4


@pytest.mark.parametrize("name", sorted(NEGATIVES))
def test_a_wrong_stack_fails_the_runner_tolerances(tiny, name):
    """Each read OUTSIDE the tolerance: the comparison can fail.  (The
    wrong side is the reference's copy; the program is as it is.)"""
    changes, outside = NEGATIVES[name]
    read = limits.read(tiny, reference=limits.reference_with(**changes))
    assert limits.none_inside(read, *outside), read


def test_a_hidden_that_composes_another_stack_fails_the_runner_tolerances(tiny):
    """``_hidden`` over a stack whose state-space layers are skipped (the
    layers themselves as they are) reads outside ``hidden_token_median``."""
    sound, cfg, params, ids, tgt = tiny
    model = DMoETransformerLM(cfg, _one_device_mesh())
    layer = model._layer
    model._layer = lambda lp, x, *rest: (
        (x, None) if "ssm" in lp and x.shape[0] != 1 else layer(lp, x, *rest))
    read = limits.read(tiny, model)
    assert limits.inside(read, "hidden_token_median")
    model._layer = lambda lp, x, *rest: (
        (x, None) if "ssm" in lp else layer(lp, x, *rest))
    whole = jax.jit(lambda p: model._hidden(p, ids[:1])[0])(params)
    right = jax.jit(lambda p: sound._hidden(p, ids[:1])[0])(params)
    rel = np.median(np.linalg.norm(np.asarray(whole - right), axis=-1)
                    / np.linalg.norm(np.asarray(right), axis=-1))
    assert rel > runner.TOLERANCES["hidden_token_median"]


def test_lower_precisions_fail_the_runner_tolerances(tiny):
    """The reference with float8 operands in the program's place reads
    outside the layer and logits limits, with bf16 operands inside; the
    program's scan with bf16 decays reads worse than with float32 ones."""
    held = ("layers_rms", "ssm_rms", "logits_rms")
    assert limits.none_inside(
        limits.read(tiny, operand_dtype=jnp.float8_e4m3fn), *held)
    assert limits.inside(limits.read(tiny, operand_dtype=jnp.bfloat16), *held)
    exact = limits.read(tiny)
    rough = limits.read(tiny, decay_dtype=jnp.bfloat16)
    assert rough["ssm_rms"] > 100 * exact["ssm_rms"]
    assert rough["ssm_state_rms"] > 100 * exact["ssm_state_rms"]
