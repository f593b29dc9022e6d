"""The benchmark runner's comparison of Olmo-Hybrid's tiny stack with its
reference, and what must fail it: a wrong delta layer, a wrong program, a
``_hidden`` that composes another stack, a lower precision.  A module apart
from ``tests/test_olmo_hybrid.py`` (each case compiles the comparison's
programs anew), so that ``--dist loadfile`` can spread the two.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runner_limits import Limits, compiled_once  # noqa: F401  (a fixture)
from test_olmo_hybrid import (  # noqa: F401  (``tiny`` is a fixture)
    TINY_FILE,
    _one_device_mesh,
    reference,
    runner,
    tiny,
)
from learning_at_home_tpu.models.transformer import AttentionLayer, DMoETransformerLM

pytestmark = pytest.mark.usefixtures("compiled_once")
limits = Limits(runner, reference, TINY_FILE)


def test_the_stack_as_it_is_reads_inside_the_runner_tolerances(tiny):
    read = limits.read(tiny)
    assert limits.outside(read, near_ties=True) == []
    assert len(read["embed_and_layers_rms"]) == 9  # the embedding, eight layers
    assert len(read["delta_layers_rms"]) == len(read["delta_states_rms"]) == 6
    # the mixer's output by its worst layer, the state by its median layer
    assert read["delta_rms"] == max(read["delta_layers_rms"])
    assert read["delta_state_rms"] == pytest.approx(
        np.median(read["delta_states_rms"]))
    assert read["delta_state_rms_max"] == max(read["delta_states_rms"])
    assert read["near_tie_share"] == 0.0


WRONG_REFERENCES = {
    "a_write_strength_without_the_factor_two": (
        dict(write_strength=jax.nn.sigmoid), ("delta_rms", "delta_state_rms")),
    "a_gate_applied_before_the_norm": (
        dict(output_gate=lambda o, z, scale, eps: reference.rms(
            o * jax.nn.silu(z), scale, eps)), ("delta_rms", "layers_rms")),
}


@pytest.mark.parametrize("name", sorted(WRONG_REFERENCES))
def test_a_wrong_delta_layer_fails_the_runner_tolerances(tiny, name):
    """Each read OUTSIDE the tolerance: the comparison can fail.  (The
    wrong side is the reference's copy; the program is as it is.)"""
    changes, outside = WRONG_REFERENCES[name]
    read = limits.read(tiny, reference=limits.reference_with(**changes))
    assert limits.none_inside(read, *outside), read


@pytest.mark.parametrize("name, changes, outside", [
    ("the_norm_on_each_parts_input", {"norm_place": "input"},
     ("layers_rms", "delta_rms")),
    ("a_rotated_full_attention_layer",
     {"layer_pattern": (AttentionLayer(None, False, "delta"),) * 3
      + (AttentionLayer(None, True),)}, ("layers_rms",)),
])
def test_a_wrong_program_fails_the_runner_tolerances(tiny, name, changes, outside):
    """The same weights under a program that norms a part's input, or that
    rotates the full layers' queries and keys, against the reference as it
    is."""
    model = DMoETransformerLM(
        dataclasses.replace(tiny[1], **changes), _one_device_mesh())
    read = limits.read(tiny, model)
    assert limits.none_inside(read, *outside), (name, read)


def test_a_hidden_that_composes_another_stack_fails_the_runner_tolerances(tiny):
    """``_hidden`` over a stack whose delta layers are skipped (the layers
    themselves as they are) reads outside ``hidden_token_median``."""
    model = DMoETransformerLM(tiny[1], _one_device_mesh())
    layer = model._layer
    model._layer = lambda lp, x, *rest: (
        (x, None) if "delta" in lp else layer(lp, x, *rest))
    assert limits.none_inside(limits.read(tiny, model), "hidden_token_median")


def test_lower_precisions_fail_the_runner_tolerances(tiny):
    """The reference with float8 operands in the program's place reads
    outside the layer, delta and logits limits, with bf16 operands inside;
    the program's rule with its decays summed in bf16 reads worse than
    with float32 sums."""
    held = ("layers_rms", "delta_rms", "logits_rms")
    assert limits.none_inside(
        limits.read(tiny, operand_dtype=jnp.float8_e4m3fn), *held)
    assert limits.inside(limits.read(tiny, operand_dtype=jnp.bfloat16), *held)
    exact = limits.read(tiny)
    rough = limits.read(tiny, decay_dtype=jnp.bfloat16)
    assert rough["delta_rms"] > 100 * exact["delta_rms"]
    assert rough["delta_state_rms"] > 100 * exact["delta_state_rms"]
