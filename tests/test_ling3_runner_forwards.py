"""The limits of ``benchmarks/runners/train_recipe_ling3.py`` on the tiny
Ling-3.0 stack, the cases that compare no backward pass: each wrong FORWARD
and the reference at float8 read outside them, and the counters' limits tell
a share no group reaches (``tests/test_ling3_runner.py`` has the stack as it
is and the wrong steps)."""

import jax.numpy as jnp
import pytest

from test_ling3 import runner, tiny  # noqa: F401  (a fixture)
from test_ling3_runner import (  # noqa: F401  (a fixture)
    STEPS,
    a_wrong_program_fails,
    compiled_once,
    limits,
)

pytestmark = pytest.mark.usefixtures("compiled_once")


@pytest.mark.parametrize("name", sorted(set(runner.WRONG_PROGRAMS) - set(STEPS)))
def test_a_wrong_program_fails_the_runner_tolerances(tiny, name, monkeypatch):
    a_wrong_program_fails(tiny, name, monkeypatch)


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    read = limits.read(tiny, operand_dtype=jnp.float8_e4m3fn)
    assert len(limits.outside(read)) >= 4, {k: read[k] for k in runner.TOLERANCES}


def test_the_counters_limits_tell_a_share_no_group_reaches():
    good = {"dropped_fraction": [0.0], "local_rows_over_level": [1.0],
            "expert_load_max_over_mean": [1.4], "delta_decay_min": [0.02],
            "delta_beta_max": [0.9], "attention_gate_mean": [0.5],
            "groups_reaching_share": [0.5]}
    assert runner.share_problems(good) == []
    for name, bad in (("groups_reaching_share", 0.0), ("groups_reaching_share", 1.0),
                      ("delta_beta_max", 1.7), ("delta_decay_min", 0.0),
                      ("attention_gate_mean", 1.0), ("dropped_fraction", 0.01)):
        assert runner.share_problems({**good, name: [bad]}), name
