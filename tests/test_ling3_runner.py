"""The limits of ``benchmarks/runners/train_recipe_ling3.py`` on the tiny
Ling-3.0 stack: the stack as it is reads inside them, each named wrong
program and the reference at float8 outside, and the counters' limits tell
a share no group reaches.  A module apart from ``tests/test_ling3.py``
(whose fixture and files it uses) so that ``--dist loadfile`` can give the
comparison's compiles a worker of their own."""

import jax.numpy as jnp
import pytest

from test_ling3 import TINY_FILE, reference, runner, tiny  # noqa: F401  (a fixture)


def _compare(tiny, **how):
    model, _, params, ids, tgt = tiny
    return runner.compare_with_reference(
        model, params, reference, TINY_FILE, ids[:1], tgt[:1], **how)


def test_the_stack_as_it_is_reads_inside_the_runner_tolerances(tiny):
    read = _compare(tiny)
    limits = {**runner.TOLERANCES, "near_tie_share": 1.0}
    assert all(read[k] <= limit for k, limit in limits.items()), {
        k: read[k] for k, limit in limits.items() if not read[k] <= limit}
    assert len(read["delta_layers_rms"]) == 3 and len(read["attention_layers_rms"]) == 1
    assert read["near_tie_shares"][0] == 0.0 and read["step_read"]
    assert max(read[k] for k in runner.GRADIENT_READINGS) < 1e-3
    # the backward pass is compared for every layer with a mixture (both
    # kinds: a KDA mixer, the latent one) and the head; the leading dense
    # layer's leaves and the embedding are held to having moved
    assert list(runner.compared_layers(tiny[2]["layers"])) == [1, 2, 3]
    assert len(read["grad_stream_layers_rms"]) == 4
    assert read["leaves_held_to_moving"] >= 1


@pytest.mark.parametrize("name", sorted(runner.WRONG_PROGRAMS))
def test_a_wrong_program_fails_the_runner_tolerances(tiny, name, monkeypatch):
    """Each named fault falls outside one limit at least, at tiny sizes in
    float32 (where a bf16 sum of decays is the only rounding there is)."""
    if "leading layer" in name:
        # the tiny leading layer's out-projection has 1,536 elements, the
        # cell's 10.5 M: a leaf is held to moving on its own from SMALL_LEAF
        # up (a bf16 norm scale of ones cannot move by 1e-4 of itself)
        monkeypatch.setattr(runner, "SMALL_LEAF", 1024)
    read = _compare(tiny, **runner.WRONG_PROGRAMS[name])
    if "leading layer" in name:
        assert read["update_norm"] == 1.0  # what a state left unchanged reads
        assert read["update_norm_worst_leaf"] == "['layers'][0]['delta']['w_out']"
    if "bfloat16" in name:
        # 64 positions of float32 hold no rounding but this one, and a chunk
        # of 16 sums little: the limit is the chip's (PERF.md section 2), and
        # here the reading is a thousand times the program's own
        assert read["delta_rms"] > 3e-4 and read["delta_state_rms"] > 3e-4
        return
    over = [k for k, limit in runner.TOLERANCES.items()
            if k != "near_tie_share" and not read[k] <= limit]
    assert over, {k: read[k] for k in runner.TOLERANCES}


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny):
    read = _compare(tiny, operand_dtype=jnp.float8_e4m3fn)
    over = [k for k, limit in runner.TOLERANCES.items()
            if k != "near_tie_share" and not read[k] <= limit]
    assert len(over) >= 4, {k: read[k] for k in runner.TOLERANCES}


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
