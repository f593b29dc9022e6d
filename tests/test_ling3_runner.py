"""The limits of ``benchmarks/runners/train_recipe_ling3.py`` on the tiny
Ling-3.0 stack: the stack as it is reads inside them and each wrong STEP
outside (the cases that compare the backward pass and the update: they share
the comparison's compiled programs, ``compiled_once``).  The wrong forwards,
the reference at float8 and the counters' limits are
``tests/test_ling3_runner_forwards.py``'s.  Modules apart from
``tests/test_ling3.py`` (whose fixture and files they use) so that ``--dist
loadfile`` can give the comparison's compiles workers of their own."""

import pytest

from runner_limits import Limits, compiled_once  # noqa: F401  (a fixture)
from test_ling3 import TINY_FILE, reference, runner, tiny  # noqa: F401  (a fixture)

pytestmark = pytest.mark.usefixtures("compiled_once")
limits = Limits(runner, reference, TINY_FILE)
STEPS = sorted(name for name in runner.WRONG_PROGRAMS if name.startswith("the step"))


def test_the_stack_as_it_is_reads_inside_the_runner_tolerances(tiny):
    read = limits.read(tiny)
    assert limits.outside(read) == [], read
    assert len(read["delta_layers_rms"]) == 3 and len(read["attention_layers_rms"]) == 1
    assert read["near_tie_shares"][0] == 0.0 and read["step_read"]
    assert max(read[k] for k in runner.GRADIENT_READINGS) < 1e-3
    # the backward pass is compared for every layer with a mixture (both
    # kinds: a KDA mixer, the latent one) and the head; the leading dense
    # layer's leaves and the embedding are held to having moved
    assert list(runner.compared_layers(tiny[2]["layers"])) == [1, 2, 3]
    assert len(read["grad_stream_layers_rms"]) == 4
    assert read["leaves_held_to_moving"] >= 1


def a_wrong_program_fails(tiny, name, monkeypatch):
    """Each named fault falls outside one limit at least, at tiny sizes in
    float32 (where a bf16 sum of decays is the only rounding there is)."""
    if "leading layer" in name:
        # the tiny leading layer's out-projection has 1,536 elements, the
        # cell's 10.5 M: a leaf is held to moving on its own from SMALL_LEAF
        # up (a bf16 norm scale of ones cannot move by 1e-4 of itself)
        monkeypatch.setattr(runner, "SMALL_LEAF", 1024)
    read = limits.read(tiny, **runner.WRONG_PROGRAMS[name])
    if "leading layer" in name:
        assert read["update_norm"] == 1.0  # what a state left unchanged reads
        assert read["update_norm_worst_leaf"] == "['layers'][0]['delta']['w_out']"
    if "bfloat16" in name:
        # 64 positions of float32 hold no rounding but this one, and a chunk
        # of 16 sums little: the limit is the chip's (PERF.md section 2), and
        # here the reading is a thousand times the program's own
        assert read["delta_rms"] > 3e-4 and read["delta_state_rms"] > 3e-4
        return
    assert limits.outside(read), {k: read[k] for k in runner.TOLERANCES}


@pytest.mark.parametrize("name", STEPS)
def test_a_wrong_program_fails_the_runner_tolerances(tiny, name, monkeypatch):
    a_wrong_program_fails(tiny, name, monkeypatch)
