"""The guard of a public-model cell of the benchmark, once: ``selfcheck.py``
passes on the manifest and on the cell's rehearsal; the cell is its
configuration under its traffic on its chips and the rate lists it; it
reports its count of readings and the ones named; and the runner rehearses
at tiny sizes on the CPU, untraced and traced, ``correct``, with what its
row says of the result line and of ``SETUP`` / ``COUNTERS`` / ``REFERENCE``.

Everything is said in READINGS, the part of a metric's name after the
first dot (``olmoe.step_ms_p50`` and ``train.step_ms_p50`` are both
``step_ms_p50``), and looked up by name: what a cell should hold is read
from the manifest, so a fold that renames, reorders or merges entries is
followed and one that loses a reading is caught.

This module holds ``Row``, ``ROWS`` and the guard (``rehearse``) and is no
test module itself.  A rehearsal is two cold runs of ``benchmarks/run.py``,
30 to 90 s, and under ``--dist loadfile`` a file is one worker's: so each
row runs from a module of its own, ``tests/test_benchmark_cell_<model>.py``,
which is one call of ``rehearsal_of``.  A new configuration adds a row to
``ROWS`` HERE and such a module; ``tests/test_benchmark_cells.py`` fails
while a row has no module or a module no row.
"""

import json
import os
import subprocess
import sys
from typing import Callable, NamedTuple

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

LEVELLING = "load_max_over_mean_before_and_after_levelling"


def reading_of(entry: dict) -> str:
    return entry["name"].split(".", 1)[-1]


def readings_of_cell(manifest: dict, cell: str) -> dict:
    """``{reading: entry}`` of the per-layer metrics reported in ``cell``;
    two entries with one reading there (a fold that left a copy behind)
    fail."""
    readings: dict = {}
    for entry in harness.metrics_of_cell(manifest["per_layer"], cell):
        reading = reading_of(entry)
        assert reading not in readings, (
            f"{cell}: {readings[reading]['name']!r} and {entry['name']!r} "
            f"are both the reading {reading!r}")
        readings[reading] = entry
    return readings


def layer_metric_file(manifest: dict, entry: dict) -> dict:
    return harness.load_json(harness.find_file(
        manifest, "layer_metrics", entry["name"] + ".json"))


# ---- what a row says of the SETUP, COUNTERS and REFERENCE lines ----


def _glm_lines(setup, counters, reference):
    assert "ce_mtp" in counters
    assert "mtp_logits_rms" in reference


def _nemotron_lines(setup, counters, reference):
    assert "ssm_decay_min" in counters
    assert "ssm_rms" in reference and "ssm_state_rms" in reference
    assert len(reference["ssm_layers_rms"]) == 4


def _olmo_hybrid_lines(setup, counters, reference):
    assert setup["expert_param_bytes"] == 0 and setup["param_bytes_per_device"] > 0
    assert set(counters) == {"delta_decay_min", "delta_beta_max"}
    assert len(reference["delta_layers_rms"]) == len(reference["delta_states_rms"]) == 6


def _qwen3next_lines(setup, counters, reference):
    assert setup["expert_param_bytes"] > 0 and "level_router_bias" in setup["phases"]
    assert {"dropped_fraction", "held_experts_empty", "delta_decay_min",
            "delta_beta_max", "attention_gate_mean", "shared_gate_mean"} <= set(counters)
    assert counters["delta_beta_max"]["max"] <= 1.0  # sigmoid(b): no factor 2
    assert len(reference["delta_layers_rms"]) == len(reference["delta_states_rms"]) == 3
    assert len(reference["attention_layers_rms"]) == 1
    assert len(reference["router_logits_layers_rms"]) == 4  # every layer routes
    # the backward pass and the update, float32 here: every leaf of a period
    assert len(reference["grad_stream_layers_rms"]) == 5
    for name in ("grads_rms", "grad_stream_rms", "step_grad_norms", "update_norm"):
        assert 0.0 <= reference[name] < 1e-4, (name, reference[name])


def _sdar_lines(setup, counters, reference):
    assert setup["expert_param_bytes"] > 0 and setup["step_programs"] >= 2
    # the set-up's placement: each layer's rows here over level, before | after
    assert all(abs(after - 1.0) <= abs(before - 1.0) + 1e-9 and 0.7 < after < 1.3
               for before, after, _, _ in setup[LEVELLING]), setup[LEVELLING]
    assert {"dropped_fraction", "held_experts_empty", "masked_share",
            "loss_weight_mean", "attention_admitted_pairs",
            "attention_visited_pairs"} <= set(counters)
    # 8 blocks of 4 a row, static: L'^2 nb (nb + 1); the xla core visits all
    assert counters["attention_admitted_pairs"]["min"] == 16 * 8 * 9
    assert counters["attention_visited_pairs"]["max"] == 64 * 64
    assert reference["noise_mismatches"] == 0.0
    assert len(reference["attention_layers_rms"]) == 2
    assert len(reference["grad_stream_layers_rms"]) == 3
    for name in ("grads_rms", "grad_stream_rms", "step_grad_norms",
                 "step_gate_grad_norms", "update_norm", "update_over_rule",
                 "update_total"):
        assert 0.0 <= reference[name] < 1e-4, (name, reference[name])


def _lfm2_lines(setup, counters, reference):
    assert setup["expert_param_bytes"] > 0
    assert {"dropped_fraction", "expert_load_max_over_mean",
            "router_bias_abs_max", "shortconv_out_rms"} <= set(counters)
    assert counters["shortconv_out_rms"]["min"] > 0.05  # no dead gate
    assert len(reference["shortconv_layers_rms"]) == 2  # C A C at tiny sizes
    assert len(reference["attention_layers_rms"]) == 1
    assert reference["near_tie_shares"][0] == 0.0  # the dense layer routes nothing
    assert len(reference["grad_stream_layers_rms"]) == 4
    for name in ("grads_rms", "grad_stream_rms", "step_grad_norms", "update_norm"):
        assert 0.0 <= reference[name] < 1e-4, (name, reference[name])


def _xing4_lines(setup, counters, reference):
    assert setup["expert_param_bytes"] > 0
    assert {"ce_mtp", "hc_res_marginal_error", "hc_stream_rms_spread"} <= set(counters)
    assert counters["hc_res_marginal_error"]["max"] < 1e-3
    assert 1.0 <= counters["hc_stream_rms_spread"]["max"] < 4.0
    # two layers and the prediction block's, at tiny sizes
    assert len(reference["stream_layers_rms"]) == 3
    assert len(reference["hc_coeff_layers_rms"]) == 3
    assert "mtp_logits_rms" in reference
    assert len(reference["grad_stream_stages_rms"]) == 6
    for name in ("grads_rms", "grad_stream_rms", "step_grad_norms"):
        assert 0.0 <= reference[name] < 1e-3, (name, reference[name])
    # the block's ``phi`` moves by parts in 1e4 of itself a step here: its
    # change reads 4e-5 to 1e-3 apart by how many steps the 2 s held
    assert 0.0 <= reference["update_norm"] < 1e-2, reference["update_norm"]


def _ling3_lines(setup, counters, reference):
    assert setup["expert_param_bytes"] > 0
    assert {"dropped_fraction", "groups_reaching_share", "delta_decay_min",
            "delta_beta_max", "attention_gate_mean",
            "router_bias_abs_max"} <= set(counters)
    assert counters["delta_beta_max"]["max"] <= 1.0  # sigmoid(b): no factor 2
    assert counters["delta_decay_min"]["min"] > 6e-3  # the gate's bound: e^-5
    assert 0.0 < counters["groups_reaching_share"]["min"] <= 1.0
    # a dense KDA layer, then KDA KDA latent with mixtures, at tiny sizes
    assert len(reference["delta_layers_rms"]) == len(reference["delta_states_rms"]) == 3
    assert len(reference["attention_layers_rms"]) == 1
    assert reference["near_tie_shares"][0] == 0.0  # the dense layer routes nothing
    # the head and the three layers with a mixture (both kinds of mixer:
    # train_recipe_ling3.compared_layers); the rest held to having moved
    assert len(reference["grad_stream_layers_rms"]) == 4
    assert reference["leaves_held_to_moving"] >= 1
    for name in ("grads_rms", "grad_stream_rms", "step_grad_norms", "update_norm"):
        assert 0.0 <= reference[name] < 1e-3, (name, reference[name])


class Row(NamedTuple):
    cell: str
    config: str
    traffic: str
    chips: int
    rehearsal: str  # its manifest under benchmarks/rehearsal/
    seed: int
    count: int  # readings the cell reports
    named: tuple = ()  # readings it must report
    traced: tuple = ("expert_load_max_over_mean",)  # its traced line must hold
    levelled: int | None = None  # mixture layers SETUP says were levelled
    lines: Callable | None = None  # what else SETUP, COUNTERS, REFERENCE hold
    levelling_phase: str = "level_router_bias"  # the set-up phase that levels


_LEVELLED = ("local_rows_over_level", "expert_load_max_over_mean", "step_ms_p50")
ROWS = (
    Row("olmoe-1b-7b-train-zipf4k", "olmoe-1b-7b", "train-zipf4k", 1,
        "manifest_olmoe.json", 2700000001, 10),
    Row("smallthinker-21b-a3b-train-zipf16k", "smallthinker-21b-a3b",
        "train-zipf16k", 1, "manifest_smallthinker.json", 3100000001, 13),
    Row("k-exaone-236b-a23b-train-zipf16k", "k-exaone-236b-a23b",
        "train-zipf16k", 1, "manifest_kexaone.json", 3300000007, 16,
        traced=_LEVELLED, levelled=4),
    Row("glm-4.7-flash-train-zipf16k", "glm-4.7-flash", "train-zipf16k", 1,
        "manifest_glm47.json", 3700000007, 18,
        ("attention_latent_share", "mtp_share", "attention_core_roofline",
         "expert_matmul_roofline"),
        _LEVELLED, 5, _glm_lines),
    Row("nemotron-labs-twotower-30b-a3b-train-zipf16k",
        "nemotron-labs-twotower-30b-a3b", "train-zipf16k", 1,
        "manifest_nemotron.json", 3900000007, 20,
        ("ssm_share", "ssm_scan_share", "ssm_proj_share", "ssm_conv_share",
         "ssm_scan_roofline", "attention_core_roofline", "expert_matmul_roofline"),
        _LEVELLED, 4, _nemotron_lines),
    Row("olmo-hybrid-7b-train-zipf16k", "olmo-hybrid-7b", "train-zipf16k", 1,
        "manifest_olmohybrid.json", 4500000007, 15,
        ("mfu", "delta_share", "delta_core_share", "delta_core_roofline",
         "attention_core_roofline", "delta_gate_norm_share"),
        ("step_ms_p50",), 0, _olmo_hybrid_lines),  # no mixture layer to level
    Row("qwen3-next-80b-a3b-train-zipf16k", "qwen3-next-80b-a3b", "train-zipf16k",
        1, "manifest_qwen3next.json", 5500000007, 22,
        ("mfu", "delta_share", "delta_core_share", "delta_core_roofline",
         "attention_core_roofline", "expert_matmul_roofline",
         "delta_gate_norm_share", "attention_gate_share", "shared_expert_share"),
        ("step_ms_p50", "expert_load_max_over_mean", "local_rows_over_level"),
        0, _qwen3next_lines),  # a share with no selection bias: nothing to level
    Row("sdar-30b-a3b-train-zipf8k", "sdar-30b-a3b", "train-zipf8k", 1,
        "manifest_sdar.json", 5700000007, 17,
        ("mfu", "attention_core_roofline", "expert_matmul_roofline",
         "noise_share", "attention_visited_over_admitted", "masked_share"),
        ("step_ms_p50", "expert_load_max_over_mean", "local_rows_over_level",
         "attention_visited_over_admitted", "masked_share"),
        2, _sdar_lines,  # no selection bias: the runner's own set-up phase
        "remake_gates_and_place_experts"),
    Row("lfm2-8b-a1b-train-zipf16k", "lfm2-8b-a1b", "train-zipf16k", 1,
        "manifest_lfm2.json", 6100000007, 18,
        ("mfu", "shortconv_share", "shortconv_proj_share", "shortconv_core_share",
         "shortconv_core_roofline", "attention_core_roofline",
         "expert_matmul_roofline", "dense_ffn_share"),
        ("step_ms_p50", "expert_load_max_over_mean"), 2, _lfm2_lines),
    Row("xing4.0-29b-a4b-train-zipf16k", "xing4.0-29b-a4b", "train-zipf16k", 1,
        "manifest_xing4.json", 6400000007, 22,
        ("mfu", "hc_share", "hc_coeff_share", "hc_mix_share", "hc_mix_roofline",
         "attention_latent_share", "mtp_share", "attention_core_roofline",
         "expert_matmul_roofline"),
        _LEVELLED, 2, _xing4_lines),
    Row("ling-3.0-flash-vl-train-zipf16k", "ling-3.0-flash-vl", "train-zipf16k", 1,
        "manifest_ling3.json", 6600000007, 26,
        ("mfu", "kda_share", "kda_core_share", "kda_core_roofline",
         "kda_decay_share", "kda_proj_share", "kda_conv_share",
         "kda_gate_norm_share", "attention_latent_share", "router_groups_share",
         "groups_reaching_share", "attention_core_roofline",
         "expert_matmul_roofline"),
        _LEVELLED + ("groups_reaching_share",), 3, _ling3_lines),
)


def rehearse(row: Row, tmp_path) -> None:
    """``selfcheck.py`` on the manifest and on the cell's rehearsal, the
    cell and its readings as the manifest has them, then the cell's runner
    for 2 s at tiny sizes on the CPU, untraced and traced: it cannot rot
    unrun."""
    from learning_at_home_tpu.utils.subproc import clean_jax_subprocess_env

    env = clean_jax_subprocess_env(REPO, platform="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    rehearsal = "benchmarks/rehearsal/" + row.rehearsal
    check = subprocess.run(
        [sys.executable, "benchmarks/selfcheck.py", "BENCHMARK.json",
         "benchmarks/rehearsal/manifest.json", rehearsal],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert check.returncode == 0 and "selfcheck: ok" in check.stdout, check.stdout

    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = harness.by_name(manifest["workloads"], row.cell, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        row.config, row.traffic, row.chips)
    rate = harness.by_name(manifest["end_to_end"], "train_tokens_per_s_per_chip", "metric")
    assert row.cell in rate["workloads"]
    readings = readings_of_cell(manifest, row.cell)
    assert len(readings) == row.count, sorted(readings)
    assert set(row.named) <= set(readings), set(row.named) - set(readings)

    # the names a rehearsal's line holds are its OWN manifest's for the cell
    rehearsed = readings_of_cell(harness.load_json(os.path.join(REPO, rehearsal)), row.cell)

    def printed(reading):
        return "cpu_rehearsal." + rehearsed[reading]["name"]

    for trace in ("0", "1"):
        run = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--manifest", rehearsal,
             "--workload", row.cell, "--seed", str(row.seed), "--seconds", "2",
             "--trace", trace],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        line = json.loads(run.stdout.strip().splitlines()[-1])
        problems = [l for l in run.stderr.splitlines() if l.startswith("INCORRECT")]
        assert line["correct"] is True and line["failed"] == 0, (
            problems or run.stderr[-2000:])
        names = set(line["metrics"])
        if trace == "0":
            assert names == {"cpu_rehearsal.train_tokens_per_s_per_chip",
                             "cpu_rehearsal.setup_s"}
        else:  # a CPU has no peak: the shares of one are left out
            if "moe_dropped_share" in rehearsed:  # a stack with a mixture layer
                assert line["metrics"][printed("moe_dropped_share")]["value"] == 0.0
            assert {printed(reading) for reading in row.traced} <= names
            assert not any("mfu" in n or "roofline" in n for n in names)
    if row.levelled is None:
        return
    said = {l.split(" ", 1)[0]: json.loads(l.split(" ", 1)[1])
            for l in run.stdout.splitlines()
            if l.startswith(("SETUP ", "COUNTERS ", "REFERENCE "))}
    assert len(said["SETUP"][LEVELLING]) == row.levelled
    if row.levelled:
        assert row.levelling_phase in said["SETUP"]["phases"]
    if row.lines:
        row.lines(said["SETUP"], said["COUNTERS"], said["REFERENCE"])


def rehearsal_of(cell: str) -> Callable:
    """The test a row's module holds: ``rehearse`` on the row of ``cell``,
    under the name and the id it had as one case of seven."""
    row = next(row for row in ROWS if row.cell == cell)

    @pytest.mark.parametrize("row", [row], ids=[cell])
    def test_benchmark_manifests_pass_selfcheck_and_the_runner_rehearses(row, tmp_path):
        rehearse(row, tmp_path)

    return test_benchmark_manifests_pass_selfcheck_and_the_runner_rehearses
