"""The chip bring-up surface, kept from rotting between chip runs.

``chip_smoke.py`` only ever passes on a TPU; these drive its two phases at
tiny shapes with the expected platform given as the argument ``"cpu"``,
and pin the rules PR 21 set: no default platform for a spawned process,
one compile-cache location, one ``device_kind`` table, no second process
on a held chip.
"""

import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture
def cache_in_tmp(tmp_path, monkeypatch):
    """Children inherit the environment: their compile cache goes where
    JAX_COMPILATION_CACHE_DIR says, which must be the only place."""
    cache = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    return cache


def test_import_chip_smoke_leaves_jax_out():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "assert 'jax' not in sys.modules, 'parent imported jax'; "
         "assert 'learning_at_home_tpu' not in sys.modules"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr[-2000:]


def test_trainer_phase_tiny_on_cpu(cache_in_tmp):
    import chip_smoke

    report = chip_smoke.trainer_phase("cpu", tiny=True)
    assert report["device"]["platform"] == "cpu"
    assert len(report["losses"]) == 5
    assert report["losses"][-1] < report["losses"][0]
    assert report["compile_cache_dir"] == str(cache_in_tmp)
    assert report["compile_cache_misses"] > 0
    assert any(cache_in_tmp.iterdir()), "cache not written where told"


def test_trainer_phase_refuses_the_wrong_platform():
    """Expecting a TPU on this machine must fail, not move to the CPU."""
    import chip_smoke

    with pytest.raises(chip_smoke.PhaseFailed, match="trainer exited rc="):
        chip_smoke.trainer_phase("tpu", tiny=True)


def test_run_trainer_checks_layout_on_four_devices(cache_in_tmp):
    """The four-chip probe's work (tools/chip_probe.py fourchip) on four
    virtual devices: expert stacks split over the expert axis, trunk
    replicated, optimizer state where opt_state_shardings says.  (In this
    process; ``cache_in_tmp`` keeps the helper from pointing the whole
    pytest session's jax at the checkout's cache.)"""
    import chip_smoke

    report = chip_smoke.run_trainer(
        "cpu", tiny=True, mesh_axes={"data": 2, "expert": 2}, steps=1
    )
    assert report["expert_param_bytes_per_device"] * 2 == (
        report["expert_param_bytes"]
    )
    assert len(report["peak_bytes_in_use"]) == 4


def test_server_phase_tiny_on_cpu(cache_in_tmp):
    import chip_smoke

    report = chip_smoke.server_phase("cpu", tiny=True)
    assert report["server_platform"] == "cpu"
    assert report["samples_dropped"] == 0
    assert 0 < report["server_updates"] <= report["backward_rpcs_sent"]
    assert any(cache_in_tmp.iterdir()), "server wrote no compile cache"


def test_compile_cache_dir_is_fixed_or_from_env(tmp_path):
    """Unset: the same in-checkout path from any cwd and pid.  Set: JAX's
    own variable wins and the helper sets no other directory."""
    code = (
        "from learning_at_home_tpu.utils.chip import enable_compile_cache; "
        "import jax; d = enable_compile_cache(); "
        "print('DIR=' + d + '|' + str(jax.config.jax_compilation_cache_dir))"
    )

    def run(cwd, env_dir=None):
        env = dict(os.environ, PYTHONPATH=REPO)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        r = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        line = [l for l in r.stdout.splitlines() if l.startswith("DIR=")][-1]
        return line[4:].split("|")

    expected = os.path.join(REPO, ".jax_compile_cache")
    assert run(REPO) == [expected, expected]
    assert run(str(tmp_path)) == [expected, expected]
    told = str(tmp_path / "elsewhere")
    assert run(str(tmp_path), env_dir=told) == [told, told]


def test_device_kind_table_raises_on_unknown_kind():
    from learning_at_home_tpu.utils.chip import hbm_bytes, peak_bf16_flops

    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert peak_bf16_flops(v5e) == 197e12
    with pytest.raises(KeyError, match="TPU v9 turbo"):
        peak_bf16_flops(types.SimpleNamespace(device_kind="TPU v9 turbo"))
    # the CPU backend reports no memory limit: an error, never 16e9
    import jax

    with pytest.raises(RuntimeError, match="bytes_limit"):
        hbm_bytes(jax.devices()[0])


def test_subprocess_env_needs_a_platform():
    from learning_at_home_tpu.utils.subproc import (
        clean_jax_subprocess_env,
        spawn_expert_servers,
    )

    with pytest.raises(TypeError):
        clean_jax_subprocess_env()
    with pytest.raises(TypeError):
        clean_jax_subprocess_env(REPO)
    assert clean_jax_subprocess_env(REPO, platform="cpu")["JAX_PLATFORMS"] == "cpu"
    with pytest.raises(TypeError):
        spawn_expert_servers(REPO, "x", (0.0,))


def test_launchers_refuse_to_share_a_chip():
    from learning_at_home_tpu.utils.subproc import (
        require_free_chip,
        spawn_expert_servers,
    )

    require_free_chip("cpu", 8, "test")  # CPU children never contend
    require_free_chip("tpu", 1, "test")  # this process holds only the CPU
    with pytest.raises(RuntimeError, match="would share one chip"):
        spawn_expert_servers(REPO, "x", (0.0, 0.0), platform="tpu")
