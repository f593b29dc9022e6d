"""Checkpoint/resume tests: sharded train state and per-expert server state."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from __graft_entry__ import (
    flagship_one_chip,
    glm_4_7_flash_one_chip,
    k_exaone_one_chip,
    nemotron_labs_twotower_one_chip,
    olmoe_one_chip,
    smallthinker_one_chip,
)
from learning_at_home_tpu.parallel import batch_sharding, make_mesh
from learning_at_home_tpu.utils.checkpoint import (
    CheckpointManager,
    TrainCheckpointer,
    latest_step,
    list_steps,
    mark_step_complete,
    next_step,
    prune_old_steps,
    save_pytree,
)


@pytest.mark.parametrize("recipe, axes", [
    (flagship_one_chip, {"data": 2, "expert": 4}),
    # the dropless recipes hold every expert on every device
    (olmoe_one_chip, {"data": 2, "expert": 1}),
    (smallthinker_one_chip, {"data": 2, "expert": 1}),
    (k_exaone_one_chip, {"data": 2, "expert": 1}),
    (glm_4_7_flash_one_chip, {"data": 2, "expert": 1}),
    (nemotron_labs_twotower_one_chip, {"data": 2, "expert": 1}),
], ids=["dmoe", "olmoe", "smallthinker", "k-exaone", "glm-4.7-flash", "nemotron"])
def test_train_checkpointer_roundtrip_sharded(tmp_path, recipe, axes):
    """Each recipe's tiny stack: a tuple of per-layer trees that differ
    (dense, mixture, state-space; the prediction block's ``mtp`` subtree)
    and its optimizer's state survive save and restore leaf for leaf,
    values and shardings, and the resumed step is the original's."""
    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n_dev])
    model, cfg, opt, batch = recipe(mesh, tiny=True)
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = model.init_opt_state(opt, params)
    step_fn = model.make_train_step(opt)

    rs = np.random.RandomState(0)
    ids, tgt = (
        jax.device_put(
            jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len))),
            batch_sharding(mesh),
        )
        for _ in range(2)
    )
    params, opt_state, loss1, _ = step_fn(params, opt_state, ids, tgt)

    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"), keep_last=2)
    ckpt.save(1, params, opt_state)
    assert latest_step(str(tmp_path / "ckpt")) == 1

    # fresh model instance restores onto the SAME shardings
    model2 = recipe(mesh, tiny=True)[0]
    params2 = model2.init_params(jax.random.PRNGKey(99))  # different values
    opt_state2 = model2.init_opt_state(opt, params2)
    restored = ckpt.restore_latest(params2, opt_state2)
    assert restored is not None
    step, rparams, ropt = restored
    assert step == 1
    assert isinstance(rparams["layers"], tuple)
    assert len(rparams["layers"]) == cfg.n_layers
    for saved, rest in ((params, rparams), (opt_state, ropt)):
        assert jax.tree_util.tree_structure(saved) == (
            jax.tree_util.tree_structure(rest))
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(saved)[0],
            jax.tree_util.tree_leaves(rest),
        ):
            name = jax.tree_util.keystr(path)
            assert a.dtype == b.dtype, name
            np.testing.assert_array_equal(
                np.asarray(a.astype(jnp.float32)),
                np.asarray(b.astype(jnp.float32)), err_msg=name)
            assert b.sharding.is_equivalent_to(a.sharding, a.ndim), (
                name, a.sharding, b.sharding)
    moe = next(lp for lp in rparams["layers"] if "moe" in lp)["moe"]
    stacks = [name for name in moe if name.startswith("w")]  # the experts'
    assert stacks and all(
        "expert" in str(moe[name].sharding.spec) for name in stacks)
    # resumed training continues identically
    _, _, loss_resumed, _ = step_fn(rparams, ropt, ids, tgt)
    _, _, loss_orig, _ = step_fn(params, opt_state, ids, tgt)
    np.testing.assert_allclose(float(loss_resumed), float(loss_orig), rtol=1e-5)


def test_train_checkpointer_prunes(tmp_path):
    ckpt = TrainCheckpointer(str(tmp_path / "c"), keep_last=2)
    tree = {"a": jnp.ones(3)}
    for s in (1, 2, 3, 4):
        ckpt.save(s, tree, tree)
    assert list_steps(str(tmp_path / "c")) == [3, 4]


# ---------------------------------------------------------------------------
# crash safety (ISSUE 9 satellites): a kill mid-save must never corrupt
# recovery, and pruning must never delete the only complete step
# ---------------------------------------------------------------------------


def _crash_mid_save(root: str, step: int, tree):
    """Simulate a kill between item writes and the completion marker:
    the step directory exists with saved items but NO marker."""
    save_pytree(root, step, "params", tree)
    # crash here: mark_step_complete(root, step) never runs


def test_restore_latest_ignores_kill_mid_save(tmp_path):
    """Kill mid-save → restore_latest returns the last COMPLETE step."""
    root = str(tmp_path / "crash")
    tree_v1 = {"a": jnp.arange(4.0)}
    ckpt = TrainCheckpointer(root, keep_last=3)
    ckpt.save(1, tree_v1, tree_v1)
    # a newer save dies after writing items but before the marker
    _crash_mid_save(root, 2, {"a": jnp.zeros(4)})
    assert latest_step(root) == 1
    assert list_steps(root, only_complete=False) == [1, 2]
    restored = ckpt.restore_latest(tree_v1, tree_v1)
    assert restored is not None
    step, params, _ = restored
    assert step == 1
    np.testing.assert_array_equal(np.asarray(params["a"]), np.arange(4.0))
    # the next save NEVER reuses the crashed step's directory (a retry
    # merging into half-written items would be unverifiable)
    assert next_step(root) == 3


def test_prune_never_deletes_only_complete_step(tmp_path):
    root = str(tmp_path / "prune")
    tree = {"a": jnp.ones(2)}
    save_pytree(root, 5, "item", tree)
    mark_step_complete(root, 5)
    # crashed half-saves around it, newer and older
    _crash_mid_save(root, 3, tree)
    _crash_mid_save(root, 7, tree)
    prune_old_steps(root, keep_last=1)
    # the only complete step survives any keep_last >= 1 ...
    assert list_steps(root) == [5]
    # ... the OLD crashed step is swept, and the NEWEST directory is
    # kept (it may be another process's save still in progress)
    assert list_steps(root, only_complete=False) == [5, 7]
    prune_old_steps(root, keep_last=5)
    assert list_steps(root) == [5]


def test_checkpoint_manager_periodic_prune_and_restart_counter(tmp_path):
    import time

    root = str(tmp_path / "mgr")
    tree = {"a": jnp.ones(2)}

    def save_fn(step):
        save_pytree(root, step, "item", tree)
        mark_step_complete(root, step)

    mgr = CheckpointManager(root, keep_last=2)
    assert mgr.save_now(save_fn) == 1
    assert mgr.save_now(save_fn) == 2
    assert mgr.save_now(save_fn) == 3
    assert list_steps(root) == [2, 3]  # pruned to keep_last
    assert mgr.saves == 3

    # a failing save_fn is counted, never raises out of the manager
    def bad_save(step):
        raise RuntimeError("disk full")

    assert mgr.save_now(bad_save) is None
    assert mgr.save_failures == 1

    # periodic thread keeps stepping until stopped
    mgr2 = CheckpointManager(root, keep_last=2)
    mgr2.start_periodic(save_fn, every_s=0.05)
    deadline = time.time() + 10
    while time.time() < deadline and mgr2.saves < 2:
        time.sleep(0.05)
    mgr2.stop()
    assert mgr2.saves >= 2
    saved = mgr2.saves
    time.sleep(0.2)
    assert mgr2.saves == saved  # really stopped

    # restart counter persists across manager instances (it counts the
    # restarts it survives)
    assert mgr.restart_count() == 0
    assert mgr.record_restart() == 1
    assert CheckpointManager(root).restart_count() == 1
    assert CheckpointManager(root).record_restart() == 2


def test_server_checkpoint_resume(tmp_path):
    from learning_at_home_tpu.server.server import background_server

    root = str(tmp_path / "server_ckpt")
    with background_server(num_experts=2, hidden_dim=16, seed=1) as (ep, srv):
        # do one update so state differs from init
        x = np.random.RandomState(0).randn(4, 16).astype(np.float32)
        g = np.ones((4, 16), np.float32)
        srv.experts["expert.0"].backward([x], [g])
        srv.save_checkpoint(root, step=7)
        want = {
            uid: b.state_dict()["params"] for uid, b in srv.experts.items()
        }

    # a NEW server (fresh params) restores the snapshot
    with background_server(num_experts=2, hidden_dim=16, seed=999) as (ep, srv2):
        restored_step = srv2.load_checkpoint(root)
        assert restored_step == 7
        for uid, backend in srv2.experts.items():
            got = backend.state_dict()["params"]
            for a, b in zip(
                jax.tree_util.tree_leaves(want[uid]),
                jax.tree_util.tree_leaves(got),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert srv2.experts["expert.0"].update_count == 1
