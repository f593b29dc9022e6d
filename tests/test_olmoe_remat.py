"""Remat changes no number: every recipe's tiny stack with and without
``jax.checkpoint`` around the layer, two compiled programs a case.  A module
apart from ``tests/test_olmoe.py`` (which holds the recipes' hashes), so that
``--dist loadfile`` can give the sixteen compiles a worker of their own.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_olmoe import _decisive, _one_device_mesh
from __graft_entry__ import (
    flagship_one_chip,
    glm_4_7_flash_one_chip,
    k_exaone_one_chip,
    nemotron_labs_twotower_one_chip,
    olmoe_one_chip,
    smallthinker_one_chip,
)
from learning_at_home_tpu.models.transformer import DMoETransformerLM


@pytest.mark.parametrize("recipe, norm_place", [
    (flagship_one_chip, "input"), (olmoe_one_chip, "input"),
    (smallthinker_one_chip, "input"), (k_exaone_one_chip, "input"),
    (glm_4_7_flash_one_chip, "input"), (nemotron_labs_twotower_one_chip, "input"),
    (olmoe_one_chip, "output"), (glm_4_7_flash_one_chip, "output"),
], ids=["dmoe", "olmoe", "smallthinker", "k-exaone", "glm-4.7-flash", "nemotron",
        "olmoe-norm-on-outputs", "glm-4.7-flash-norm-on-outputs"])
def test_remat_changes_no_loss_or_gradient(recipe, norm_place):
    """Every cell runs its per-layer trees under ``remat``; the plain
    references are compared without it.  From the same weights a recipe's
    tiny stack gives one loss and one set of gradients with and without
    ``jax.checkpoint`` around the layer: the dropless block's row gathers
    replay their ``custom_vjp`` under it, and so do the share's, the
    latent block's with its prediction block (which runs the same
    checkpointed layer) and the state-space kernels' plain forms.  The
    attention part's products are kept across the backward pass and not
    run again (PR 53: ``trunk.ATTENTION_PRODUCTS``), in both projection
    functions (``qkv_projections``; ``glm-4.7-flash``:
    ``latent_qkv_projections``) and with the norm on a part's input, as
    the six recipes have it, or on its output (``olmo-hybrid``'s place,
    whose own tiny stack differs by more with and without remat, on the
    parent too: its delta rule's plain form solves in another order)."""
    mesh = _one_device_mesh()
    under_remat, cfg, _, batch = recipe(mesh, tiny=True)
    assert cfg.remat and cfg.norm_place == "input"
    if norm_place != cfg.norm_place:
        cfg = dataclasses.replace(cfg, norm_place=norm_place)
        under_remat = DMoETransformerLM(cfg, mesh)
    plain = DMoETransformerLM(dataclasses.replace(cfg, remat=False), mesh)
    params = _decisive(plain.init_params(jax.random.PRNGKey(5)))
    rs = np.random.RandomState(9)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, cfg.seq_len + 1)))

    def loss_and_grads(model):
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, ids[:, :-1], ids[:, 1:])[0]))(params)

    want, want_grads = loss_and_grads(plain)
    got, got_grads = loss_and_grads(under_remat)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got_grads)[0],
        jax.tree_util.tree_leaves(want_grads),
    ):
        # the same operations in another compiled program: the order of a
        # few additions may differ (on one CPU device they read bit for
        # bit the same today), so a few ulp of the leaf's scale in the
        # leaf's own dtype (bf16 in the dmoe recipe)
        ulp = float(jnp.finfo(w.dtype).eps) * float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32)),
            rtol=0, atol=8 * ulp, err_msg=jax.tree_util.keystr(path),
        )
