"""What remat keeps of the blocked kernel (its output and row sums, PR 38) and
of the attention part's products (PR 53), at the smallest shape the kernel
takes: the gradient of a two-layer stack AOT-compiled for a described (not
attached) ``v5e`` chip, and a forward-only program lowered for the TPU.  A
module apart from ``tests/test_olmoe_chip.py`` (the cell's own widths), so
that ``--dist loadfile`` can spread the compiles.
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from test_olmoe import _one_device_mesh, probe
from test_olmoe_chip import _no_compile_cache
from __graft_entry__ import glm_4_7_flash_one_chip, olmoe_one_chip
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.models.transformer import DMoETransformerLM


# ---- remat keeps the kernel's output and row sums (PR 38) ----


def _the_parents_formula(monkeypatch):
    """The layer's remat, the kernel's constructor and the attention
    part's products as the commit before any name wrote them (PR 38's
    parent): ``jax.checkpoint`` under no policy, the kernel's forward and
    the products (PR 53) naming nothing."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    checkpoint, make = jax.checkpoint, splash.make_splash_mha_single_device
    monkeypatch.setattr(
        jax, "checkpoint", lambda fn, policy=None, **kw: checkpoint(fn, **kw))
    monkeypatch.setattr(
        splash, "make_splash_mha_single_device",
        lambda residual_checkpoint_name=None, **kw: make(**kw))
    monkeypatch.setattr(trunk, "checkpoint_name", lambda x, name: x)


# By recipe: the changes that take its tiny block to the smallest shape the
# blocked kernel takes (512 positions, heads of 64, bf16; the latent form:
# two layers and the prediction block, heads of [48 | 16 rotated]); what a
# layer names, in the order it computes (the three products of
# ``qkv_projections``, the kernel's output and row sums, the output
# projection; in the latent form the two products down to the latents in
# the three's place: the three up from them are run again, not kept); its
# matrix products a layer; the kept results' width (q, k, v and the
# stream, or the two latents with the rotated key part and the stream).
_KERNEL_SIZED = {
    olmoe_one_chip: dict(
        changes=dict(d_model=256, n_heads=4),
        names=[trunk.ATTENTION_PRODUCTS] * 3 + [trunk.FLASH_RESIDUALS] * 2
        + [trunk.ATTENTION_PRODUCTS],
        products=4, kept_width=4 * 256),
    glm_4_7_flash_one_chip: dict(
        changes=dict(n_layers=2, ffn_pattern=("dense", "moe"), head_dim=64,
                     rope_head_dim=16),
        names=[trunk.ATTENTION_PRODUCTS] * 2 + [trunk.FLASH_RESIDUALS] * 2
        + [trunk.ATTENTION_PRODUCTS],
        products=6, kept_width=24 + (16 + 16) + 64),
}


def _kernel_sized(mesh, recipe=olmoe_one_chip, **changes):
    """A recipe's tiny block at the smallest shape the blocked kernel
    takes (``_KERNEL_SIZED``)."""
    _, cfg, _, _ = recipe(mesh, tiny=True)
    cfg = dataclasses.replace(
        cfg, seq_len=512, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        **(_KERNEL_SIZED[recipe]["changes"] | changes))
    model = DMoETransformerLM(cfg, mesh)
    assert model.attn_impl == "flash"
    return model, cfg


@pytest.mark.parametrize(
    "recipe", list(_KERNEL_SIZED), ids=["qkv_projections", "latent_qkv_projections"])
@pytest.mark.parametrize("formula", ["kept", "parents"])
def test_remat_recomputes_no_forward_kernel_call(
    v5e_chip, monkeypatch, formula, recipe
):
    """The gradient of a two-layer stack under ``remat``, compiled for a
    described chip at a small kernel shape: the traced step names the
    kernel's output and its row sums, two arrays a kernel layer, and both
    the traced and the compiled step hold ONE forward call a layer beside
    the fused backward's; under the parent's formula the same count reads two forwards a layer,
    so the count can tell.  The same of the attention part's matrix
    products (PR 53): the traced step names their results, the compiled
    step holds none of them under ``rematted_computation`` (in the latent
    form the three products up from the latents, which are not kept), and
    under the parent's formula four a layer (six in the latent form)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if formula == "parents":
        _the_parents_formula(monkeypatch)
    mesh = Mesh(np.array([v5e_chip]), ("expert",))
    model, cfg = _kernel_sized(mesh, recipe)
    sized = _KERNEL_SIZED[recipe]
    assert cfg.remat and cfg.n_layers == 2
    one = NamedSharding(mesh, P())
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32, sharding=one)
    traced = jax.jit(jax.value_and_grad(
        lambda p, i, t: model.loss_fn(p, i, t)[0])).trace(shapes, ids, ids)
    bodies = cfg.n_layers + cfg.mtp_layers
    forwards = bodies * (2 if formula == "parents" else 1)
    named = [eqn.params["name"]
             for _, eqn in probe._equations(traced.jaxpr.jaxpr, "name")]
    assert named == ([] if formula == "parents" else sized["names"] * bodies)
    # the output [B, H, S, hd] bf16 and the row sums [B, H, S] float32
    assert probe.kept_residual_bytes(traced.jaxpr.jaxpr) == (
        0 if formula == "parents" else bodies * 2 * 4 * 512 * (64 * 2 + 4))
    assert probe.kept_residual_bytes(  # bf16 [B, S, the kept width]
        traced.jaxpr.jaxpr, trunk.ATTENTION_PRODUCTS
    ) == (0 if formula == "parents" else bodies * 2 * 512 * sized["kept_width"] * 2)
    calls = collections.Counter(
        eqn.params["name"]
        for _, eqn in probe._equations(traced.jaxpr.jaxpr, "pallas_call"))
    want = {"splash_mha_fwd_residuals": forwards,
            "splash_mha_dkv_no_residuals": bodies}
    assert calls == want
    with _no_compile_cache():
        text = traced.lower().compile().as_text()
    assert probe.attention_kernel_calls(text) == want
    kept = sized["names"].count(trunk.ATTENTION_PRODUCTS)
    assert probe.recomputed_attention_products(text) == bodies * (
        sized["products"] - (0 if formula == "parents" else kept))


@pytest.mark.parametrize("program", ["apply", "cached_prefill"])
def test_an_undifferentiated_kernel_call_lowers_to_the_parents_text(
    monkeypatch, program
):
    """Outside a checkpoint a name is the identity: the model's forward
    and the cached decoder's prefill through the kernel lower for the TPU
    to the operations of the formula that names nothing, one for one and
    in order (the kernel's serialized module with them).  Since PR 53 the
    NUMBER at the end of private functions' symbols moves
    (``@argsort_<n>``, ``@_splash_attention_<n>``, ..): an equation takes
    its number from the module's symbol table while it is lowered, a
    ``name`` equation too, and a second kind of ``name`` equation (the
    products' beside the kernel's) collides with the first.  So a
    forward-only program is keyed anew in the compile cache once, and
    computes what it computed."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _one_device_mesh()

    def lowered():
        model, cfg = _kernel_sized(mesh)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        ids = jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
        if program == "apply":
            fn, args = jax.jit(lambda p, i: model.apply(p, i)[0]), (shapes, ids)
        else:
            fn = jax.jit(model.decode_model()._generate_cached, static_argnums=(2, 3))
            args = (shapes, ids, 4, 0.0, jax.random.PRNGKey(0))
        return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()

    def unnumbered(text):
        return re.sub(r"(@[A-Za-z_]+)_\d+\b", r"\1", text)

    # both from ONE line: the kernel's serialized module carries its
    # callers' line numbers, this one's too where the path is short
    texts = []
    for formula in (None, _the_parents_formula):
        if formula is not None:
            formula(monkeypatch)
        texts.append(lowered())
    text, parents = texts
    assert text != parents  # the numbers below do move: not the same runs
    assert text.count("splash_mha_fwd") >= 2  # a kernel call a layer
    assert unnumbered(parents) == unnumbered(text)
    moved = {a for a, b in zip(text.split(), parents.split()) if a != b}
    assert all(re.match(r"@[A-Za-z_]+_\d+\b", word) for word in moved), moved
