"""OLMoE's block in the pod step (``__graft_entry__.olmoe_one_chip``)
against its plain reference (``benchmarks/configs/olmoe_1b_7b_reference.py``),
the dropless path's properties, and the benchmark's runner for it.

Tiny sizes on the CPU.  The AOT compiles at published widths for a described
(not attached) ``v5e`` chip are ``tests/test_olmoe_chip.py``'s, and remat
against no remat, recipe by recipe, ``tests/test_olmoe_remat.py``'s.
"""

import collections
import dataclasses
import functools
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

from __graft_entry__ import (  # noqa: E402
    flagship_one_chip,
    glm_4_7_flash_one_chip,
    k_exaone_one_chip,
    nemotron_labs_twotower_one_chip,
    olmo_hybrid_7b_one_chip,
    olmoe_one_chip,
    qwen3_next_one_chip,
    smallthinker_one_chip,
)
from learning_at_home_tpu.models import transformer, trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.ops.moe_dispatch import (  # noqa: E402
    dropless_routing,
    grouped_matmul,
    grouped_matmul_tiles,
)
from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh  # noqa: E402
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts  # noqa: E402
from runner_limits import (  # noqa: E402
    one_device_mesh as _one_device_mesh,
    tiny_stack,
)

reference = harness.load_path(
    os.path.join(REPO, "benchmarks", "configs", "olmoe_1b_7b_reference.py")
)
runner = harness.load_path(
    os.path.join(REPO, "benchmarks", "runners", "train_recipe.py")
)
probe = harness.load_path(os.path.join(REPO, "tools", "smallthinker_probe.py"))


def _sizes(cfg):
    return dict(
        reference.SIZES, n_heads=cfg.n_heads, experts_per_token=cfg.k,
        aux_loss_weight=cfg.aux_loss_weight,
        router_z_weight=cfg.router_z_weight,
    )


def _decisive(params):
    """Seeded weights with a router that decides: the program's init
    (normal 1e-2) gives near-equal gates at tiny widths, under which the
    expert layer is 1 % of the stream and a wrong expert layer would hide
    inside any tolerance.  Norm scales leave 1 so that a missing or
    misplaced scale shows."""
    rs = np.random.RandomState(7)

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "gate" in name and "w_gate" not in name:
            return a * 10.0
        if "scale" in name:
            return a * jnp.asarray(rs.uniform(0.5, 1.5, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    """(model, cfg, float32 params, ids, targets) on one device."""
    return tiny_stack(olmoe_one_chip, _decisive)


@pytest.fixture(scope="module")
def want(tiny):
    """The reference's logits, loss and gradients on the tiny weights."""
    _, cfg, params, ids, tgt = tiny
    logits = jax.jit(lambda p: reference.forward(p, ids, _sizes(cfg))[0])(params)
    loss, grads = jax.jit(
        lambda p: reference.loss_and_grads(p, ids, tgt, _sizes(cfg)))(params)
    return np.asarray(logits), float(loss), grads


def test_block_matches_reference_in_float32(tiny, want):
    """Logits, loss and the gradient of EVERY leaf to 1e-4 of the
    reference's largest entry of that leaf: float32 on both sides, so the
    only differences are orders of summation (sorted rows through a
    grouped matmul against a masked loop over experts; a chunked loss
    against full logits)."""
    model, cfg, params, ids, tgt = tiny
    want_logits, want_loss, want_grads = want
    logits, _ = jax.jit(model.apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits), want_logits, rtol=0,
        atol=1e-4 * np.abs(want_logits).max(),
    )
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss_fn, has_aux=True)
    )(params, ids, tgt)
    assert abs(float(loss) - want_loss) <= 1e-4 * abs(want_loss)
    assert float(metrics["dropped_fraction"]) == 0.0
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want_grads),
    ):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=0, atol=1e-4 * np.abs(w).max(),
            err_msg=jax.tree_util.keystr(path),
        )


def _bf16(model, cfg):
    return DMoETransformerLM(
        dataclasses.replace(cfg, dtype=jnp.bfloat16), model.mesh
    )


def test_block_in_bf16_is_inside_the_runner_tolerances(tiny, want):
    """bf16 compute against the float32 reference, at the limits the
    benchmark's runner holds the chip to."""
    model, cfg, params, ids, tgt = tiny
    m16 = _bf16(model, cfg)
    logits, _ = jax.jit(m16.apply)(params, ids)
    loss, _ = jax.jit(m16.loss_fn)(params, ids, tgt)
    readings = runner.readings(logits, loss, want[0], want[1])
    assert not runner.over_tolerance(readings), readings


def _per_head_norm_projections(lp, x, n_heads, positions=None, **_):
    """The mutation 'query/key norm per head': each head normalised by its
    own mean square (with its slice of the scale)."""
    b, s, d = x.shape
    hd = d // n_heads

    def project(w, norm):
        y = (x @ lp[w].astype(x.dtype)).reshape(b, s, n_heads, hd)
        if norm is not None:
            y = trunk.rms_norm(
                {"scale": lp[norm]["scale"].reshape(n_heads, hd)}, y
            )
        return y

    q, k, v = project("wq", "q_norm"), project("wk", "k_norm"), project("wv", None)
    return trunk.rotary(q, positions), trunk.rotary(k, positions), v


def _strip_qk_norm(params):
    return {**params, "layers": tuple(
        {k: v for k, v in lp.items() if k not in ("q_norm", "k_norm")}
        for lp in params["layers"]
    )}


MUTATIONS = {
    # name: (config changes, params transform, (module, attribute, value))
    "renormalised_top8": ({"renormalize": True}, None, None),
    "no_qk_norm": ({"qk_norm": False}, _strip_qk_norm, None),
    "rotary_off": ({}, None, (trunk, "rotary", lambda x, positions, theta: x)),
    "per_head_qk_norm": (  # what _qkv calls; the fourth result is the gate
        {}, None, (transformer, "gated_qkv_projections", lambda *args, **how: (
            *_per_head_norm_projections(*args, **how), None))
    ),
    "gelu_for_silu": ({}, None, (jax.nn, "silu", jax.nn.gelu)),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_block_fails_the_runner_tolerances(tiny, want, name, monkeypatch):
    """The tolerance is tight: each of five plausible misreadings of the
    block, computed in bf16 like the program, reads outside it."""
    changes, transform, patch = MUTATIONS[name]
    model, cfg, params, ids, tgt = tiny
    want_logits, want_loss, _ = want  # the reference ran before any patch
    if patch is not None:
        monkeypatch.setattr(*patch)
    m16 = _bf16(model, dataclasses.replace(cfg, **changes))
    if transform is not None:
        params = transform(params)
    logits, _ = jax.jit(m16.apply)(params, ids)
    loss, _ = jax.jit(m16.loss_fn)(params, ids, tgt)
    readings = runner.readings(logits, loss, want_logits, want_loss)
    assert runner.over_tolerance(readings), readings


def test_reference_at_a_lower_precision_fails_the_runner_tolerances(tiny, want):
    """What the runner's docstring promises of its limits: the reference
    itself with every matmul operand rounded to float8_e4m3, the nearest
    precision below the configuration's bf16, is outside them; rounded to
    bf16 it is inside."""
    _, cfg, params, ids, tgt = tiny
    for dtype, outside in ((jnp.float8_e4m3fn, True), (jnp.bfloat16, False)):
        logits, aux, z = reference.forward(params, ids, _sizes(cfg), dtype)
        loss = (reference.ce_of_logits(logits, tgt)
                + cfg.aux_loss_weight * aux + cfg.router_z_weight * z)
        readings = runner.readings(logits, loss, want[0], want[1])
        assert bool(runner.over_tolerance(readings)) is outside, (dtype, readings)


# ---- the dropless path ----


def _moe(mesh, **kw):
    kw = {"expert_kind": "gated_silu", "routing": "dropless",
          "renormalize": False, **kw}
    return ShardedMixtureOfExperts(
        mesh, hidden_dim=32, num_experts=8, k=4, ffn_dim=16,
        dtype=jnp.float32, **kw,
    )


def test_dropless_plan_counts_every_assignment():
    logits = jnp.asarray(np.random.RandomState(0).randn(96, 8) * 3, jnp.float32)
    plan = dropless_routing(logits, 4, renormalize=False)
    assert int(plan.group_sizes.sum()) == 96 * 4
    assert sorted(np.asarray(plan.order).tolist()) == list(range(96 * 4))
    experts = np.asarray(jax.lax.top_k(jax.nn.softmax(logits), 4)[1]).reshape(-1)
    by_expert = experts[np.asarray(plan.order)]
    assert (np.diff(by_expert) >= 0).all()  # rows grouped by expert
    np.testing.assert_array_equal(
        np.bincount(by_expert, minlength=8), np.asarray(plan.group_sizes)
    )
    w = np.asarray(plan.weights)
    assert (w.sum(axis=1) < 1.0).all()  # as the softmax gives them
    renormalised = dropless_routing(logits, 4, renormalize=True)
    np.testing.assert_allclose(
        np.asarray(renormalised.weights).sum(axis=1), 1.0, rtol=1e-6
    )


def test_dropless_output_does_not_depend_on_token_order():
    moe = _moe(_one_device_mesh())
    params = moe.init_params(jax.random.PRNGKey(0))
    params["gate"] = params["gate"] * 100.0
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(64, 32), jnp.float32)
    perm = rs.permutation(64)
    y, aux = jax.jit(moe.__call__)(params, x)
    y_perm, aux_perm = jax.jit(moe.__call__)(params, x[perm])
    assert float(aux["dropped_fraction"]) == 0.0
    assert float(aux["expert_load_max_over_mean"]) >= 1.0
    np.testing.assert_allclose(
        np.asarray(y)[perm], np.asarray(y_perm), rtol=0, atol=1e-6
    )
    for key in aux:
        np.testing.assert_allclose(
            float(aux[key]), float(aux_perm[key]), rtol=1e-6
        )


@pytest.mark.parametrize("k, kernels", [
    (8, set()),              # OLMoE's: the sum of 8 rows is the compiler's
    (6, {"moe_rows_sum"}),   # SmallThinker's top-6
])
def test_the_chip_form_of_the_sorted_layer_keeps_loss_and_gradients(
        tiny, monkeypatch, k, kernels):
    """``_local_forward_dropless`` as a TPU backend runs it (the combine
    with one gather backward and no gathered residual, ``ops/moe_rows.py``'s
    kernel under ``interpret`` where its rule admits the shape) against
    the form every other backend keeps, in bf16 under remat: one loss, and
    gradients within the bf16 roundings that another order of the same
    float32 sums can move."""
    from test_moe_rows_kernel import chip_form

    _, cfg, _, _, _ = tiny
    cfg = dataclasses.replace(
        cfg, d_model=128, k=k, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)  # whole lane tiles, the cells' dtypes
    model = DMoETransformerLM(cfg, _one_device_mesh())
    params = _decisive(model.init_params(jax.random.PRNGKey(11)))
    ids = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (4, cfg.seq_len + 1)))  # 128 tokens a layer

    def loss_and_grads():  # a new program a call: traced under the form of the hour
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, ids[:, :-1], ids[:, 1:])[0]))(params)

    want, want_grads = loss_and_grads()
    called = chip_form(monkeypatch)
    got, got_grads = loss_and_grads()
    assert set(called) == kernels
    np.testing.assert_allclose(float(got), float(want), rtol=2e-4)  # the cell's limit
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got_grads)[0],
        jax.tree_util.tree_leaves(want_grads),
    ):
        g, w = np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2.0 ** -6 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("expert_kind", ["gated_silu", "gelu"])
def test_dropless_equals_the_capacity_path_when_nothing_is_dropped(expert_kind):
    """Both expert kinds, both routings, one set of weights: with room for
    every assignment the slot program computes what the sorted one does."""
    mesh = _one_device_mesh()
    sorted_moe = _moe(mesh, expert_kind=expert_kind, renormalize=True)
    slot_moe = _moe(mesh, expert_kind=expert_kind, renormalize=True,
                    routing="capacity", capacity_factor=8.0)
    params = sorted_moe.init_params(jax.random.PRNGKey(2))
    params["gate"] = params["gate"] * 100.0
    if expert_kind == "gelu":  # biases that matter
        rs = np.random.RandomState(4)
        params["b1"] = jnp.asarray(rs.randn(*params["b1"].shape) * 0.5, jnp.float32)
        params["b2"] = jnp.asarray(rs.randn(*params["b2"].shape) * 0.5, jnp.float32)
    x = jnp.asarray(np.random.RandomState(5).randn(48, 32), jnp.float32)

    def loss(moe):
        def f(p, x):
            y, aux = moe(p, x)
            return (y ** 2).sum() + aux["aux_loss"] + aux["router_z_loss"], (
                y, aux["dropped_fraction"])
        return jax.jit(jax.value_and_grad(f, has_aux=True))

    (l_sorted, (y_sorted, _)), g_sorted = loss(sorted_moe)(params, x)
    (l_slot, (y_slot, dropped)), g_slot = loss(slot_moe)(params, x)
    # no assignment of the 48 x 4 is dropped: one would read 1/192, and the
    # compiled quotient of two equal float32 counts an ulp of 1 off zero
    assert abs(float(dropped)) < 0.5 / (48 * 4)
    np.testing.assert_allclose(np.asarray(y_sorted), np.asarray(y_slot), atol=2e-5)
    np.testing.assert_allclose(float(l_sorted), float(l_slot), rtol=1e-5)
    for key in g_sorted:
        np.testing.assert_allclose(
            np.asarray(g_sorted[key]), np.asarray(g_slot[key]),
            atol=1e-4 * float(jnp.abs(g_slot[key]).max()), err_msg=key,
        )


def test_dropless_on_a_data_mesh_equals_one_device(tiny):
    """``data=2 x expert=1``: each shard sorts its own rows; loss and every
    gradient equal the one-device step's.  The load-balance loss is left
    out of this comparison: on every routing it is the mean over shards
    of a product of per-shard statistics, which is another number than
    the product of the global ones."""
    _, cfg, params, ids, tgt = tiny
    cfg = dataclasses.replace(cfg, aux_loss_weight=0.0)
    mesh2 = make_mesh({"data": 2, "expert": 1}, devices=jax.devices()[:2])
    model, model2 = DMoETransformerLM(cfg, _one_device_mesh()), DMoETransformerLM(cfg, mesh2)
    params2 = jax.device_put(params, model2.param_shardings(params))
    ids2, tgt2 = (jax.device_put(a, batch_sharding(mesh2)) for a in (ids, tgt))
    grad = lambda m: jax.jit(jax.value_and_grad(m.loss_fn, has_aux=True))  # noqa: E731
    (l1, m1), g1 = grad(model)(params, ids, tgt)
    (l2, m2), g2 = grad(model2)(params2, ids2, tgt2)
    assert float(m2["dropped_fraction"]) == 0.0
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-5)
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(g2)[0], jax.tree_util.tree_leaves(g1)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=1e-4 * float(np.abs(np.asarray(b)).max()),
            err_msg=jax.tree_util.keystr(path),
        )


def test_dropless_refuses_experts_split_over_devices():
    mesh = make_mesh({"expert": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="ragged all-to-all"):
        _moe(mesh)


def test_cached_decode_matches_the_full_forward(tiny):
    """Rotary positions, the query/key norm and the gated experts reach
    the KV-cache decoder: greedy tokens equal the re-forward decoder's."""
    model, cfg, params, ids, _ = tiny
    prompt = ids[:, :8]
    full = model.generate(params, prompt, 6)
    cached = model.generate(params, prompt, 6, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


# ---- the grouped matmul's tiles are read from the call's shape ----

CELL_ROWS = 4 * 4096 * 8  # olmoe-1b-7b-train-zipf4k: tokens a step x experts a token
SMALLTHINKER_ROWS = 16384 * 6  # smallthinker-21b-a3b-train-zipf16k


@pytest.mark.parametrize(
    "call, tiles",
    [
        # gate and up, and the rows' gradient of down: an expert's whole
        # matrix is the weight tile
        ((CELL_ROWS, 2048, 1024, jnp.bfloat16), (256, 2048, 1024)),
        # down, and the rows' gradient of gate and up
        ((CELL_ROWS, 1024, 2048, jnp.bfloat16), (256, 1024, 2048)),
        ((CELL_ROWS, 2048, 1024, jnp.bfloat16, True), (256, 1024, 1024)),
        ((CELL_ROWS, 1024, 2048, jnp.bfloat16, True), (256, 1024, 1024)),
        # narrower than a tile: the tile is the dimension
        ((CELL_ROWS, 512, 256, jnp.bfloat16), (256, 512, 256)),
        # wider: 2 Mi elements of weights a tile, 1 Mi for their gradient
        ((8192, 4096, 4096, jnp.bfloat16), (256, 2048, 1024)),
        ((8192, 512, 4096, jnp.bfloat16, True), (256, 512, 2048)),
        # SmallThinker's widths, no powers of two: gate and up, and the
        # rows' gradient of down, an expert's whole 2560 x 768 matrix
        ((SMALLTHINKER_ROWS, 2560, 768, jnp.bfloat16), (256, 2560, 768)),
        # down, and the rows' gradient of gate and up
        ((SMALLTHINKER_ROWS, 768, 2560, jnp.bfloat16), (256, 768, 2560)),
        # half of it for the weights' gradients: 1280 = 2560 / 2
        ((SMALLTHINKER_ROWS, 2560, 768, jnp.bfloat16, True), (256, 1280, 768)),
        ((SMALLTHINKER_ROWS, 768, 2560, jnp.bfloat16, True), (256, 768, 1280)),
        # 1536 = 12 x 128: its largest divisor that leaves 2048 x tn in 2 Mi
        ((CELL_ROWS, 2048, 1536, jnp.bfloat16), (256, 2048, 768)),
        # 1000 = 7.8 x 128: no multiple of the 128 lanes divides it
        ((CELL_ROWS, 2048, 1000, jnp.bfloat16), None),
        ((CELL_ROWS, 1000, 2048, jnp.bfloat16, True), None),
        # 33,000 = 128.9 x 256: the row tile does not divide
        ((33000, 2048, 1024, jnp.bfloat16), None),
        # float32 operands were not measured and take twice the VMEM
        ((CELL_ROWS, 2048, 1024, jnp.float32), None),
        # the tiny recipe's (olmoe_one_chip(tiny=True): 64 tokens x 8, float32)
        ((512, 32, 64, jnp.float32), None),
        # fewer rows than were measured
        ((256, 2048, 1024, jnp.bfloat16, True), None),
    ],
    ids=["gate-up", "down", "gate-up-weights", "down-weights", "narrow",
         "wide", "wide-weights", "smallthinker-gate-up", "smallthinker-down",
         "smallthinker-gate-up-weights", "smallthinker-down-weights",
         "n-not-divided", "n-no-lane-multiple", "k-no-lane-multiple",
         "m-not-divided", "float32", "tiny", "few-rows"],
)
def test_grouped_matmul_tiles_are_read_from_the_shape(call, tiles):
    assert grouped_matmul_tiles(*call) == tiles


def _call_and_gradients(fn, x, w, g, sizes):
    """(result, rows' gradient, weights' gradient) of ``fn(x, w, sizes)``."""
    out, vjp = jax.vjp(lambda x, w: fn(x, w, sizes), x, w)
    return (out,) + vjp(g)


def _compiled_tilings(text):
    """The tiles the compiled grouped-matmul instructions carry."""
    return set(re.findall(
        r'%ragged-dot-none[^\n]*ragged_dot_tiling="([\d,]+)"', text))


def test_every_grouped_matmul_of_a_layer_carries_its_tiles():
    """One layer of the recipe at the cell's sizes, traced from shapes
    and lowered for the TPU platform (nothing compiles, nothing runs):
    the 3 forward grouped matmuls, the 3 rows' gradients and the 3
    weights' gradients each carry the ``ragged_dot_tiling`` that
    ``grouped_matmul_tiles`` reads from their shape, all sit under scope
    ``experts``, and no other operation carries the attribute."""
    model, cfg, _, batch = olmoe_one_chip(_one_device_mesh())
    lp = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))["layers"][0]
    x = jax.ShapeDtypeStruct((batch, cfg.seq_len, cfg.d_model), cfg.dtype)

    def layer_loss(lp, x):
        y, aux = model._layer(lp, x, 0, None, cfg.attention_layer(0))
        return (y.astype(jnp.float32) ** 2).mean() + aux["aux_loss"]

    text = (
        jax.jit(jax.value_and_grad(layer_loss, argnums=(0, 1)))
        .trace(lp, x).lower(lowering_platforms=("tpu",))
        .as_text(debug_info=True)
    )
    where = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    carrying = [line for line in text.splitlines() if "ragged_dot_tiling" in line]
    calls = [line for line in text.splitlines() if '"chlo.ragged_dot"' in line]
    assert len(calls) == 9 and carrying == calls
    seen = collections.Counter()
    for line in calls:
        tiles = tuple(map(int, re.search(
            r'ragged_dot_tiling = "([\d,]+)"', line).group(1).split(",")))
        (m, a), _, out = (
            tuple(map(int, dims.split("x")))
            for dims in re.findall(r"tensor<([\dx]+)xbf16>", line)
        )
        path = where[re.search(r"loc\((#loc\d+)\)", line).group(1)]
        assert "/experts/" in path, path
        weights = len(out) == 3  # [m, a], [m, b] -> [G, a, b]
        assert tiles == grouped_matmul_tiles(
            m, a, out[-1], jnp.bfloat16, weights_gradient=weights
        ), line
        kind = "weights" if weights else "rows" if "transpose(" in path else "forward"
        seen[kind, path.split("/")[-2]] += 1
    assert seen == {
        (kind, scope): count
        for kind in ("forward", "rows", "weights")
        for scope, count in (("gate_up", 2), ("down", 1))
    }


def _group_sizes(how, groups, m):
    if how == "uniform":
        return np.full(groups, m // groups, np.int32)
    sizes = np.full(groups, 3, np.int32)  # one group holds nearly every row
    sizes[2] = 0
    sizes[1] = m - sizes.sum() + 3
    return sizes


@pytest.mark.parametrize("how", ["uniform", "collapsed"])
@pytest.mark.parametrize(
    "m, a, b, dtype",
    [(8192, 512, 256, jnp.bfloat16), (96, 32, 16, jnp.float32)],
    ids=["tiled", "tiny"],
)
def test_grouped_matmul_and_its_gradients_are_ragged_dots(m, a, b, dtype, how):
    """On the CPU the attribute means nothing: result, rows' gradient and
    weights' gradient are ``jax.lax.ragged_dot``'s, bit for bit, at a
    shape that has tiles and at one that has none, with a group of no
    rows among them."""
    assert (grouped_matmul_tiles(m, a, b, dtype) is not None) == (m == 8192)
    sizes = jnp.asarray(_group_sizes(how, 8, m))
    assert int(sizes.sum()) == m
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (m, a), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[1], (8, a, b), jnp.float32).astype(dtype)
    g = jax.random.normal(keys[2], (m, b), jnp.float32).astype(dtype)

    def plain(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)

    got, want = jax.jit(lambda *args: tuple(
        _call_and_gradients(fn, *args) for fn in (grouped_matmul, plain)
    ))(x, w, g, sizes)
    for name, a_, b_ in zip(("result", "rows", "weights"), got, want):
        assert a_.dtype == b_.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(a_.astype(jnp.float32)), np.asarray(b_.astype(jnp.float32)),
            err_msg=name,
        )


# ---- the cells' programs are the programs they were ----

# sha256 of make_train_step(...).lower(...).as_text() with this container's
# jax 0.9.0: flagship_one_chip(tiny=True) on a one-device CPU mesh, the
# same recipe on data=2 x expert=2 (dmoe256-train-pod4's program),
# olmoe_one_chip(tiny=True) on one device (olmoe-1b-7b-train-zipf4k's) and
# the five newer recipes' tiny steps on one device.
# ALL EIGHT re-taken on PR 53's tree (parent 950e362), which was meant to
# alter them: the layer's checkpoint keeps the results of the attention
# part's matrix products (``trunk.ATTENTION_PRODUCTS``: q, k, v seen as
# heads where no norm spans the whole projection, and the output
# projection's; the latent form's two products down to the latents), on the
# ``xla`` core too, so every step holds a ``reduce_precision`` of each kept
# result behind its product and no second product in the backward pass.
# They read before, from the PRs named: dmoe one-chip e0b11dba135b..46
# and pod4 e58a362779f1..90, olmoe a5ef2b58eef2..51 (PR 34: the loss layer's
# gradients in its forward scan); smallthinker a4a7bb6bcd9b..13, k-exaone
# acf47b0ff668..af, glm-4.7-flash 6d0b221f0ca4..22 (first taken on PR 43's
# parent); nemotron f88020c63901..d1 (PR 47: the gate and grouped RMSNorm
# moved into ``ops/gate_norm.py``); olmo-hybrid b6ddcfc300b4..f0 (PR 46:
# the unit-length scaling moved into the rule).
DMOE_TINY_STEP_SHA256 = (
    "c35682256a505a684ac98ecf12bc914847891861fcae9af32ae618c94c4d2c54"
)
DMOE_TINY_POD4_STEP_SHA256 = (
    "6093d5c44a08f09e847f0da05944c2bfc3be22acae613d26cb6b634bdc2142fb"
)
OLMOE_TINY_STEP_SHA256 = (
    "41902b870772c0eeff1f85b4d1fc3db4404f11c2c554912fabd6ffa4e5a53883"
)
SMALLTHINKER_TINY_STEP_SHA256 = (
    "25ac4616b8fd1eb481cda9480d25abfe698976401d8b458a7ff1c3b0e9203b94"
)
K_EXAONE_TINY_STEP_SHA256 = (
    "ff9fdb8aaf7b8f9b680c38ab5ac08819ce9089655d81a5d5416dccea14d8e65f"
)
GLM_4_7_FLASH_TINY_STEP_SHA256 = (
    "2745ce8e2222a635da57fe8f5d1f8f2cf1e152326f31723778d6eaba086ef541"
)
NEMOTRON_TINY_STEP_SHA256 = (
    "56dff9681de0fcee09a3b172dbc956c1a8aac71c19a7ce12525ee130dd0c12e2"
)
OLMO_HYBRID_TINY_STEP_SHA256 = (
    "34383f25753571f7f98b0735a82b045f784e5e1f03d7603259aa62623a3db7fb"
)
# first taken on PR 55's tree, which brought the recipe (the eight above are
# as PR 53 left them: this model's fields changed no other program); its tiny
# preset is ONE period of the stack (two were 5d41bf4d...)
QWEN3_NEXT_TINY_STEP_SHA256 = (
    "0e9b8ba91a6b316bdcc7c6daec550e4ce3f0e28789508693aa777546a9ec2640"
)


@pytest.mark.parametrize(
    "recipe, axes, sha256",
    [
        (flagship_one_chip, {"expert": 1}, DMOE_TINY_STEP_SHA256),
        (flagship_one_chip, {"data": 2, "expert": 2}, DMOE_TINY_POD4_STEP_SHA256),
        (olmoe_one_chip, {"expert": 1}, OLMOE_TINY_STEP_SHA256),
        (smallthinker_one_chip, {"expert": 1}, SMALLTHINKER_TINY_STEP_SHA256),
        (k_exaone_one_chip, {"expert": 1}, K_EXAONE_TINY_STEP_SHA256),
        (glm_4_7_flash_one_chip, {"expert": 1}, GLM_4_7_FLASH_TINY_STEP_SHA256),
        (nemotron_labs_twotower_one_chip, {"expert": 1}, NEMOTRON_TINY_STEP_SHA256),
        (olmo_hybrid_7b_one_chip, {"expert": 1}, OLMO_HYBRID_TINY_STEP_SHA256),
        (qwen3_next_one_chip, {"expert": 1}, QWEN3_NEXT_TINY_STEP_SHA256),
    ],
    ids=["dmoe-one-chip", "dmoe-pod4", "olmoe-one-chip", "smallthinker-one-chip",
         "k-exaone-one-chip", "glm-4.7-flash-one-chip", "nemotron-one-chip",
         "olmo-hybrid-one-chip", "qwen3-next-one-chip"],
)
def test_dmoe256_lowered_step_is_text_identical_to_the_parents(
    recipe, axes, sha256
):
    """The programs the train cells run lower to the same
    StableHLO, letter for letter, as on the commit their hash was taken
    on: what a PR takes out of the pod step (PR 27 made the block's shape
    part of the configuration; PR 29 deleted the forks beside the path)
    was not on the path.  A change that is MEANT to alter a program
    updates its hash with the reason."""
    text = lowered_tiny_step(recipe, axes).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha256


def lowered_tiny_step(recipe, axes):
    """A recipe's tiny train step on a mesh of ``axes``, lowered for
    abstract arguments placed as the recipe places them."""
    from learning_at_home_tpu.parallel.mesh import opt_state_shardings

    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n_dev])
    model, cfg, opt, batch = recipe(mesh, tiny=True)
    shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    shard = model.param_shardings(shape)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            tree, shardings,
        )

    p = placed(shape, shard)
    o = jax.eval_shape(opt.init, p)
    o = placed(o, opt_state_shardings(o, shard, p, mesh))
    ids = jax.ShapeDtypeStruct(
        (batch, cfg.seq_len), jnp.int32, sharding=batch_sharding(mesh)
    )
    return model.make_train_step(opt).lower(p, o, ids, ids)


# ---- the stack has one layout ----


@pytest.mark.parametrize("owner, name", [
    (transformer.DMoETransformerConfig, "scan_layers"),
    (transformer.DMoETransformerConfig, "stack_layers"),
    (transformer.DMoETransformerConfig, "attn_impl"),
    (ShardedMixtureOfExperts, "dispatch_impl"),
], ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_the_stack_has_one_layout(owner, name):
    """The stack's layout, the attention core and the dispatch plan are
    not options: a tuple of per-layer trees under the unrolled loop,
    ``auto_attn_impl`` and ``choose_dispatch_impl``.  The constructor
    refuses the keyword, no dataclass field carries the name, and the two
    layout names read ``False`` off a config: what the CFG_FIELDS tables
    of the benchmark's six train runners compare with their files."""
    build = owner if dataclasses.is_dataclass(owner) else functools.partial(
        _moe, _one_device_mesh())
    with pytest.raises(TypeError, match=name):
        build(**{name: False})
    if dataclasses.is_dataclass(owner):
        assert name not in {f.name for f in dataclasses.fields(owner)}
        with pytest.raises(TypeError, match=name):
            dataclasses.replace(owner(), **{name: False})
    if name in runner.CFG_FIELDS:  # the two the benchmark's tables read
        assert getattr(owner(), name) is False


# ---- the benchmark's files for it ----


def test_runner_attributes_device_time_by_scope():
    """``scope_times``: a traced operation takes the scope its instruction's
    ``op_name`` names, as a path component or inside the ``jvp(..)`` that
    differentiation wraps around a top-level scope; the grouped-matmul
    kernel, whose call loses the path, is the expert layer's by name."""
    hlo = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(layer_0)/attention/rope/mul"}
  %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(layer_1))/jvp(layer_1)/checkpoint/rematted_computation/experts/gate_up/mul" source_file="x.py"}
  %ragged-dot-none.7 = bf16[8,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-metadata.3 = (s32[65]{0}) custom-call(%g), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %fusion.3 = f32[] fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(ce))/while/body/dot_general"}
  ROOT %fusion.4 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/optimizer/mul"}
  %copy.9 = bf16[8]{0} copy(%p)
"""
    s = 10 ** 9  # one second of device time, in the trace's nanoseconds
    ops = [("fusion.1", 0, s), ("fusion.2", s, 3 * s),
           ("ragged-dot-none.7", 3 * s, 5 * s), ("ragged-dot-none.7", 5 * s, 7 * s),
           ("ragged-dot-metadata.3", 7 * s, 8 * s), ("fusion.3", 8 * s, 16 * s),
           ("fusion.4", 16 * s, 32 * s), ("copy.9", 32 * s, 64 * s)]
    got = runner.scope_times(ops, hlo)
    assert got["by_scope"] == {"attention": 1.0, "experts": 7.0, "ce": 8.0,
                               "optimizer": 16.0, "other": 32.0}
    assert got["total_s"] == 64.0
    assert (got["grouped_matmul_s"], got["grouped_matmul_calls"]) == (4.0, 2)
