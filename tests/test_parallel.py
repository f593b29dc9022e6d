"""Tests for the ICI tier: sharded MoE + DMoE transformer on the virtual
8-device CPU mesh (SURVEY.md §4 'TPU-build implication').  The chunked
cross-entropy against the full logits is
``tests/test_parallel_chunked_ce.py``'s and ``tests/test_parallel_ce_per_shard.py``'s."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_at_home_tpu.models.transformer import (
    DMoETransformerConfig,
    DMoETransformerLM,
)
from learning_at_home_tpu.parallel import (
    ShardedMixtureOfExperts,
    batch_sharding,
    make_mesh,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def dense_mixture(params, x):
    """Reference computation: full softmax mixture over all experts."""
    gates = jax.nn.softmax(np.asarray(x) @ params["gate"], axis=-1)
    h = np.einsum("nd,edf->enf", np.asarray(x), params["w1"]) + params["b1"][:, None]
    h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
    ye = np.einsum("enf,efd->end", h, params["w2"]) + params["b2"][:, None]
    return np.einsum("ne,end->nd", gates, ye)


def test_sharded_moe_matches_dense_full_routing():
    mesh = make_mesh({"data": 2, "expert": 4})
    moe = ShardedMixtureOfExperts(
        mesh, hidden_dim=16, num_experts=8, k=8, capacity_factor=8.0,
        dtype=jnp.float32,
    )
    params = moe.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    y, aux = jax.jit(moe.__call__)(params, x)
    expected = dense_mixture(jax.device_get(params), x)
    np.testing.assert_allclose(np.asarray(y), expected, atol=1e-5)
    assert float(aux["dropped_fraction"]) == 0.0


def test_sharded_moe_1d_expert_mesh():
    mesh = make_mesh({"expert": 8})
    moe = ShardedMixtureOfExperts(
        mesh, hidden_dim=8, num_experts=16, k=16, capacity_factor=16.0,
        dtype=jnp.float32,
    )
    params = moe.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8), jnp.float32)
    y, _ = jax.jit(moe.__call__)(params, x)
    np.testing.assert_allclose(
        np.asarray(y), dense_mixture(jax.device_get(params), x), atol=1e-5
    )


def test_sharded_moe_grads_flow_to_experts():
    mesh = make_mesh({"data": 2, "expert": 4})
    moe = ShardedMixtureOfExperts(
        mesh, hidden_dim=16, num_experts=8, k=2, dtype=jnp.float32
    )
    params = moe.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16), jnp.float32)

    def loss(params):
        y, aux = moe(params, x)
        return (y**2).mean() + 0.01 * aux["aux_loss"]

    grads = jax.jit(jax.grad(loss))(params)
    for name in ("gate", "w1", "w2"):
        assert float(jnp.abs(grads[name]).sum()) > 0, name
    # expert grads keep the expert sharding (no accidental replication)
    assert grads["w1"].sharding.spec == params["w1"].sharding.spec


def test_sharded_moe_tensor_parallel_matches_dense():
    """Experts sharded over 'expert' AND their FFN dim over 'model' (tp)."""
    mesh = make_mesh({"data": 2, "expert": 2, "model": 2})
    moe = ShardedMixtureOfExperts(
        mesh, hidden_dim=16, num_experts=4, k=4, capacity_factor=8.0,
        dtype=jnp.float32,
    )
    params = moe.init_params(jax.random.PRNGKey(0))
    assert "model" in str(params["w1"].sharding.spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    y, aux = jax.jit(moe.__call__)(params, x)
    np.testing.assert_allclose(
        np.asarray(y), dense_mixture(jax.device_get(params), x), atol=1e-5
    )

    def loss(params):
        out, _ = moe(params, x)
        return (out**2).mean()

    grads = jax.jit(jax.grad(loss))(params)
    # tp-sharded grads keep their sharding; psum over 'model' happened
    assert grads["w1"].sharding.spec == params["w1"].sharding.spec
    assert float(jnp.abs(grads["w2"]).sum()) > 0


def test_capacity_drop_under_imbalance():
    mesh = make_mesh({"expert": 8})
    moe = ShardedMixtureOfExperts(
        mesh, hidden_dim=8, num_experts=8, k=1, capacity_factor=1.0,
        dtype=jnp.float32,
    )
    params = moe.init_params(jax.random.PRNGKey(0))
    # steer every token to expert 0 via an extreme gate
    params = dict(params)
    params["gate"] = jnp.zeros_like(params["gate"]).at[:, 0].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 8), jnp.float32)
    y, aux = jax.jit(moe.__call__)(params, x)
    assert float(aux["dropped_fraction"]) > 0.5  # most tokens dropped
    # dropped tokens produce zero output rows, kept ones nonzero
    row_norms = np.linalg.norm(np.asarray(y), axis=1)
    assert (row_norms == 0).sum() >= 32


def test_host_local_array_to_global():
    from jax.sharding import PartitionSpec as P

    from learning_at_home_tpu.parallel import host_local_array_to_global

    mesh = make_mesh({"data": 2, "expert": 4})
    x = np.arange(32, dtype=np.int32).reshape(16, 2)
    g = host_local_array_to_global(x, mesh)
    assert g.shape == (16, 2)
    np.testing.assert_array_equal(np.asarray(g), x)
    # default layout == batch_sharding == what the train step expects
    assert g.sharding.spec == batch_sharding(mesh).spec
    # seq-bearing mesh: sequence axis sharded too
    mesh_sp = make_mesh({"data": 2, "expert": 2, "seq": 2})
    g2 = host_local_array_to_global(np.ones((8, 4), np.float32), mesh_sp)
    assert g2.sharding.spec == batch_sharding(mesh_sp).spec
    # explicit override honored
    g3 = host_local_array_to_global(x, mesh, spec=P("data"))
    assert g3.sharding.spec == P("data")


def _tiny_model(mesh, remat=False):
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, seq_len=16,
        num_experts=8, k=2, dtype=jnp.float32, remat=remat,
    )
    return DMoETransformerLM(cfg, mesh), cfg


def test_transformer_trains_and_keeps_shardings():
    mesh = make_mesh({"data": 2, "expert": 4})
    model, cfg = _tiny_model(mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-3)
    opt_state = model.init_opt_state(opt, params)
    step = model.make_train_step(opt)

    rs = np.random.RandomState(0)
    ids = jax.device_put(
        jnp.asarray(rs.randint(0, 64, (8, 16))), batch_sharding(mesh)
    )
    tgt = jax.device_put(
        jnp.asarray(rs.randint(0, 64, (8, 16))), batch_sharding(mesh)
    )
    losses = []
    for _ in range(6):
        params, opt_state, loss, metrics = step(params, opt_state, ids, tgt)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
    w1 = params["layers"][0]["moe"]["w1"]
    assert "expert" in str(w1.sharding.spec)


def test_opt_state_shardings_factored_optimizer():
    """adafactor's v_row/v_col/v reuse param key paths at REDUCED rank; they
    must not be handed the param's higher-rank spec (the exact crash that
    killed the first real-TPU bench attempt: a rank-1 ``v`` leaf annotated
    P(None, 'expert'))."""
    mesh = make_mesh({"data": 2, "expert": 4})
    model, _ = _tiny_model(mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = optax.adafactor(1e-3)
    opt_state = model.init_opt_state(opt, params)  # crashed before the fix
    # same-shape leaves (e.g. adamw's mu/nu) still inherit the param spec
    adam_state = model.init_opt_state(optax.adamw(1e-3), params)
    flat_p = {
        jax.tree_util.keystr(kp): v.sharding
        for kp, v in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    hits = 0
    for kp, leaf in jax.tree_util.tree_flatten_with_path(adam_state)[0]:
        ks = jax.tree_util.keystr(kp)
        for pks, sharding in flat_p.items():
            if ks.endswith(pks) and "expert" in str(sharding.spec):
                assert leaf.sharding.spec == sharding.spec, (ks, leaf.sharding)
                hits += 1
    assert hits > 0
    # and the factored state actually trains
    step = model.make_train_step(opt)
    rs = np.random.RandomState(2)
    ids = jax.device_put(
        jnp.asarray(rs.randint(0, 64, (8, 16))), batch_sharding(mesh)
    )
    params, opt_state, loss, _ = step(params, opt_state, ids, ids)
    assert np.isfinite(float(loss))


def test_factored_stats_keep_the_expert_axis():
    """At a size adafactor factors (dims >= 128), the row/column statistics
    of an expert stack are the param's shape minus one axis: they take the
    param's spec minus that axis, i.e. stay split over 'expert' — where
    the train step returns them.  Placed replicated, step 2 saw new input
    shardings and compiled the whole step a second time (seen on four
    v5e chips, PR 21)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

    mesh = make_mesh({"data": 2, "expert": 2}, devices=jax.devices()[:4])
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=128, n_layers=2, n_heads=4, seq_len=16,
        num_experts=4, k=2, dtype=jnp.float32,
    )
    model = DMoETransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = fused_adafactor(1e-3)
    opt_state = model.init_opt_state(opt, params)
    w1_rows = opt_state.v_row["layers"][0]["moe"]["w1"]
    assert w1_rows.ndim == 2  # factored: one axis gone
    want = NamedSharding(mesh, P("expert"))
    assert w1_rows.sharding.is_equivalent_to(want, w1_rows.ndim), (
        w1_rows.sharding
    )
    ids = jax.device_put(jnp.zeros((8, 16), jnp.int32), batch_sharding(mesh))
    step = model.make_train_step(opt)
    before = jax.tree_util.tree_leaves((params, opt_state))
    placed = [(l.sharding, l.ndim) for l in before]
    after = jax.tree_util.tree_leaves(step(params, opt_state, ids, ids)[:2])
    moved = [
        (str(was), str(leaf.sharding))
        for (was, ndim), leaf in zip(placed, after)
        if not leaf.sharding.is_equivalent_to(was, ndim)
    ]
    assert not moved, f"step returned state under new shardings: {moved[:3]}"


def test_grad_accumulation_matches_mean_of_micro_grads():
    """accum_steps=2 must equal hand-averaged per-microbatch grads fed to
    one optimizer update (same capacity per microbatch, so exact match)."""
    mesh = make_mesh({"data": 2, "expert": 4})
    model, cfg = _tiny_model(mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(1e-2)
    opt_state = model.init_opt_state(opt, params)
    rs = np.random.RandomState(7)
    ids = jnp.asarray(rs.randint(0, 64, (2, 8, 16)))
    tgt = jnp.asarray(rs.randint(0, 64, (2, 8, 16)))

    # reference: average grads of the two microbatches, one update
    gfn = jax.jit(jax.grad(lambda p, i, t: model.loss_fn(p, i, t)[0]))
    g0 = gfn(params, ids[0], tgt[0])
    g1 = gfn(params, ids[1], tgt[1])
    gavg = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, g0, g1)
    upd, _ = opt.update(gavg, opt_state, params)
    ref = optax.apply_updates(params, upd)

    step = model.make_train_step(opt, accum_steps=2)
    got, _, loss, metrics = step(params, opt_state, ids, tgt)
    for a, b in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(got)
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-5, atol=2e-6,
        )
    assert np.isfinite(float(loss))
    assert 0.0 <= float(metrics["dropped_fraction"]) <= 1.0


def _loss_layer_ops(hlo_text, opcode_re):
    """``op_name`` of every instruction of the optimized HLO whose opcode
    matches.  The step differentiates the scope ``ce``, so a path reads
    ``jvp(ce)/...`` or ``transpose(jvp(ce))/...``: the transforms'
    brackets are taken off and ``ce/shard_map`` is what remains."""
    names = re.findall(
        r"\s(?:%s)\(.*?op_name=\"([^\"]*)\"" % opcode_re, hlo_text
    )
    return [
        re.sub(r"\b(?:jvp|transpose)\(|\)", "", name) for name in names
    ]


def _loss_layer_alone(axes, dtype, chunk, vocab=64, batch=8, seed=11):
    """A model on ``axes`` whose loss layer is called by itself: hidden
    states [batch, 16, 32] and targets laid out like a step's batch, and
    an untied head [32, vocab], in ``dtype``."""
    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n_dev])
    m = DMoETransformerLM(
        DMoETransformerConfig(
            vocab_size=vocab, d_model=32, n_layers=1, n_heads=4, seq_len=16,
            num_experts=8, k=2, dtype=jnp.dtype(dtype), ce_chunk=chunk,
            tie_embeddings=False, seq_parallel="seq" in axes,
        ),
        mesh,
    )
    rs = np.random.RandomState(seed)
    spec = batch_sharding(mesh).spec
    x = jax.device_put(
        jnp.asarray(rs.randn(batch, 16, 32), dtype),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec, None)),
    )
    head = jnp.asarray(0.3 * rs.randn(32, vocab), dtype)
    tgt = jax.device_put(
        jnp.asarray(rs.randint(0, vocab, (batch, 16))), batch_sharding(mesh)
    )
    return m, x, head, tgt


def _head_products(lowered):
    """``(operand types, result type)`` of every ``dot_general`` of the
    lowered program that comes from the logits' einsum (``_logits``) or
    from its transposes, as the StableHLO text has them."""
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))
    found = []
    for line in text.splitlines():
        if "stablehlo.dot_general" not in line:
            continue
        if "...d,dv->...v" in names[re.findall(r"#loc\d+", line)[-1]]:
            operands, result = re.search(
                r": \((.*)\) -> (tensor<[^>]*>)", line
            ).groups()
            found.append((tuple(re.findall(r"tensor<([^>]*)>", operands)),
                          result[len("tensor<"):-1]))
    return found


@pytest.mark.parametrize(
    "axes", [{"expert": 1}, {"data": 2, "expert": 2}],
    ids=["one-device", "data2xexpert2"],
)
def test_train_step_multiplies_by_the_head_three_times(axes):
    """The lowered tiny train step has three products of the head's under
    ``ce``, a scan of chunks on one device and per shard on a mesh: the
    logits and the two gradients.  Recomputing the logits in the backward
    made it four.  The compiled step agrees: three matmuls under ``ce``,
    all in the scan's body."""
    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n_dev])
    _, cfg = _tiny_model(mesh)
    m = DMoETransformerLM(dataclasses.replace(cfg, ce_chunk=16), mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    ids = jax.device_put(jnp.zeros((8, 16), jnp.int32), batch_sharding(mesh))
    lowered = m.make_train_step(opt).lower(
        params, m.init_opt_state(opt, params), ids, ids
    )
    assert sorted(_head_products(lowered)) == sorted([
        (("16x32xf32", "32x64xf32"), "16x64xf32"),  # logits
        (("16x64xf32", "16x32xf32"), "64x32xf32"),  # the head's gradient
        (("16x64xf32", "32x64xf32"), "16x32xf32"),  # the rows' gradient
    ])
    # a chunk's rows are an array of their own before the products take
    # them, as under jax.checkpoint's barrier: fused into each product's
    # operand, the slice costs the chip's head-gradient product a fifth
    assert lowered.as_text().count("stablehlo.optimization_barrier") == 1
    dots = [
        n for n in _loss_layer_ops(lowered.compile().as_text(), "dot|convolution")
        if "/ce/" in n
    ]
    assert len(dots) == 3 and all("/while/body/" in n for n in dots), dots


def test_loss_without_a_gradient_multiplies_by_the_head_once():
    """Evaluation (and ``jax.eval_shape``) runs the plain scan: one product
    a chunk, the logits, and nothing of the head's shape [32, 72] or its
    transpose's is made anywhere (the step, which holds the head's
    gradient, makes both)."""
    m, x, head, tgt = _loss_layer_alone({"expert": 1}, "float32", 16, vocab=72)

    def results(lowered):
        return set(re.findall(
            r"-> tensor<(\d+x\d+)xf32>", lowered.as_text()
        ))

    def ce(x, h):
        return m._chunked_ce(x, h, tgt)

    loss = jax.jit(ce).lower(x, head)
    assert _head_products(loss) == [(("16x32xf32", "32x72xf32"), "16x72xf32")]
    assert not results(loss) & {"32x72", "72x32"}
    assert jax.eval_shape(ce, x, head) == jax.ShapeDtypeStruct((), jnp.float32)
    grad = jax.jit(jax.grad(ce, argnums=(0, 1))).lower(x, head)
    assert len(_head_products(grad)) == 3
    assert results(grad) >= {"32x72", "72x32"}


@pytest.mark.parametrize(
    "dtype, operand", [("bfloat16", "bf16"), ("float32", "f32")]
)
def test_head_gradient_products_keep_their_types(dtype, operand):
    """Read off the lowered step of PR 34's parent (90b8760), bf16 model:
    the logits are ``(16x32xbf16, 32x64xbf16) -> 16x64xf32``; both gradient
    products take float32 operands (the float32 ``d`` and the rows or the
    head converted up) and give float32, ``(16x64xf32, 16x32xf32) ->
    64x32xf32`` and ``(16x64xf32, 32x64xf32) -> 16x32xf32``, cast to bf16
    afterwards.  A float32 model is float32 throughout.  Nothing is
    narrower now."""
    m, x, head, tgt = _loss_layer_alone({"expert": 1}, dtype, 16)
    grad = jax.jit(
        jax.grad(lambda x, h: m._chunked_ce(x, h, tgt), argnums=(0, 1))
    ).lower(x, head)
    assert sorted(_head_products(grad)) == sorted([
        ((f"16x32x{operand}", f"32x64x{operand}"), "16x64xf32"),
        (("16x64xf32", "16x32xf32"), "64x32xf32"),
        (("16x64xf32", "32x64xf32"), "16x32xf32"),
    ])


def test_train_step_ce_has_no_all_gather_on_a_mesh():
    """The compiled pod step on ``data=2 x expert=2``: no all-gather under
    ``ce`` (the global scan's ``dynamic_slice`` of a batch-sharded stack
    was gather-then-slice, 352 times a flagship step), and the loss
    layer's matmuls carry ``ce/shard_map``."""
    mesh = make_mesh({"data": 2, "expert": 2}, devices=jax.devices()[:4])
    _, cfg = _tiny_model(mesh)
    # 8 rows x 16 tokens a step, 2 rows a shard: a scan of 2 chunks of 16
    m = DMoETransformerLM(dataclasses.replace(cfg, ce_chunk=16), mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = m.init_opt_state(opt, params)
    ids = jax.device_put(jnp.zeros((8, 16), jnp.int32), batch_sharding(mesh))
    hlo = m.make_train_step(opt).lower(
        params, opt_state, ids, ids
    ).compile().as_text()
    gathers = _loss_layer_ops(hlo, "all-gather(?:-start)?")
    assert not [n for n in gathers if "/ce/" in n], gathers
    dots = [n for n in _loss_layer_ops(hlo, "dot|convolution") if "/ce/" in n]
    assert dots and all("/ce/shard_map/" in n for n in dots), dots
    assert any("/ce/shard_map/while/body/" in n for n in dots), dots


def test_train_step_on_one_device_has_no_shard_map_in_ce():
    """One device: the scan over all tokens as before.  No location under
    ``ce`` in the lowered step names a ``shard_map`` and no collective of
    the compiled one lies under ``ce`` (the expert layer keeps its
    ``shard_map`` and its ``psum`` on every mesh, so the whole step has
    both)."""
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    _, cfg = _tiny_model(mesh)
    m = DMoETransformerLM(dataclasses.replace(cfg, ce_chunk=16), mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = m.init_opt_state(opt, params)
    ids = jnp.zeros((8, 16), jnp.int32)
    lowered = m.make_train_step(opt).lower(params, opt_state, ids, ids)
    ce_locs = [
        l for l in lowered.as_text(debug_info=True).splitlines()
        if re.search(r"\(ce\)+/", l)
    ]
    assert any("while" in l for l in ce_locs)  # the scan is there
    assert not [l for l in ce_locs if "shard_map" in l]
    collectives = _loss_layer_ops(
        lowered.compile().as_text(),
        "all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute",
    )
    assert not [n for n in collectives if "/ce/" in n], collectives


def test_transformer_remat_matches():
    mesh = make_mesh({"data": 2, "expert": 4})
    model, _ = _tiny_model(mesh, remat=False)
    model_r, _ = _tiny_model(mesh, remat=True)
    params = model.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(1)
    ids = jnp.asarray(rs.randint(0, 64, (4, 16)))
    tgt = jnp.asarray(rs.randint(0, 64, (4, 16)))
    l1, _ = jax.jit(model.loss_fn)(params, ids, tgt)
    l2, _ = jax.jit(model_r.loss_fn)(params, ids, tgt)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


@pytest.mark.parametrize("against", ["no_remat", "the_parents_remat"])
def test_remat_policy_keeps_the_products_on_the_xla_core(against, monkeypatch):
    """``remat`` keeps what the blocked attention kernel names (PR 38) and
    the results of the attention part's matrix products (PR 53).  On the
    ``xla`` core the kernel names nothing and the products are kept all
    the same: the lowered program holds them behind the checkpoint's
    ``reduce_precision``, four a layer, where the parent's formula
    (``jax.checkpoint`` under no policy) holds none, and a two-layer
    model's loss and every gradient are those of that formula and those
    without remat to a few ulp."""
    mesh = make_mesh({"data": 2, "expert": 4})
    model_r, _ = _tiny_model(mesh, remat=True)
    params = model_r.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(1)
    ids = jnp.asarray(rs.randint(0, 64, (4, 16)))
    tgt = jnp.asarray(rs.randint(0, 64, (4, 16)))

    def loss_and_grads(model):
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, ids, tgt)[0]))

    got_fn = loss_and_grads(model_r)
    got = got_fn(params)
    kept = got_fn.lower(params).as_text().count("stablehlo.reduce_precision")
    assert kept == 4 * model_r.cfg.n_layers
    if against == "no_remat":
        want_fn = loss_and_grads(_tiny_model(mesh, remat=False)[0])
    else:
        checkpoint = jax.checkpoint
        monkeypatch.setattr(
            jax, "checkpoint", lambda fn, policy=None, **kw: checkpoint(fn, **kw))
        want_fn = loss_and_grads(_tiny_model(mesh, remat=True)[0])
        assert "stablehlo.reduce_precision" not in want_fn.lower(params).as_text()
    want = want_fn(params)
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got)[0],
        jax.tree_util.tree_leaves(want),
    ):
        # another compiled program either way: on this eight-device mesh
        # the backward adds the two layers' cotangents of the residual
        # stream in another order (1 ulp in a third of the embedding's
        # gradient), so a few ulp of the leaf's scale, not bits
        atol = 8 * np.finfo(np.float32).eps * float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0, atol=atol,
            err_msg=jax.tree_util.keystr(path))


def test_transformer_zigzag_matches_contiguous():
    """Flagship with the model-boundary zigzag permute produces the same
    loss as the contiguous ring on identical params/batch."""
    import dataclasses

    mesh = make_mesh({"data": 2, "expert": 2, "seq": 2})
    # capacity_factor high enough that nothing drops: capacity dropping
    # is token-ORDER-dependent, and zigzag reorders tokens — with drops
    # the two layouts legitimately diverge, without them they must match
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, seq_len=16,
        num_experts=8, k=2, dtype=jnp.float32, seq_parallel=True,
        seq_layout="contiguous", capacity_factor=8.0,
    )
    model_c = DMoETransformerLM(cfg, mesh)
    model_z = DMoETransformerLM(
        dataclasses.replace(cfg, seq_layout="zigzag"), mesh
    )
    assert model_z._zig is not None  # really on the pre-permuted path
    params = model_c.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(2)
    ids = jnp.asarray(rs.randint(0, 64, (4, 16)))
    tgt = jnp.asarray(rs.randint(0, 64, (4, 16)))
    l1, _ = jax.jit(model_c.loss_fn)(params, ids, tgt)
    l2, _ = jax.jit(model_z.loss_fn)(params, ids, tgt)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)

    # wrong sequence length must fail loudly, never silently misattend
    import pytest as _pytest

    bad = jnp.asarray(rs.randint(0, 64, (4, 8)))
    with _pytest.raises(ValueError, match="zigzag layout was built"):
        model_z.apply(params, bad)


def test_attn_impl_resolves_to_xla_on_cpu():
    """The model never picks the TPU-only flash kernel on CPU, below the
    kernel's shortest length or above it."""
    import dataclasses

    mesh = make_mesh({"expert": 8})
    _, cfg = _tiny_model(mesh)
    for seq_len in (16, 1024):
        m = DMoETransformerLM(dataclasses.replace(cfg, seq_len=seq_len), mesh)
        assert m.attn_impl == "xla"


def test_generate_greedy_decode_and_shapes():
    import dataclasses

    mesh = make_mesh({"expert": 8})
    model, cfg = _tiny_model(mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    out = model.generate(params, prompt, max_new_tokens=5)
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out[:, :3]), np.asarray(prompt))
    assert int(out.max()) < cfg.vocab_size and int(out.min()) >= 0
    # greedy decode is deterministic
    out2 = model.generate(params, prompt, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # temperature sampling needs a key, runs, and stays in range
    out3 = model.generate(
        params, prompt, max_new_tokens=5, temperature=1.0,
        rng=jax.random.PRNGKey(1),
    )
    assert out3.shape == (2, 8) and int(out3.max()) < cfg.vocab_size
    # overflow guard
    with pytest.raises(ValueError):
        model.generate(params, prompt, max_new_tokens=cfg.seq_len)


def test_jittered_model_decodes_on_clean_gates():
    """``router_jitter`` is a training-only regularizer: the decode model
    of a jittered model is a memoised twin with jitter 0 (so that its
    compiled decoders are reused), and a clean model decodes as itself."""
    mesh = make_mesh({"expert": 8})
    clean, base = _tiny_model(mesh)
    assert clean.decode_model() is clean
    jittered = DMoETransformerLM(
        dataclasses.replace(base, router_jitter=0.2), mesh
    )
    twin = jittered.decode_model()
    assert twin is not jittered and twin.cfg.router_jitter == 0.0
    assert dataclasses.replace(twin.cfg, router_jitter=0.2) == jittered.cfg
    assert jittered.decode_model() is twin
    # generate() goes through the twin with the model's own weights
    params = jittered.init_params(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = jittered.generate(params, prompt, max_new_tokens=4)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(twin.generate(params, prompt, 4))
    )
    assert list(twin._gen_jit) == [False] and not jittered._gen_jit


def test_padding_content_cannot_leak_into_decode_logits():
    """Round-3 advisor (medium): MoE capacity routing is cross-token, so
    with batch > 1 a row's padding tokens could exhaust expert capacity
    ahead of later rows' real tokens.  With token_mask, valid-position
    logits must be bit-independent of what the padding buffer holds."""
    # single-device mesh: the whole [B*S] buffer is ONE token shard, so
    # row 0's padding precedes row 1's real tokens in slot-claim order —
    # exactly the single-chip decode layout where the bug bites
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, seq_len=16,
        num_experts=8, k=2, dtype=jnp.float32,
        capacity_factor=0.5,  # tight capacity: padding CAN evict real tokens
    )
    model = DMoETransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(0))

    p = 4  # real prompt length; the rest of the buffer is padding
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 64, (2, p))
    pad_a = np.zeros((2, 16 - p), np.int64)
    pad_b = rs.randint(0, 64, (2, 16 - p))
    ids_a = jnp.asarray(np.concatenate([prompt, pad_a], axis=1))
    ids_b = jnp.asarray(np.concatenate([prompt, pad_b], axis=1))
    mask = jnp.asarray(np.arange(16)[None, :] < p).repeat(2, axis=0)

    apply = jax.jit(model.apply)  # eagerly every layer's shard_map compiles anew
    la, _ = apply(params, ids_a, token_mask=mask)
    lb, _ = apply(params, ids_b, token_mask=mask)
    np.testing.assert_array_equal(
        np.asarray(la[:, :p]), np.asarray(lb[:, :p])
    )
    # sanity: WITHOUT the mask the tight capacity makes valid logits
    # depend on padding occupancy — the bug the mask exists to fix
    ua, _ = apply(params, ids_a)
    ub, _ = apply(params, ids_b)
    assert not np.array_equal(np.asarray(ua[:, :p]), np.asarray(ub[:, :p]))


def test_kv_cache_decode_matches_full_forward():
    """generate(use_cache=True) must reproduce the re-forward decoder's
    tokens exactly when expert capacity never binds (the one regime where
    the per-step and whole-buffer routing coincide — see generate())."""
    import dataclasses

    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, seq_len=24,
        num_experts=8, k=2, dtype=jnp.float32,
        capacity_factor=8.0,  # capacity never binds: routing identical
    )
    model = DMoETransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3, 4], [9, 8, 7, 6]], jnp.int32)

    full = model.generate(params, prompt, max_new_tokens=8)
    cached = model.generate(params, prompt, max_new_tokens=8, use_cache=True)
    # Greedy-token comparison is only meaningful while argmax is not
    # sitting on a tie: verify the top-1/top-2 logit margin at every
    # decoded position is far above f32 noise, so a backend/dtype change
    # that perturbs low bits cannot flip a token (round-4 advisor).  A
    # genuine near-tie fails HERE, naming the position, instead of as an
    # inscrutable token mismatch below.  (capacity_factor=8 ⇒ routing on
    # the teacher-forced full sequence equals the per-step decode regime.)
    logits_all, _ = jax.jit(model.apply)(params, jnp.asarray(full))
    p = prompt.shape[1]
    decode_logits = np.asarray(logits_all)[:, p - 1:-1]  # predicts full[:, p:]
    top2 = np.sort(decode_logits, axis=-1)[..., -2:]
    margins = top2[..., 1] - top2[..., 0]
    assert margins.min() > 1e-3, f"argmax tie at margin {margins.min()}"
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))

    # single-token decode exercises the prefill-only path
    one = model.generate(params, prompt, max_new_tokens=1, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full[:, :5]), np.asarray(one))

    # temperature sampling runs and stays in range
    t = model.generate(
        params, prompt, max_new_tokens=4, temperature=1.0,
        rng=jax.random.PRNGKey(3), use_cache=True,
    )
    assert t.shape == (2, 8) and int(t.max()) < cfg.vocab_size

    # seq_parallel is explicitly unsupported with the cache
    sp_cfg = dataclasses.replace(cfg, seq_parallel=True)
    mesh_sp = make_mesh({"expert": 4, "seq": 2})
    sp_model = DMoETransformerLM(sp_cfg, mesh_sp)
    sp_params = sp_model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        sp_model.generate(sp_params, prompt, max_new_tokens=2, use_cache=True)


def test_kv_cache_decode_guards_row_shard_divisibility():
    """On a multi-shard mesh the cached decoder routes only B rows per
    step; B (and B*P) must divide the token shards or generate() must say
    so clearly instead of crashing inside shard_map."""
    mesh = make_mesh({"expert": 8})
    model, cfg = _tiny_model(mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)  # B=2 < 8
    with pytest.raises(ValueError, match="token shards"):
        model.generate(params, prompt, max_new_tokens=2, use_cache=True)
    # a batch that divides the shards decodes fine (B=8, B*P=24 % 8 == 0... 
    prompt8 = jnp.asarray(np.tile([[1, 2, 3, 4]], (8, 1)), jnp.int32)
    out = model.generate(params, prompt8, max_new_tokens=2, use_cache=True)
    assert out.shape == (8, 6)


def test_generate_zero_new_tokens_returns_prompt():
    """max_new_tokens=0 is a no-op on BOTH decode paths — the cached path
    used to allocate a (b, 0) buffer and die at trace time on .at[:, 0]
    (round-4 advisor)."""
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, seq_len=16,
        num_experts=4, k=2, dtype=jnp.float32,
    )
    model = DMoETransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    for use_cache in (False, True):
        out = model.generate(
            params, prompt, max_new_tokens=0, use_cache=use_cache
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))
    # negative budgets are caller bugs, not no-ops
    with pytest.raises(ValueError, match="max_new_tokens"):
        model.generate(params, prompt, max_new_tokens=-1)
