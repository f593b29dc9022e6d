"""The pod step's chunked cross-entropy as the scan a shard (every mesh the
benchmark's cells and their neighbours use) against the global scan and the
full logits: loss and every gradient; and the loss layer called by itself, a
shard's gradients against the full logits' and the backward scan's bits.  A module apart from
``tests/test_parallel_chunked_ce.py``: three compiled programs a case, and
under ``--dist loadfile`` a file is one worker's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_parallel import (  # noqa: F401  (``pytestmark``: the 8-device skip)
    _loss_layer_alone,
    _tiny_model,
    pytestmark,
)
from learning_at_home_tpu.models.transformer import DMoETransformerLM
from learning_at_home_tpu.parallel import batch_sharding, make_mesh


@pytest.mark.parametrize(
    "axes, batch, chunk",
    [
        # tokens a shard / chunk: a scan of 2 and no remainder; a scan of
        # 2 and a remainder; one chunk and a remainder; less than a chunk
        ({"data": 2, "expert": 2}, 8, 16),
        ({"data": 2, "expert": 2}, 12, 20),
        ({"data": 2, "expert": 4}, 16, 16),
        ({"data": 2, "expert": 4}, 16, 24),
        ({"expert": 8}, 16, 16),
        ({"expert": 8}, 24, 20),
        ({"expert": 8}, 8, 128),
        # the sequence sharded too, 16 tokens a shard: a scan of 4; a
        # scan of 2 and a remainder
        ({"data": 2, "expert": 2, "seq": 2}, 8, 4),
        ({"expert": 4, "seq": 2}, 8, 6),
    ],
    ids=lambda v: (
        "x".join(f"{k}{n}" for k, n in v.items()) if isinstance(v, dict)
        else str(v)
    ),
)
def test_chunked_ce_per_shard_matches_global_scan(axes, batch, chunk):
    """On a multi-device mesh the chunked CE scans each shard's own rows
    under ``shard_map``: the loss is the full-logits loss, and every
    gradient leaf is that of the global scan over the unsharded arrays."""
    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n_dev])
    _, cfg = _tiny_model(mesh)
    cfg = dataclasses.replace(
        cfg, ce_chunk=chunk, n_layers=1, seq_parallel="seq" in axes
    )
    m = DMoETransformerLM(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(7)
    ids, tgt = (
        jax.device_put(
            jnp.asarray(rs.randint(0, 64, (batch, 16))), batch_sharding(mesh)
        )
        for _ in range(2)
    )

    def with_aux(ce, aux):
        return (
            ce
            + cfg.aux_loss_weight * aux["aux_loss"]
            + cfg.router_z_weight * aux["router_z_loss"]
        )

    def per_shard_loss(p):
        return m.loss_fn(p, ids, tgt)[0]

    def global_scan_loss(p):  # loss_fn with the CE of the one-device path
        x, aux = m._hidden(p, ids)
        return with_aux(m._chunked_ce_sum(x, m._head(p), tgt, tgt.size), aux)

    # the path under test is the per-shard one (the expert layer has a
    # shard_map of its own, so the loss layer is traced alone)
    x_head = jax.eval_shape(lambda p: (m._hidden(p, ids)[0], m._head(p)), params)
    assert "shard_map" in str(
        jax.make_jaxpr(lambda x, h: m._chunked_ce(x, h, tgt))(*x_head)
    )
    loss, grads = jax.jit(jax.value_and_grad(per_shard_loss))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(global_scan_loss))(params)
    logits, aux = jax.jit(m.apply)(params, ids)
    full = with_aux(
        optax.softmax_cross_entropy_with_integer_labels(logits, tgt).mean(), aux
    )
    assert abs(float(loss) - float(full)) < 1e-5
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    # f32 throughout: the two differ only in the order of the last f32
    # additions (per-shard sums, then across shards; the head's cotangent
    # summed per shard, then over shards), a few ulp of leaves whose
    # largest entries are 1e-2..1: 1e-5 absolute is 100 times that and
    # 1000 times under a wrong 1/n (the shard's token count for the
    # global one would scale every leaf by the shard count)
    for (path, g), r in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(ref_grads),
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=0, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("cotangent", [1.0, -0.75], ids=["one", "other"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "axes, chunk",
    [
        # tokens a shard / chunk: 32 / 16; 32 / 12 (a remainder); 16 / 128;
        # with the sequence sharded too: 16 / 4, 32 / 12
        ({"data": 4, "expert": 1}, 16),
        ({"data": 2, "expert": 2}, 12),
        ({"expert": 8}, 128),
        ({"data": 2, "expert": 2, "seq": 2}, 4),
        ({"expert": 2, "seq": 2}, 12),
    ],
    ids=lambda v: (
        "x".join(f"{k}{n}" for k, n in v.items()) if isinstance(v, dict)
        else str(v)
    ),
)
def test_loss_layer_gradients_per_shard_match_full_logits(
    axes, chunk, dtype, cotangent
):
    """The loss layer takes its gradients in its forward scan, per shard
    on a mesh: the value, and the gradients with respect to the hidden
    states and the head under any cotangent, are those autodiff gives the
    loss over the whole float32 logits (float32: 1e-5; bf16 values at
    bf16's resolution, as ``test_chunked_ce_matches_full_logits``)."""
    m, x, head, tgt = _loss_layer_alone(axes, dtype, chunk)
    assert "shard_map" in str(
        jax.make_jaxpr(lambda x, h: m._chunked_ce(x, h, tgt))(x, head)
    )

    def full_ce(x, h):
        return optax.softmax_cross_entropy_with_integer_labels(
            m._logits(x, h), tgt
        ).mean()

    got, want = (
        jax.jit(jax.value_and_grad(
            lambda x, h: cotangent * ce(x, h), argnums=(0, 1)
        ))(x, head)
        for ce in (lambda x, h: m._chunked_ce(x, h, tgt), full_ce)
    )
    loss_tol, grad_tol = (1e-5, 1e-5) if dtype == "float32" else (1e-3, 2e-2)
    assert got[0].dtype == jnp.float32
    assert abs(float(got[0]) - float(want[0])) < loss_tol
    for g, w, like in zip(got[1], want[1], (x, head)):
        assert g.dtype == like.dtype and g.shape == like.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= grad_tol * np.abs(w).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "batch, chunk",
    # 128 tokens in 8 chunks; 80 in 5; 88 in 5 and a remainder; 24 in one
    # and a remainder; 48 under a chunk
    [(8, 16), (5, 16), (11, 8), (3, 32), (3, 128)],
)
def test_loss_layer_gradients_keep_the_bits_of_the_backward_scan(
    batch, chunk, dtype
):
    """Before PR 34 every chunk ran under ``jax.checkpoint`` and autodiff
    made the gradients in a backward scan that computed each chunk's
    logits again.  The forward scan that takes them now makes the same
    products of the same operands and adds the chunks' shares of the
    head's gradient in the same order and dtype: not a bit of either
    gradient differs, in float32 or with bf16 storage (where the head's
    gradient accumulates in bf16, as it did)."""
    m, x, head, tgt = _loss_layer_alone({"expert": 1}, dtype, chunk, batch=batch)

    def checkpointed_scan(x, head):
        n = x.shape[0] * x.shape[1]
        flat_x, flat_t = x.reshape(n, -1), tgt.reshape(n)
        c = min(chunk, n)

        def chunk_ce(carry, xt):
            ce = optax.softmax_cross_entropy_with_integer_labels(
                m._logits(xt[0], head), xt[1]
            )
            return carry + ce.sum(), None

        ce_sum, main = jnp.float32(0), (n // c) * c
        if main > c:
            ce_sum, _ = jax.lax.scan(
                jax.checkpoint(chunk_ce), ce_sum,
                (flat_x[:main].reshape(main // c, c, -1),
                 flat_t[:main].reshape(main // c, c)),
            )
        elif main:
            ce_sum, _ = jax.checkpoint(chunk_ce)(
                ce_sum, (flat_x[:main], flat_t[:main])
            )
        if n > main:
            ce_sum, _ = jax.checkpoint(chunk_ce)(
                ce_sum, (flat_x[main:], flat_t[main:])
            )
        return ce_sum / n

    got, want = (
        jax.jit(jax.value_and_grad(ce, argnums=(0, 1)))(x, head)
        for ce in (lambda x, h: m._chunked_ce(x, h, tgt), checkpointed_scan)
    )
    # the value is the same terms added last chunk first: within 2 ulp
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=3e-7)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32)
        )
