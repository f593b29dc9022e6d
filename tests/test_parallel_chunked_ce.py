"""The pod step's chunked cross-entropy against the loss over the whole
logits, value and gradients, on the virtual 8-device CPU mesh: token counts
a chunk divides and does not, bf16 storage, a vocabulary no chunk divides
(the scan a shard against the global scan is
``tests/test_parallel_ce_per_shard.py``'s).  A module apart from
``tests/test_parallel.py``: two compiled programs a case, and under ``--dist
loadfile`` a file is one worker's.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_parallel import _tiny_model, pytestmark  # noqa: F401  (the 8-device skip)
from learning_at_home_tpu.models.transformer import (
    DMoETransformerConfig,
    DMoETransformerLM,
)
from learning_at_home_tpu.parallel import batch_sharding, make_mesh


def _assert_ce_matches_full_logits(
    m, params, ids, tgt, loss_tol, grad_tol, cotangent=1.0
):
    """``loss_fn``'s chunked CE against the loss over the whole [B, S, V]
    float32 logits: the value, the gradients with respect to the hidden
    states and the head (the loss layer alone, which takes them in its
    forward scan and multiplies them by the ``cotangent`` that arrives:
    1 in a train step), and the gradients with respect to every parameter
    (a tied head takes the embedding's cotangent from both ends).
    ``grad_tol`` bounds ``max|a-b| / max|b|`` per leaf."""
    cfg = m.cfg

    def full_ce(x, head):
        return optax.softmax_cross_entropy_with_integer_labels(
            m._logits(x, head), tgt
        ).mean()

    def full_loss(p):
        x, aux = m._hidden(p, ids)
        return (
            full_ce(x, m._head(p))
            + cfg.aux_loss_weight * aux["aux_loss"]
            + cfg.router_z_weight * aux["router_z_loss"]
        )

    def close(got, want):
        for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_leaves(want),
        ):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            assert np.abs(g - w).max() <= grad_tol * np.abs(w).max(), (
                jax.tree_util.keystr(path)
            )

    @functools.partial(jax.jit, static_argnums=(0, 1))  # eager: 30 s a case
    def both(loss_of_params, ce_of_x_head, p):
        x, head = m._hidden(p, ids)[0], m._head(p)
        return (
            jax.value_and_grad(loss_of_params)(p),
            jax.grad(ce_of_x_head, argnums=(0, 1))(x, head),
        )

    (loss, grads), ce_grads = both(
        lambda p: m.loss_fn(p, ids, tgt)[0],
        lambda x, h: cotangent * m._chunked_ce(x, h, tgt), params,
    )
    (ref, ref_grads), ref_ce_grads = both(
        full_loss, lambda x, h: cotangent * full_ce(x, h), params
    )
    assert loss.dtype == jnp.float32
    assert abs(float(loss) - float(ref)) < loss_tol
    close(grads, ref_grads)
    close(ce_grads, ref_ce_grads)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize(
    "batch, chunk, dtype, cotangent",
    # n = 128, 80, 48 tokens: divisible, a remainder, less than a chunk;
    # then bf16 storage, whose statistics must stay float32; then a
    # cotangent other than a train step's 1 into the loss layer
    [(8, 16, "float32", 1.0), (5, 16, "float32", 1.0),
     (3, 128, "float32", 1.0), (8, 16, "bfloat16", 1.0),
     (5, 16, "float32", -2.5), (8, 16, "bfloat16", 0.37)],
)
def test_chunked_ce_matches_full_logits(batch, chunk, dtype, cotangent, tied):
    """loss_fn's chunked CE must equal the full-logits loss, value and
    gradients, for divisible AND indivisible token counts (the
    indivisible remainder goes through one more chunk, never full [n,V]
    logits).  With bf16 operands the logits and the softmax
    statistics stay float32, so the loss sits within bf16 rounding of the
    float32-logits reference computed from the SAME bf16 inputs (a bf16
    softmax over 64 classes would be 1e-2 away); the gradients are bf16
    values, compared at bf16's resolution."""
    mesh = make_mesh({"data": 2, "expert": 4})
    _, cfg = _tiny_model(mesh)
    m = DMoETransformerLM(
        dataclasses.replace(
            cfg, ce_chunk=chunk, tie_embeddings=tied, dtype=jnp.dtype(dtype),
            n_layers=1,
        ),
        mesh,
    )
    params = m.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, 64, (batch, 16)))
    tgt = jnp.asarray(rs.randint(0, 64, (batch, 16)))
    loss_tol, grad_tol = (1e-5, 1e-5) if dtype == "float32" else (1e-3, 2e-2)
    _assert_ce_matches_full_logits(
        m, params, ids, tgt, loss_tol, grad_tol, cotangent
    )


@pytest.mark.parametrize(
    "axes", [{"expert": 1}, {"data": 2, "expert": 2}],
    ids=["one-device", "data2xexpert2"],
)
def test_chunked_ce_at_a_vocabulary_no_chunk_divides(axes):
    """OLMoE's vocabulary is 50,304 = 128 x 393: no multiple of the chunk
    or of 1,024.  The chunked CE tiles tokens, never the vocabulary, so
    393 classes give the full-logits loss and gradients on one device
    (the scan) and on pod4's mesh (the scan per shard)."""
    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n_dev])
    cfg = DMoETransformerConfig(
        vocab_size=393, d_model=32, n_layers=1, n_heads=4, seq_len=16,
        num_experts=4, k=2, dtype=jnp.float32, ce_chunk=24,
        tie_embeddings=False,
    )
    m = DMoETransformerLM(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(5)
    ids, tgt = (
        jax.device_put(
            jnp.asarray(rs.randint(0, 393, (8, 16))), batch_sharding(mesh)
        )
        for _ in range(2)
    )
    _assert_ce_matches_full_logits(m, params, ids, tgt, 1e-5, 1e-5)
