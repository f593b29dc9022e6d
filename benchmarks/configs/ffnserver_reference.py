"""Plain reference of the ``ffn`` expert (``models/layers.py:
FeedforwardBlock``): ``x + W2 gelu(W1 LN(x) + b1) + b2``, pre-LN residual
MLP with a 4x hidden layer, tanh-approximated GELU (flax's default) and
LayerNorm epsilon 1e-6.  float32 throughout, matmuls at "highest"
precision, no batching, no padding, written from the block's equations
and sharing no code with it.  Takes the flax parameter tree as the server
hosts it."""

import jax
import jax.numpy as jnp

LN_EPSILON = 1e-6


def apply(params, x):
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                               params["params"])
    x = jnp.asarray(x, jnp.float32)
    with jax.default_matmul_precision("highest"):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
        h = (x - mean) / jnp.sqrt(var + LN_EPSILON)
        h = h * p["LayerNorm_0"]["scale"] + p["LayerNorm_0"]["bias"]
        h = h @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]
        h = 0.5 * h * (1.0 + jnp.tanh(
            jnp.sqrt(2.0 / jnp.pi) * (h + 0.044715 * h ** 3)
        ))
        h = h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]
        return x + h
