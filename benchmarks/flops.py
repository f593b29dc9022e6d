"""Operations the algorithm needs, computed from the configuration's sizes.

``dmoe_train_flops_per_token`` is ``bench.py:_model_flops_per_step`` per
token: forward matmuls, times three for forward plus backward.  What
remat recomputes is not counted.  It credits every routed assignment
(``experts_per_token`` expert FFNs per token per layer) whether or not
the capacity limit dropped it, so a utilization built on it overstates
the useful share by the dropped share of the expert term.
"""


def dmoe_train_flops_per_token(sizes: dict) -> float:
    d = sizes["d_model"]
    s = sizes["seq_len"]
    v = sizes["vocab_size"]
    layers = sizes["n_layers"]
    k = sizes["experts_per_token"]
    f = sizes["ffn_mult"] * d
    forward = (
        2 * d * v  # logits projection (tied embedding)
        + layers * (
            8 * d * d      # q, k, v, o projections
            + 4 * s * d    # scores and weighted values, full (not causal-halved)
            + k * 4 * d * f  # two matmuls of each routed expert FFN
        )
    )
    return 3.0 * forward
