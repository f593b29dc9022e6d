"""Operations and bytes of the ``olmoe-1b-7b`` configuration, from the
sizes in its file (Hugging Face key names).

``train_flops_per_token``: what the forward and backward passes need a
token (forward matmuls times three); what remat recomputes is not
counted.  Routing is dropless, so every one of a token's
``num_experts_per_tok`` expert FFNs is computed and credited: nothing is
credited that did not run.

``grouped_matmul_flops`` / ``grouped_matmul_bytes``: ONE grouped matmul
of the expert layer over a step's sorted rows.  Forward (``[m, a] x
[E, a, b]``), the gradient to the rows and the gradient to the stack all
multiply the same three extents, so each call executes ``2 m a b``
operations whatever its mode and whatever the group sizes.
"""


def train_flops_per_token(sizes: dict) -> float:
    d = sizes["hidden_size"]
    s = sizes["seq_len"]
    v = sizes["vocab_size"]
    f = sizes["intermediate_size"]
    k = sizes["num_experts_per_tok"]
    e = sizes["num_experts"]
    forward = (
        2 * d * v  # untied head
        + sizes["n_layers"] * (
            8 * d * d      # q, k, v, o projections
            + 4 * s * d    # scores and weighted values, full (not causal-halved)
            + 2 * d * e    # router
            + k * 6 * d * f  # gate, up and down of each routed expert
        )
    )
    return 3.0 * forward


def grouped_matmul_flops(sizes: dict, tokens: int) -> float:
    rows = tokens * sizes["num_experts_per_tok"]
    return 2.0 * rows * sizes["hidden_size"] * sizes["intermediate_size"]


def grouped_matmul_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """Rows in and out once and the whole expert stack once: the least a
    call moves (the backward calls move the same three arrays)."""
    rows = tokens * sizes["num_experts_per_tok"]
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    return float(itemsize * (
        rows * d + rows * f + sizes["num_experts"] * d * f
    ))
