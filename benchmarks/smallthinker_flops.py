"""Operations and bytes of the ``smallthinker-21b-a3b`` configuration, from
the sizes in its file (SmallThinker's ``config.json`` key names).

``train_flops_per_token``: what the forward and backward passes need a
token (forward matmuls times three); what remat recomputes is not
counted.  Routing is dropless, so every one of a token's 6 expert FFNs is
computed and credited.  The attention core is credited with **the
elements the mask admits** (:func:`admitted_scores`), not the uncut S x S
square and not the blocks the kernel visits: work that did not have to
run is credited nowhere, so neither the utilization nor the kernel's
roofline share can pass 100 % for it.

``grouped_matmul_flops`` / ``grouped_matmul_bytes``: ONE grouped matmul of
the expert layer over a step's sorted rows (``2 m a b`` whatever its mode).

``attention_kernel_flops``: ONE call of the blocked attention kernel on a
layer of a kind, forward (scores and weighted values: 2 matmuls an
admitted element) or the fused backward (scores again, and the gradients
of the probabilities, values, keys and queries: 5).
"""


def admitted_scores(seq_len: int, window: int | None = None) -> int:
    """(query, key) pairs a causal mask admits, a head: every key up to
    the query's own, or the ``window`` keys that end with it."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _window(sizes: dict, layer: int) -> int | None:
    return (sizes["sliding_window_size"]
            if sizes["sliding_window_layout"][layer] else None)


def train_flops_per_token(sizes: dict) -> float:
    d = sizes["hidden_size"]
    s = sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f = sizes["moe_ffn_hidden_size"]
    forward = 2 * d * sizes["vocab_size"]  # untied head
    for layer in range(sizes["n_layers"]):
        forward += (
            2 * d * hd * (2 * heads + 2 * kv)  # q, o and k, v projections
            + 4 * hd * heads * admitted_scores(s, _window(sizes, layer)) / s
            + 2 * d * sizes["moe_num_primary_experts"]  # router
            + sizes["moe_num_active_primary_experts"] * 6 * d * f
        )
    return 3.0 * forward


def grouped_matmul_flops(sizes: dict, tokens: int) -> float:
    rows = tokens * sizes["moe_num_active_primary_experts"]
    return 2.0 * rows * sizes["hidden_size"] * sizes["moe_ffn_hidden_size"]


def grouped_matmul_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """Rows in and out once and the whole expert stack once: the least a
    call moves."""
    rows = tokens * sizes["moe_num_active_primary_experts"]
    d, f = sizes["hidden_size"], sizes["moe_ffn_hidden_size"]
    return float(itemsize * (
        rows * d + rows * f + sizes["moe_num_primary_experts"] * d * f
    ))


ATTENTION_KERNEL_MATMULS = {"forward": 2, "backward": 5}


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` (rows of ``seq_len``): ``kind``
    ``global`` or ``window``, ``way`` ``forward`` or ``backward``."""
    s = sizes["seq_len"]
    window = sizes["sliding_window_size"] if kind == "window" else None
    return float(
        (tokens // s) * sizes["num_attention_heads"]
        * admitted_scores(s, window)
        * 2 * sizes["head_dim"] * ATTENTION_KERNEL_MATMULS[way]
    )
