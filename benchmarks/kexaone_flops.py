"""Operations and bytes of the ``k-exaone-236b-a23b`` configuration, from
the sizes in its file (K-EXAONE's ``config.json`` key names; ``num_experts``
is the experts HELD, ``num_experts_published`` the router's width).

``train_flops_per_token``: what the forward and backward passes need a
token (forward matmuls times three); what remat recomputes is not
counted.  The attention core is credited with **the elements the mask
admits** (:func:`admitted_scores`: 128 keys a query on a window layer),
not the uncut S x S square and not the 1024-wide blocks the kernel
visits.  The routed experts are credited with **the rows the step
counted** on this chip (``local_rows_over_level`` times the level share
``k * held / published`` of a token's assignments), never the buffer's
size: an empty buffer row is no work, so neither the utilization nor the
grouped matmul's roofline share can pass 100 % for it.

``grouped_matmul_flops`` / ``grouped_matmul_bytes``: ONE grouped matmul of
the expert layer over a step's counted rows (``2 m a b`` whatever its
mode).

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
    local = sizes["layer_types"][layer] == "sliding_attention"
    return sizes["sliding_window"] if local else None


def level_rows_per_token(sizes: dict) -> float:
    """A token's assignments that fall on this chip's experts when loads
    are level: ``k * held / published``."""
    return (sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["num_experts_published"])


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward matmul operations a token, by part of the model."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f = sizes["moe_intermediate_size"]
    layers = range(sizes["n_layers"])
    sparse = sum(sizes["mlp_layer_types"][i] == "sparse" for i in layers)
    return {
        # q, o and k, v projections
        "projections": sizes["n_layers"] * 2 * d * hd * (2 * heads + 2 * kv),
        "attention_core": sum(
            4 * hd * heads * admitted_scores(s, _window(sizes, i)) / s
            for i in layers),
        "dense_ffn": (sizes["n_layers"] - sparse) * 6 * d * sizes["intermediate_size"],
        "shared_expert": sparse * sizes["num_shared_experts"] * 6 * d * f,
        "router": sparse * 2 * d * sizes["num_experts_published"],
        "routed_experts": (
            sparse * rows_over_level * level_rows_per_token(sizes) * 6 * d * f),
        "head": 2 * d * sizes["vocab_size"],  # untied
    }


def train_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> float:
    return 3.0 * sum(forward_flops_per_token(sizes, rows_over_level).values())


def rows_over_level(obs: dict) -> float | None:
    """Mean over a run's steps of the rows a step COMPUTED over the level
    share, from the program's own step metrics: those routed here
    (``local_rows_over_level``) less those the buffer dropped
    (``dropped_fraction``).  None where the program reports no such
    counter."""
    rows = obs.get("local_rows_over_level")
    if not rows:
        return None
    dropped = obs.get("dropped_fraction") or [0.0] * len(rows)
    return sum(r * (1.0 - d) for r, d in zip(rows, dropped)) / len(rows)


def counted_rows(sizes: dict, tokens: int, rows_over_level: float) -> float:
    """Rows of a layer's sorted buffer that hold an assignment, a step."""
    return tokens * level_rows_per_token(sizes) * rows_over_level


def grouped_matmul_flops(sizes: dict, tokens: int,
                         rows_over_level: float = 1.0) -> float:
    return (2.0 * counted_rows(sizes, tokens, rows_over_level)
            * sizes["hidden_size"] * sizes["moe_intermediate_size"])


def grouped_matmul_bytes(sizes: dict, tokens: int, rows_over_level: float = 1.0,
                         itemsize: int = 2) -> float:
    """Counted rows in and out once and the held expert stack once: the
    least a call moves."""
    rows = counted_rows(sizes, tokens, rows_over_level)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return float(itemsize * (rows * d + rows * f + sizes["num_experts"] * d * f))


ATTENTION_KERNEL_MATMULS = {"forward": 2, "backward": 5}


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` (rows of ``seq_len``): ``kind``
    ``global`` or ``window``, ``way`` ``forward`` or ``backward``."""
    s = sizes["seq_len"]
    window = sizes["sliding_window"] if kind == "window" else None
    return float(
        (tokens // s) * sizes["num_attention_heads"]
        * admitted_scores(s, window)
        * 2 * sizes["head_dim"] * ATTENTION_KERNEL_MATMULS[way]
    )
