"""Operations and bytes of the ``glm-4.7-flash`` configuration, from the
sizes in its file (GLM-4.7-Flash's ``config.json`` key names;
``n_routed_experts`` is the experts HELD, ``n_routed_experts_published``
the router's width).

``train_flops_per_token``: what the forward and backward passes need a
token (forward matmuls times three); what remat recomputes is not
counted.  The stack's ``n_layers`` layers AND the next-but-one-token block
(``num_nextn_predict_layers``: one more mixture layer behind a [2d, d]
projection) are counted, and the head twice: both losses go through it.
The attention core is credited with **the elements the causal mask
admits** (``kexaone_flops.admitted_scores``: S (S + 1) / 2 a head), at
``qk_nope_head_dim + qk_rope_head_dim`` for the scores and ``v_head_dim``
for the weighted values of a pair of positions, never the blocks the
kernel visits.  The routed experts are credited with **the rows the step
counted** on this chip (``local_rows_over_level`` times the level share
``k * held / published`` of a token's assignments), never the buffer's
size, so neither the utilization nor the grouped matmul's roofline share
can pass 100 % for it.

``grouped_matmul_flops`` / ``grouped_matmul_bytes``: ONE grouped matmul of
an expert layer over a step's counted rows (``2 m a b`` whatever its
mode).

``attention_kernel_flops``: ONE call of the blocked attention kernel,
forward (scores and weighted values) or the FUSED backward (scores again,
the gradients of the probabilities, values, keys and queries): the regime
``trunk.flash_block_sizes`` chose for heads of 256 (PERF.md section 6,
PR 37).  An unfused backward would be two calls of four and three
products, not one of five: this count would then credit them too much.
"""

from kexaone_flops import admitted_scores, rows_over_level  # noqa: F401


def level_rows_per_token(sizes: dict) -> float:
    """A token's assignments that fall on this chip's experts when loads
    are level: ``k * held / published``."""
    return (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["n_routed_experts_published"])


def _qk_dim(sizes: dict) -> int:
    return sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]


def attention_forward_flops_per_token(sizes: dict) -> dict:
    """One layer's attention, forward matmul operations a token."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    c_q, c_kv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dv, s = sizes["v_head_dim"], sizes["seq_len"]
    return {
        # both down-projections, both expansions, the output projection
        "latent_projections": 2 * (
            d * c_q + c_q * heads * _qk_dim(sizes)
            + d * (c_kv + sizes["qk_rope_head_dim"])
            + c_kv * heads * (sizes["qk_nope_head_dim"] + dv)
            + heads * dv * d),
        "attention_core": 2 * heads * (_qk_dim(sizes) + dv) * admitted_scores(s) / s,
    }


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward matmul operations a token, by part of the model."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    blocks = sizes["num_nextn_predict_layers"]
    layers = sizes["n_layers"] + blocks
    dense = min(sizes["first_k_dense_replace"], sizes["n_layers"])
    sparse = layers - dense
    attention = attention_forward_flops_per_token(sizes)
    return {
        "latent_projections": layers * attention["latent_projections"],
        "attention_core": layers * attention["attention_core"],
        "dense_ffn": dense * 6 * d * sizes["intermediate_size"],
        "shared_expert": sparse * sizes["n_shared_experts"] * 6 * d * f,
        "router": sparse * 2 * d * sizes["n_routed_experts_published"],
        "routed_experts": (
            sparse * rows_over_level * level_rows_per_token(sizes) * 6 * d * f),
        "mtp_combine": blocks * 2 * (2 * d) * d,
        # untied; once for the next token's loss, once a prediction block
        "head": (1 + blocks) * 2 * d * sizes["vocab_size"],
    }


def train_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> float:
    return 3.0 * sum(forward_flops_per_token(sizes, rows_over_level).values())


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
    return float(itemsize * (rows * d + rows * f
                             + sizes["n_routed_experts"] * d * f))


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` (rows of ``seq_len``); ``kind`` is
    ``global`` (every layer's mask is the causal one), ``way`` ``forward``
    (a product over the key size, one over the value size, an admitted
    element) or ``backward`` (fused: three over the key size, two over the
    value size)."""
    if kind != "global":
        raise ValueError(f"no {kind!r} layer in this configuration")
    s, dk, dv = sizes["seq_len"], _qk_dim(sizes), sizes["v_head_dim"]
    per_element = {"forward": 2 * (dk + dv), "backward": 2 * (3 * dk + 2 * dv)}
    return float((tokens // s) * sizes["num_attention_heads"]
                 * admitted_scores(s) * per_element[way])
