"""Operations and bytes of the ``nemotron-labs-twotower-30b-a3b``
configuration, from the sizes in its file (the ``nemotron_h`` key names of
``config.json``; ``n_routed_experts`` is the experts HELD,
``n_routed_experts_published`` the router's width; the layers run are the
first ``n_layers`` characters of ``hybrid_override_pattern``: ``M`` a
state-space layer, ``*`` attention, ``E`` the mixture).

``train_flops_per_token``: what the forward and backward passes need a
token (forward operations times three); what remat recomputes is not
counted.  The attention core is credited with **the elements the mask
admits** (:func:`admitted_scores`).  The routed experts are credited with
**the rows the step counted** on this chip (``local_rows_over_level``
times the level share ``k * held / published`` of a token's assignments),
never the buffer's size.  The recurrence is credited with ``4 H P N`` a
token (the state's update, ``h <- decay h + dt x B^T``, and its read-out,
``y = h C``: two operations an element of the ``H x P x N`` state each)
**whatever chunk the program takes**: the chunked form's products inside
a chunk are how the program gets there, not work the model asks for.

``grouped_matmul_flops`` / ``grouped_matmul_bytes``: ONE grouped matmul of
the expert layer over a step's counted rows (``2 m a b`` whatever its
mode; an expert here is two of them forward, no gate branch).

``attention_kernel_flops``: ONE call of the blocked attention kernel,
forward (2 matmuls an admitted element) or the fused backward (5).

``ssd_scan_flops`` / ``ssd_scan_bytes`` / ``ssd_scan_least_seconds``: the
recurrence (scope ``ssm/scan``) of the step's state-space layers, forward
and backward: the operations above times three, and the least it moves:
``x``, ``B``, ``C``, ``dt`` read and ``y`` written once forward; those
five and the five gradients once backward (three times the forward's
bytes a step).  The least time is the larger of the operations at the
bf16 peak and the bytes at the HBM peak (``peaks.py``): the same work
whatever implements the scan.
"""

import peaks


def admitted_scores(seq_len: int) -> int:
    """(query, key) pairs a causal mask admits, a head."""
    return seq_len * (seq_len + 1) // 2


def pattern(sizes: dict) -> str:
    """The layers run: ``M``, ``*`` or ``E`` each."""
    return sizes["hybrid_override_pattern"][: sizes["n_layers"]]


def level_rows_per_token(sizes: dict) -> float:
    """A token's assignments that fall on this chip's experts when loads
    are level: ``k * held / published``."""
    return (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["n_routed_experts_published"])


def ssm_widths(sizes: dict) -> tuple:
    """``(d_inner, the convolution's channels, the in-projection's width)``."""
    d_inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv_dim = d_inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    return d_inner, conv_dim, d_inner + conv_dim + sizes["mamba_num_heads"]


def recurrence_flops_per_token(sizes: dict) -> int:
    """``4 H P N``: update and read-out of one layer's state, forward."""
    return (4 * sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
            * sizes["ssm_state_size"])


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward operations a token, by part of the model."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f = sizes["moe_intermediate_size"]
    layers = pattern(sizes)
    n_ssm, n_attn, n_moe = (layers.count(c) for c in "M*E")
    d_inner, _, in_width = ssm_widths(sizes)
    return {
        "ssm_projections": n_ssm * 2 * d * (in_width + d_inner),
        "ssm_recurrence": n_ssm * recurrence_flops_per_token(sizes),
        # q, o and k, v projections
        "projections": n_attn * 2 * d * hd * (2 * heads + 2 * kv),
        "attention_core": n_attn * 4 * hd * heads * admitted_scores(s) / s,
        # un-gated: two matrices an expert
        "shared_expert": (n_moe * sizes["n_shared_experts"] * 4 * d
                          * sizes["moe_shared_expert_intermediate_size"]),
        "router": n_moe * 2 * d * sizes["n_routed_experts_published"],
        "routed_experts": (
            n_moe * rows_over_level * level_rows_per_token(sizes) * 4 * d * f),
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
    return float(itemsize * (rows * d + rows * f
                             + sizes["n_routed_experts"] * d * f))


ATTENTION_KERNEL_MATMULS = {"forward": 2, "backward": 5}


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` (rows of ``seq_len``): ``kind``
    is ``global`` (this model has no other), ``way`` ``forward`` or
    ``backward``."""
    s = sizes["seq_len"]
    return float(
        (tokens // s) * sizes["num_attention_heads"] * admitted_scores(s)
        * 2 * sizes["head_dim"] * ATTENTION_KERNEL_MATMULS[way]
    )


def ssd_scan_flops(sizes: dict, tokens: int) -> float:
    """The recurrences of a step's state-space layers, forward and
    backward (3 x forward, no recompute)."""
    return float(3 * pattern(sizes).count("M") * tokens
                 * recurrence_flops_per_token(sizes))


def ssd_scan_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """The least the same recurrences move: forward ``x``, ``B``, ``C``,
    ``dt`` read and ``y`` written once; backward those five and the five
    gradients once."""
    d_inner, conv_dim, _ = ssm_widths(sizes)
    forward = tokens * itemsize * (
        conv_dim + sizes["mamba_num_heads"] + d_inner)
    return float(3 * pattern(sizes).count("M") * forward)


def ssd_scan_least_seconds(sizes: dict, tokens: int, device_kind: str) -> float:
    """The least a step's recurrences take on ``device_kind``."""
    return max(
        ssd_scan_flops(sizes, tokens) / peaks.peak_bf16_flops(device_kind),
        ssd_scan_bytes(sizes, tokens) / peaks.PEAK_HBM_BYTES_PER_S[device_kind],
    )
