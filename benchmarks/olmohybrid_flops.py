"""Operations and bytes of the ``olmo-hybrid-7b`` configuration, from the
sizes in its file (the ``olmo_hybrid`` key names of ``config.json``; the
layers run are the first ``n_layers`` entries of ``layer_types``:
``linear_attention`` a gated delta-rule layer, ``full_attention`` softmax
attention; every layer has a dense gated block beside its mixer).

``train_flops_per_token``: what the forward and backward passes need a
token (forward operations times three); what remat recomputes is not
counted.  The attention core is credited with **the elements the mask
admits** (:func:`admitted_scores`).  The delta rule is credited with ``6 H
dk dv`` a token a layer (what the state answers for the key, ``(alpha
S)^T k``; the state's update, ``alpha S + k (..)^T``; its read-out, ``S^T
q``: two operations an element of the ``H x dk x dv`` state each)
**whatever chunk or solve the program takes**: the chunked form's products
inside a chunk and its triangular solve are how the program gets there,
not work the model asks for.

``attention_kernel_flops``: ONE call of the blocked attention kernel,
forward (2 matmuls an admitted element) or the fused backward (5).

``delta_core_flops`` / ``delta_core_bytes`` / ``delta_core_least_seconds``:
the rule (scope ``delta/core``) of the step's delta layers, forward and
backward: the operations above times three, and the least it moves:
``q``, ``k``, ``v``, the decays and write strengths read and ``o`` written
once forward; ``q``, ``k``, ``v``, those two and ``o``'s cotangent read and
the five gradients written once backward.  The least time is the larger of
the operations at the bf16 peak and the bytes at the HBM peak
(``peaks.py``): the same work whatever chunk or kernel implements it.
"""

import peaks


def admitted_scores(seq_len: int) -> int:
    """(query, key) pairs a causal mask admits, a head."""
    return seq_len * (seq_len + 1) // 2


def layers(sizes: dict) -> list:
    """The layers run: ``linear_attention`` or ``full_attention`` each."""
    return sizes["layer_types"][: sizes["n_layers"]]


def delta_widths(sizes: dict) -> tuple:
    """``(q's and k's channels together, v's channels, the in-projection's
    width [q | k | v | z | b | a])``."""
    h = sizes["linear_num_key_heads"]
    d_qk = 2 * h * sizes["linear_key_head_dim"]
    d_v = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
    return d_qk, d_v, d_qk + 2 * d_v + 2 * h


def recurrence_flops_per_token(sizes: dict) -> int:
    """``6 H dk dv``: one layer's rule, forward."""
    return (6 * sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
            * sizes["linear_value_head_dim"])


def forward_flops_per_token(sizes: dict) -> dict:
    """Forward operations a token, by part of the model."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kinds = layers(sizes)
    n_delta = kinds.count("linear_attention")
    n_full = kinds.count("full_attention")
    _, d_v, in_width = delta_widths(sizes)
    return {
        "delta_projections": n_delta * 2 * d * (in_width + d_v),
        "delta_recurrence": n_delta * recurrence_flops_per_token(sizes),
        # q, k, v and o projections, as many key/value heads as query heads
        "projections": n_full * 2 * d * 4 * heads * hd,
        "attention_core": n_full * 4 * hd * heads * admitted_scores(s) / s,
        # gate, up and down
        "dense_ffn": len(kinds) * 6 * d * sizes["intermediate_size"],
        "head": 2 * d * sizes["vocab_size"],  # untied
    }


def train_flops_per_token(sizes: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(sizes).values())


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


def delta_core_flops(sizes: dict, tokens: int) -> float:
    """The rules of a step's delta layers, forward and backward (3 x
    forward, no recompute)."""
    return float(3 * layers(sizes).count("linear_attention") * tokens
                 * recurrence_flops_per_token(sizes))


def delta_core_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """The least the same rules move: forward ``q``, ``k``, ``v`` (and the
    float32 decays and write strengths, one a head each) read and ``o``
    written once; backward ``q``, ``k``, ``v``, those two and ``o``'s
    cotangent read and the five gradients written once."""
    d_qk, d_v, _ = delta_widths(sizes)
    per_head = 2 * 4 * sizes["linear_num_key_heads"]  # g and beta, float32
    forward = itemsize * (d_qk + d_v + d_v) + per_head
    backward = itemsize * 2 * (d_qk + d_v) + itemsize * d_v + 2 * per_head
    return float(layers(sizes).count("linear_attention") * tokens
                 * (forward + backward))


def delta_core_least_seconds(sizes: dict, tokens: int, device_kind: str) -> float:
    """The least a step's delta rules take on ``device_kind``."""
    return max(
        delta_core_flops(sizes, tokens) / peaks.peak_bf16_flops(device_kind),
        delta_core_bytes(sizes, tokens) / peaks.PEAK_HBM_BYTES_PER_S[device_kind],
    )
