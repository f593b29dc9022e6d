"""Operations and bytes of the ``lfm2-8b-a1b`` configuration, from the
sizes in its file (the ``lfm2_moe`` key names of ``config.json``; the layers
run are entries ``first_layer ..`` of ``layer_types``, ``conv`` or
``full_attention``, dense where their index in the model is under
``num_dense_layers``; every expert is held).

``train_flops_per_token``: what the forward and backward passes need a
token (forward operations times three); what remat recomputes is not
counted.  The attention core is credited with **the elements the mask
admits** (:func:`admitted_scores`), the conv mixer's core with ``2 K``
operations a channel a position (K multiply-adds; the two gates' products
are not counted), the head with its product though the table is tied.
Routing is dropless and every expert is here, so every one of a token's 4
expert blocks is computed and credited.

``grouped_matmul_flops``: ONE grouped matmul of the expert layer over a
step's sorted rows.  ``attention_kernel_flops``: ONE call of the blocked
attention kernel, forward (2 matmuls an admitted element) or the fused
backward (5).  ``shortconv_core_flops`` / ``shortconv_core_bytes`` /
``shortconv_core_least_seconds``: the gated short convolution (scope
``shortconv/core``) of the step's conv layers, forward and backward: the
operations above times three, and the least it moves: ``[B | C | u]`` read
and the result written once forward; those three, the cotangent read and
``d[B | C | u]`` written once backward.  The least time is the larger of
the operations at the bf16 peak and the bytes at the HBM peak
(``peaks.py``): the bytes, by three orders of magnitude.
"""

import peaks


def admitted_scores(seq_len: int) -> int:
    """(query, key) pairs a causal mask admits, a head."""
    return seq_len * (seq_len + 1) // 2


def layers(sizes: dict) -> list:
    """``(mixer, feed-forward)`` of each layer run."""
    first = sizes["first_layer"]
    return [(sizes["layer_types"][i],
             "dense" if i < sizes["num_dense_layers"] else "sparse")
            for i in range(first, first + sizes["n_layers"])]


def conv_layers(sizes: dict) -> int:
    return [m for m, _ in layers(sizes)].count("conv")


def forward_flops_per_token(sizes: dict) -> dict:
    """Forward operations a token, by part of the model."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    run = layers(sizes)
    n_conv = conv_layers(sizes)
    n_full = len(run) - n_conv
    sparse = [f for _, f in run].count("sparse")
    return {
        # [d, 3 d] in and [d, d] out
        "shortconv_projections": n_conv * 2 * d * 4 * d,
        "shortconv_core": n_conv * 2 * sizes["conv_L_cache"] * d,
        # q, o and k, v projections
        "projections": n_full * 2 * d * hd * (2 * heads + 2 * kv),
        "attention_core": n_full * 4 * hd * heads * admitted_scores(s) / s,
        "dense_ffn": (len(run) - sparse) * 6 * d * sizes["intermediate_size"],
        "router": sparse * 2 * d * sizes["num_experts"],
        "routed_experts": (
            sparse * sizes["num_experts_per_tok"] * 6 * d
            * sizes["moe_intermediate_size"]),
        "head": 2 * d * sizes["vocab_size"],  # tied: the product all the same
    }


def train_flops_per_token(sizes: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(sizes).values())


def grouped_matmul_flops(sizes: dict, tokens: int) -> float:
    rows = tokens * sizes["num_experts_per_tok"]
    return 2.0 * rows * sizes["hidden_size"] * sizes["moe_intermediate_size"]


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


def shortconv_core_flops(sizes: dict, tokens: int) -> float:
    """The gated convolutions of a step's conv layers, forward and backward
    (3 x forward, no recompute)."""
    return float(3 * conv_layers(sizes) * tokens * 2 * sizes["conv_L_cache"]
                 * sizes["hidden_size"])


def shortconv_core_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """The least the same convolutions move: forward ``[B | C | u]`` read
    and the result written once (4 channel-widths a position); backward
    those three and the cotangent read and ``d[B | C | u]`` written once
    (7)."""
    return float(conv_layers(sizes) * tokens * 11 * sizes["hidden_size"]
                 * itemsize)


def shortconv_core_least_seconds(sizes: dict, tokens: int,
                                 device_kind: str) -> float:
    """The least a step's gated convolutions take on ``device_kind``."""
    return max(
        shortconv_core_flops(sizes, tokens) / peaks.peak_bf16_flops(device_kind),
        shortconv_core_bytes(sizes, tokens)
        / peaks.PEAK_HBM_BYTES_PER_S[device_kind],
    )
