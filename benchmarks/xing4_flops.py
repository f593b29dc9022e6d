"""Operations and bytes of the ``xing4.0-29b-a4b`` configuration, from the
sizes in its file (Xing4.0-29B-A4B's ``config.json`` key names, which are
GLM-4.7-Flash's; ``n_routed_experts`` is the experts HELD,
``n_routed_experts_published`` the router's width; ``dense_layers_run`` of
the ``n_layers`` run are dense).

``train_flops_per_token``: what the forward and backward passes need a
token (forward matmuls times three); what remat recomputes is not
counted.  The stack's ``n_layers`` layers AND the next-but-one-token block
are counted, the head twice, and the hyper-connections' ``u phi`` products
(``[n C] x [n C, 2 n + n^2]`` a part, two parts a layer).  The attention
core is credited with **the elements the causal mask admits** at
``qk_nope_head_dim + qk_rope_head_dim`` (192) for the scores and
``v_head_dim`` (128) for the weighted values, never the blocks the kernel
visits nor the columns a padded head would carry.  The routed experts are
credited with **the rows the step counted** (``glm47_flops``'s rule).  The
mixing itself (the read, the write) is no matrix work and is not in this
count: :func:`hc_mix_least_seconds` holds it to its bytes.

``attention_kernel_flops``: ONE call of the blocked attention kernel,
forward (scores and weighted values) or backward.  The backward this
configuration's tiles choose is UNFUSED (``trunk.flash_block_sizes`` for
queries and keys of 192 over values of 128: the fused kernel's partials of
the queries' gradient do not fit the step, PERF.md section 6, PR 64): two
calls a layer, the keys' and values' (scores again, the gradients of the
probabilities, values and keys: two products over the key size, two over
the value size) and the queries' (scores, the probabilities' gradient, the
queries': two over the key size, one over the value size); the reducer
multiplies every backward call by one number, so a call is credited with
the pair's mean.

``hc_mix_least_seconds``: the least a step's stream mixing takes (scopes
``hc/pre`` and ``hc/post`` of every part): the larger of its operations at
the bf16 peak and of its least bytes at the HBM peak.  A part forward
reads the streams twice (the read; the write) and writes them once, writes
``h`` and reads ``y``: ``(3 n + 2) C``; remat's second forward the same; the
backward reads the streams, the written streams' cotangent, ``y`` and
``h``'s cotangent and writes the streams' and ``y``'s: ``(3 n + 3) C``; two
bytes a number.
"""

import peaks
from glm47_flops import (  # noqa: F401  (the harness reads them off this module)
    _qk_dim,
    attention_forward_flops_per_token,
    counted_rows,
    grouped_matmul_bytes,
    grouped_matmul_flops,
    level_rows_per_token,
)
from kexaone_flops import admitted_scores, rows_over_level  # noqa: F401


def parts(sizes: dict) -> int:
    """Parts that read and write the streams a step: two a layer run, the
    prediction block's layer among them."""
    return 2 * (sizes["n_layers"] + sizes["num_nextn_predict_layers"])


def hc_coefficients(sizes: dict) -> int:
    """``2 n + n^2``: a part's read, write and mixing coefficients."""
    n = sizes["hc_mult"]
    return 2 * n + n * n


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward matmul operations a token, by part of the model."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    blocks = sizes["num_nextn_predict_layers"]
    layers = sizes["n_layers"] + blocks
    dense = sizes["dense_layers_run"]
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
        "hc_coefficients": (
            parts(sizes) * 2 * sizes["hc_mult"] * d * hc_coefficients(sizes)),
    }


def train_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> float:
    return 3.0 * sum(forward_flops_per_token(sizes, rows_over_level).values())


# products an admitted element, (over the key size, over the value size)
ATTENTION_KERNEL_PRODUCTS = {
    "forward": (1, 1),
    # the unfused pair's mean: dK/dV (2, 2) and dQ (2, 1)
    "backward": (2, 1.5),
}


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` (rows of ``seq_len``); ``kind`` is
    ``global`` (every layer's mask is the causal one), ``way`` ``forward``
    or ``backward`` (one of the unfused pair: their mean)."""
    if kind != "global":
        raise ValueError(f"no {kind!r} layer in this configuration")
    s = sizes["seq_len"]
    over_keys, over_values = ATTENTION_KERNEL_PRODUCTS[way]
    return float(
        (tokens // s) * sizes["num_attention_heads"] * admitted_scores(s)
        * 2 * (over_keys * _qk_dim(sizes) + over_values * sizes["v_head_dim"]))


def hc_mix_flops(sizes: dict, tokens: int) -> float:
    """Multiplies and adds of the read and the write of every part:
    forward ``(2 n^2 + 4 n) C`` a token, again under remat, and the
    backward's ``(4 n^2 + 8 n) C`` (the streams', ``y``'s and the
    coefficients' gradients)."""
    n = sizes["hc_mult"]
    return float(parts(sizes) * tokens * (8 * n * n + 16 * n)
                 * sizes["hidden_size"])


def hc_mix_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """The least the same passes move (the module's docstring)."""
    n = sizes["hc_mult"]
    return float(parts(sizes) * tokens * (2 * (3 * n + 2) + (3 * n + 3))
                 * sizes["hidden_size"] * itemsize)


def hc_mix_least_seconds(sizes: dict, tokens: int, device_kind: str) -> float:
    """The least a step's stream mixing takes on ``device_kind``."""
    return max(
        hc_mix_flops(sizes, tokens) / peaks.peak_bf16_flops(device_kind),
        hc_mix_bytes(sizes, tokens) / peaks.PEAK_HBM_BYTES_PER_S[device_kind],
    )
