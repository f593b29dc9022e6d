"""Operations and bytes of the ``ling-3.0-flash-vl`` configuration, from the
sizes in its file (the catalog's key names of Ling-3.0-flash-VL's
``config.json``; ``num_experts`` is the experts HELD,
``num_experts_published`` the router's width; the ``n_layers`` run are
published layers ``first_layer ..``: layer ``i`` latent attention where ``(i
+ 1) % layer_group_size == 0`` and Kimi Delta Attention elsewhere, its
feed-forward part dense where ``i < first_k_dense_replace``).

``train_flops_per_token``: what the forward and backward passes need a
token (forward operations times three); what remat recomputes is not
counted.  The rule is credited with ``6 H dk dv`` a token a layer (what the
state answers for the key, the state's update, its read-out: two
operations an element of the ``H x dk x dv`` state each) **whatever chunk,
kernel or reference scaling implements it**: the channel decays' row
scalings are no matrix work.  The attention core is credited with **the
elements the causal mask admits** at ``qk_nope_head_dim + qk_rope_head_dim``
(192) for the scores and ``v_head_dim`` (128) for the weighted values.  The
routed experts are credited with **the rows the step counted** on this chip
(``local_rows_over_level`` times the level share ``k * held / published``),
never the buffer's size.

``kda_core_flops`` / ``kda_core_bytes`` / ``kda_core_least_seconds``: the
rule (scope ``delta/core``) of the step's KDA layers at ``[1, H, S, dk |
dv]`` with a decay a key channel, forward and backward: the operations above
times three, and the least it moves: ``q``, ``k``, ``v``, the float32
log-decays ``[S, H, dk]`` and write strengths read and ``o`` written once
forward; those, ``o``'s cotangent read and the five gradients written once
backward.  The least time is the larger of the operations at the bf16 peak
and the bytes at the HBM peak (``peaks.py``).
"""

import peaks


def admitted_scores(seq_len: int) -> int:
    """(query, key) pairs a causal mask admits, a head."""
    return seq_len * (seq_len + 1) // 2


def layers(sizes: dict) -> list:
    """``(mixer, feed-forward part)`` of each layer run."""
    first = sizes["first_layer"]
    return [("latent" if (i + 1) % sizes["layer_group_size"] == 0 else "kda",
             "dense" if i < sizes["first_k_dense_replace"] else "moe")
            for i in range(first, first + sizes["n_layers"])]


def _count(sizes: dict, what: str) -> int:
    return sum(what in pair for pair in layers(sizes))


def recurrence_flops_per_token(sizes: dict) -> int:
    """``6 H dk dv``: one layer's rule, forward (keys and values of
    ``head_dim``)."""
    return 6 * sizes["num_attention_heads"] * sizes["head_dim"] ** 2


def level_rows_per_token(sizes: dict) -> float:
    """A token's assignments that fall on this chip's experts when loads
    are level: ``k * held / published``."""
    return (sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["num_experts_published"])


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward operations a token, by part of the model."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    hd_v, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    f = sizes["moe_intermediate_size"]
    n_kda, n_latent = _count(sizes, "kda"), _count(sizes, "latent")
    n_moe = _count(sizes, "moe")
    return {
        # q, k, v, the decays' projection and the output's: five of [d, H hd];
        # the write strengths' and the gate's: two of [d, H]
        "kda_projections": n_kda * 2 * d * (5 * heads * hd + 2 * heads),
        "kda_recurrence": n_kda * recurrence_flops_per_token(sizes),
        "latent_projections": n_latent * 2 * (
            d * heads * qk + d * (rank + sizes["qk_rope_head_dim"])
            + rank * heads * (sizes["qk_nope_head_dim"] + hd_v)
            + heads * hd_v * d + d * heads),
        "attention_core": (
            n_latent * 2 * heads * (qk + hd_v) * admitted_scores(s) / s),
        "dense_ffn": _count(sizes, "dense") * 6 * d * sizes["intermediate_size"],
        "shared_expert": (
            n_moe * 6 * d * sizes["moe_shared_expert_intermediate_size"]),
        "router": n_moe * 2 * d * sizes["num_experts_published"],
        "routed_experts": (
            n_moe * rows_over_level * level_rows_per_token(sizes) * 6 * d * f),
        "head": 2 * d * sizes["vocab_size"],  # untied
    }


def train_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> float:
    return 3.0 * sum(forward_flops_per_token(sizes, rows_over_level).values())


def rows_over_level(obs: dict) -> float | None:
    """Mean over a run's steps of the rows a step COMPUTED over the level
    share: those routed here (``local_rows_over_level``) less those the
    buffer dropped (``dropped_fraction``).  None where the program reports
    no such counter."""
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


# products an admitted element, (over the key size, over the value size):
# the backward at queries and keys of 192 over values of 128 is UNFUSED
# (``trunk.flash_block_sizes``; ``xing4_flops`` has the same shape), a dK/dV
# call of (2, 2) and a dQ call of (2, 1), each credited with their mean
ATTENTION_KERNEL_PRODUCTS = {"forward": (1, 1), "backward": (2, 1.5)}


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` (rows of ``seq_len``); ``kind`` is
    ``global`` (the latent layer's mask is the causal one)."""
    if kind != "global":
        raise ValueError(f"no {kind!r} layer in this configuration")
    s = sizes["seq_len"]
    over_keys, over_values = ATTENTION_KERNEL_PRODUCTS[way]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return float(
        (tokens // s) * sizes["num_attention_heads"] * admitted_scores(s)
        * 2 * (over_keys * qk + over_values * sizes["v_head_dim"]))


def kda_core_flops(sizes: dict, tokens: int) -> float:
    """The rules of a step's KDA layers, forward and backward (3 x forward,
    no recompute)."""
    return float(3 * _count(sizes, "kda") * tokens
                 * recurrence_flops_per_token(sizes))


def kda_core_bytes(sizes: dict, tokens: int, itemsize: int = 2) -> float:
    """The least the same rules move: forward ``q``, ``k``, ``v``, the
    float32 log-decays (one a key channel) and write strengths (one a head)
    read and ``o`` written once; backward those and ``o``'s cotangent read
    and the five gradients written once."""
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    wide = heads * hd  # q, k, v, o: [H hd] each a token
    decays = 4 * wide + 4 * heads  # g and beta, float32
    forward = itemsize * 4 * wide + decays
    backward = itemsize * 7 * wide + 2 * decays
    return float(_count(sizes, "kda") * tokens * (forward + backward))


def kda_core_least_seconds(sizes: dict, tokens: int, device_kind: str) -> float:
    """The least a step's KDA rules take on ``device_kind``."""
    return max(
        kda_core_flops(sizes, tokens) / peaks.peak_bf16_flops(device_kind),
        kda_core_bytes(sizes, tokens) / peaks.PEAK_HBM_BYTES_PER_S[device_kind],
    )
