"""Operations and bytes of the ``qwen3-next-80b-a3b`` configuration, from the
sizes in its file (the ``qwen3_next`` key names of ``config.json``;
``num_experts`` is the experts HELD, ``num_experts_published`` the router's
width; layer ``i`` of the ``n_layers`` run is full attention where ``(i + 1)
% full_attention_interval == 0`` and the gated delta rule elsewhere; every
layer routes).

``train_flops_per_token``: what the forward and backward passes need a
token (forward operations times three); what remat recomputes is not
counted.  The attention core is credited with **the elements the mask
admits** (:func:`admitted_scores`).  The delta rule is credited with ``6 Hv
dk dv`` a token a layer (what the state answers for the key, the state's
update, its read-out: two operations an element of the ``Hv x dk x dv``
state each) **whatever chunk, kernel or head-sharing the program takes**.
The routed experts are credited with **the rows the step counted** on this
chip (``local_rows_over_level`` times the level share ``k * held /
published`` of a token's assignments), never the buffer's size.

``grouped_matmul_flops``: ONE grouped matmul of the expert layer over a
step's counted rows.  ``attention_kernel_flops``: ONE call of the blocked
attention kernel, forward (2 matmuls an admitted element) or the fused
backward (5).  ``delta_core_flops`` / ``delta_core_bytes`` /
``delta_core_least_seconds``: the rule (scope ``delta/core``) of the
step's delta layers, forward and backward: the operations above times
three, and the least it moves, ``q`` and ``k`` counted ONCE A KEY HEAD
(the value heads that share a key head read the same rows): ``q``, ``k``,
``v``, the decays and write strengths read and ``o`` written once forward;
those, ``o``'s cotangent read and the five gradients written once
backward.  The least time is the larger of the operations at the bf16
peak and the bytes at the HBM peak (``peaks.py``).
"""

import peaks


def admitted_scores(seq_len: int) -> int:
    """(query, key) pairs a causal mask admits, a head."""
    return seq_len * (seq_len + 1) // 2


def layers(sizes: dict) -> list:
    """The layers run: ``linear_attention`` or ``full_attention`` each."""
    interval = sizes["full_attention_interval"]
    return ["full_attention" if (i + 1) % interval == 0 else "linear_attention"
            for i in range(sizes["n_layers"])]


def delta_widths(sizes: dict) -> tuple:
    """``(q's and k's channels together, v's channels, the in-projection's
    width [q | k | v | z | b | a])``."""
    d_qk = 2 * sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    h_v = sizes["linear_num_value_heads"]
    d_v = h_v * sizes["linear_value_head_dim"]
    return d_qk, d_v, d_qk + 2 * d_v + 2 * h_v


def recurrence_flops_per_token(sizes: dict) -> int:
    """``6 Hv dk dv``: one layer's rule, forward."""
    return (6 * sizes["linear_num_value_heads"] * sizes["linear_key_head_dim"]
            * sizes["linear_value_head_dim"])


def level_rows_per_token(sizes: dict) -> float:
    """A token's assignments that fall on this chip's experts when loads
    are level: ``k * held / published``."""
    return (sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["num_experts_published"])


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward operations a token, by part of the model."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f = sizes["moe_intermediate_size"]
    kinds = layers(sizes)
    n_delta = kinds.count("linear_attention")
    n_full = kinds.count("full_attention")
    _, d_v, in_width = delta_widths(sizes)
    return {
        "delta_projections": n_delta * 2 * d * (in_width + d_v),
        "delta_recurrence": n_delta * recurrence_flops_per_token(sizes),
        # wq with its gate's half, wo, and wk, wv
        "projections": n_full * 2 * d * hd * (3 * heads + 2 * kv),
        "attention_core": n_full * 4 * hd * heads * admitted_scores(s) / s,
        # gate, up and down, and the gate's one number a token
        "shared_expert": len(kinds) * (
            6 * d * sizes["shared_expert_intermediate_size"] + 2 * d),
        "router": len(kinds) * 2 * d * sizes["num_experts_published"],
        "routed_experts": (
            len(kinds) * rows_over_level * level_rows_per_token(sizes)
            * 6 * d * f),
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
    """The least the same rules move, ``q`` and ``k`` once a KEY head:
    forward ``q``, ``k``, ``v`` (and the float32 decays and write
    strengths, one a value head each) read and ``o`` written once;
    backward ``q``, ``k``, ``v``, those two and ``o``'s cotangent read and
    the five gradients written once."""
    d_qk, d_v, _ = delta_widths(sizes)
    per_head = 2 * 4 * sizes["linear_num_value_heads"]  # g and beta, float32
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
