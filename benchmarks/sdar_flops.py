"""Operations of the ``sdar-30b-a3b`` configuration, from the sizes in its
file (the ``sdar_moe`` key names of ``config.json``; ``num_experts`` is the
experts HELD, ``num_experts_published`` the router's width; every layer
routes; ``seq_len`` is the DATA tokens of a row, ``S``, and the stack runs
``2 S`` positions, the noised copy then the clean copy).

Everything is **a data token** (a step's tokens are ``rows * S``: the clean
copy is context, not trained tokens), so a quantity a POSITION counts twice.
``train_flops_per_token``: what the forward and backward passes need a token
(forward operations times three); what remat recomputes is not counted.
Only **the operations the loss depends on** are counted: the loss reads the
noised half of the last layer's stream, so in the last layer the clean
half's queries, their core, their output projection and their mixture feed
nothing and are left out (its keys and values are counted: the noised
queries read them); at 8 layers that is a sixteenth of the layers' work,
where the published 48 make it a ninety-sixth.  The attention core is
credited with **the pairs the mask admits** (:func:`admitted_pairs`:
``L'^2 nb (nb + 1)`` a head over both halves, half of that where the
noised queries alone count) whatever implements the mask.  The routed
experts are credited with **the rows the step counted** on this chip
(``local_rows_over_level`` times the level share ``k * held / published``
of a position's assignments), never the buffer's size.  The head runs over
the ``S`` noised positions alone.

``grouped_matmul_flops``: ONE grouped matmul of the expert layer over a
step's counted rows (``2 S`` positions a row).  ``attention_kernel_flops``:
ONE call of the blocked attention kernel, forward (2 matmuls an admitted
pair) or the fused backward (5), at the MEAN over the layers of the pairs
the loss depends on (a layer's call is one call: the last layer's counts
its noised queries alone).
"""


def blocks(sizes: dict) -> int:
    return sizes["seq_len"] // sizes["block_length"]


def admitted_pairs(sizes: dict) -> int:
    """(query, key) pairs the block-diffusion mask admits over the doubled
    row, a head: a query of block ``b``, noised or clean, sees ``L' (b +
    1)`` keys."""
    n, length = blocks(sizes), sizes["block_length"]
    return length * length * n * (n + 1)


def loss_pairs_per_layer(sizes: dict) -> float:
    """Mean over the layers run of the admitted pairs the loss depends on,
    a head: all of them but in the last layer, whose clean queries (half
    the pairs) feed nothing."""
    layers = sizes["n_layers"]
    return admitted_pairs(sizes) * (layers - 0.5) / layers


def level_rows_per_position(sizes: dict) -> float:
    """A position's assignments that fall on this chip's experts when loads
    are level: ``k * held / published``."""
    return (sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["num_experts_published"])


def forward_flops_per_token(sizes: dict, rows_over_level: float = 1.0) -> dict:
    """Forward operations a DATA token that the loss depends on, by part."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f = sizes["moe_intermediate_size"]
    layers = sizes["n_layers"]
    # positions a data token whose stream feeds the loss: two a layer, one
    # in the last (its clean half's keys and values apart)
    fed = 2 * layers - 1
    return {
        # wq and wo over the positions that feed the loss; wk and wv over all
        "projections": fed * 4 * d * hd * heads + 2 * layers * 4 * d * hd * kv,
        "attention_core": layers * 4 * hd * heads * loss_pairs_per_layer(sizes) / s,
        "router": fed * 2 * d * sizes["num_experts_published"],
        "routed_experts": (
            fed * rows_over_level * level_rows_per_position(sizes) * 6 * d * f),
        "head": 2 * d * sizes["vocab_size"],  # untied, the noised half alone
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
    """Rows of a layer's sorted buffer that hold an assignment, a step of
    ``tokens`` data tokens (twice as many positions)."""
    return 2 * tokens * level_rows_per_position(sizes) * rows_over_level


def grouped_matmul_flops(sizes: dict, tokens: int,
                         rows_over_level: float = 1.0) -> float:
    return (2.0 * counted_rows(sizes, tokens, rows_over_level)
            * sizes["hidden_size"] * sizes["moe_intermediate_size"])


ATTENTION_KERNEL_MATMULS = {"forward": 2, "backward": 5}


def attention_kernel_flops(sizes: dict, tokens: int, kind: str,
                           way: str) -> float:
    """One call over a step's ``tokens`` data tokens (rows of ``seq_len``):
    ``kind`` is ``global`` (this model has no other), ``way`` ``forward``
    or ``backward``."""
    return float(
        (tokens // sizes["seq_len"]) * sizes["num_attention_heads"]
        * loss_pairs_per_layer(sizes) * 2 * sizes["head_dim"]
        * ATTENTION_KERNEL_MATMULS[way]
    )
