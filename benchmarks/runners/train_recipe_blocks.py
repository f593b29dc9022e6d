"""Runner ``train_recipe_blocks``: ``train_recipe``'s run for a recipe whose
logits do not fit the chip whole and whose layers differ.

It IS ``train_recipe``'s run: that module is loaded through ``harness``
and its ``run`` is called as it is, so the window, the warm-up, the
checks (finite losses, the first pool batch's loss falls, nothing
compiled in the window, ``dropped_fraction`` 0), the Zipf generator and
the printed lines (``REFERENCE``, ``INTERVALS``, ``COUNTERS``, ``SETUP``,
``SCOPES``) are that file's own code, not a copy.  Four of the names its
``run`` looks up in its module are replaced, in this process's private
copy of it, with what this file defines:

- ``_check_sizes``: the configuration file restates the sizes under
  SmallThinker's key names, and its two layouts are compared with the
  program's ``layer_pattern`` entry by entry.
- ``compare_with_reference`` / ``TOLERANCES``: the reference is given each
  layer's index and the stream the program's layer was given, and neither
  side's logits are ever whole: they go through their heads a block of
  ``LOGIT_BLOCK`` positions at a time, and each block adds to the sums
  the readings are made from.
- ``scope_times``: instructions are joined with their ``op_name`` across
  the newline that the attention kernel writes into its call's
  attributes, so the kernel's calls are found (by instruction name
  ``splash_mha*``), fall under their layer's ``attention`` scope, and are
  also reported alone, by kind of layer and by direction.

Reference comparison (published widths, one seeded row of ``seq_len``
Zipf ids, the weights as the window left them), A LAYER AT A TIME ON THE
PROGRAM'S OWN STREAM: the program's embedding, then each ``_layer`` (what
``_hidden`` composes) on the stream the layer before it left, against
``configs/<reference>``'s layer on the float32 cast of the same weights
AND THE SAME INPUT; then the program's final norm and head against the
reference's on the program's final stream.

- ``layers_rms``: the largest, over the embedding and the layers, of the
  root of the summed squares of the differences over that of the
  reference's output, over the positions whose routing is decided (the
  reference's k-th and (k+1)-th router logits ``MARGIN`` apart or more).
- ``logits_rms``: exact, over all ``seq_len x vocab`` differences.
  ``logits_p999``: the 99.9th percentile of the absolute difference over
  the reference's rms, FROM A HISTOGRAM: counts of elements above each of
  ``EDGES`` (8 edges an octave, so a bin is 9 % wide), interpolated in the
  logarithm inside the bin that holds the quantile.
  ``logits_token_median``: exact, the median over the positions of a
  position's rms difference over its own reference logits' rms.
- ``loss``: relative; the program's ``loss_fn`` WHOLE against the
  reference's cross-entropy, load-balance and z losses along the stream
  above.  ``hidden_token_median``: the program's ``_hidden`` whole (what
  ``apply`` and ``loss_fn`` run) against the final norm of the stream
  above, the median position's relative difference: the whole composes
  the layers that were compared.
- ``near_tie_share``: the largest share of positions a layer left out.

Why the reference is given the program's stream, and not its own from
the embedding on (PERF.md section 6, PR 31): this block's six chosen gates
are renormalised to sum to 1, about a sixth each, so a token whose 6th
and 7th largest router logits swap between bf16 and float32 moves by a
quarter of its stream.  Down two whole stacks the swaps compound (one
token in eight by the fourth layer), every occurrence of a token id
swaps together in the first layer (one id is 8 % of a Zipf row), and
attention spreads the swapped tokens' error over every position: rms,
99.9th percentile and even the median position then read which tokens
happened to swap, 0.5 % in most runs and three times that in one of
six, with no bound that tells a wrong block from an unlucky seed.  Given
the same input, the two sides differ by the layer's arithmetic alone,
and the positions whose choice of experts hangs on the rounding of the
router's input are known from the reference's own logits.
"""

from __future__ import annotations

import math
import re

import harness
from harness import BenchError

# the file's key (SmallThinker's config.json, then this repo's) -> the
# program's config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "seq_len": "seq_len", "moe_num_primary_experts": "num_experts",
    "moe_num_active_primary_experts": "k",
    "moe_ffn_hidden_size": "expert_ffn_dim", "norm_topk_prob": "renormalize",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "norm": "norm", "positions": "positions", "expert_kind": "expert_kind",
    "routing": "routing", "router_input": "router_input",
    "aux_loss_weight": "aux_loss_weight", "router_z_weight": "router_z_weight",
    "remat": "remat", "scan_layers": "scan_layers",
    "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 31): the largest the program gave over its seeds,
# and the reference itself with every matmul operand rounded to
# float8_e4m3 (the nearest precision below the configuration's bf16), run
# through this same comparison in the program's place: it is outside the
# first four.  ``loss`` hardly moves with the precision (float8 reads
# 0.8e-4 to 1.5e-4): its limit is 3.5 times the program's largest reading
# alone.  ``hidden_token_median`` has no
# second precision (both sides are the program): a ``_hidden`` that
# composes another pattern than the layers run reads 20 % and more.
# ``near_tie_share`` guards the comparison itself: at least a quarter of
# the positions are compared in every layer.
TOLERANCES = {"layers_rms": 3e-2, "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2, "loss": 2e-4,
              "hidden_token_median": 2e-2, "near_tie_share": 0.75}
# A token whose k-th and (k+1)-th largest router logits lie closer than
# this in the reference is not compared in that layer: the program's
# router reads the bf16 rounding of the same input (its logits differ by
# 8.4e-4 rms on the chip, so this is nine of those), and which of the two
# experts it takes there is no error of either side.
MARGIN = 2.0 ** -7


def over_tolerance(read: dict) -> list:
    return [f"{k} {read[k]:.3e} > {limit:g}"
            for k, limit in TOLERANCES.items() if not read[k] <= limit]


LOGIT_BLOCK = 1024  # positions a block: [1024, 151936] float32 is 622 MB
# edges of the histogram of |difference|: 2**-24 .. 2**8, 8 an octave
EDGES = [2.0 ** (i / 8) for i in range(-24 * 8, 8 * 8 + 1)]


def _check_sizes(config: dict, cfg) -> None:
    import jax.numpy as jnp

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    layers = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    got["sliding_window_layout"] = [int(a.window is not None) for a in layers]
    got["rope_layout"] = [int(a.rotary) for a in layers]
    windows = {a.window for a in layers if a.window is not None}
    got["sliding_window_size"] = windows.pop() if len(windows) == 1 else windows
    want = dict(config)
    for key in ("sliding_window_layout", "rope_layout"):  # the layers run
        want[key] = config[key][: cfg.n_layers]
    wrong = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def reference_sizes(config: dict) -> dict:
    """What the reference is given: the FILE's sizes, not the program's."""
    return dict(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        experts_per_token=config["moe_num_active_primary_experts"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        sliding_window_size=config["sliding_window_size"],
        sliding_window_layout=config["sliding_window_layout"],
        rope_layout=config["rope_layout"],
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
    )


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None) -> dict:
    """The program against the reference on ``ids`` [1, S], a layer at a
    time ON THE PROGRAM'S OWN STREAM and the logits a block of positions
    at a time.  With ``operand_dtype`` the REFERENCE at that precision
    takes the program's place (what a too-low precision would read)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = reference_sizes(config)
    n_layers = len(params["layers"])
    head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    edges = jnp.asarray(EDGES, jnp.float32)

    def f32(a):
        return a.astype(jnp.float32)

    if operand_dtype is None:
        cfg = model.cfg
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def program_kind(index):
            return cfg.attention_layer(index)

        def got_layer(lp, x, index):
            y, aux = model._layer(lp, x, index, None, program_kind(index))
            return y, aux["aux_loss"], aux["router_z_loss"]

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        x = reference.embed(params, ids)

        def program_kind(index):
            return None

        def got_layer(lp, x, index):
            return reference.layer(lp, x, sizes, index, operand_dtype)

        def got_logits(head_params, x):
            return reference.head(head_params, x, sizes, operand_dtype)

    def position_sums(got, want):
        """Sums of squares a position: of the difference, of the reference."""
        diff = f32(got) - want
        return (jnp.sum(diff * diff, axis=-1).ravel(),
                jnp.sum(want * want, axis=-1).ravel())

    def one_layer(lp, x, index):
        got, got_aux, got_z = got_layer(lp, x, index)
        want, aux, z = reference.layer(lp, f32(x), sizes, index)
        return (got, position_sums(got, want),
                reference.router_margin(lp, f32(x), sizes),
                (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, then the layers: one compiled pair a KIND of layer
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, ids)), slice(None))]
    near_tie = []
    compiled = {}
    got_aux = got_z = aux = z = 0.0
    for index, lp in enumerate(params["layers"]):
        kind = (sizes["sliding_window_layout"][index],
                sizes["rope_layout"][index], program_kind(index))
        if kind not in compiled:
            compiled[kind] = jax.jit(
                lambda lp, x, index=index: one_layer(lp, x, index))
        x, sums, margin, got_side, want_side = compiled[kind](lp, x)
        decided = np.asarray(margin) >= MARGIN
        near_tie.append(1.0 - float(decided.mean()))
        layers_rms.append(decided_rms(sums, decided))
        got_aux, got_z = got_aux + float(got_side[0]), got_z + float(got_side[1])
        aux, z = aux + float(want_side[0]), z + float(want_side[1])

    @jax.jit
    def block_sums(head_params, x, tgt):
        want = reference.head(head_params, f32(x), sizes)
        got = f32(got_logits(head_params, x))
        diff = jnp.abs(got - want)
        above = jax.lax.map(lambda edge: jnp.sum(diff > edge), edges)
        return (position_sums(got, want), above,
                reference.ce_sum_of_logits(want, tgt),
                reference.ce_sum_of_logits(got, tgt))

    s = ids.shape[1]
    block = min(LOGIT_BLOCK, s)
    if s % block:
        raise BenchError(f"seq_len {s} is no multiple of {block}")
    want_ce = got_ce = 0.0
    diff_sq, want_sq = [], []  # a position, float64
    above = [0] * len(EDGES)
    for start in range(0, s, block):
        part = slice(start, start + block)
        (d2, w2), counts, wce, gce = block_sums(
            head_params, x[:, part], targets[:, part])
        diff_sq.append(np.asarray(d2, np.float64))
        want_sq.append(np.asarray(w2, np.float64))
        want_ce, got_ce = want_ce + float(wce), got_ce + float(gce)
        above = [a + int(c) for a, c in zip(above, counts)]
    diff_sq, want_sq = np.concatenate(diff_sq), np.concatenate(want_sq)
    elements = s * config["vocab_size"]
    want_loss = reference.total_loss(want_ce / s, aux, z, n_layers, sizes)
    if operand_dtype is None:
        # the program WHOLE, as apply and loss_fn compose it
        got_loss, whole = jax.jit(lambda p, i, t: (
            model.loss_fn(p, i, t)[0], model._hidden(p, i)[0]))(
                params, ids, targets)
        got_loss = float(got_loss)
        layered = jax.jit(lambda p, x: f32(model._norm(p, x)))(
            params["ln_f"], x)
        h2, l2 = jax.jit(position_sums)(whole, layered)
        hidden_median = float(np.median(np.sqrt(
            np.asarray(h2, np.float64) / np.asarray(l2, np.float64))))
    else:
        got_loss = reference.total_loss(got_ce / s, got_aux, got_z, n_layers,
                                        sizes)
        hidden_median = 0.0
    scale = math.sqrt(want_sq.sum() / elements)
    return {
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        "logits_rms": math.sqrt(diff_sq.sum() / elements) / scale,
        "logits_p999": quantile_from_counts(above, elements, 0.999) / scale,
        "logits_token_median": float(np.median(np.sqrt(diff_sq / want_sq))),
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "hidden_token_median": hidden_median,
        "near_tie_share": max(near_tie),
        "reference_loss": want_loss,
        "reference_logits_rms": scale,
        "embed_and_layers_rms": layers_rms,
    }


def quantile_from_counts(above: list, total: int, q: float) -> float:
    """The ``q`` quantile of ``total`` values of which ``above[i]`` exceed
    ``EDGES[i]``: the edge pair that brackets ``(1 - q) * total`` values
    above, interpolated in the logarithm of the value."""
    target = (1.0 - q) * total
    if above[0] <= target:  # under the first edge (2**-24): as good as 0
        return EDGES[0]
    for i in range(1, len(EDGES)):
        if above[i] <= target:
            lo, hi = above[i - 1], above[i]
            share = (lo - target) / (lo - hi) if lo > hi else 0.0
            return EDGES[i - 1] * (EDGES[i] / EDGES[i - 1]) ** share
    raise BenchError(f"more than {1 - q:g} of the differences exceed {EDGES[-1]}")


INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
OP_NAME_ANYWHERE = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
ATTENTION_KERNEL = "splash_mha"  # the blocked kernel's instructions


def op_names(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name``, each instruction taken with
    the lines that follow it up to the next one: the attention kernel
    writes a newline into its call's attributes, so its ``metadata`` is
    on a later line than its name."""
    names: dict = {}
    current = None
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            current = m.group(1)
        if current is not None and current not in names:
            found = OP_NAME_ANYWHERE.search(line)
            if found:
                names[current] = found.group(1)
    return names


def make_scope_times(base):
    """``scope_times(ops, hlo_text)`` over ``base`` (the loaded
    ``train_recipe``): its scopes, its grouped-matmul rule, and the
    attention kernel's calls besides."""
    import trace_reduce

    def scope_times(ops: list, hlo_text: str) -> dict:
        op_name = op_names(hlo_text)

        def is_matmul(name: str) -> bool:
            return name.startswith(base.GROUPED_MATMUL) and not name.startswith(
                base.GROUPED_MATMUL_LAYOUT)

        def kernel(name: str) -> str | None:
            """``<global|window>.<forward|backward>`` of an attention
            kernel's instruction, None for any other."""
            if not name.startswith(ATTENTION_KERNEL):
                return None
            kind = "window" if "attention/window" in op_name.get(name, "") else "global"
            return f"{kind}.{'forward' if '_fwd' in name else 'backward'}"

        self_ns = trace_reduce.self_times(ops)
        by_scope: dict = {}
        kernels: dict = {}
        for name, ns in self_ns.items():
            scope = "other"
            if name.startswith(base.GROUPED_MATMUL):
                scope = "experts"
            else:
                path = "/" + op_name.get(name, "") + "/"
                for candidate, pattern in base.SCOPES:
                    if pattern.search(path):
                        scope = candidate
                        break
            by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
            if kernel(name):
                kernels.setdefault(kernel(name), {"s": 0.0, "calls": 0})["s"] += ns / 1e9
        for name, _, _ in ops:
            if kernel(name):
                kernels[kernel(name)]["calls"] += 1
        return {
            "by_scope": by_scope,
            "total_s": sum(by_scope.values()),
            "grouped_matmul_s": sum(
                ns for name, ns in self_ns.items() if is_matmul(name)) / 1e9,
            "grouped_matmul_calls": sum(
                1 for name, _, _ in ops if is_matmul(name)),
            "attention_kernel_s": sum(k["s"] for k in kernels.values()),
            "attention_kernels": dict(sorted(kernels.items())),
        }

    return scope_times


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    manifest = harness.load_manifest(args.manifest)
    base = harness.load_module(manifest, "runners", "train_recipe")
    # this process's own copy of the module: its run() looks these up
    base._check_sizes = _check_sizes
    base.compare_with_reference = compare_with_reference
    base.TOLERANCES = TOLERANCES  # its over_tolerance and REFERENCE line read it
    base.scope_times = make_scope_times(base)
    return base.run(cell, config, traffic, args, clock)
