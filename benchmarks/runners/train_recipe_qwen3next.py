"""Runner ``train_recipe_qwen3next``: ``train_recipe_share``'s run for a stack
whose EVERY layer is a mixer and a mixture, the mixer the gated delta rule
in three layers of four and gated softmax attention in the fourth, a share
of the experts held and NO selection bias to level (``qwen3-next-80b-a3b``).

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is, so the set-up, the warm-up,
the window, the checks (finite losses, the first pool batch's loss falls,
nothing compiled in the window), the Zipf generator and the printed lines
are that file's own code, not a copy (the levelling call of its set-up
stays: the program's ``level_router_bias`` returns a model without a bias
as it was given).  The names its ``run`` looks up in its module are
replaced, in this process's private copy of it, with what this file
defines:

- ``CFG_FIELDS`` / ``_check_sizes``: the configuration file restates the
  sizes under the ``qwen3_next`` key names; ``num_experts`` is the experts
  HELD and ``num_experts_published`` the router's width; the layers' kinds
  follow from ``full_attention_interval``, the rotated part from
  ``partial_rotary_factor``, and every layer must route.
- ``share_problems``: ``dropped_fraction`` 0 in every step (nothing
  overflowed the sorted-row buffer) and the delta rule's and the gates'
  counters in their ranges.  The loads are what the data gives (no bias, no
  levelling pass): ``local_rows_over_level`` and
  ``expert_load_max_over_mean`` are reported, and limited only by the
  buffer (twice the level share).
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``: a layer at a
  time ON THE PROGRAM'S OWN STREAM, the program's layer composed of its own
  pieces (``hidden_token_median`` holds ``_hidden`` whole to them): the
  mixer's output (a delta layer's state after the last position besides),
  the router's logits, the layer's output over the positions whose 10th
  and 11th router logits, ONE OF THE TWO A HELD EXPERT, lie ``MARGIN``
  apart or more in the reference; then the logits a block of positions at
  a time and the loss; then the BACKWARD pass and the update
  (:func:`compare_gradients`): each layer's ``jax.vjp`` against the
  reference's on the program's own stream and cotangent, leaf by leaf, and
  ONE call of the timed train step whose gradients' norms and whose change
  of every leaf are held to that chain.  ``WRONG_PROGRAMS`` names programs
  that must fall outside (``tools/smallthinker_probe.py float8`` runs them
  on the chip).
- ``STEP_COUNTERS`` / ``EXTRA_SCOPES``: the share's, the delta rule's and
  the gates'; ``_blocks``: ``train_recipe_blocks`` with a scope table that
  also says what lies under the attention gate's scope
  (``attention_gate_s``, which ``qwen3next.attention_gate_share`` reads;
  it stays inside ``attention`` in ``by_scope``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import re
import types

import harness
from harness import BenchError

# the file's key (qwen3_next's config.json, then this repo's) -> the
# program's config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "seq_len": "seq_len", "num_experts_published": "num_experts",
    "num_experts": "held_experts", "first_held_expert": "first_held_expert",
    "num_experts_per_tok": "k", "moe_intermediate_size": "expert_ffn_dim",
    "shared_expert_intermediate_size": "shared_expert_dim",
    "norm_topk_prob": "renormalize", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "linear_num_key_heads": "n_heads",
    "linear_num_value_heads": "delta_value_heads",
    "linear_key_head_dim": "delta_key_dim",
    "linear_value_head_dim": "delta_value_dim",
    "linear_conv_kernel_dim": "delta_conv_kernel", "delta_chunk": "delta_chunk",
    "delta_neg_eigval": "delta_neg_eigval", "attention_gate": "attention_gate",
    "shared_expert_gate": "shared_expert_gate", "norm": "norm",
    "positions": "positions", "qk_norm": "qk_norm",
    "expert_kind": "expert_kind", "routing": "routing",
    "router_score": "router_score", "aux_loss_weight": "aux_loss_weight",
    "router_z_weight": "router_z_weight", "remat": "remat",
    "scan_layers": "scan_layers", "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 55): the largest the program gave over its seeds,
# and the reference itself with every matmul operand rounded to
# float8_e4m3 (the nearest precision below the configuration's bf16), run
# through this same comparison in the program's place, which must fall
# outside: it is outside eight.  ``delta_rms`` is the delta layers' mixer
# output, the worst layer (0.65 % | 8.6 %), ``delta_state_rms`` their state
# after the last position, the MEDIAN layer (0.56 % | 6.5 %; one snapshot a
# layer: train_recipe_delta.py says why the median; the worst is reported
# beside it): the program's rule with its decays' sums in bf16 reads 1.72
# and 1.59 %, so these two limits are tighter than olmo-hybrid's.
# ``attention_rms`` is the gated attention's output, the worse layer
# (0.91 % | 88 %; without its gate 83 %).  ``router_logits_rms`` is the
# router's ARITHMETIC alone: its logits against the reference's product on
# the very input the program's router read (float32, the highest
# precision), over the logits' own rms, the worst layer (0 | 0): a router
# whose logits are rounded to bf16 reads 2^-9 / sqrt(3) = 1.66e-3 there and
# nothing else moves.  ``layers_rms`` is over the decided positions
# (1.08 % | 20.5 %): a program without the shared expert's gate reads 44 %.
# ``loss`` hardly moves with the precision (8.4e-5 the largest of 25 seeds |
# 1.5e-4 at float8): its second reading is a named fault, a ``_hidden`` whose
# rule writes ``2 sigmoid(b)`` (1.43e-3; a step on half the loss reads 0.5).
# ``hidden_token_median`` has no second precision either (both sides are the
# program) and reads 2.7-3.5 % here where other cells read under 1 %: this
# seeded stack amplifies a perturbation of its stream 1.2 to 2.5 times a
# layer, so last-bit differences between one program and eight grow; that
# same wrong ``_hidden`` reads 61.6 % at 16,384 on the chip.
# ``near_tie_share`` guards the comparison itself: at least three quarters
# of the positions are compared in every layer.  The backward pass and the
# update (:func:`compare_gradients`; the program over 11 seeds | the second
# reading): ``grads_rms``, the worst leaf of a layer's ``jax.vjp``, 3.9-5.8 %
# (layer 0's router every time: its gradient is the small difference of
# ten gates' pulls) | 306 % at float8; ``grad_stream_rms`` 1.00-1.07 % |
# 100 %; ``step_grad_norms`` 2.2-4.5 % | 51.1 % for a step on half the loss
# (64 % under the wrong ``_hidden``); ``update_norm`` 1.3-5.1 % | 1 for a
# leaf left as it was, which is what an unchanged state reads, with the
# more room above the first reading.
TOLERANCES = {"layers_rms": 2e-2, "delta_rms": 1.1e-2, "delta_state_rms": 1e-2,
              "attention_rms": 2e-2, "router_logits_rms": 1e-4,
              "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2, "loss": 3e-4,
              "hidden_token_median": 1e-1, "near_tie_share": 0.25,
              "grads_rms": 1.5e-1, "grad_stream_rms": 3e-2,
              "step_grad_norms": 1.5e-1, "update_norm": 2e-1}
# A token whose 10th and 11th largest router logits lie closer than this in
# the reference, ONE OF THE TWO A HELD EXPERT, is not compared in that
# layer: the program's router reads the bf16 stream its bf16 mixer left,
# so its logits differ from the reference's by 2.7e-3 rms in layer 0 and
# 1.1e-3 to 1.9e-3 in the others (``router_logits_abs_rms_on_the_
# references_stream``; this is three to seven of those), and which of the
# two experts it takes there is no error of either side.
MARGIN = 2.0 ** -7
# programs that must fall outside the limits, by name: what
# ``compare_with_reference(.., wrong=name)`` puts in the program's place
WRONG_PROGRAMS = {
    "the program, its rule's decays in bfloat16": {"wrong": "bf16_decay"},
    "the program, its router's logits in bfloat16": {"wrong": "bf16_router"},
    "the program without the attention gate": {"wrong": "no_attention_gate"},
    "the program without the shared expert's gate": {"wrong": "no_shared_gate"},
    "a _hidden whose rule writes 2 sigmoid(b)": {"wrong": "hidden_twice_beta"},
    "the step on half the loss": {"wrong": "half_loss"},
    "the step with a leaf left as it was": {"wrong": "frozen_leaf"},
}
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "local_rows_over_level", "held_experts_empty",
                 "delta_decay_min", "delta_beta_max", "attention_gate_mean",
                 "shared_gate_mean")
EXTRA_SCOPES = ("delta/in_proj", "delta/conv", "delta/core",
                "delta/gate_norm", "delta/out_proj", "delta", "shared_expert")
GATE_SCOPES = {
    "attention_gate_s": re.compile(r"[/(]attention/global/(?:proj/)?gate[/)]"),
}


def _check_sizes(config: dict, cfg) -> None:
    import jax.numpy as jnp

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    layers = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    got["layer_types"] = [a.mixer for a in layers]
    got["rotated_layers"] = [i for i, a in enumerate(layers) if a.rotary]
    got["windowed_layers"] = [
        i for i, a in enumerate(layers) if a.window is not None]
    got["mixture_layers"] = cfg.mixture_layers()
    got["rotary_dim"] = cfg.rotary_dim
    got["shared_experts"] = cfg.shared_experts
    got["router_bias"] = cfg.router_bias
    full = [i for i in range(config["n_layers"])
            if (i + 1) % config["full_attention_interval"] == 0]
    want = dict(
        config,
        layer_types=["softmax" if i in full else "delta"
                     for i in range(config["n_layers"])],
        rotated_layers=full, windowed_layers=[],
        mixture_layers=config["n_layers"],  # decoder_sparse_step 1
        rotary_dim=int(config["partial_rotary_factor"] * config["head_dim"]),
        shared_experts=1, router_bias=False,
    )
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise BenchError("the runner knows the stack whose every layer routes")
    wrong = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def share_problems(counters: dict) -> list:
    """What the share, the delta rule and the gates must read in every step
    of the window.  The loads are the data's (the model has no bias to
    level): only the buffer bounds them."""
    problems = []
    dropped = counters.get("dropped_fraction", [1.0])
    if any(x != 0.0 for x in dropped):
        problems.append(
            f"the sorted-row buffer overflowed: dropped_fraction up to "
            f"{max(dropped):.3e}")
    decay = counters.get("delta_decay_min", [math.nan])
    if not all(0.0 <= x <= 1.0 for x in decay):
        problems.append(f"delta_decay_min outside [0, 1]: {min(decay)}..{max(decay)}")
    beta = counters.get("delta_beta_max", [math.nan])
    if not all(0.0 < x <= 1.0 for x in beta):  # sigmoid(b): no factor 2
        problems.append(f"delta_beta_max outside (0, 1]: {min(beta)}..{max(beta)}")
    for name in ("attention_gate_mean", "shared_gate_mean"):
        gate = counters.get(name, [math.nan])
        if not all(0.0 < x < 1.0 for x in gate):
            problems.append(f"{name} outside (0, 1): {min(gate)}..{max(gate)}")
    return problems


def reference_sizes(config: dict) -> dict:
    """What the reference is given: the FILE's sizes, not the program's."""
    interval = config["full_attention_interval"]
    return dict(
        layer_types=("linear_attention",) * (interval - 1) + ("full_attention",),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rotary_dim=int(config["partial_rotary_factor"] * config["head_dim"]),
        rope_theta=config["rope_theta"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        norm_eps=config["rms_norm_eps"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        held=(config["first_held_expert"], config["num_experts"]),
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
    )


def _train_recipe_blocks():
    return harness.load_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "train_recipe_blocks.py"))


def _blocks():
    """``train_recipe_blocks`` as ``train_recipe_share.run`` sees it: its
    scope table also says what lies under the attention gate's scope."""
    blocks = _train_recipe_blocks()

    def make_scope_times(base):
        inner = blocks.make_scope_times(base)

        def scope_times(ops: list, hlo_text: str) -> dict:
            import trace_reduce

            table = inner(ops, hlo_text)
            names = blocks.op_names(hlo_text)
            self_ns = trace_reduce.self_times(ops)
            for key, pattern in GATE_SCOPES.items():
                table[key] = sum(
                    ns for name, ns in self_ns.items()
                    if pattern.search("/" + names.get(name, "") + "/")) / 1e9
            return table

        return scope_times

    view = types.SimpleNamespace(**vars(blocks))
    view.make_scope_times = make_scope_times
    return view


def _wrong_program(model, wrong: str | None):
    """What stands in the program's place: ``pieces`` the model whose layers
    are compared one at a time (with ``decay_dtype`` for its rule's decays
    and, where ``shared_gate`` is False, without the shared expert's gate),
    ``whole`` the model whose ``_hidden``, ``loss_fn`` and train step are
    held to those pieces (None where the pieces are the wrong ones),
    ``frozen``: whether that step's first delta out-projection is put back
    as it was, and ``gradients``: whether the backward pass is compared (a
    program whose fault a forward reading names is read forward alone)."""
    import jax.numpy as jnp

    def twin(**changes):
        return type(model)(
            dataclasses.replace(model.cfg, **changes), model.mesh)

    program = types.SimpleNamespace(
        pieces=model, decay_dtype=None, shared_gate=True, whole=model,
        frozen=False, gradients=wrong in (None, "half_loss", "frozen_leaf"))
    if wrong == "bf16_decay":
        program.decay_dtype, program.whole = jnp.bfloat16, None
    elif wrong == "no_shared_gate":
        program.shared_gate, program.whole = False, None
    elif wrong == "no_attention_gate":
        program.pieces, program.whole = twin(), None
        program.pieces._qkv = lambda *args: (*model._qkv(*args)[:3], None)
    elif wrong == "bf16_router":
        def bf16_logits(params, router_x):
            return (router_x.astype(jnp.bfloat16)
                    @ params["gate"].astype(jnp.bfloat16)).astype(jnp.float32)
        program.pieces, program.whole = twin(), None
        program.pieces.moe.router_logits = bf16_logits
    elif wrong == "hidden_twice_beta":  # 2 sigmoid(b) in _delta_block alone
        program.whole = twin(delta_neg_eigval=True)
    elif wrong == "half_loss":
        program.whole = twin()
        whole_loss = program.whole.loss_fn

        def half(params, ids, targets):
            loss, metrics = whole_loss(params, ids, targets)
            return 0.5 * loss, metrics
        program.whole.loss_fn = half
    elif wrong == "frozen_leaf":
        program.frozen = True
    elif wrong is not None:
        raise BenchError(f"no wrong program {wrong!r}")
    return program


def _first_step(p, g, learning_rate: float):
    """A leaf after Adafactor's FIRST step from an empty state, restated
    plainly (optax's rule at its defaults, which ``fused_adafactor`` keeps:
    second moments factored over the two largest axes where the smaller of
    them is 128 or more, the update clipped to a root mean square of 1 and
    scaled by the leaf's own, 1e-3 at least; float32, rounded to the leaf's
    dtype at the end)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    g, p32 = g.astype(jnp.float32), p.astype(jnp.float32)
    squares = g * g + 1e-30
    order = np.argsort(p.shape)
    if p.ndim >= 2 and p.shape[order[-2]] >= 128:
        d1, d0 = int(order[-2]), int(order[-1])
        rows = jnp.mean(squares, axis=d0, keepdims=True)
        cols = jnp.mean(squares, axis=d1, keepdims=True)
        u = g * jax.lax.rsqrt(
            rows / jnp.mean(rows, axis=d1, keepdims=True)) * jax.lax.rsqrt(cols)
    else:
        u = g * jax.lax.rsqrt(squares)
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u)))
    scale = learning_rate * jnp.maximum(jnp.sqrt(jnp.mean(p32 * p32)), 1e-3)
    # reduce_precision: the rounding is the result (a bf16 leaf moves where
    # the step exceeds half its spacing there); the TPU compiler takes a
    # round trip through astype out of a program that reads the leaf back
    # in float32 (my chip run, PR 55: all 32,768 elements "moved", not 851)
    kept = jnp.finfo(p.dtype)
    return jax.lax.reduce_precision(
        p32 - scale * u, kept.nexp, kept.nmant).astype(p.dtype)


# (model, optimizer, step) of each train step made while :func:`run` runs
_MADE_STEPS: list = []


def _timed_step(model, config: dict) -> tuple:
    """``(optimizer, train step)`` of ``model``: the very step the window
    timed where :func:`run` saw it made (no second compile: 86 s where the
    compile cache misses), else the recipe's optimizer and a step made
    here, the same program (the probe's and the tests' models, a wrong
    program's twin)."""
    for made_for, optimizer, step in _MADE_STEPS:
        if made_for is model:
            return optimizer, step
    import __graft_entry__ as entry

    optimizer = getattr(entry, config["recipe"])(
        model.mesh, tiny=bool(config.get("tiny")))[2]
    return optimizer, model.make_train_step(optimizer)


GRADIENT_READINGS = ("grads_rms", "grad_stream_rms", "step_grad_norms",
                     "update_norm")


@contextlib.contextmanager
def _kept_out_of_the_compile_cache():
    """What compiles inside is not written to the persistent cache: the
    backward comparison's programs run once a run and are large, and in a
    capped cache (192 MiB on the chip's machine) they pushed out the step
    and the initialisation's programs, so that EVERY run compiled
    everything again (``setup_s`` 160 s for 38; my chip runs, PR 55)."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        yield
    finally:
        jax.config.update(name, was)
# a leaf is held to the norm of its own change where the plain rule moves at
# least this many of its elements; the others are held together, as one
CHANGED_ELEMENTS_MIN = 256


def compare_gradients(program, model, params, reference, config, sizes, ids,
                      targets, got_layer, got_logits, streams, decided_at,
                      x_final, operand_dtype) -> dict:
    """The backward pass and the update against the reference, as the
    forward pass is compared: a layer at a time ON THE PROGRAM'S OWN STREAM
    AND ITS OWN COTANGENT, from the loss down.

    ``grads_rms``: each layer's ``jax.vjp`` of the program's pieces against
    the reference's, for the cotangent the program's chain brought there,
    zero at the positions that layer does not compare (a near tie is routed
    otherwise by either side and no cotangent reaches it); the worst LEAF of
    the tree by the difference's norm over the reference's.
    ``grad_stream_rms``: the same for what a layer hands the layer below.
    ``step_grad_norms``: ONE call of the timed train step from an empty
    optimizer state, whose second moments are then its gradients' mean
    squares: each leaf's norm against the chain's (every position), the
    worst leaf's ``|ratio - 1|``.  ``update_norm``: the norm of each leaf's
    change over that step against what the plain rule (:func:`_first_step`)
    makes of the chain's gradient, ``|ratio - 1|``: a leaf left as it was
    reads 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _train_recipe_blocks()
    n_layers, s = len(params["layers"]), ids.shape[1]
    block = min(blocks.LOGIT_BLOCK, s)
    learning_rate = float(re.fullmatch(
        r"fused_adafactor\((.+)\)", config["optimizer"]).group(1))
    side = (sizes["aux_loss_weight"] / n_layers, sizes["router_z_weight"] / n_layers)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def sq(a):
        return jnp.sum(jnp.square(a.astype(jnp.float32)))

    def names(tree, prefix):
        return [prefix + jax.tree_util.keystr(path) for path, _ in
                jax.tree_util.tree_flatten_with_path(tree)[0]]

    def against(got, want):
        """Sums of squares a leaf: of the difference, of the reference."""
        got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        return (jnp.stack([sq(f32(g) - w) for g, w in zip(got, want)]),
                jnp.stack([sq(w) for w in want]))

    def chain_stats(p_tree, g_tree):
        """A leaf: its gradient's sum of squares; what the plain rule's
        first step changes it by (sum of squares, elements moved)."""
        rows = []
        for p, g in zip(jax.tree_util.tree_leaves(p_tree),
                        jax.tree_util.tree_leaves(g_tree)):
            after = _first_step(p, g.astype(p.dtype), learning_rate)
            rows.append(jnp.stack([
                sq(g), sq(f32(after) - f32(p)),
                jnp.sum(after != p).astype(jnp.float32)]))
        return jnp.stack(rows)

    # ---- the timed step, once, from an empty optimizer state --------------
    stepped = None
    if program.whole is not None and operand_dtype is None:
        from learning_at_home_tpu.parallel.mesh import batch_sharding

        optimizer, step = _timed_step(program.whole, config)
        placed = batch_sharding(model.mesh)  # as the window's batches are
        new, opt_state, _, _ = step(
            jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))(
                params),  # the step donates
            model.init_opt_state(optimizer, params),
            jax.device_put(ids, placed), jax.device_put(targets, placed))
        if not hasattr(opt_state, "v_row"):
            raise BenchError("the step's gradients are read off Adafactor's "
                             f"second moments; the state is {type(opt_state)}")
        leaf_names = names(params, "")
        new_leaves = jax.tree_util.tree_leaves(new)
        if program.frozen:
            at = next(i for i, n in enumerate(leaf_names)
                      if n.endswith("['delta']['w_out']"))
            new_leaves[at] = jax.tree_util.tree_leaves(params)[at]

        @jax.jit
        def read_step(new_leaves, old, v_row, v):
            rows = []
            for after, p, by_row, whole in zip(
                    new_leaves, jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(v_row), jax.tree_util.tree_leaves(v)):
                moments = whole if whole.shape == p.shape else by_row
                rows.append(jnp.stack([
                    jnp.mean(f32(moments)) * p.size, sq(f32(after) - f32(p))]))
            return jnp.stack(rows)

        stepped = dict(zip(leaf_names, np.asarray(read_step(
            new_leaves, params, opt_state.v_row, opt_state.v), np.float64)))
        del new, new_leaves, opt_state

    # ---- the head: the loss's gradient on the final stream, in blocks -----
    def head_gradients(logits_fn, head_params, x):
        @jax.jit
        def one_block(head_params, xb, tb):
            return jax.grad(lambda hp, xb: reference.ce_sum_of_logits(
                logits_fn(hp, xb).astype(jnp.float32), tb) / s,
                argnums=(0, 1))(head_params, xb)

        total, cotangent = None, []
        for start in range(0, s, block):
            part = slice(start, start + block)
            g, c = one_block(head_params, x[:, part], targets[:, part])
            total = f32(g) if total is None else jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), total, g)
            cotangent.append(c)
        return total, jnp.concatenate(cotangent, axis=1)

    head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    got_head, cotangent = head_gradients(got_logits, head_params, x_final)
    want_head, want_cotangent = head_gradients(
        lambda hp, x: reference.head(hp, x, sizes), f32(head_params),
        f32(x_final))
    leaf_rms = {}  # a leaf of the tree: the difference over the reference

    def record(leaf_names, sums):
        diff, want = (np.asarray(a, np.float64) for a in sums)
        leaf_rms.update(zip(leaf_names, np.sqrt(diff / want)))

    def whole_rms(sums) -> float:
        diff, want = (float(np.asarray(a, np.float64).sum()) for a in sums)
        return math.sqrt(diff / want)

    record(names(head_params, ""), jax.jit(against)(got_head, want_head))
    stream_rms = [whole_rms(jax.jit(against)(cotangent, want_cotangent))]
    chain = dict(zip(names(head_params, ""), np.asarray(
        jax.jit(chain_stats)(head_params, got_head), np.float64)))
    del got_head, want_head, want_cotangent

    # ---- the layers, from the last: one compiled pair a KIND of layer -----
    def got_side(lp, x, c, mask, index):
        def pieces(lp, x):
            y, _, _, aux, z, _, _ = got_layer(lp, x, index)
            return y, jnp.float32(aux), jnp.float32(z)

        y, back = jax.vjp(pieces, lp, x)
        ones = tuple(jnp.float32(w) for w in side)
        grads, below = back((c.astype(y[0].dtype), *ones))
        compared, compared_below = back(((c * mask).astype(y[0].dtype), *ones))
        return below, chain_stats(lp, grads), compared, compared_below

    def want_side(lp, x, c, got_grads, got_below, index):
        def plain(lp, x):  # a part's intermediates at a time (5.3 GB of them)
            out = jax.checkpoint(lambda lp, x: reference.mixer_part(
                lp, x, sizes, index)[0])(lp, x)
            return jax.checkpoint(lambda lp, h: reference.ffn_part(
                lp, h, sizes))(lp, x + out)

        _, back = jax.vjp(plain, f32(lp), f32(x))
        grads, below = back((f32(c), *(jnp.float32(w) for w in side)))
        return against(got_grads, grads), against(got_below, below)

    compiled = {}
    for index in reversed(range(n_layers)):
        lp, x = params["layers"][index], streams[index]
        which = reference.kind(sizes, index)
        if which not in compiled:
            compiled[which] = (
                jax.jit(lambda lp, x, c, mask, index=index: got_side(
                    lp, x, c, mask, index)),
                jax.jit(lambda lp, x, c, g, b, index=index: want_side(
                    lp, x, c, g, b, index)))
        mask = jnp.asarray(decided_at[index], x.dtype).reshape(1, s, 1)
        below, stats, compared, compared_below = compiled[which][0](
            lp, x, cotangent, mask)
        leaf_sums, below_sums = compiled[which][1](
            lp, x, cotangent * mask, compared, compared_below)
        leaf_names = names(lp, f"['layers'][{index}]")
        record(leaf_names, leaf_sums)
        chain.update(zip(leaf_names, np.asarray(stats, np.float64)))
        stream_rms.append(whole_rms(below_sums))
        cotangent = below
        del compared, compared_below
    embed = jnp.zeros(params["embed"].shape, jnp.float32).at[ids[0]].add(
        cotangent[0].astype(jnp.float32))
    chain["['embed']"] = np.asarray(jax.jit(chain_stats)(
        {"embed": params["embed"]}, {"embed": embed}), np.float64)[0]

    worst = max(leaf_rms, key=lambda n: (np.isnan(leaf_rms[n]), leaf_rms[n]))
    read = {
        "grads_rms": float(leaf_rms[worst]), "grads_rms_worst_leaf": worst,
        "grad_stream_rms": float(np.max(stream_rms)),
        "grad_stream_layers_rms": stream_rms[::-1],  # the embedding's first
        "step_grad_norms": 0.0, "update_norm": 0.0,
    }
    if stepped is None:
        return read
    if set(stepped) != set(chain):
        raise BenchError("the step's leaves are not the chain's: "
                         f"{sorted(set(stepped) ^ set(chain))}")
    norms = {n: abs(math.sqrt(stepped[n][0] / chain[n][0]) - 1.0)
             for n in chain}
    worst = max(norms, key=lambda n: (np.isnan(norms[n]), norms[n]))
    read.update(step_grad_norms=float(norms[worst]),
                step_grad_norms_worst_leaf=worst)
    # the change: a leaf of its own where the plain rule moves enough of it
    groups = {}
    for n in chain:
        group = n if chain[n][2] >= CHANGED_ELEMENTS_MIN else "the small leaves"
        was = groups.get(group, (0.0, 0.0))
        groups[group] = (was[0] + stepped[n][1], was[1] + chain[n][1])
    changes = {n: (abs(math.sqrt(got / want) - 1.0) if want else
                   (0.0 if not got else math.inf))
               for n, (got, want) in groups.items()}
    worst = max(changes, key=lambda n: (np.isnan(changes[n]), changes[n]))
    read.update(update_norm=float(changes[worst]), update_norm_worst_leaf=worst,
                update_groups=len(groups))
    return read


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None, wrong=None) -> dict:
    """The program against the reference on ``ids`` [1, S], a layer at a
    time ON THE PROGRAM'S OWN STREAM and the logits a block of positions
    at a time.  With ``operand_dtype`` the REFERENCE at that precision
    takes the program's place (what a too-low precision would read); with
    ``wrong`` one of ``WRONG_PROGRAMS`` does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _train_recipe_blocks()
    sizes = reference_sizes(config)
    head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_layers = len(params["layers"])

    def f32(a):
        return a.astype(jnp.float32)

    if operand_dtype is None:
        from learning_at_home_tpu.models.trunk import delta_mixer

        program = _wrong_program(model, wrong)
        got_model, decay_dtype = program.pieces, program.decay_dtype
        cfg = got_model.cfg
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def got_layer(lp, x, index):
            """The program's layer from its own pieces (what ``_layer``
            composes; ``hidden_token_median`` holds ``_hidden`` to it):
            ``(y, the mixer's output, the delta state or 0, aux, z, the
            router's logits, what the router read)``."""
            kind = cfg.attention_layer(index)
            if kind.mixer == "delta":
                out, state, _, _ = delta_mixer(
                    lp["delta"], got_model._part_input(lp["ln1"], x),
                    cfg.n_heads, cfg.delta_chunk, cfg.norm_eps,
                    neg_eigval=cfg.delta_neg_eigval,
                    **({} if decay_dtype is None else {"decay_dtype": decay_dtype}))
                h = x + out
            else:
                h, _, _ = got_model._attention_part(lp, x, kind)
                out, state = h - x, jnp.float32(0)
            if not program.shared_gate:
                lp = {k: v for k, v in lp.items() if k != "shared_gate"}
            y, aux = got_model._ffn_block(lp, h, None, index)
            m = got_model._norm(lp["ln2"], h).reshape(-1, h.shape[-1])
            logits = got_model.moe.router_logits(lp["moe"], m)
            return y, out, state, aux["aux_loss"], aux["router_z_loss"], logits, m

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        program = types.SimpleNamespace(whole=None, gradients=True)  # pieces alone
        x = reference.embed(params, ids)

        def got_layer(lp, x, index):
            out, state = reference.mixer_part(lp, x, sizes, index, operand_dtype)
            h = x + out
            y, aux, z = reference.ffn_part(lp, h, sizes, operand_dtype)
            m = reference.norm(h, lp["ln2"], sizes["norm_eps"])
            return (y, out, jnp.float32(0) if state is None else state, aux, z,
                    reference.router_logits(lp, h, sizes),
                    m.reshape(-1, h.shape[-1]))

        def got_logits(head_params, x):
            return reference.head(head_params, x, sizes, operand_dtype)

    def rel_rms(got, want):
        diff = f32(got) - want
        return jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(want * want))

    def position_sums(got, want):
        """Sums of squares a position: of the difference, of the reference."""
        diff = f32(got) - want
        return (jnp.sum(diff * diff, axis=-1).ravel(),
                jnp.sum(want * want, axis=-1).ravel())

    def one_layer(lp, x, index):
        got, out, state, got_aux, got_z, logits, m = got_layer(lp, x, index)
        want_out, want_state = reference.mixer_part(lp, f32(x), sizes, index)
        h = f32(x) + want_out
        want, aux, z = reference.ffn_part(lp, h, sizes)
        # the router's arithmetic alone: the reference's product on what the
        # program's router read; and its logits against the reference's own,
        # whose input the reference's mixer left (absolute: MARGIN's measure)
        with jax.default_matmul_precision("highest"):
            same_input = reference.router(
                {"gate": f32(lp["moe"]["gate"])}, f32(m), sizes)[0]
        stream_diff = logits - reference.router_logits(lp, h, sizes)
        return (got.astype(x.dtype), position_sums(got, want),
                reference.router_margin(lp, h, sizes),
                (rel_rms(logits, same_input),
                 jnp.sqrt(jnp.mean(stream_diff * stream_diff))),
                rel_rms(out, want_out),
                jnp.float32(0) if want_state is None else rel_rms(state, want_state),
                (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, then the layers: one compiled pair a KIND of layer
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, ids)), slice(None))]
    near_tie, logits_rms, stream_rms = [], [], []
    delta_rms, state_rms, attention_rms = [], [], []
    compiled = {}
    got_aux = got_z = aux = z = 0.0
    streams, decided_at = [], []  # what each layer read; where it is compared
    for index, lp in enumerate(params["layers"]):
        which = reference.kind(sizes, index)
        streams.append(x)
        if which not in compiled:
            compiled[which] = jax.jit(
                lambda lp, x, index=index: one_layer(lp, x, index))
        (x, sums, margin, router_rms, mixer_rms, last_rms, got_side,
         want_side) = compiled[which](lp, x)
        decided = np.asarray(margin) >= MARGIN
        decided_at.append(decided)
        near_tie.append(1.0 - float(decided.mean()))
        logits_rms.append(float(router_rms[0]))
        stream_rms.append(float(router_rms[1]))
        layers_rms.append(decided_rms(sums, decided))
        if which == "linear_attention":
            delta_rms.append(float(mixer_rms))
            state_rms.append(float(last_rms))
        else:
            attention_rms.append(float(mixer_rms))
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
    block = min(blocks.LOGIT_BLOCK, s)
    if s % block:
        raise BenchError(f"seq_len {s} is no multiple of {block}")
    want_ce = got_ce = 0.0
    diff_sq, want_sq = [], []  # a position, float64
    above = [0] * len(blocks.EDGES)
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
    if operand_dtype is None and program.whole is not None:
        # the program WHOLE, as apply and loss_fn compose it
        got_loss, whole = jax.jit(lambda p, i, t: (
            program.whole.loss_fn(p, i, t)[0], program.whole._hidden(p, i)[0]))(
                params, ids, targets)
        got_loss = float(got_loss)
        layered = jax.jit(lambda p, x: f32(model._norm(p, x)))(
            params["ln_f"], x)
        h2, l2 = jax.jit(position_sums)(whole, layered)
        hidden_median = float(np.median(np.sqrt(
            np.asarray(h2, np.float64) / np.asarray(l2, np.float64))))
    else:
        got_loss = reference.total_loss(got_ce / s, got_aux, got_z, n_layers, sizes)
        hidden_median = 0.0
    scale = math.sqrt(want_sq.sum() / elements)
    gradients = dict.fromkeys(GRADIENT_READINGS, 0.0)
    if program.gradients:
        with _kept_out_of_the_compile_cache():
            gradients = compare_gradients(
                program, model, params, reference, config, sizes, ids,
                targets, got_layer, got_logits, streams, decided_at, x,
                operand_dtype)
    return {
        **gradients,
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        "delta_rms": float(np.max(delta_rms)),
        "delta_state_rms": float(np.median(state_rms)),  # see TOLERANCES
        "delta_state_rms_max": float(np.max(state_rms)),
        "attention_rms": float(np.max(attention_rms)),
        "router_logits_rms": float(np.max(logits_rms)),
        "logits_rms": math.sqrt(diff_sq.sum() / elements) / scale,
        "logits_p999": blocks.quantile_from_counts(above, elements, 0.999) / scale,
        "logits_token_median": float(np.median(np.sqrt(diff_sq / want_sq))),
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "hidden_token_median": hidden_median,
        "near_tie_share": max(near_tie),
        "reference_loss": want_loss,
        "reference_logits_rms": scale,
        "embed_and_layers_rms": layers_rms,
        "near_tie_shares": near_tie,
        "router_logits_layers_rms": logits_rms,
        "router_logits_abs_rms_on_the_references_stream": stream_rms,
        "delta_layers_rms": delta_rms,
        "delta_states_rms": state_rms,
        "attention_layers_rms": attention_rms,
    }


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    from learning_at_home_tpu.models.transformer import DMoETransformerLM

    manifest = harness.load_manifest(args.manifest)
    share = harness.load_module(manifest, "runners", "train_recipe_share")
    make = DMoETransformerLM.make_train_step

    def remembered(self, optimizer, *args, **kwargs):
        """The program's own method; the comparison finds the step again."""
        step = make(self, optimizer, *args, **kwargs)
        _MADE_STEPS.append((self, optimizer, step))
        return step

    # this process's own copy of the module: its run() looks these up
    share.CFG_FIELDS = CFG_FIELDS
    share._check_sizes = _check_sizes
    share._blocks = _blocks
    share.compare_with_reference = compare_with_reference
    share.TOLERANCES = TOLERANCES  # its over_tolerance and REFERENCE line read it
    share.MARGIN = MARGIN
    share.share_problems = share_problems
    share.STEP_COUNTERS = STEP_COUNTERS
    share.EXTRA_SCOPES = EXTRA_SCOPES
    DMoETransformerLM.make_train_step = remembered
    try:
        return share.run(cell, config, traffic, args, clock)
    finally:
        DMoETransformerLM.make_train_step = make
        _MADE_STEPS.clear()
