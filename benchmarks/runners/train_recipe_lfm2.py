"""Runner ``train_recipe_lfm2``: ``train_recipe_share``'s run for a stack
whose token mixer is a gated short convolution in three layers of four and
softmax attention in the fourth, whose leading layer's feed-forward part is
dense and whose mixture layers hold EVERY expert their sigmoid routers
score (``lfm2-8b-a1b``).

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is, so the set-up (with the
levelling of the routers' selection biases on the pool), the warm-up, the
window, the checks (finite losses, the first pool batch's loss falls,
nothing compiled in the window), the Zipf generator and the printed lines
are that file's own code, not a copy.  The names its ``run`` looks up in its
module are replaced, in this process's private copy of it, with what this
file defines (``train_recipe_qwen3next`` does the same, and its plain
restatement of Adafactor's first step is used from there as it is; the
comparison's programs ARE written to the compile cache here: the cell's
programs are 64 MB without them, and compiled anew in every run they took
a warm run to 183-190 s where the cell is to stay under 150):

- ``CFG_FIELDS`` / ``_check_sizes``: the configuration file restates the
  sizes under the ``lfm2_moe`` key names; the layers run are entries
  ``first_layer ..`` of ``layer_types``, dense where their index in the
  model is under ``num_dense_layers``; every expert is held; and the
  program's parameter count is the file's ``parameters``.
- ``share_problems``: ``dropped_fraction`` 0 in every step, the loads under
  ``LOAD_MAX_OVER_MEAN``, and ``shortconv_out_rms`` (the smallest rms of a
  conv mixer's output over the conv layers) above ``SHORTCONV_OUT_RMS_MIN``,
  so that a dead gate cannot pass as fast.
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``: a layer at a
  time ON THE PROGRAM'S OWN STREAM, the program's layer composed of its own
  pieces (``hidden_token_median`` holds ``_hidden`` whole to them): each
  kind of layer's mixer output, the gated convolution's arithmetic alone,
  what the mixture adds, the router's logits, the layer's output
  over the positions whose 4th and 5th largest ``score + bias`` lie
  ``MARGIN`` apart or more in the reference; then the logits a block of
  positions at a time and the loss; then the BACKWARD pass and the update
  (:func:`compare_gradients`): each layer's ``jax.vjp`` against the
  reference's on the program's own stream and cotangent, leaf by leaf, and
  ONE call of the timed train step whose gradients' norms and whose change
  of every leaf are held to that chain.  ``WRONG_PROGRAMS`` names programs
  that must fall outside (``tools/smallthinker_probe.py float8`` runs them
  on the chip).
- ``STEP_COUNTERS`` / ``EXTRA_SCOPES``: the conv mixer's beside the share's.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import re
import types

import harness
from harness import BenchError
from lfm2_flops import layers as layers_run  # (mixer, feed-forward) a layer run

# the file's key (lfm2_moe's config.json, then this repo's) -> the program's
# config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "seq_len": "seq_len", "num_experts": "num_experts",
    "num_experts_per_tok": "k", "moe_intermediate_size": "expert_ffn_dim",
    "intermediate_size": "dense_ffn_dim", "conv_L_cache": "short_conv_kernel",
    "norm_topk_prob": "renormalize", "routed_scaling_factor": "routed_scale",
    "use_expert_bias": "router_bias", "router_bias_rate": "router_bias_rate",
    "norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "norm": "norm",
    "positions": "positions", "qk_norm": "qk_norm",
    "expert_kind": "expert_kind", "routing": "routing",
    "router_score": "router_score", "aux_loss_weight": "aux_loss_weight",
    "router_z_weight": "router_z_weight", "remat": "remat",
    "scan_layers": "scan_layers", "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 61; the program over eleven seeds | the second
# reading): the largest the program gave, and the reference itself with
# every matmul operand AND the convolution's operands rounded to
# float8_e4m3 (the nearest precision below the configuration's bf16), run
# through this same comparison in the program's place, which must fall
# outside: it is outside ten.  ``shortconv_rms`` is the conv layers' mixer
# output, the worst layer (0.47-0.48 % | 7.9 %), ``attention_rms`` the
# attention layer's (0.32-0.33 % | 77 %), each read where the part makes it
# (as ``h - x`` of two bf16 streams the attention's read 2.3 %: the stream's
# rounding).  ``shortconv_core_rms`` is the gated convolution's ARITHMETIC
# alone: its result against the reference's on the very ``[B | C | u]`` it
# read, the worst layer: float32 arithmetic rounded ONCE to bf16 reads
# 1.66e-3 there whatever the seed | 3.64e-3 with ``v``, the products and
# the partial sums rounded to bf16 on the way.  ``routed_rms`` is what a
# MIXTURE adds to the stream, over the decided positions, the worst layer
# (0.63 % | 1.30 % with the weights taken from ``s + b``, 26 % at float8):
# the stream it is added to, and the dense layer's 0.78 %, hide that fault
# from ``layers_rms``.  ``router_logits_rms`` is the router's ARITHMETIC
# alone: its logits against the reference's product on the very input the
# program's router read (float32, the highest precision), over the logits'
# own rms, the worst layer (0 | 1.1e-3 = 2^-9 / sqrt(3) for logits rounded
# to bf16, by construction).  ``layers_rms`` is over the decided positions
# (0.57-0.59 % | 9.9 %; 70 % with a SiLU left on the convolution, 120 % with
# B and C exchanged).  ``logits_rms`` 0.166 % | 4.1 %, ``logits_p999`` 0.55 %
# | 13.6 %.  ``loss`` hardly moves with the precision (1e-5 to 7.7e-5, 1.2e-4
# once on a sibling program | 3.8e-4 at float8): its limit is
# ``qwen3-next``'s, four times the largest reading, and its second reading a
# named fault (a step on half the loss reads 0.5).  ``hidden_token_median``
# has no second precision (both sides are the program; 1.0-1.3 %): a
# ``_hidden`` that composes another stack than the layers run reads tens of
# percent.  ``near_tie_share`` guards the comparison itself (11-12 %): at
# least three quarters of the positions are compared in every layer.  The
# backward pass and the update (:func:`compare_gradients`): ``grads_rms``
# the worst leaf of a layer's ``jax.vjp`` (0.82-0.84 % | 100 % at float8),
# ``grad_stream_rms`` what a layer hands the layer below (0.66-0.67 % | 100
# %), ``step_grad_norms`` the timed step's against the chain (0.4-3.7 %, a
# head's norm scale of 64 numbers | 50 % for a step on half the loss),
# ``update_norm`` (0.1-1.9 % | 1 for a leaf left as it was, which is what an
# unchanged state reads, with the more room above the first reading).
TOLERANCES = {"layers_rms": 2e-2, "shortconv_rms": 1e-2,
              "shortconv_core_rms": 2.4e-3, "attention_rms": 1e-2,
              "routed_rms": 9e-3, "router_logits_rms": 1e-4,
              "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2, "loss": 3e-4,
              "hidden_token_median": 5e-2, "near_tie_share": 0.25,
              "grads_rms": 5e-2, "grad_stream_rms": 3e-2,
              "step_grad_norms": 1e-1, "update_norm": 2e-1}
# A token whose 4th and 5th largest ``sigmoid score + bias`` lie closer than
# this in the reference is not compared in that layer: the program's router
# reads the bf16 stream its bf16 mixer left, so its scores differ from the
# reference's by ``router_score_rms`` (the REFERENCE line reports it), and
# which of the two experts it takes there is no error of either side.
MARGIN = 2.0 ** -9
LOAD_MAX_OVER_MEAN = 3.0
# the smallest rms a conv mixer's output may have in any step of the
# window: the reference's reads 0.2 to 0.5 under seeded weights (a product
# of three unit-scale projections and a filter of unit fan-in); a gate that
# multiplies by zero reads 0
SHORTCONV_OUT_RMS_MIN = 0.05
# programs that must fall outside the limits, by name: what
# ``compare_with_reference(.., wrong=name)`` puts in the program's place
WRONG_PROGRAMS = {
    "the program, its convolution accumulated in bfloat16": {"wrong": "bf16_conv"},
    "the program with a SiLU left on the convolution": {"wrong": "silu_conv"},
    "the program with B and C exchanged": {"wrong": "swapped_gates"},
    "the program with its weights taken from s + b": {"wrong": "biased_weights"},
    "the step on half the loss": {"wrong": "half_loss"},
    "the step with a leaf left as it was": {"wrong": "frozen_leaf"},
}
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "router_bias_abs_max", "shortconv_out_rms")
EXTRA_SCOPES = ("shortconv/in_proj", "shortconv/core", "shortconv/out_proj",
                "shortconv", "dense_ffn", "router_bias")
GRADIENT_READINGS = ("grads_rms", "grad_stream_rms", "step_grad_norms",
                     "update_norm")
# (model, optimizer, step) of each train step made while :func:`run` runs
_MADE_STEPS: list = []


@functools.cache
def _beside(name: str):
    return harness.load_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), name))


def _check_sizes(config: dict, cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.models.transformer import DMoETransformerLM
    from learning_at_home_tpu.parallel.mesh import make_mesh

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    layers = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    ffns = cfg.ffn_pattern or ("moe",) * cfg.n_layers
    got["layers_run"] = [
        ({"softmax": "full_attention"}.get(a.mixer, a.mixer),
         {"moe": "sparse"}.get(f, f)) for a, f in zip(layers, ffns)]
    got["rotated_layers"] = [i for i, a in enumerate(layers) if a.rotary]
    got["windowed_layers"] = [
        i for i, a in enumerate(layers) if a.window is not None]
    got["held_experts"] = cfg.held_experts
    got["shared_experts"] = cfg.shared_experts
    got["dense_layers_run"] = list(ffns).count("dense")
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    got["parameters"] = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(DMoETransformerLM(cfg, mesh).init_params,
                           jax.random.PRNGKey(0))))
    run = layers_run(config)
    want = dict(
        config, layers_run=run, windowed_layers=[], held_experts=None,
        shared_experts=0,
        rotated_layers=[i for i, (m, _) in enumerate(run) if m == "full_attention"],
    )
    wrong = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def share_problems(counters: dict) -> list:
    """What every step of the window must read: nothing dropped, loads a
    levelled router keeps, and a conv mixer that gives something."""
    problems = []
    dropped = counters.get("dropped_fraction", [1.0])
    if any(x != 0.0 for x in dropped):
        problems.append(f"dropped_fraction up to {max(dropped):.3e}, not 0")
    load = counters.get("expert_load_max_over_mean", [math.inf])
    if not max(load) < LOAD_MAX_OVER_MEAN:
        problems.append(
            f"expert_load_max_over_mean up to {max(load):.3f}, not under "
            f"{LOAD_MAX_OVER_MEAN}")
    rms = counters.get("shortconv_out_rms", [0.0])
    if not min(rms) >= SHORTCONV_OUT_RMS_MIN:  # a nan fails too
        problems.append(
            f"shortconv_out_rms down to {min(rms):.3e}, under "
            f"{SHORTCONV_OUT_RMS_MIN}")
    return problems


def reference_sizes(config: dict) -> dict:
    """What the reference is given: the FILE's sizes, not the program's."""
    run = layers_run(config)
    return dict(
        layer_types=[m for m, _ in run], mlp_layer_types=[f for _, f in run],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=config["rope_theta"], norm_eps=config["norm_eps"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
    )


# ---- programs that must fall outside ---------------------------------------


def _wrong_cores():
    """What a wrong program runs in ``gated_short_conv``'s place."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.ops.short_conv import gated_short_conv_plain

    def taps_of(v, w, rounded=lambda a: a):
        """The convolution of ``v`` [B, S, Ch] float32, every product and
        every partial sum through ``rounded``."""
        s, taps = v.shape[1], w.shape[1]
        padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        total = rounded(w[:, 0] * padded[:, :s])
        for j in range(1, taps):
            total = rounded(total + rounded(w[:, j] * padded[:, j:j + s]))
        return total

    def bf16(a):  # the rounding itself: the TPU compiler computes a chain
        # of bf16 products and sums in float32 and rounds it once
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def bf16_conv(bcu, w):  # v, every product and every partial sum rounded
        b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
        return bf16(c * taps_of(bf16(b * u), w, bf16)).astype(bcu.dtype)

    def silu_conv(bcu, w):
        b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
        return (c * jax.nn.silu(taps_of(b * u, w))).astype(bcu.dtype)

    def swapped_gates(bcu, w):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        return gated_short_conv_plain(jnp.concatenate([c, b, u], axis=-1), w)

    return {"bf16_conv": bf16_conv, "silu_conv": silu_conv,
            "swapped_gates": swapped_gates}


@contextlib.contextmanager
def _weights_from_biased_scores():
    """While tracing inside, the router weighs by ``s + b`` where it must
    weigh by ``s`` alone."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.ops import moe_dispatch

    right = moe_dispatch.router_choice

    def wrong(logits, k, renormalize=True, score="softmax", bias=None,
              scale=1.0):
        gates, _, top_i = right(logits, k, renormalize, score, bias, scale)
        top_w = jnp.take_along_axis(
            jax.nn.sigmoid(logits) + bias, top_i, axis=-1)
        return gates, scale * top_w / top_w.sum(axis=-1, keepdims=True), top_i

    moe_dispatch.router_choice = wrong
    try:
        yield
    finally:
        moe_dispatch.router_choice = right


def _wrong_program(model, wrong: str | None):
    """What stands in the program's place: ``pieces`` the model whose layers
    are compared one at a time, ``conv_core`` the conv layers' gated
    convolution in them (between the mixer's two products, as
    ``trunk.short_conv_mixer`` composes them), ``routing`` a context
    in which those pieces are traced, ``whole`` the model whose ``_hidden``,
    ``loss_fn`` and train step are held to those pieces (None where the
    pieces are the wrong ones), ``frozen``: whether that step's first conv
    out-projection is put back as it was, and ``gradients``: whether the
    backward pass is compared (a program whose fault a forward reading
    names is read forward alone)."""
    from learning_at_home_tpu.ops.short_conv import gated_short_conv

    def twin():
        return type(model)(dataclasses.replace(model.cfg), model.mesh)

    program = types.SimpleNamespace(
        pieces=model, conv_core=gated_short_conv, routing=contextlib.nullcontext,
        whole=model, frozen=False,
        gradients=wrong in (None, "half_loss", "frozen_leaf"))
    cores = _wrong_cores()
    if wrong in cores:
        program.conv_core, program.whole = cores[wrong], None
    elif wrong == "biased_weights":
        # a model of its own: the mixture's traced body is cached by the
        # instance it is bound to, and the program's own has been traced
        program.pieces, program.whole = twin(), None
        program.routing = _weights_from_biased_scores
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


def _timed_step(model, config: dict) -> tuple:
    """``(optimizer, train step)`` of ``model``: the very step the window
    timed where :func:`run` saw it made (no second compile), else the
    recipe's optimizer and a step made here, the same program (the probe's
    and the tests' models, a wrong program's twin)."""
    for made_for, optimizer, step in _MADE_STEPS:
        if made_for is model:
            return optimizer, step
    import __graft_entry__ as entry

    optimizer = getattr(entry, config["recipe"])(
        model.mesh, tiny=bool(config.get("tiny")))[2]
    return optimizer, model.make_train_step(optimizer)


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
    the tree by the difference's norm over the reference's (the selection
    biases aside: no gradient reaches them on either side).
    ``grad_stream_rms``: the same for what a layer hands the layer below.
    ``step_grad_norms``: ONE call of the timed train step from an empty
    optimizer state, whose second moments are then its gradients' mean
    squares: each leaf's norm against the chain's (every position), the
    worst leaf's ``|ratio - 1|``.  ``update_norm``: the norm of each leaf's
    change over that step against what the plain rule
    (``train_recipe_qwen3next._first_step``) makes of the chain's gradient,
    ``|ratio - 1|``: a leaf left as it was reads 1.  The embedding table is
    the head too: its gradient in the chain is the head's share and the
    lookup's, added."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _beside("train_recipe_blocks.py")
    plain = _beside("train_recipe_qwen3next.py")
    n_layers, s = len(params["layers"]), ids.shape[1]
    block = min(blocks.LOGIT_BLOCK, s)
    learning_rate = float(re.fullmatch(
        r"fused_adafactor\((.+)\)", config["optimizer"]).group(1))

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
            after = plain._first_step(p, g.astype(p.dtype), learning_rate)
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
                      if n.endswith("['conv']['w_out']"))
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

    head_params = {"ln_f": params["ln_f"], "embed": params["embed"]}
    got_head, cotangent = head_gradients(got_logits, head_params, x_final)
    want_head, want_cotangent = head_gradients(
        lambda hp, x: reference.head(hp, x, sizes), f32(head_params),
        f32(x_final))
    leaf_rms = {}  # a leaf of the tree: the difference over the reference

    def record(leaf_names, sums):
        diff, want = (np.asarray(a, np.float64) for a in sums)
        leaf_rms.update({
            n: math.sqrt(d / w) for n, d, w in zip(leaf_names, diff, want)
            if not n.endswith("['router_bias']")})

    def whole_rms(sums) -> float:
        diff, want = (float(np.asarray(a, np.float64).sum()) for a in sums)
        return math.sqrt(diff / want)

    record(names(head_params, "head:"), jax.jit(against)(got_head, want_head))
    stream_rms = [whole_rms(jax.jit(against)(cotangent, want_cotangent))]
    embed_from_head = got_head["embed"]
    final_norm = {"ln_f": params["ln_f"]}
    chain = dict(zip(names(final_norm, ""), np.asarray(jax.jit(chain_stats)(
        final_norm, {"ln_f": got_head["ln_f"]}), np.float64)))
    del got_head, want_head, want_cotangent

    # ---- the layers, from the last: one compiled pair a KIND of layer -----
    def got_side(lp, x, c, mask, index):
        with program.routing():
            y, back = jax.vjp(
                lambda lp, x: got_layer(lp, x, index)[0], lp, x)
        grads, below = back(c.astype(y.dtype))
        compared, compared_below = back((c * mask).astype(y.dtype))
        return below, chain_stats(lp, grads), compared, compared_below

    def want_side(lp, x, c, got_grads, got_below, index):
        def layer(lp, x):  # a part's intermediates at a time
            out = jax.checkpoint(lambda lp, x: reference.mixer_part(
                lp, x, sizes, index))(lp, x)
            return jax.checkpoint(lambda lp, h: reference.ffn_part(
                lp, h, sizes, index)[0])(lp, x + out)

        _, back = jax.vjp(layer, f32(lp), f32(x))
        grads, below = back(f32(c))
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
    embed = embed_from_head.astype(jnp.float32).at[ids[0]].add(
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
    # a selection bias has no gradient (and the balancing rule, not the
    # optimizer, moves it): neither side of either ratio
    held = [n for n in chain if not n.endswith("['router_bias']")]
    norms = {n: abs(math.sqrt(stepped[n][0] / chain[n][0]) - 1.0) for n in held}
    worst = max(norms, key=lambda n: (np.isnan(norms[n]), norms[n]))
    read.update(step_grad_norms=float(norms[worst]),
                step_grad_norms_worst_leaf=worst)
    # the change: a leaf of its own where the plain rule moves enough of it
    groups = {}
    for n in held:
        group = n if chain[n][2] >= plain.CHANGED_ELEMENTS_MIN else "the small leaves"
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

    blocks = _beside("train_recipe_blocks.py")
    sizes = reference_sizes(config)
    head_params = {"ln_f": params["ln_f"], "embed": params["embed"]}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_sparse = reference.sparse_layers(params, sizes)

    def f32(a):
        return a.astype(jnp.float32)

    if operand_dtype is None:
        from learning_at_home_tpu.models.trunk import (
            attention_core,
            gated_mlp,
            output_projection,
        )

        program = _wrong_program(model, wrong)
        cfg = model.cfg
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def got_layer(lp, x, index):
            """The program's layer from its own pieces, as ``_layer``
            composes them (``hidden_token_median`` holds ``_hidden`` to it):
            ``(y, the mixer's output, a conv layer's [B | C | u] and its gated
            convolution or 0 and 0, what the feed-forward part adds, aux, z,
            the router's logits, what the router read)``, the last four 0
            for a dense layer.  The two parts' outputs are taken where they
            are made: as a difference of two bf16 streams a part that adds
            little reads the stream's rounding, not its own (the attention
            layer's 2.3 % was that: 0.2 % is the kernel's)."""
            kind = cfg.attention_layer(index)
            a = model._norm(lp["ln1"], x)
            core = (jnp.float32(0), jnp.float32(0))
            if "conv" in lp:
                p = lp["conv"]
                # ONE bf16 array for the kernel and for the reference's
                # arithmetic on it: without the barrier the TPU compiler
                # hands the reference the product before its rounding
                # (my chip run, PR 61: 3.3e-3 where the rounding of the
                # result alone reads half that)
                bcu = jax.lax.optimization_barrier(
                    a @ p["w_in"].astype(a.dtype))
                core = (bcu, program.conv_core(bcu, p["conv_w"]))
                out = core[1] @ p["w_out"].astype(a.dtype)
            else:
                q, k, v, gate = model._qkv(
                    lp, a, np.arange(x.shape[1]), kind.rotary)
                out = output_projection(lp, attention_core(
                    q, k, v, model.attn_impl, kind.window), gate)
            h = x + out
            m = model._norm(lp["ln2"], h)
            if "ffn" in lp:
                add = gated_mlp(lp["ffn"], m, model._gate_act)
                return (h + add, out, core, add, 0.0, 0.0, jnp.float32(0),
                        jnp.float32(0))
            m = m.reshape(-1, h.shape[-1])
            routed, aux = program.pieces.moe(lp["moe"], m, jitter_salt=index)
            add = routed.reshape(h.shape)
            return (h + add, out, core, add, aux["aux_loss"],
                    aux["router_z_loss"],
                    program.pieces.moe.router_logits(lp["moe"], m), m)

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        program = types.SimpleNamespace(  # the reference's pieces alone
            whole=None, gradients=True, routing=contextlib.nullcontext)
        x = reference.embed(params, ids)

        def got_layer(lp, x, index):
            core = (jnp.float32(0), jnp.float32(0))
            if "conv" in lp:
                *core, out = reference.conv_parts(lp, x, sizes, operand_dtype)
            else:
                out = reference.mixer_part(lp, x, sizes, index, operand_dtype)
            h = x + out
            y, aux, z = reference.ffn_part(lp, h, sizes, index, operand_dtype)
            if "moe" not in lp:
                return (y, out, core, y - h, aux, z, jnp.float32(0),
                        jnp.float32(0))
            m = reference.norm(h, lp["ln2"], sizes["norm_eps"])
            return (y, out, core, y - h, aux, z,
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
        with program.routing():
            got, out, (bcu, gated), add, got_aux, got_z, logits, m = got_layer(
                lp, x, index)
        want_out = reference.mixer_part(lp, f32(x), sizes, index)
        # the gated convolution's ARITHMETIC alone: the reference's on the
        # very [B | C | u] the program's read
        core_rms = rel_rms(gated, reference.short_conv(
            f32(bcu), f32(lp["conv"]["conv_w"]))) if "conv" in lp else jnp.float32(0)
        h = f32(x) + want_out
        want, aux, z = reference.ffn_part(lp, h, sizes, index)
        if "moe" in lp:
            # the router's arithmetic alone: the reference's product on what
            # the program's router read; and its scores against the
            # reference's own, whose input the reference's mixer left
            # (absolute: MARGIN's measure)
            with jax.default_matmul_precision("highest"):
                same_input = f32(m) @ f32(lp["moe"]["gate"])
            score_diff = jax.nn.sigmoid(logits) - jax.nn.sigmoid(
                reference.router_logits(lp, h, sizes))
            router_rms = (rel_rms(logits, same_input),
                          jnp.sqrt(jnp.mean(score_diff * score_diff)))
            margin = reference.router_margin(lp, h, sizes)
        else:  # a dense layer routes nothing: every position is decided
            router_rms = (jnp.float32(0), jnp.float32(0))
            margin = jnp.full((x.shape[0] * x.shape[1],), jnp.inf)
        return (got.astype(x.dtype),
                (position_sums(got, want), position_sums(add, want - h)), margin,
                router_rms, (rel_rms(out, want_out), core_rms),
                (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, then the layers: one compiled pair a KIND of layer
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, ids)), slice(None))]
    near_tie, logits_rms, score_rms, ffn_rms = [], [], [], []
    shortconv_rms, core_rms, attention_rms = [], [], []
    compiled = {}
    got_aux = got_z = aux = z = 0.0
    streams, decided_at = [], []  # what each layer read; where it is compared
    for index, lp in enumerate(params["layers"]):
        which = reference.kind(sizes, index)
        streams.append(x)
        if which not in compiled:
            compiled[which] = jax.jit(
                lambda lp, x, index=index: one_layer(lp, x, index))
        x, sums, margin, router_rms, mixer_rms, got_side, want_side = (
            compiled[which](lp, x))
        decided = np.asarray(margin) >= MARGIN
        decided_at.append(decided)
        near_tie.append(1.0 - float(decided.mean()))
        logits_rms.append(float(router_rms[0]))
        score_rms.append(float(router_rms[1]))
        layers_rms.append(decided_rms(sums[0], decided))
        ffn_rms.append(decided_rms(sums[1], decided))
        if which[0] == "conv":
            shortconv_rms.append(float(mixer_rms[0]))
            core_rms.append(float(mixer_rms[1]))
        else:
            attention_rms.append(float(mixer_rms[0]))
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
    want_loss = reference.total_loss(want_ce / s, aux, z, n_sparse, sizes)
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
        got_loss = reference.total_loss(got_ce / s, got_aux, got_z, n_sparse, sizes)
        hidden_median = 0.0
    scale = math.sqrt(want_sq.sum() / elements)
    gradients = dict.fromkeys(GRADIENT_READINGS, 0.0)
    if program.gradients:
        gradients = compare_gradients(
            program, model, params, reference, config, sizes, ids, targets,
            got_layer, got_logits, streams, decided_at, x, operand_dtype)
    return {
        **gradients,
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        "shortconv_rms": float(np.max(shortconv_rms)),
        "shortconv_core_rms": float(np.max(core_rms)),
        "attention_rms": float(np.max(attention_rms)),
        "routed_rms": float(np.max([
            rms for rms, lp in zip(ffn_rms, params["layers"]) if "moe" in lp])),
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
        "router_score_rms": score_rms,
        "shortconv_layers_rms": shortconv_rms,
        "shortconv_core_layers_rms": core_rms,
        "attention_layers_rms": attention_rms,
        "ffn_layers_rms": ffn_rms,
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
