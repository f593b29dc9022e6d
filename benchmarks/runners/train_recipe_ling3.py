"""Runner ``train_recipe_ling3``: ``train_recipe_share``'s run for a stack of
Kimi-Delta-Attention layers (a delta rule whose decay is a number a KEY
CHANNEL) with gated latent attention in one layer of six, a dense leading
layer, and mixtures whose sigmoid router chooses inside the best groups of
its experts, ONE group held (``ling-3.0-flash-vl``).

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is (set-up with the levelling of
the selection biases on the pool, warm-up, window, checks, the Zipf
generator, the printed lines), with the names its ``run`` looks up replaced
in this process's private copy, as ``train_recipe_qwen3next`` does; that
file's plain restatement of Adafactor's first step, its reader of the scope
table's gates and its compile-cache guard are used from there as they are,
and ``train_recipe_xing4``'s ONE call of the timed step on the caller's own
arrays (this configuration's parameters are 5.6 GB: no second copy fits
beside the step).

- ``CFG_FIELDS`` / ``_check_sizes``: the file restates the sizes under the
  catalog's key names; ``num_experts`` is the experts HELD and
  ``num_experts_published`` the router's width; the layers run are
  published layers ``first_layer ..``, latent where ``(i + 1) %
  layer_group_size == 0`` and dense where ``i < first_k_dense_replace``;
  the program's parameter count is the file's ``parameters``.
- ``share_problems``: the share's own (``dropped_fraction`` 0, rows and
  loads within the levelled share's limits), the delta rule's and the
  gate's counters in their ranges, and ``groups_reaching_share`` within
  ``GROUPS_REACHING`` (half the tokens keep the held group at level loads).
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``: a layer at a
  time ON THE PROGRAM'S OWN STREAM, the program's layer composed of its own
  pieces: the mixer's output (a KDA layer's state after the last position
  besides), the router's logits, the layer's output over the positions the
  reference's router decides by ``MARGIN`` or more (its 8th and 9th
  selection scores inside the kept groups, one of them held, AND its 4th
  and 5th group scores); the logits a block at a time and the loss; then
  the backward pass of the head and of every layer that holds a mixture
  (:func:`compared_layers`: both kinds of mixer) and the update, every
  leaf of the step held to having moved (:func:`compare_gradients`).
  ``WRONG_PROGRAMS`` names programs that must fall outside.
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
from ling3_flops import layers as layers_run  # (mixer, feed-forward) a layer run

# the file's key (the catalog's, then this repo's) -> the program's field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "seq_len": "seq_len", "num_experts_published": "num_experts",
    "num_experts": "held_experts", "first_held_expert": "first_held_expert",
    "num_experts_per_tok": "k", "moe_intermediate_size": "expert_ffn_dim",
    "intermediate_size": "dense_ffn_dim",
    "kv_lora_rank": "kv_latent_dim", "q_lora_rank": "q_latent_dim",
    "qk_rope_head_dim": "rope_head_dim", "v_head_dim": "v_head_dim",
    "head_dim": "delta_key_dim", "short_conv_kernel_size": "delta_conv_kernel",
    "kda_lower_bound": "delta_decay_floor", "delta_chunk": "delta_chunk",
    "delta_neg_eigval": "delta_neg_eigval", "attention_gate": "attention_gate",
    "norm_topk_prob": "renormalize", "routed_scaling_factor": "routed_scale",
    "moe_router_enable_expert_bias": "router_bias",
    "router_bias_rate": "router_bias_rate", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "norm": "norm", "positions": "positions", "qk_norm": "qk_norm",
    "expert_kind": "expert_kind", "routing": "routing",
    "router_score": "router_score", "aux_loss_weight": "aux_loss_weight",
    "router_z_weight": "router_z_weight", "remat": "remat",
    "scan_layers": "scan_layers", "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 66): the largest the program gave over its seeds,
# and a second reading that must fall outside: the reference itself with
# every matmul operand rounded to float8_e4m3 (the nearest precision below
# the configuration's bf16) run through this same comparison in the
# program's place, or, where the precision hardly moves a number, a named
# wrong program.  ``delta_rms`` is the KDA layers' mixer output, the worst
# layer; ``delta_state_rms`` their state after the last position, the MEDIAN
# layer (train_recipe_delta.py says why); ``attention_rms`` the latent
# layer's output; ``router_logits_rms`` the router's arithmetic alone;
# ``layers_rms`` over the decided positions.  The readings are in PERF.md.
# ``grads_rms`` and ``grad_stream_rms`` are wide because the readings are:
# under gates that DO decay (the seeded ``dt_bias``) the gradient of a
# log-decay is a difference of two sums of bf16 products, and the leaves it
# reaches (``w_decay``, ``dt_bias``) read 25-33 % where every other leaf
# reads under 6 % (the plain form in bf16 and the kernels alike, 3.5 and 5 %
# an element where the inputs' rounding alone gives 0.3 %: PERF.md section
# 6, PR 66, after the review); the reference at float8 reads 100 %
TOLERANCES = {"layers_rms": 2e-2, "delta_rms": 1.5e-2, "delta_state_rms": 1.5e-2,
              "attention_rms": 2e-2, "router_logits_rms": 1e-4,
              "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2,
              "hidden_token_median": 7e-2, "near_tie_share": 0.25,
              "grads_rms": 6e-1, "grad_stream_rms": 2.5e-1,
              "step_grad_norms": 2.5e-1, "update_norm": 2e-1}
# A token is left out of a layer's comparison where, in the reference, its
# 8th and 9th largest ``score + bias`` inside its kept groups (one of the
# two a held expert) or its 4th and 5th group scores lie closer than this:
# the program's router reads the bf16 stream its bf16 mixer left, so which
# expert (or which GROUP) it takes there is no error of either side
# (train_recipe_latent's margin, for its reason)
MARGIN = 2.0 ** -9
WRONG_PROGRAMS = {
    "the program, ONE decay a head (the channels' mean)": {"wrong": "mean_decay"},
    "the program, its rule's sums kept in bfloat16": {"wrong": "bf16_sums"},
    "the program without the group mask": {"wrong": "no_group_mask"},
    "the program without the latent layer's head gate": {"wrong": "no_head_gate"},
    "a _hidden without the group mask": {"wrong": "hidden_no_group_mask"},
    "the step on half the loss": {"wrong": "half_loss"},
    "the step with a leaf left as it was": {"wrong": "frozen_leaf"},
    "the step with a leaf of the leading layer left as it was": {
        "wrong": "frozen_leaf_below"},
}
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "local_rows_over_level", "router_bias_abs_max",
                 "groups_reaching_share", "delta_decay_min", "delta_beta_max",
                 "attention_gate_mean")
EXTRA_SCOPES = ("delta/in_proj", "delta/conv", "delta/decay", "delta/core",
                "delta/gate_norm", "delta/out_proj", "delta", "shared_expert",
                "dense_ffn", "router_bias", "latent_down", "latent_up", "rope")
# what lies under these scopes is ALSO a key of the scope table (it stays
# inside ``attention`` and ``router`` in ``by_scope``)
GATE_SCOPES = {
    "attention_gate_s": re.compile(r"[/(]attention/global/(?:proj/)?gate[/)]"),
    "router_groups_s": re.compile(r"[/(]router/groups[/)]"),
}
# the share of tokens any of whose kept groups is the held one: 4 of 8 at
# level loads; the levelled bias keeps it near there
GROUPS_REACHING = (0.25, 0.75)
# a STEP's largest load over the mean, over all 512 experts: an expert's
# level share of one row's 131,072 assignments is 256, so the sampling alone
# spreads it (2.16-2.65 over thirteen runs of 37-39 steps after a levelling
# to 1.008 on the pool: my chip runs, PR 66) where the share's own limit of 3
# was set at 1,024 and more rows an expert
LOAD_MAX_OVER_MEAN = 4.0
GRADIENT_READINGS = ("grads_rms", "grad_stream_rms", "step_grad_norms",
                     "update_norm")
CHANGED_ELEMENTS_MIN = 256
SMALL_LEAF = 4096  # elements: see compare_gradients
# The backward pass is compared with the reference, on the program's own
# cotangent from the loss down, for the head and EVERY layer that holds a
# mixture: two kinds of layer (a KDA mixer or the latent one over a mixture),
# a pair of programs a kind, and a layer more of a kind costs its runs alone
# (2-3 s).  The leading dense layer is a kind of its own and has no pair: a
# third took a warm run to 320-343 s, 100 s of it the host compiling six
# float32 programs whatever their order or threads (my chip runs, PR 66),
# where a run is cut at 360.  Its mixer is the other KDA layers' code, its
# forward pass is compared here, its gradients at tiny sizes
# (tests/test_ling3.py), and its leaves, as every leaf of the step, are held
# to having MOVED (compare_gradients: ``update_norm``)
def compared_layers(layers) -> range:
    """The layers whose backward pass is compared: from the first above
    which every layer holds a mixture, to the top."""
    dense = [i for i, lp in enumerate(layers) if "moe" not in lp]
    return range(dense[-1] + 1 if dense else 0, len(layers))


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
    got["qk_nope_head_dim"] = cfg.head_dim - cfg.rope_head_dim
    got["kda_value_dim"] = cfg.delta_value_dim
    got["n_group"], got["topk_group"] = cfg.router_groups
    got["num_shared_experts"] = cfg.shared_experts
    got["layers_run"] = [
        ("kda" if cfg.attention_layer(i).mixer == "delta" else "latent", ffn)
        for i, ffn in enumerate(cfg.ffn_pattern)]
    got["rotated_layers"] = [
        i for i in range(cfg.n_layers) if cfg.attention_layer(i).rotary]
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    got["parameters"] = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(DMoETransformerLM(cfg, mesh).init_params,
                           jax.random.PRNGKey(0))))
    run = [tuple(pair) for pair in layers_run(config)]
    want = dict(
        config, layers_run=run, kda_value_dim=config["head_dim"],
        num_shared_experts=config["num_shared_experts"],
        rotated_layers=[i for i, (mixer, _) in enumerate(run) if mixer == "latent"])
    wrong = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def reference_sizes(config: dict) -> dict:
    """What the reference is given: the FILE's sizes, not the program's."""
    return dict(
        layer_types=tuple(mixer for mixer, _ in layers_run(config)),
        n_heads=config["num_attention_heads"],
        kda_key_dim=config["head_dim"], kda_value_dim=config["head_dim"],
        conv_kernel=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        experts_per_token=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        held=(config["first_held_expert"], config["num_experts"]),
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
    )


def share_problems(counters: dict) -> list:
    """What the levelled share, the rule, the gate and the groups must read
    in every step of the window."""
    share = _beside("train_recipe_share.py")
    share.LOAD_MAX_OVER_MEAN = LOAD_MAX_OVER_MEAN
    problems = share.share_problems(counters)
    decay = counters.get("delta_decay_min", [math.nan])
    if not all(0.0 < x <= 1.0 for x in decay):  # e^-5 at the least
        problems.append(f"delta_decay_min outside (0, 1]: {min(decay)}..{max(decay)}")
    beta = counters.get("delta_beta_max", [math.nan])
    if not all(0.0 < x <= 1.0 for x in beta):  # sigmoid(b): no factor 2
        problems.append(f"delta_beta_max outside (0, 1]: {min(beta)}..{max(beta)}")
    gate = counters.get("attention_gate_mean", [math.nan])
    if not all(0.0 < x < 1.0 for x in gate):
        problems.append(f"attention_gate_mean outside (0, 1): {min(gate)}..{max(gate)}")
    reach = counters.get("groups_reaching_share", [math.nan])
    low, high = GROUPS_REACHING
    if not all(low <= x <= high for x in reach):
        problems.append(
            f"groups_reaching_share {min(reach):.3f}..{max(reach):.3f} "
            f"outside {low}..{high}")
    return problems


def _blocks():
    """``train_recipe_blocks`` as ``train_recipe_share.run`` sees it, its
    scope table with :data:`GATE_SCOPES`' keys besides
    (``train_recipe_qwen3next._blocks``, given this file's patterns)."""
    qwen = _beside("train_recipe_qwen3next.py")
    qwen.GATE_SCOPES = GATE_SCOPES
    return qwen._blocks()


@contextlib.contextmanager
def _rule_through(stand_in):
    """While it lasts the mixer's rule is ``stand_in(the rule)``."""
    from learning_at_home_tpu.models import trunk

    rule = trunk.gated_delta_chunked
    trunk.gated_delta_chunked = stand_in(rule)
    try:
        yield
    finally:
        trunk.gated_delta_chunked = rule


def _one_decay_a_head():
    """The rule given the MEAN of a head's channel decays in every channel's
    place: what a program with one decay a head computes."""
    import jax.numpy as jnp

    def stand_in(rule):
        def mean_decay(q, k, v, g, beta, *args, **kwargs):
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
            return rule(q, k, v, g, beta, *args, **kwargs)
        return mean_decay
    return _rule_through(stand_in)


def _sums_in_bfloat16():
    """The rule keeping the sums of its log-decays in bfloat16
    (``decay_dtype``; the plain form: the kernels are float32's) where the
    configuration states float32."""
    import jax.numpy as jnp

    def stand_in(rule):
        def bf16_sums(q, k, v, g, beta, chunk, decay_dtype=None, **kwargs):
            return rule(q, k, v, g, beta, chunk, jnp.bfloat16, **kwargs)
        return bf16_sums
    return _rule_through(stand_in)


def _wrong_program(model, wrong: str | None):
    """What stands in the program's place (``train_recipe_qwen3next.
    _wrong_program``'s fields): ``pieces`` the model whose layers are
    compared one at a time, ``whole`` the model whose ``_hidden``,
    ``loss_fn`` and step are held to them (None where the pieces are the
    wrong ones), ``around`` a context the pieces are traced in, ``frozen``
    which KDA layer's out-projection the step is read as having left as it
    was (the last: its change is held to the plain rule's; the first: the
    leading layer's, held to having moved; None: no leaf)."""
    def twin(**changes):
        return type(model)(
            dataclasses.replace(model.cfg, **changes), model.mesh)

    frozen = {"frozen_leaf": -1, "frozen_leaf_below": 0}  # which KDA layer's
    program = types.SimpleNamespace(
        pieces=model, head_gate=True, whole=model,
        frozen=frozen.get(wrong), around=contextlib.nullcontext,
        gradients=wrong in (None, "half_loss", *frozen))
    if wrong == "mean_decay":
        program.around, program.whole = _one_decay_a_head, None
    elif wrong == "bf16_sums":
        program.around, program.whole = _sums_in_bfloat16, None
    elif wrong == "no_group_mask":
        program.pieces, program.whole = twin(router_groups=None), None
    elif wrong == "no_head_gate":
        program.head_gate, program.whole = False, None
    elif wrong == "hidden_no_group_mask":  # in _hidden and loss_fn alone
        program.whole = twin(router_groups=None)
    elif wrong == "half_loss":
        program.whole = twin()
        whole_loss = program.whole.loss_fn

        def half(params, ids, targets):
            loss, metrics = whole_loss(params, ids, targets)
            return 0.5 * loss, metrics
        program.whole.loss_fn = half
    elif wrong is not None and wrong not in frozen:
        raise BenchError(f"no wrong program {wrong!r}")
    return program


def _compiled_together(lowered: dict) -> dict:
    """``{key: (lowered program, ..)}`` -> ``{key: (compiled, ..)}``, every
    program's compile on a thread of its own.  The comparison's programs
    (float32 at the highest precision, the reference's loops) took 123 s of
    a warm run compiled one after another, 7 to 33 s each, on a host whose
    other cores stood idle (my chip runs, PR 66); XLA's compile releases the
    interpreter, so together they take as long as the longest."""
    return {key: tuple(waited.result() for waited in programs)
            for key, programs in _compiling(lowered).items()}


def _compiling(lowered: dict) -> dict:
    """``{key: (lowered program, ..)}`` -> ``{key: (future of the compiled
    program, ..)}``: the compiles run on threads of their own from now."""
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=sum(map(len, lowered.values())))
    started = {key: tuple(pool.submit(program.compile) for program in programs)
               for key, programs in lowered.items()}
    pool.shutdown(wait=False)
    return started


def compare_gradients(program, model, params, reference, config, sizes, ids,
                      targets, got_layer, got_logits, streams, decided_at,
                      x_final, operand_dtype, compiled=None) -> dict:
    """The backward pass and the update against the reference
    (``train_recipe_qwen3next.compare_gradients``, whose readings these
    are), a layer at a time ON THE PROGRAM'S OWN STREAM AND ITS OWN
    COTANGENT, from the loss down; the ONE call of the timed step is
    ``train_recipe_xing4.one_timed_step`` on the caller's own arrays.
    Called TWICE: without ``compiled`` (``x_final`` then any array of a
    stream's shape) it lowers a pair of programs a kind of layer, starts
    their compiles on threads of their own and returns them
    (:func:`_compiling`); with what that call returned it compares, waiting
    for a pair where it first needs it: the forward comparison, the timed
    step and the head run while the host compiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _beside("train_recipe_blocks.py")
    first_step = _beside("train_recipe_qwen3next.py")._first_step
    s = ids.shape[1]
    block = min(blocks.LOGIT_BLOCK, s)
    learning_rate = float(re.fullmatch(
        r"fused_adafactor\((.+)\)", config["optimizer"]).group(1))
    n_sparse = reference.sparse_layers(params)
    side = (sizes["aux_loss_weight"] / n_sparse, sizes["router_z_weight"] / n_sparse)

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
            after = first_step(p, g.astype(p.dtype), learning_rate)
            rows.append(jnp.stack([
                sq(g), sq(f32(after) - f32(p)),
                jnp.sum(after != p).astype(jnp.float32)]))
        return jnp.stack(rows)

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
        def plain(lp, x):  # a part's intermediates at a time
            out = jax.checkpoint(lambda lp, x: reference.mixer_part(
                lp, x, sizes, index)[0])(lp, x)
            return jax.checkpoint(lambda lp, h: reference.ffn_part(
                lp, h, sizes))(lp, x + out)

        _, back = jax.vjp(plain, f32(lp), f32(x))
        grads, below = back((f32(c), *(jnp.float32(w) for w in side)))
        return against(got_grads, grads), against(got_below, below)

    def kind_of(index):
        return (reference.kind(sizes, index), "moe" in params["layers"][index])

    def both_sides(index):
        """The two programs of layer ``index``'s kind, lowered for its
        shapes (traced HERE, under the program's context)."""
        lp, x = params["layers"][index], x_final  # every stream's shape
        mask = jax.ShapeDtypeStruct((1, s, 1), x.dtype)
        got = jax.jit(lambda lp, x, c, mask: got_side(lp, x, c, mask, index))
        with program.around():
            _, _, compared, compared_below = jax.eval_shape(got, lp, x, x, mask)
            got = got.lower(lp, x, x, mask)
        want = jax.jit(lambda lp, x, c, g, b: want_side(lp, x, c, g, b, index))
        return got, want.lower(lp, x, x, compared, compared_below)

    compared = compared_layers(params["layers"])
    if compiled is None:  # the first call: start the compiles, hand them back
        first_of = {}
        for index in compared:
            first_of.setdefault(kind_of(index), index)
        return _compiling({
            which: both_sides(index) for which, index in first_of.items()})

    # ---- the timed step, once, from an empty optimizer state --------------
    stepped = None
    if program.whole is not None and operand_dtype is None:
        stepped, params = _beside("train_recipe_xing4.py").one_timed_step(
            program.whole, model, params, config, ids, targets)
        if stepped is not None and program.frozen is not None:
            at = [n for n in stepped
                  if n.endswith("['delta']['w_out']")][program.frozen]
            stepped[at] = np.asarray([stepped[at][0], 0.0])

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
        for name, d, w in zip(leaf_names, diff, want):
            if w:  # a selection bias has no gradient on either side
                leaf_rms[name] = math.sqrt(d / w)
            elif d:
                leaf_rms[name] = math.inf

    def whole_rms(sums) -> float:
        diff, want = (float(np.asarray(a, np.float64).sum()) for a in sums)
        return math.sqrt(diff / want)

    record(names(head_params, ""), jax.jit(against)(got_head, want_head))
    stream_rms = [whole_rms(jax.jit(against)(cotangent, want_cotangent))]
    chain = dict(zip(names(head_params, ""), np.asarray(
        jax.jit(chain_stats)(head_params, got_head), np.float64)))
    del got_head, want_head, want_cotangent

    for index in reversed(compared):
        lp, x = params["layers"][index], streams[index]
        got_program, want_program = (
            waited.result() for waited in compiled[kind_of(index)])
        mask = jnp.asarray(decided_at[index], x.dtype).reshape(1, s, 1)
        below, stats, compared, compared_below = got_program(
            lp, x, cotangent, mask)
        leaf_sums, below_sums = want_program(
            lp, x, cotangent * mask, compared, compared_below)
        leaf_names = names(lp, f"['layers'][{index}]")
        record(leaf_names, leaf_sums)
        chain.update(zip(leaf_names, np.asarray(stats, np.float64)))
        stream_rms.append(whole_rms(below_sums))
        cotangent = below
        del compared, compared_below
    sizes_of = dict(zip(names(params, ""), (
        leaf.size for leaf in jax.tree_util.tree_leaves(params))))
    worst = max(leaf_rms, key=lambda n: (np.isnan(leaf_rms[n]), leaf_rms[n]))
    read = {
        "grads_rms": float(leaf_rms[worst]), "grads_rms_worst_leaf": worst,
        "grad_stream_rms": float(np.max(stream_rms)),
        "grad_stream_layers_rms": stream_rms[::-1],  # the lowest compared first
        "step_grad_norms": 0.0, "update_norm": 0.0, "step_read": stepped is not None,
    }
    if stepped is None:
        return read
    if set(chain) - set(stepped):
        raise BenchError("the chain's leaves are not the step's: "
                         f"{sorted(set(chain) - set(stepped))}")
    # the selection biases have no gradient on either side and move by the
    # balancing rule, not by the optimizer.  A leaf of fewer than
    # SMALL_LEAF elements (a head's ``A_log``, a norm's scale) is held with
    # the others of its size together, as one: the gradient of 32 numbers
    # hangs on a few positions, and a near tie routed otherwise upstream of
    # them moved one such leaf's norm by 17 % (my chip runs, PR 66)
    held = [n for n in chain if not n.endswith("['router_bias']")]
    sums = {}
    for n in held:
        group = n if sizes_of[n] >= SMALL_LEAF else "the small leaves"
        was = sums.get(group, (0.0, 0.0))
        sums[group] = (was[0] + stepped[n][0], was[1] + chain[n][0])
    norms = {n: abs(math.sqrt(got / want) - 1.0) for n, (got, want) in sums.items()}
    worst = max(norms, key=lambda n: (np.isnan(norms[n]), norms[n]))
    read.update(step_grad_norms=float(norms[worst]),
                step_grad_norms_worst_leaf=worst)
    # the change: a leaf of its own where the plain rule moves enough of it
    groups = {}
    for n in held:
        group = n if chain[n][2] >= CHANGED_ELEMENTS_MIN else "the small leaves"
        was = groups.get(group, (0.0, 0.0))
        groups[group] = (was[0] + stepped[n][1], was[1] + chain[n][1])
    changes = {n: (abs(math.sqrt(got / want) - 1.0) if want else
                   (0.0 if not got else math.inf))
               for n, (got, want) in groups.items()}
    # every other leaf of the step (the embedding, the layers no reference's
    # backward pass is run for) is held to its own change alone: a leaf of
    # SMALL_LEAF elements or more whose gradient's moment is not 0 and which
    # the step left as it was reads 1, what a state left unchanged reads
    others = [n for n in set(stepped) - set(chain)
              if sizes_of[n] >= SMALL_LEAF and not n.endswith("['router_bias']")]
    changes.update({n: 1.0 for n in others
                    if stepped[n][0] > 0.0 and not stepped[n][1] > 0.0})
    worst = max(changes, key=lambda n: (np.isnan(changes[n]), changes[n]))
    read.update(update_norm=float(changes[worst]), update_norm_worst_leaf=worst,
                update_groups=len(groups), leaves_held_to_moving=len(others))
    return read


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None, wrong=None) -> dict:
    """The program against the reference on ``ids`` [1, S], a layer at a
    time ON THE PROGRAM'S OWN STREAM and the logits a block of positions
    at a time.  With ``operand_dtype`` the REFERENCE at that precision
    takes the program's place; with ``wrong`` one of ``WRONG_PROGRAMS``."""
    with contextlib.ExitStack() as keep_out:
        return _compare(model, params, reference, config, ids, targets,
                        operand_dtype, wrong, keep_out)


def _compare(model, params, reference, config, ids, targets, operand_dtype,
             wrong, keep_out) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _beside("train_recipe_blocks.py")
    sizes = reference_sizes(config)
    head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_sparse = reference.sparse_layers(params)

    def f32(a):
        return a.astype(jnp.float32)

    if operand_dtype is None:
        from learning_at_home_tpu.models.trunk import delta_mixer

        program = _wrong_program(model, wrong)
        got_model = program.pieces
        cfg = got_model.cfg
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def got_layer(lp, x, index):
            """The program's layer from its own pieces (what ``_layer``
            composes; ``hidden_token_median`` holds ``_hidden`` to it):
            ``(y, the mixer's output, the KDA state or 0, aux, z, the
            router's logits, what the router read)``."""
            kind = cfg.attention_layer(index)
            if kind.mixer == "delta":
                out, state, _, _ = delta_mixer(
                    lp["delta"], got_model._part_input(lp["ln1"], x),
                    cfg.n_heads, cfg.delta_chunk, cfg.norm_eps,
                    neg_eigval=cfg.delta_neg_eigval,
                    decay_floor=cfg.delta_decay_floor)
                h = x + out
            else:
                if not program.head_gate:
                    lp = {k: v for k, v in lp.items() if k != "w_gate"}
                h, _, _ = got_model._attention_part(lp, x, kind)
                out, state = h - x, jnp.float32(0)
            y, aux = got_model._ffn_block(lp, h, None, index)
            if aux is None:  # a dense layer routes nothing
                zero = jnp.float32(0)
                return y, out, state, zero, zero, jnp.ones((1, 1)), jnp.ones((1, 1))
            m = got_model._norm(lp["ln2"], h).reshape(-1, h.shape[-1])
            logits = got_model.moe.router_logits(lp["moe"], m)
            return y, out, state, aux["aux_loss"], aux["router_z_loss"], logits, m

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        program = types.SimpleNamespace(
            whole=None, gradients=True, around=contextlib.nullcontext)
        x = reference.embed(params, ids)

        def got_layer(lp, x, index):
            out, state = reference.mixer_part(lp, x, sizes, index, operand_dtype)
            h = x + out
            y, aux, z = reference.ffn_part(lp, h, sizes, operand_dtype)
            state = jnp.float32(0) if state is None else state
            if "moe" not in lp:
                return y, out, state, aux, z, jnp.ones((1, 1)), jnp.ones((1, 1))
            m = reference.norm(h, lp["ln2"], sizes["norm_eps"])
            return (y, out, state, aux, z, reference.router_logits(lp, h, sizes),
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
        if "moe" in lp:
            # the router's arithmetic alone: the reference's product on what
            # the program's router read
            with jax.default_matmul_precision("highest"):
                same_input = f32(m) @ f32(lp["moe"]["gate"])
            router_rms = rel_rms(logits, same_input)
            margin = reference.router_margin(lp, h, sizes)
        else:  # a dense layer routes nothing: every position is decided
            router_rms = jnp.float32(0)
            margin = jnp.full((x.shape[0] * x.shape[1],), jnp.inf)
        return (got.astype(x.dtype), position_sums(got, want), margin,
                router_rms, rel_rms(out, want_out),
                jnp.float32(0) if want_state is None else rel_rms(state, want_state),
                (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, then the layers: one compiled program a KIND of layer
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, ids)), slice(None))]
    near_tie, logits_rms = [], []
    delta_rms, state_rms, attention_rms = [], [], []
    first_of = {}
    for index, lp in enumerate(params["layers"]):
        first_of.setdefault((reference.kind(sizes, index), "moe" in lp), index)
    with program.around():  # traced here; compiled at once
        lowered = {which: (jax.jit(
            lambda lp, x, index=index: one_layer(lp, x, index)).lower(
                params["layers"][index], x),)
            for which, index in first_of.items()}
    compiled = _compiled_together(lowered)
    backward = None
    if program.gradients:
        # the backward's programs stay out of the persistent cache: written
        # to it they pushed the step's and the set-up's out of the machine's
        # 192 MiB, and EVERY run compiled everything (76 of 79 programs
        # missed, 605-644 s a run over six runs: my chip runs, PR 66).  They
        # compile from HERE, on threads, while the device compares forward
        keep_out.enter_context(_beside(
            "train_recipe_qwen3next.py")._kept_out_of_the_compile_cache())
        backward = compare_gradients(
            program, model, params, reference, config, sizes, ids, targets,
            got_layer, got_logits, None, None, x, operand_dtype)
    got_aux = got_z = aux = z = 0.0
    streams, decided_at = [], []  # what each layer read; where it is compared
    for index, lp in enumerate(params["layers"]):
        which = (reference.kind(sizes, index), "moe" in lp)
        streams.append(x)
        (x, sums, margin, router_rms, mixer_rms, last_rms, got_side,
         want_side) = compiled[which][0](lp, x)
        decided = np.asarray(margin) >= MARGIN
        decided_at.append(decided)
        near_tie.append(1.0 - float(decided.mean()))
        logits_rms.append(float(router_rms))
        layers_rms.append(decided_rms(sums, decided))
        if which[0] == "kda":
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
        del whole, layered
    else:
        got_loss = reference.total_loss(got_ce / s, got_aux, got_z, n_sparse, sizes)
        hidden_median = 0.0
    scale = math.sqrt(want_sq.sum() / elements)
    gradients = dict.fromkeys(GRADIENT_READINGS, 0.0)
    if program.gradients:
        gradients = compare_gradients(
            program, model, params, reference, config, sizes, ids, targets,
            got_layer, got_logits, streams, decided_at, x, operand_dtype,
            compiled=backward)
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
        "delta_layers_rms": delta_rms,
        "delta_states_rms": state_rms,
        "attention_layers_rms": attention_rms,
    }


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    from learning_at_home_tpu.models.transformer import DMoETransformerLM

    manifest = harness.load_manifest(args.manifest)
    share = harness.load_module(manifest, "runners", "train_recipe_share")
    xing4 = _beside("train_recipe_xing4.py")
    lfm2 = xing4._beside("train_recipe_lfm2.py")  # whose _MADE_STEPS it reads
    make = DMoETransformerLM.make_train_step

    def remembered(self, optimizer, *args, **kwargs):
        """The program's own method; the comparison finds the step again."""
        step = make(self, optimizer, *args, **kwargs)
        lfm2._MADE_STEPS.append((self, optimizer, step))
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
    xing4._CALLERS_PARAMS_ARE_DONE_WITH = True  # share.run reads their shapes alone
    try:
        return share.run(cell, config, traffic, args, clock)
    finally:
        xing4._CALLERS_PARAMS_ARE_DONE_WITH = False
        DMoETransformerLM.make_train_step = make
        lfm2._MADE_STEPS.clear()
