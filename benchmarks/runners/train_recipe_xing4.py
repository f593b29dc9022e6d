"""Runner ``train_recipe_xing4``: ``train_recipe_share``'s run for a share
whose residual stream is FOUR streams a token mixed by hyper-connections
round every part, whose attention is expanded from latents at heads of 192
over values of 128 under YaRN, and whose stack is followed by a block that
predicts the next-but-one token (``xing4.0-29b-a4b``).

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is, so the set-up (the
levelling call included), the warm-up, the window, the share's checks on
every step, the Zipf generator and the printed lines are that file's own
code, not a copy (``train_recipe_latent`` and ``train_recipe_lfm2`` do the
same; the first's scope table with the prediction block and the second's
way of finding the timed step again are used from there as they are).  The
names its ``run`` looks up in its module are replaced, in this process's
private copy of it, with what this file defines:

- ``CFG_FIELDS`` / ``_check_sizes``: the configuration file restates the
  sizes under the ``xing4_0`` key names (GLM-4.7-Flash's, with the
  hyper-connections' and YaRN's); the head's size is ``qk_nope_head_dim +
  qk_rope_head_dim`` and the values' ``v_head_dim``; ``dense_layers_run``
  of the layers run are dense; the program's parameter count is the file's
  ``parameters``.
- ``share_problems``: the share's, and in every step of the window
  ``hc_res_marginal_error`` (the largest |row or column sum - 1| of any
  part's mixing matrix) under ``HC_RES_MARGINAL_ERROR_MAX`` and
  ``hc_stream_rms_spread`` (the largest over the smallest rms of the
  streams entering a final sum) under ``HC_STREAM_RMS_SPREAD_MAX``.
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``: a layer at a
  time ON THE PROGRAM'S OWN STREAMS, all four of them, the layer composed
  of the program's two parts (``hidden_token_median`` holds ``_hidden``
  whole to them); each part's 24 coefficients a token against the
  reference's on the very streams the program read; the prediction block's
  combine and its layer; BOTH heads' logits a block of positions at a time
  and both losses; then the BACKWARD pass and the update
  (:func:`compare_gradients`).  ``WRONG_PROGRAMS`` names programs that must
  fall outside (``tools/smallthinker_probe.py float8`` runs them on the
  chip).
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

# the file's key (xing4_0's config.json, then this repo's) -> the program's
# config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "q_lora_rank": "q_latent_dim", "kv_lora_rank": "kv_latent_dim",
    "qk_rope_head_dim": "rope_head_dim", "v_head_dim": "v_head_dim",
    "seq_len": "seq_len", "n_routed_experts_published": "num_experts",
    "n_routed_experts": "held_experts", "first_held_expert": "first_held_expert",
    "num_experts_per_tok": "k", "moe_intermediate_size": "expert_ffn_dim",
    "intermediate_size": "dense_ffn_dim", "n_shared_experts": "shared_experts",
    "norm_topk_prob": "renormalize", "router_score": "router_score",
    "routed_scaling_factor": "routed_scale", "router_bias": "router_bias",
    "router_bias_rate": "router_bias_rate", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "num_nextn_predict_layers": "mtp_layers",
    "mtp_loss_weight": "mtp_loss_weight",
    "hc_mult": "hc_streams", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps",
    "norm": "norm", "positions": "positions", "expert_kind": "expert_kind",
    "routing": "routing", "router_input": "router_input",
    "aux_loss_weight": "aux_loss_weight", "router_z_weight": "router_z_weight",
    "remat": "remat", "scan_layers": "scan_layers",
    "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 64): the largest the program gave over its seeds,
# and a second reading that must fall outside: the reference itself with
# every matmul operand rounded to float8_e4m3 (the nearest precision below
# the configuration's bf16) run through this same comparison in the
# program's place, or, where the precision does not move a reading (the
# hyper-connections are float32 on both sides), the named wrong program
# that does.  ``layers_rms`` is over the four streams together at the
# decided positions, ``stream_rms`` the worst ONE stream of any layer,
# ``part_rms`` the streams between a layer's two parts; ``hc_coeff_rms`` a
# part's 24 coefficients a token against the reference's ON THE VERY
# STREAMS the program read, the worst part (the arithmetic of the
# coefficients alone: bf16 products summed in float32 | one iteration, a
# static matrix, a bf16 Sinkhorn).  ``mtp_*`` are the prediction block's
# head.  ``loss`` (the weighted sum of both cross-entropies, relative) is
# read and printed and has NO limit here: the accepted cells' 3e-4 left the
# first seven seeds' largest reading (6.1e-5) five times of room, and the
# fourteenth seed read 2.05e-4 (a mean over 16,384 positions that near-tied
# routers move by whole tokens: 2e-6 to 7e-5 on the other thirteen), which
# leaves it one and a half against a float8 reference's 5.5e-4: no limit
# lies between with room on both sides, and nine other limits refuse the
# float8 reference (PERF.md sections 2 and 7, PR 64); tier-1 holds both
# losses to the reference's at float32 (``tests/test_xing4.py``).
# ``hidden_token_median`` has no second precision (both sides are the
# program).  ``near_tie_share`` guards the comparison itself.  The backward
# pass and the update (:func:`compare_gradients`) as ``train_recipe_lfm2``
# holds them.
TOLERANCES = {"layers_rms": 2e-2, "stream_rms": 2.5e-2, "part_rms": 2e-2,
              "hc_coeff_rms": 1.2e-3,
              "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2, "mtp_logits_rms": 1e-2,
              "mtp_logits_p999": 3e-2, "mtp_logits_token_median": 1e-2,
              "hidden_token_median": 5e-2, "near_tie_share": 0.25,
              "grads_rms": 1e-1, "grad_stream_rms": 3e-2,
              "step_grad_norms": 2e-1, "update_norm": 2e-1}
# train_recipe_latent's margin, for its reason
MARGIN = 2.0 ** -9
# train_recipe_latent's, for its reason (global attention in every layer
# under seeded weights: a Zipf row's commonest id goes to its four experts
# whole)
LOAD_MAX_OVER_MEAN = 6.0
# 20 Sinkhorn iterations leave a seeded matrix within 1e-5 of doubly
# stochastic but for the widest of a step's 200,000 (16,384 tokens, twelve
# parts): the largest a step reads on the chip is 2.5e-5 to 4.6e-4 (my chip
# runs, PR 64); ONE iteration reads 3e-2 and a bf16 Sinkhorn 8e-3
HC_RES_MARGINAL_ERROR_MAX = 3e-3
# the streams entering a final sum have rms within a few tens of percent of
# each other (1.1 to 1.6): a write that feeds one stream alone, or a
# mixing matrix that does not mix, reads several
HC_STREAM_RMS_SPREAD_MAX = 4.0
# programs that must fall outside the limits, by name: what
# ``compare_with_reference(.., wrong=name)`` puts in the program's place
WRONG_PROGRAMS = {
    "the program with ONE Sinkhorn iteration": {"wrong": "one_iteration"},
    "the program with H_res the identity": {"wrong": "identity_res"},
    "the program with alpha = 0 (static coefficients)": {"wrong": "static"},
    "the program with H_post without its 2": {"wrong": "post_1"},
    "the program with coefficients from the un-normalised stream": {
        "wrong": "unnormalised"},
    "the program with plain rotary, no YaRN": {"wrong": "plain_rotary"},
    "the program with the softmax scale without mscale^2": {
        "wrong": "plain_scale"},
    "the program with its Sinkhorn in bfloat16": {"wrong": "bf16_sinkhorn"},
}
# a layer's leaves that its feed-forward part reads; the others are its
# attention part's
FFN_LEAVES = ("ln2", "hc_ffn", "ffn", "moe", "shared")
MTP = re.compile(r"[/(]mtp[/)]")  # train_recipe_latent's pattern, whose
# table ``run`` uses: ``tools/scope_tree.py`` files ``mtp`` last where a
# runner has the name
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "local_rows_over_level", "router_bias_abs_max", "ce_mtp",
                 "hc_res_marginal_error", "hc_stream_rms_spread")
EXTRA_SCOPES = ("hc/coeff", "hc/sinkhorn", "hc/pre", "hc/post", "hc",
                "shared_expert", "dense_ffn", "router_bias",
                "latent_down", "latent_up", "rope")
GRADIENT_READINGS = ("grads_rms", "grad_stream_rms", "step_grad_norms",
                     "update_norm")
# The timed step donates its parameters, and at this configuration's size
# the chip holds them once beside the step (4.5 GB and 10.5).  Where the
# caller reads nothing of its parameters after the comparison but their
# shapes (``train_recipe_share.run``: :func:`run` says so here), the
# caller's OWN arrays are handed to the step and a host copy is put back
# for the rest of the comparison.  Elsewhere (the tests, ``tools/
# smallthinker_probe.py float8``: they compare again on the same arrays) a
# device copy is handed over where the parameters are small enough for one,
# and where they are not the step's two readings are left out (0.0, and
# ``step_read`` false): every run of the cell reads them.
_CALLERS_PARAMS_ARE_DONE_WITH = False
COPY_FITS_BYTES = 2 << 30


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
    got["num_key_value_heads"] = cfg.n_kv_heads or cfg.n_heads
    got["qk_nope_head_dim"] = cfg.head_dim - cfg.rope_head_dim
    got["mhc_h_res_clamp_min"], got["mhc_h_res_clamp_max"] = cfg.hc_res_clamp
    got["rope_scaling"] = dict(
        dataclasses.asdict(cfg.rope_scaling), type="yarn")
    pattern = cfg.ffn_pattern or ("moe",) * cfg.n_layers
    dense = config["dense_layers_run"]
    got["dense_layers_run"] = (
        dense if pattern == ("dense",) * dense + ("moe",) * (cfg.n_layers - dense)
        else pattern)
    got["layer_pattern"] = cfg.layer_pattern  # every layer global and rotated
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    got["parameters"] = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(DMoETransformerLM(cfg, mesh).init_params,
                           jax.random.PRNGKey(0))))
    want = dict(config, layer_pattern=None)
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
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        experts_per_token=config["num_experts_per_tok"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        first_k_dense_replace=config["dense_layers_run"],  # of the layers RUN
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        held=(config["first_held_expert"], config["n_routed_experts"]),
        hc_mult=config["hc_mult"], hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]),
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
        mtp_loss_weight=config["mtp_loss_weight"],
    )


def hc_problems(counters: dict) -> list:
    """What the residual streams must keep to in every step of the window."""
    problems = []
    error = counters.get("hc_res_marginal_error", [math.inf])
    if not max(error) < HC_RES_MARGINAL_ERROR_MAX:  # a nan fails too
        problems.append(
            f"hc_res_marginal_error up to {max(error):.3e}, not under "
            f"{HC_RES_MARGINAL_ERROR_MAX}")
    spread = counters.get("hc_stream_rms_spread", [math.inf])
    if not max(spread) < HC_STREAM_RMS_SPREAD_MAX:
        problems.append(
            f"hc_stream_rms_spread up to {max(spread):.3f}, not under "
            f"{HC_STREAM_RMS_SPREAD_MAX}")
    return problems


# ---- programs that must fall outside ---------------------------------------


def _wrong_coefficients(wrong: str):
    """What a wrong program runs in ``trunk.hc_coefficients``'s place."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.models import trunk

    right = trunk.hc_coefficients

    def rewritten(p, x, iters, eps, clamp, norm_eps):
        """The program's function restated with the named fault."""
        n, f32 = x.shape[2], jnp.float32
        x32 = x.astype(f32)
        m = jnp.einsum("bsnc,nco->obs", x,
                       p["phi"].astype(x.dtype).reshape(n, x.shape[3], -1),
                       preferred_element_type=f32)
        if wrong != "unnormalised":
            m = m * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=(2, 3)) + norm_eps)
        alpha, b = p["alpha"].astype(f32), p["b"].astype(f32)[:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
        start = jnp.exp(jnp.clip(
            (alpha[2] * m[2 * n:] + b[2 * n:]).reshape(n, n, *m.shape[1:]),
            *clamp))
        if wrong == "bf16_sinkhorn":
            # every quotient and every sum rounded to bf16.  reduce_precision:
            # the rounding is the result (the TPU compiler computes a chain of
            # bf16 operations in float32 and rounds it once: PERF.md section
            # 6, PR 61)
            def bf16(a):
                return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

            res = bf16(start)
            for _ in range(iters):
                res = bf16(res / bf16(functools.reduce(
                    lambda a, b: bf16(a + b), list(res)) + eps)[None])
                res = bf16(res / bf16(functools.reduce(
                    lambda a, b: bf16(a + b),
                    [res[:, j] for j in range(n)]) + eps)[:, None])
        else:
            res = trunk.sinkhorn(start, iters, eps)
        error = jnp.maximum(jnp.max(jnp.abs(res.sum(axis=0) - 1.0)),
                            jnp.max(jnp.abs(res.sum(axis=1) - 1.0)))
        return pre, post, res, error

    def patched(p, x, iters, eps, clamp, norm_eps):
        pre, post, res, error = right(p, x, iters, eps, clamp, norm_eps)
        if wrong == "identity_res":
            res = jnp.broadcast_to(
                jnp.eye(res.shape[0])[:, :, None, None], res.shape)
        if wrong == "post_1":
            post = 0.5 * post
        return pre, post, res, error

    return rewritten if wrong in ("unnormalised", "bf16_sinkhorn") else patched


@contextlib.contextmanager
def _coefficients_replaced(wrong: str):
    """While tracing inside, the model's parts take their coefficients from
    the wrong function."""
    from learning_at_home_tpu.models import transformer

    right = transformer.hc_coefficients
    transformer.hc_coefficients = _wrong_coefficients(wrong)
    try:
        yield
    finally:
        transformer.hc_coefficients = right


def _wrong_program(model, wrong: str | None):
    """What stands in the program's place: ``pieces`` the model whose parts
    are compared, ``tracing`` a context in which they are traced,
    ``params_of`` what its parameters are made of the run's, ``whole`` the
    model whose ``_hidden``, ``loss_fn`` and train step are held to those
    pieces (None where the pieces are the wrong ones) and ``gradients``:
    whether the backward pass is compared."""
    program = types.SimpleNamespace(
        pieces=model, tracing=contextlib.nullcontext,
        params_of=lambda params: params, whole=model, gradients=wrong is None)
    if wrong is None:
        return program

    def twin(**replace):
        # a model of its own: a mixture's traced body is cached by the
        # instance it is bound to, and the program's own has been traced
        return type(model)(dataclasses.replace(model.cfg, **replace), model.mesh)

    program.whole = None
    if wrong == "one_iteration":
        program.pieces = twin(hc_sinkhorn_iters=1)
    elif wrong in ("identity_res", "post_1", "unnormalised", "bf16_sinkhorn"):
        program.pieces = twin()
        program.tracing = lambda: _coefficients_replaced(wrong)
    elif wrong == "static":
        import jax

        program.pieces = twin()
        program.params_of = lambda params: jax.tree_util.tree_map_with_path(
            lambda path, a: a * 0 if jax.tree_util.keystr(path).endswith(
                "['alpha']") else a, params)
    elif wrong == "plain_rotary":
        program.pieces = twin(rope_scaling=None)
        program.pieces._attn_scale = model._attn_scale  # the scale stays
    elif wrong == "plain_scale":
        program.pieces = twin()
        program.pieces._attn_scale = None
    else:
        raise BenchError(f"no wrong program {wrong!r}")
    return program


# ---- the timed step, once, from an empty optimizer state -------------------


def one_timed_step(whole, model, params, config, ids, targets):
    """``(a leaf's name -> [its gradient's sum of squares as the step's
    second moments give it, the sum of squares of its change], the
    parameters to go on with)`` of ONE call of the timed train step of
    ``whole`` from an empty optimizer state; ``(None, params)`` where the
    step's parameters can neither be the caller's nor a copy (the note at
    ``_CALLERS_PARAMS_ARE_DONE_WITH``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.parallel.mesh import batch_sharding

    size = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
    if not _CALLERS_PARAMS_ARE_DONE_WITH and size > COPY_FITS_BYTES:
        return None, params
    optimizer, step = _beside("train_recipe_lfm2.py")._timed_step(whole, config)
    placed = batch_sharding(model.mesh)  # as the window's batches are
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, params)
    if _CALLERS_PARAMS_ARE_DONE_WITH:
        old, handed = jax.device_get(params), params
    else:
        old, handed = params, jax.jit(
            lambda tree: jax.tree_util.tree_map(jnp.copy, tree))(params)
    del params
    new, opt_state, _, _ = step(
        handed, model.init_opt_state(optimizer, handed),
        jax.device_put(ids, placed), jax.device_put(targets, placed))
    del handed
    if not hasattr(opt_state, "v_row"):
        raise BenchError("the step's gradients are read off Adafactor's "
                         f"second moments; the state is {type(opt_state)}")
    old = jax.device_put(old, shardings)

    @jax.jit
    def read_step(new, old, v_row, v):
        rows = []
        for after, p, by_row, whole_v in zip(
                jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old),
                jax.tree_util.tree_leaves(v_row), jax.tree_util.tree_leaves(v)):
            moments = whole_v if whole_v.shape == p.shape else by_row
            change = after.astype(jnp.float32) - p.astype(jnp.float32)
            rows.append(jnp.stack([
                jnp.mean(moments.astype(jnp.float32)) * p.size,
                jnp.sum(change * change)]))
        return jnp.stack(rows)

    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(old)[0]]
    return dict(zip(names, np.asarray(read_step(
        new, old, opt_state.v_row, opt_state.v), np.float64))), old


# ---- the comparison ---------------------------------------------------------


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None, wrong=None) -> dict:
    """:func:`_compare` behind ONE call of the timed step
    (:func:`one_timed_step`: first, while nothing of the comparison is on
    the device beside it), its programs kept out of the persistent compile
    cache (``train_recipe_qwen3next._kept_out_of_the_compile_cache``: they
    run once a run and are large, and in the chip's capped cache they
    pushed out the step's and the initialisation's, so that every run
    compiled everything again: ``setup_s`` 347 s; my chip runs, PR 64)."""
    stepped = None
    if operand_dtype is None and wrong is None:
        stepped, params = one_timed_step(
            model, model, params, config, ids, targets)
    with _beside("train_recipe_qwen3next.py")._kept_out_of_the_compile_cache():
        return _compare(model, params, reference, config, ids, targets,
                        operand_dtype, wrong, stepped)


def _at_little_effort(program):
    """``program`` (a jitted function of the REFERENCE's alone) compiled
    and called at the compiler's least effort on run time
    (``jax_exec_time_optimization_effort`` -1): the comparison's programs
    are compiled in every run, and the reference's part-at-a-time backward
    programs compile in a twelfth of the time so (15.9, 13.3 and 8.5 s
    against 1.1, 0.5 and 0.7; my chip runs, PR 64).  Never a program that
    holds the PROGRAM's pieces: those are compiled at the effort the timed
    step is compiled at (at the least effort a layer's streams read 1.11
    to 1.17 % of the reference's where they read 1.19 to 1.28 % so: the
    compiler rounds the bf16 pieces elsewhere)."""
    import jax

    name = "jax_exec_time_optimization_effort"

    def called(*args):
        was = getattr(jax.config, name)
        jax.config.update(name, -1.0)
        try:
            return program(*args)
        finally:
            jax.config.update(name, was)

    return called


def _compare(model, params, reference, config, ids, targets, operand_dtype,
             wrong, stepped) -> dict:
    """The program against the reference on ``ids`` [1, S] and their
    ``targets`` (each position's next id), a layer at a time ON THE
    PROGRAM'S OWN STREAMS, the prediction block as one more layer, and both
    heads' logits a block of positions at a time.  With ``operand_dtype``
    the REFERENCE at that precision takes the program's place (what a
    too-low precision would read); with ``wrong`` one of
    ``WRONG_PROGRAMS`` does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.models import trunk

    blocks = _beside("train_recipe_blocks.py")
    sizes = reference_sizes(config)
    mp = params["mtp"]
    n_layers = len(params["layers"])
    heads = {"": {"ln_f": params["ln_f"], "lm_head": params["lm_head"]},
             "mtp_": reference.mtp_head_params(params)}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_sparse = reference.sparse_layers(params, sizes)

    def f32(a):
        return a.astype(jnp.float32)

    def coefficients_of(triple):
        """[B, S, 24]: a part's read, write and mixing coefficients a token
        in the reference's order."""
        pre, post, res = triple
        return jnp.concatenate(
            [pre, post, res.reshape(*res.shape[:2], -1)], axis=-1)

    if operand_dtype is None:
        program = _wrong_program(model, wrong)
        pieces, cfg = program.pieces, program.pieces.cfg
        own = program.params_of(params)  # what the pieces run on
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def copied(x):
            return pieces._hc_copy(x)

        def got_coefficients(hp, streams):
            """[B, S, 24]: what a part of the program's reads, writes and
            mixes by on ``streams``, in the reference's order."""
            from learning_at_home_tpu.models import transformer

            pre, post, res, _ = transformer.hc_coefficients(
                hp, streams, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                cfg.hc_res_clamp, cfg.norm_eps)
            return coefficients_of((
                jnp.moveaxis(pre, 0, -1), jnp.moveaxis(post, 0, -1),
                jnp.moveaxis(res, (0, 1), (-2, -1))))

        def got_attention(lp, x, index):
            """The streams after the layer's attention part (``lp`` the
            layer, or its attention's leaves alone)."""
            return pieces._attention_part(lp, x, cfg.attention_layer(index))[0]

        def got_ffn(lp, mid, index):
            """``(the streams after the layer's feed-forward part, aux, z)``
            (``lp`` the layer, or that part's leaves alone: the router reads
            the experts' input here, so the attention's input is not
            handed over)."""
            out, aux = pieces._ffn_block(lp, mid, None, index)
            return out, aux.get("aux_loss", 0.0), aux.get("router_z_loss", 0.0)

        def got_scores(lp, mid):
            """The program's router scores plus bias, on its own streams."""
            h = pieces._hc_read(lp["hc_ffn"], mid)[1]
            m = pieces._norm(lp["ln2"], h).reshape(-1, h.shape[-1])
            return jax.nn.sigmoid(
                pieces.moe.router_logits(lp["moe"], m)) + lp["moe"]["router_bias"]

        def got_final(ln_f, x):
            return pieces._norm(ln_f, pieces._hc_sum(x)[0])

        def got_combine(mp, table, hf, next_ids):
            return pieces._mtp_input(mp, hf, next_ids, table)

        def got_logits(head_params, x):
            """On the streams ``x`` [B, n, 4, d] a block of positions."""
            return pieces._logits(
                pieces._norm(head_params["ln_f"], pieces._hc_sum(x)[0]),
                pieces._head(head_params))
    else:
        # the reference in the program's place is float32 on BOTH sides of
        # the backward comparison: it is read where the parameters are small
        # enough for that (the tests, the rehearsal), and forward alone at
        # the cell's size, where a layer's float32 streams, leaves and
        # gradients twice over do not fit beside 4.5 GB of parameters
        size = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))
        program = types.SimpleNamespace(
            whole=None, gradients=size <= COPY_FITS_BYTES,
            tracing=contextlib.nullcontext)
        own = params
        x = reference.embed(params, ids)
        got_scores = None

        def copied(x):
            return reference.copy_in(x, sizes)

        def got_attention(lp, x, index):
            return reference.attention_part(lp, x, sizes, index, operand_dtype)

        def got_ffn(lp, mid, index):
            return reference.ffn_part(lp, mid, sizes, index, operand_dtype)

        def got_coefficients(hp, streams):
            return coefficients_of(reference.hc_coefficients(hp, streams, sizes))

        def got_final(ln_f, x):
            return reference.final_norm(
                {"ln_f": ln_f}, reference.sum_out(x), sizes)

        def got_combine(mp, table, hf, next_ids):
            return reference.mtp_input(mp, table, hf, next_ids, sizes,
                                       operand_dtype)

        def got_logits(head_params, x):
            return reference.head(head_params, reference.sum_out(x), sizes,
                                  operand_dtype)

    def position_sums(got, want):
        """Sums of squares a position [S] (over the streams too): of the
        difference, of the reference."""
        diff = (f32(got) - want).reshape(want.shape[1], -1)
        return (jnp.sum(diff * diff, axis=-1),
                jnp.sum(jnp.square(want.reshape(want.shape[1], -1)), axis=-1))

    def stream_sums(got, want):
        """The same a stream: [S, n]."""
        diff = f32(got) - want
        return (jnp.sum(diff * diff, axis=-1)[0], jnp.sum(want * want, axis=-1)[0])

    def rel_rms(got, want):
        diff = f32(got) - want
        return jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(want * want))

    def attention_both(lp, own_lp, x, index):
        """A layer's attention part on the streams ``x``, ``lp`` its leaves
        (a layer without ``FFN_LEAVES``): ``(the program's streams after it,
        the reference's, the sums a position of their difference, the part's
        coefficients' rms against the reference's on those very streams)``."""
        with program.tracing():
            mid = got_attention(own_lp, x, index)
            coeff = got_coefficients(own_lp["hc_attn"], x)
        entering = f32(x)
        want_mid = reference.attention_part(lp, entering, sizes, index)
        return (mid, want_mid, position_sums(mid, want_mid),
                rel_rms(coeff, coefficients_of(reference.hc_coefficients(
                    lp["hc_attn"], entering, sizes))))

    def ffn_both(lp, own_lp, mid, want_mid, index):
        """The feed-forward part after it, ``lp`` the layer's
        ``FFN_LEAVES``: the program's on ITS streams ``mid``, the
        reference's on its own ``want_mid``."""
        with program.tracing():
            got, got_aux, got_z = got_ffn(own_lp, mid, index)
            coeff = got_coefficients(own_lp["hc_ffn"], mid)
        want, aux, z = reference.ffn_part(lp, want_mid, sizes, index)
        # the coefficients' ARITHMETIC alone: the reference's on the very
        # streams the program's part read
        coeff_rms = rel_rms(coeff, coefficients_of(reference.hc_coefficients(
            lp["hc_ffn"], f32(mid), sizes)))
        if "moe" in lp:
            margin = reference.router_margin(lp, want_mid, sizes)
            scores_sq = jnp.float32(0) if got_scores is None else jnp.mean(
                (got_scores(own_lp, mid) - reference.router_scores(
                    lp, want_mid, sizes)) ** 2)
        else:  # a dense layer routes nothing: every position is decided
            margin = jnp.full((mid.shape[0] * mid.shape[1],), jnp.inf)
            scores_sq = jnp.float32(0)
        return (got, (position_sums(got, want), stream_sums(got, want)),
                margin, scores_sq, coeff_rms, (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, the layers, the block's combine, the block's layer: one
    # compiled pair a KIND of layer (dense, mixture).  A layer is handed the
    # four streams here, the stack's first and the block's too (the program
    # hands those ONE stream and copies it inside the layer's checkpoint:
    # ``hidden_token_median`` holds that path to this one)
    embed_sums = jax.jit(lambda x, w: position_sums(x[:, :, None], w[:, :, None]))(
        x, reference.embed(params, ids))
    layers_rms = [decided_rms(embed_sums, slice(None))]
    stream_rms, part_rms, coeff_rms = [], [], []
    near_tie, score_rms = [], []
    compiled = {}
    sums_of = {"got": [0.0, 0.0], "want": [0.0, 0.0]}  # aux, z
    streams, decided_at = [], []  # what each layer read; where it is compared

    def attention_leaves(lp):
        return {k: v for k, v in lp.items() if k not in FFN_LEAVES}

    def ffn_leaves(lp):
        return {k: v for k, v in lp.items() if k in FFN_LEAVES}

    # every layer is global and rotated: ONE program for the attention part
    # (the backward comparison makes both sides' streams between the parts
    # again with it), and one a kind for the feed-forward part
    both_mids = jax.jit(
        lambda lp, own_lp, x: attention_both(lp, own_lp, x, 0))

    def run_layer(lp, own_lp, x, index):
        kind = reference.is_dense(sizes, index)
        if kind not in compiled:
            compiled[kind] = jax.jit(
                lambda lp, own_lp, mid, want_mid, index=index: ffn_both(
                    lp, own_lp, mid, want_mid, index))
        if program.gradients:  # on the host until the backward wants it
            streams.append(jax.device_get(x))
        mid, want_mid, mid_sums, c_attention = both_mids(
            attention_leaves(lp), attention_leaves(own_lp), x)
        del x
        y, sums, margin, scores_sq, c_ffn, got_side, want_side = compiled[kind](
            ffn_leaves(lp), ffn_leaves(own_lp), mid, want_mid)
        del mid, want_mid
        decided = np.asarray(margin) >= MARGIN
        decided_at.append(decided)
        near_tie.append(1.0 - float(decided.mean()))
        score_rms.append(math.sqrt(float(scores_sq)))
        layers_rms.append(decided_rms(sums[0], decided))
        d2, w2 = (np.asarray(a, np.float64) for a in sums[1])
        stream_rms.append(float(np.max(np.sqrt(
            d2[decided].sum(axis=0) / w2[decided].sum(axis=0)))))
        part_rms.append(decided_rms(mid_sums, slice(None)))
        coeff_rms.append(max(float(c_attention), float(c_ffn)))
        for side, pair in (("got", got_side), ("want", want_side)):
            sums_of[side] = [a + float(b) for a, b in zip(sums_of[side], pair)]
        return y

    x = jax.jit(copied)(x)
    for index, lp in enumerate(params["layers"]):
        x = run_layer(lp, own["layers"][index], x, index)

    @jax.jit
    def combine(mp, ln_f, table, x, next_ids):
        hf = got_final(ln_f, x)
        got = got_combine(mp, table, hf, next_ids)
        want = reference.mtp_input(mp, table, f32(hf), next_ids, sizes)
        return got, position_sums(got[:, :, None], want[:, :, None])

    inner = {k: v for k, v in mp.items() if k != "layer"}
    z_in, sums = combine(inner, params["ln_f"], params["embed"], x, targets)
    layers_rms.append(decided_rms(sums, slice(None)))
    z_in = jax.jit(copied)(z_in)
    z_out = run_layer(mp["layer"], own["mtp"]["layer"], z_in, n_layers)

    @jax.jit
    def block_sums(head_params, x, tgt):
        want = reference.head(head_params, reference.sum_out(f32(x)), sizes)
        got = f32(got_logits(head_params, x))
        diff = jnp.abs(got - want)
        above = jax.lax.map(lambda edge: jnp.sum(diff > edge), edges)
        return ((jnp.sum(diff * diff, axis=-1).ravel(),
                 jnp.sum(want * want, axis=-1).ravel()), above,
                reference.ce_sum_of_logits(want, tgt),
                reference.ce_sum_of_logits(got, tgt))

    s = ids.shape[1]
    block = min(blocks.LOGIT_BLOCK, s)
    if s % block:
        raise BenchError(f"seq_len {s} is no multiple of {block}")
    elements = s * config["vocab_size"]
    read, ce = {}, {}
    # each head on the program's streams, against its own targets (the
    # block's: the row's shifted by one, none at the last position)
    for name, stream, tgt in (("", x, targets),
                              ("mtp_", z_out, reference.after_next(targets))):
        want_ce = got_ce = 0.0
        diff_sq, want_sq = [], []  # a position, float64
        above = [0] * len(blocks.EDGES)
        for start in range(0, s, block):
            part = slice(start, start + block)
            (d2, w2), counts, wce, gce = block_sums(
                heads[name], stream[:, part], tgt[:, part])
            diff_sq.append(np.asarray(d2, np.float64))
            want_sq.append(np.asarray(w2, np.float64))
            want_ce, got_ce = want_ce + float(wce), got_ce + float(gce)
            above = [a + int(c) for a, c in zip(above, counts)]
        diff_sq, want_sq = np.concatenate(diff_sq), np.concatenate(want_sq)
        scale = math.sqrt(want_sq.sum() / elements)
        positions = s if name == "" else s - 1
        ce[name] = (want_ce / positions, got_ce / positions)
        read.update({
            name + "logits_rms": math.sqrt(diff_sq.sum() / elements) / scale,
            name + "logits_p999": blocks.quantile_from_counts(
                above, elements, 0.999) / scale,
            name + "logits_token_median": float(
                np.median(np.sqrt(diff_sq / want_sq))),
            "reference_" + name + "logits_rms": scale,
        })

    def total(side: int, aux_z) -> float:
        return float(reference.total_loss(
            ce[""][side], *aux_z, n_sparse, sizes, ce["mtp_"][side]))

    want_loss = total(0, sums_of["want"])
    hidden_median = 0.0
    if operand_dtype is None and program.whole is not None:
        # the program WHOLE, as loss_fn composes it: both cross-entropies,
        # and both final streams as its own call of ``_hidden`` gave them
        # (taken from inside it: a second call of ``_hidden`` beside it is
        # the stack traced and compiled twice, in every run)
        def whole(p, i, t):
            timed, finals = program.whole, []

            def heard(*args, **kwargs):
                finals.append(type(timed)._hidden(timed, *args, **kwargs))
                return finals[-1]

            timed._hidden = heard  # the instance's, while loss_fn is traced
            try:
                loss, metrics = timed.loss_fn(p, i, t)
            finally:
                del timed._hidden
            return loss, metrics["ce_mtp"], finals[0][:2]

        got_loss, got_ce_mtp, finals = jax.jit(whole)(params, ids, targets)
        got_loss, got_ce_mtp = float(got_loss), float(got_ce_mtp)
        layered = jax.jit(lambda ln_f, x, out, z: (
            f32(got_final(ln_f, x)), f32(got_final(out, z))))(
                params["ln_f"], x, mp["out_norm"], z_out)
        for got_stream, want_stream in zip(finals, layered):
            h2, l2 = jax.jit(lambda g, w: position_sums(
                g[:, :, None], w[:, :, None]))(got_stream, want_stream)
            hidden_median = max(hidden_median, float(np.median(np.sqrt(
                np.asarray(h2, np.float64) / np.asarray(l2, np.float64)))))
    else:
        got_loss, got_ce_mtp = total(1, sums_of["got"]), ce["mtp_"][1]
    gradients = dict.fromkeys(GRADIENT_READINGS, 0.0)
    if program.gradients:
        finals = {"x": x, "z_out": z_out}  # handed over, not held here too
        del x, z_in, z_out
        gradients = compare_gradients(
            program, model, params, reference, config, sizes, ids, targets,
            got_attention, got_ffn, got_final, got_combine, got_logits,
            both_mids, streams, decided_at, finals, stepped)
    return {
        **gradients,
        "step_read": stepped is not None,
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        "stream_rms": float(np.max(stream_rms)),
        "part_rms": float(np.max(part_rms)),
        "hc_coeff_rms": float(np.max(coeff_rms)),
        **read,
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "ce_mtp": abs(got_ce_mtp - ce["mtp_"][0]) / abs(ce["mtp_"][0]),
        "hidden_token_median": hidden_median,
        "near_tie_share": max(near_tie),
        "reference_loss": want_loss,
        "reference_ce_mtp": ce["mtp_"][0],
        # the embedding, the stack's layers, the block's combine, its layer
        "embed_and_layers_rms": layers_rms,
        "stream_layers_rms": stream_rms,
        "part_layers_rms": part_rms,
        "hc_coeff_layers_rms": coeff_rms,
        "near_tie_shares": near_tie,
        "router_score_rms": score_rms,
    }


def compare_gradients(program, model, params, reference, config, sizes, ids,
                      targets, got_attention, got_ffn, got_final, got_combine,
                      got_logits, both_mids, streams, decided_at, finals,
                      stepped) -> dict:
    """The backward pass and the update against the reference, as
    ``train_recipe_lfm2.compare_gradients`` holds them (its docstring names
    the four readings), for this model's chain: from both heads down, the
    prediction block's layer and its combine (whose cotangent joins the
    stack's head's on the summed final stream), the layers from the last,
    each ``jax.vjp`` ON THE PROGRAM'S OWN STREAMS AND ITS OWN COTANGENT
    (all four streams'), the hyper-connections' leaves among the leaves.
    The table is read by the stack's lookup and by the combine's: its
    gradient in the chain is their sum (the head is untied).  A layer's
    ``jax.vjp`` is taken a PART at a time on either side (the feed-forward
    part's on the streams between the parts, then the attention part's with
    the cotangent it hands up), each a program of its own (the streams
    between the parts are ``both_mids``'s, the forward comparison's
    program), and a layer's streams wait on the host: a mixture layer's
    float32 leaves and their gradients are 3 GB beside the 4.5 the
    parameters take.  ``streams`` are
    what each layer read, the block's layer's last; ``finals`` the streams
    after the stack and after the block's layer (taken out of it as they
    are done with); ``stepped`` is :func:`one_timed_step`'s."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _beside("train_recipe_blocks.py")
    plain = _beside("train_recipe_qwen3next.py")
    n_layers, s = len(params["layers"]), ids.shape[1]
    block = min(blocks.LOGIT_BLOCK, s)
    weight = sizes["mtp_loss_weight"]
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

    leaf_rms, stream_rms = {}, []  # a leaf: the difference over the reference
    chain: dict = {}  # a leaf of the tree: chain_stats' row
    totals: dict = {}  # leaves the chain reaches more than once: the sum

    def record(leaf_names, sums):
        diff, want = (np.asarray(a, np.float64) for a in sums)
        leaf_rms.update({
            n: math.sqrt(d / w) for n, d, w in zip(leaf_names, diff, want)
            if not n.endswith("['router_bias']")})

    def gate(name: str) -> bool:
        """A part's ``alpha`` [3] and ``b`` [24]: a few numbers each of whose
        gradients is a sum over the row's 16,384 tokens of terms of either
        sign (the mixing matrix's logits' gradients are what is left of
        near-equal rows after the Sinkhorn's projection, the streams being
        alike), so its relative error is the terms', amplified: the
        program's chain and its OWN step read ``alpha`` 16 to 110 % apart
        and ``b`` 3 to 14 % on the chip (my chip runs, PR 64), and no limit
        sits between two readings.  Reported apart (``gates_*``); held at
        float32 in tier-1 (``tests/test_xing4.py``), and their change with
        the small leaves' in ``update_norm``."""
        return name.endswith(("['alpha']", "['hc_attn']['b']", "['hc_ffn']['b']"))

    def worst_of(readings: dict, count: int = 5) -> list:
        order = sorted(readings, key=lambda n: (
            np.isnan(readings[n]), readings[n]), reverse=True)
        return [[n, float(readings[n])] for n in order[:count]]

    def whole_rms(sums) -> float:
        diff, want = (float(np.asarray(a, np.float64).sum()) for a in sums)
        return math.sqrt(diff / want)

    def add_to(name, grad):
        g = grad.astype(jnp.float32)
        totals[name] = g if name not in totals else totals[name] + g

    # ---- both heads: the loss's gradient on the final streams, in blocks --
    @functools.cache  # one program a side: both heads run it
    def head_block(logits_fn):
        return jax.jit(lambda head_params, xb, tb, scale: jax.grad(
            lambda hp, xb: scale * reference.ce_sum_of_logits(
                logits_fn(hp, xb).astype(jnp.float32), tb),
            argnums=(0, 1))(head_params, xb))

    def head_gradients(logits_fn, head_params, x, tgt, scale):
        total, cotangent = None, []
        for start in range(0, s, block):
            part = slice(start, start + block)
            g, c = head_block(logits_fn)(
                head_params, x[:, part], tgt[:, part], jnp.float32(scale))
            total = f32(g) if total is None else jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), total, g)
            cotangent.append(c)
        return total, jnp.concatenate(cotangent, axis=1)

    def want_logits(hp, x):
        return reference.head(hp, reference.sum_out(x), sizes)

    cotangents = {}
    for name in ("", "mtp_"):
        if name == "":
            x, tgt, scale = finals["x"], targets, 1.0 / s
        else:  # the stack's final streams wait for the combine: on the host
            finals["x"] = jax.device_get(finals["x"])
            x, tgt, scale = (finals.pop("z_out"), reference.after_next(targets),
                             weight / (s - 1))
        head_params = {"ln_f": params["mtp"]["out_norm"] if name else params["ln_f"],
                       "lm_head": params["lm_head"]}
        got_head, cotangents[name] = head_gradients(
            got_logits, head_params, x, tgt, scale)
        want_head, want_cotangent = head_gradients(
            want_logits, f32(head_params), f32(x), tgt, scale)
        record(names(head_params, f"{name}head:"),
               jax.jit(against)(got_head, want_head))
        stream_rms.append(whole_rms(jax.jit(against)(
            cotangents[name], want_cotangent)))
        if name == "":  # waits for the block's layer and combine: on the host
            cotangents[name] = jax.device_get(cotangents[name])
        add_to("['lm_head']", got_head["lm_head"])
        add_to("['mtp']['out_norm']['scale']" if name else "['ln_f']['scale']",
               got_head["ln_f"]["scale"])
        del got_head, want_head, want_cotangent, x

    # ---- a layer, a part at a time: one compiled program a KIND of part ----
    def stages(attention, ffn, cast):
        """``(the feed-forward part's vjp: its leaves' gradients and the
        cotangent of the streams between the parts; the attention part's)``
        of one side, not yet compiled."""
        def ffn_back(lp, mid, c, index):
            with program.tracing():
                y, back = jax.vjp(
                    lambda lp, mid: ffn(lp, mid, index)[0], cast(lp), cast(mid))
            return back(c.astype(y.dtype))

        def attention_back(lp, x, c, index):
            with program.tracing():
                y, back = jax.vjp(
                    lambda lp, x: attention(lp, x, index), cast(lp), cast(x))
            return back(c.astype(y.dtype))

        return ffn_back, attention_back

    sides = {"got": stages(got_attention, got_ffn, lambda a: a),
             "want": stages(
                 lambda lp, x, index: reference.attention_part(lp, x, sizes, index),
                 lambda lp, mid, index: reference.ffn_part(lp, mid, sizes, index),
                 f32)}
    compiled = {}

    def part(side, stage, which, index):
        """The compiled stage: the attention part's is one program for
        every layer, the feed-forward part's one a kind."""
        key = (side, stage, which if stage == 0 else None)
        if key not in compiled:
            stage_of_side = jax.jit(
                lambda *args, index=index: sides[side][stage](*args, index))
            compiled[key] = (_at_little_effort(stage_of_side)
                             if side == "want" else stage_of_side)
        return compiled[key]

    def layer_vjp(side, lp_attention, lp_ffn, x, c, which, index):
        """``(the layer's leaves' gradients, the cotangent it hands
        down)`` of a side for the output's cotangent ``c``; the streams
        between the parts are the forward comparison's program's, the
        program's or the reference's by the side, made again a call and
        gone before the attention part's program runs (the reference's are
        a float32 GB, and that program fits by less)."""
        mid = both_mids(lp_attention, lp_attention, x)[side == "want"]
        g_ffn, c_mid = part(side, 0, which, index)(lp_ffn, mid, c)
        del mid
        g_attention, below = part(side, 1, which, index)(lp_attention, x, c_mid)
        return {**g_attention, **g_ffn}, below

    def layer_back(lp, x, cotangent, decided, index, prefix):
        which = reference.is_dense(sizes, index)
        lp_ffn = {k: v for k, v in lp.items() if k in FFN_LEAVES}
        lp_attention = {k: v for k, v in lp.items() if k not in FFN_LEAVES}
        x = jnp.asarray(x)  # from the host
        grads, below = layer_vjp(
            "got", lp_attention, lp_ffn, x, cotangent, which, index)
        leaf_names = names(lp, prefix)
        chain.update(zip(leaf_names, np.asarray(
            jax.jit(chain_stats)(lp, grads), np.float64)))
        del grads
        # the comparison: zero where the layer is not compared
        masked = cotangent * jnp.asarray(decided, cotangent.dtype).reshape(
            1, s, 1, 1)
        del cotangent
        compared, compared_below = layer_vjp(
            "got", lp_attention, lp_ffn, x, masked, which, index)
        wanted, wanted_below = layer_vjp(
            "want", lp_attention, lp_ffn, x, masked, which, index)
        record(leaf_names, jax.jit(against)(compared, wanted))
        stream_rms.append(whole_rms(jax.jit(against)(
            compared_below, wanted_below)))
        return below

    def copys_transpose(c):  # one stream's cotangent: its copies' summed
        return jnp.sum(c.astype(jnp.float32), axis=2).astype(c.dtype)

    # ---- the prediction block: its layer, then its combine ---------------
    mp = params["mtp"]
    c_z = copys_transpose(layer_back(
        mp["layer"], streams.pop(), cotangents.pop("mtp_"),
        decided_at[n_layers], n_layers, "['mtp']['layer']"))
    inner = {k: v for k, v in mp.items() if k not in ("layer", "out_norm")}

    @jax.jit
    def combine_back(inner, ln_f, table, x, c):
        def got(inner, ln_f, table, x):
            return got_combine(inner, table, got_final(ln_f, x), targets)

        def want(inner, ln_f, table, x):
            hf = reference.final_norm(
                {"ln_f": ln_f}, reference.sum_out(x), sizes)
            return reference.mtp_input(inner, table, hf, targets, sizes)

        out, back = jax.vjp(got, inner, ln_f, table, x)
        grads = back(c.astype(out.dtype))
        _, back = jax.vjp(want, f32(inner), f32(ln_f), f32(table), f32(x))
        wants = back(f32(c))
        return grads, against(grads[:3], wants[:3]), against(grads[3], wants[3])

    (g_inner, g_ln_f, g_table, c_final), leaf_sums, below_sums = combine_back(
        inner, params["ln_f"], params["embed"], jnp.asarray(finals.pop("x")), c_z)
    record(names((inner, params["ln_f"], {"embed": 0}), "combine:"), leaf_sums)
    stream_rms.append(whole_rms(below_sums))
    chain.update(zip(names(inner, "['mtp']"), np.asarray(
        jax.jit(chain_stats)(inner, g_inner), np.float64)))
    add_to("['ln_f']['scale']", g_ln_f["scale"])
    add_to("['embed']", g_table)
    cotangent = (c_final.astype(jnp.float32)
                 + jnp.asarray(cotangents.pop("")).astype(jnp.float32)
                 ).astype(c_final.dtype)
    del g_inner, g_table, c_final

    # ---- the layers, from the last ----------------------------------------
    for index in reversed(range(n_layers)):
        cotangent = layer_back(
            params["layers"][index], streams.pop(), cotangent,
            decided_at[index], index, f"['layers'][{index}]")
    # the lookup's transpose: the rows' cotangents summed by their id, on the
    # host (a scatter-add of 16,384 rows takes the chip's compiler 6 s, and
    # the comparison's programs are compiled in every run)
    rows = np.asarray(copys_transpose(cotangent)[0]).astype(np.float32)
    order = np.argsort(np.asarray(ids[0]), kind="stable")
    sorted_ids = np.asarray(ids[0])[order]
    first = np.flatnonzero(np.r_[True, sorted_ids[1:] != sorted_ids[:-1]])
    looked_up = np.zeros(params["embed"].shape, np.float32)
    looked_up[sorted_ids[first]] = np.add.reduceat(rows[order], first, axis=0)
    add_to("['embed']", jnp.asarray(looked_up))
    shared = {"['lm_head']": params["lm_head"], "['embed']": params["embed"],
              "['ln_f']['scale']": params["ln_f"]["scale"],
              "['mtp']['out_norm']['scale']": mp["out_norm"]["scale"]}
    for name, leaf in shared.items():
        chain[name] = np.asarray(jax.jit(chain_stats)(
            [leaf], [totals[name]]), np.float64)[0]

    gates_rms = {n: v for n, v in leaf_rms.items() if gate(n)}
    leaf_rms = {n: v for n, v in leaf_rms.items() if not gate(n)}
    worst = max(leaf_rms, key=lambda n: (np.isnan(leaf_rms[n]), leaf_rms[n]))
    read = {
        "grads_rms": float(leaf_rms[worst]), "grads_rms_worst_leaf": worst,
        "grads_rms_worst_leaves": worst_of(leaf_rms),
        "gates_grads_rms": worst_of(gates_rms, 3),
        "grad_stream_rms": float(np.max(stream_rms)),
        # both heads, the block's layer, its combine, the layers from the last
        "grad_stream_stages_rms": stream_rms,
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
    gates_norms = {n: v for n, v in norms.items() if gate(n)}
    norms = {n: v for n, v in norms.items() if not gate(n)}
    worst = max(norms, key=lambda n: (np.isnan(norms[n]), norms[n]))
    read.update(step_grad_norms=float(norms[worst]),
                step_grad_norms_worst_leaf=worst,
                step_grad_norms_worst_leaves=worst_of(norms),
                gates_step_grad_norms=worst_of(gates_norms, 3))
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


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    from learning_at_home_tpu.models.transformer import DMoETransformerLM

    manifest = harness.load_manifest(args.manifest)
    share = harness.load_module(manifest, "runners", "train_recipe_share")
    latent = _beside("train_recipe_latent.py")
    lfm2 = _beside("train_recipe_lfm2.py")
    make = DMoETransformerLM.make_train_step
    shares_own = share.share_problems

    def remembered(self, optimizer, *args, **kwargs):
        """The program's own method; the comparison finds the step again
        (``train_recipe_lfm2._timed_step``)."""
        step = make(self, optimizer, *args, **kwargs)
        lfm2._MADE_STEPS.append((self, optimizer, step))
        return step

    # this process's own copy of the module: its run() looks these up
    share.CFG_FIELDS = CFG_FIELDS
    share._check_sizes = _check_sizes
    share.compare_with_reference = compare_with_reference
    share.TOLERANCES = TOLERANCES  # its over_tolerance and REFERENCE line read it
    share.MARGIN = MARGIN
    share.LOAD_MAX_OVER_MEAN = LOAD_MAX_OVER_MEAN
    share.share_problems = lambda counters: (
        shares_own(counters) + hc_problems(counters))
    share.STEP_COUNTERS = STEP_COUNTERS
    share.EXTRA_SCOPES = EXTRA_SCOPES
    share._blocks = latent._blocks_with_mtp
    DMoETransformerLM.make_train_step = remembered
    global _CALLERS_PARAMS_ARE_DONE_WITH
    _CALLERS_PARAMS_ARE_DONE_WITH = True  # share.run reads their shapes alone
    try:
        return share.run(cell, config, traffic, args, clock)
    finally:
        _CALLERS_PARAMS_ARE_DONE_WITH = False
        DMoETransformerLM.make_train_step = make
        lfm2._MADE_STEPS.clear()
