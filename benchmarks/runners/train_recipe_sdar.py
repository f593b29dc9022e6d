"""Runner ``train_recipe_sdar``: ``train_recipe_share``'s run for a stack that
is TRAINED BY BLOCK DIFFUSION (``sdar-30b-a3b``): every layer grouped-query
attention and a mixture with a share of the experts held and no selection
bias to level; a step noises its row on the device, runs the stack over the
doubled row ``[x_t | x_0]`` under the block-structured mask and takes the
loss over the masked positions of the noised copy, each over its block's
masking probability.

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is, so the set-up, the warm-up,
the window, the checks (finite losses, the first pool batch's loss falls,
nothing compiled in the window), the Zipf generator and the printed lines
are that file's own code, not a copy.  A step's tokens are ``rows *
cfg.seq_len``, the DATA tokens (8,192 a row): the clean copy is context.
The names its ``run`` looks up in its module are replaced, in this
process's private copy of it, with what this file defines:

- ``CFG_FIELDS`` / ``_check_sizes``: the configuration file restates the
  sizes under the ``sdar_moe`` key names; ``num_experts`` is the experts
  HELD and ``num_experts_published`` the router's width; every layer must
  route; the objective, the block length, the floor and the mask id are
  compared too.
- ``harness`` (a view of the module: two names differ).  ``load_module``
  hands ``train_recipe`` over with a ``zipf_batches`` that draws from the
  vocabulary less its last id, the mask id, which no data token carries.
  ``quiet_gc`` marks the window's end for the step below.
- the set-up call and the train step, ON THIS RUN'S MODEL ALONE (the
  instance ``share.run`` made, reached where it is first handed to a name
  this runner can replace, ``train_step._check_layout``; no class and no
  other model of the process is touched).  ``share.run`` calls
  ``model.level_router_bias(params, pool)`` there; this model has no bias,
  and the instance answers with :func:`route_like_a_trained_model`, WHICH
  REWRITES THE SEEDED GATES: it takes the ``SHARED_DIRECTIONS`` leading
  directions of each router's input out of its gate and permutes the gate's
  columns (a placement of experts on chips by load), so that this chip's
  rows START at the level share on every seed.  ``SETUP`` files its seconds
  under the phase ``remake_gates_and_place_experts`` (not
  ``level_router_bias``) and reports each layer's rows over level before and
  after under the levelling's key.  It holds at the window's START only: the
  routers train on, and inside one window ``expert_load_max_over_mean``
  climbs from under 2 to 3.8-8.5 (my chip runs, PR 57); the buffer of twice
  the level share held every row of every run (``dropped_fraction`` 0 is a
  hard check), but this cell cannot show that it would in a longer run.
- ``model.make_train_step`` on that instance: the program's own step,
  remembered so that the comparison finds it again; on the FIRST call and on
  the call AFTER THE WINDOW (the two whose losses ``run`` compares: "the
  first pool batch's loss falls") the loss it hands back is the program's
  ``loss_fn`` on the same parameters and batch under ONE FIXED noise key,
  taken before the step runs.  The step's own loss is under the step's own
  noise, fresh every step, and ``1 / p`` at a floor of 1e-3 makes two draws
  of it differ by several percent (a single token masked at ``p = 0.002`` is
  6 % of a row's loss), more than a window's training moves it: under fresh
  noise the comparison would be of the draws.  The window's steps are the
  program's, untouched.  This leans on how ``share.run`` calls the step
  (which call is first, the window being the ``quiet_gc`` block); :func:`run`
  checks afterwards that the fixed-key loss was taken exactly twice and on
  one batch, and raises by name otherwise.  A hook for such a loss in
  ``train_recipe_share.run`` is a ``benchmark`` issue's (PERF.md section 7).
- ``share_problems``: ``dropped_fraction`` 0 in every step (nothing
  overflowed the sorted-row buffer) and ``masked_share`` within
  ``MASKED_SHARE`` (2,048 blocks a row: the mean of ``p`` is 0.5).
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``: on one seeded
  row, with the noise the timed step itself draws for it from an empty
  optimizer state (``noise_key``; the draws go to the reference as inputs),
  a layer at a time ON THE PROGRAM'S OWN STREAM, the program's layer
  composed of its own pieces (``hidden_token_median`` holds ``_hidden``
  whole to them): the attention's output, the router's logits, the layer's
  output over the positions whose 8th and 9th router logits, ONE OF THE TWO
  A HELD EXPERT, lie ``MARGIN`` apart or more in the reference; then the
  logits of the noised half a block of positions at a time and the weighted
  loss whole; then the BACKWARD pass and the update
  (:func:`compare_gradients`).  ``WRONG_PROGRAMS`` names programs that must
  fall outside (``tools/smallthinker_probe.py float8`` runs them on the
  chip).
- ``STEP_COUNTERS`` / ``EXTRA_SCOPES``: the share's and the objective's;
  scope ``noise`` is filed on its own.
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

# the file's key (sdar_moe's config.json, then this repo's) -> the program's
# config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "seq_len": "seq_len", "num_experts_published": "num_experts",
    "num_experts": "held_experts", "first_held_expert": "first_held_expert",
    "num_experts_per_tok": "k", "moe_intermediate_size": "expert_ffn_dim",
    "norm_topk_prob": "renormalize", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "objective": "objective", "block_length": "diffusion_block",
    "norm": "norm", "positions": "positions", "qk_norm": "qk_norm",
    "expert_kind": "expert_kind", "routing": "routing",
    "router_score": "router_score", "aux_loss_weight": "aux_loss_weight",
    "router_z_weight": "router_z_weight", "remat": "remat",
    "scan_layers": "scan_layers", "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 8,192 data tokens,
# 16,384 positions (PERF.md section 2, PR 57): the largest the program gave
# over its seeds, and the reference itself with every matmul operand rounded
# to float8_e4m3 (the nearest precision below the configuration's bf16), run
# through this same comparison in the program's place, which must fall
# outside: it is outside seven (program | float8): ``layers_rms`` over the
# decided positions, the worst layer, layer 0 every time (0.025-0.035 | 0.81
# read at a MARGIN of 2^-9, where the program reads 0.052-0.063);
# ``attention_rms`` the attention's output, the worst layer (0.0044-
# 0.0056 | 0.73; a noised block that sees its own clean block reads 0.043);
# ``logits_rms`` / ``logits_p999`` / ``logits_token_median`` over the noised
# half (0.0015-0.0016 | 0.041, 0.0048-0.0054 | 0.136, 0.0015-0.0016 | 0.041);
# ``grads_rms`` the worst leaf of a layer's ``jax.vjp``, a router's gate
# nearly every time (0.014-0.091 | 8.2); ``grad_stream_rms`` (0.0044-0.0074
# | 1.36; at a MARGIN of 2^-9 three runs of 26 read 0.044-0.070 in ONE
# layer and a leaf of 0.22-0.24: decided positions that flipped).  ``loss``, the weighted loss whole, hardly moves with the
# precision (2e-5 to 2.6e-4 | 6.5e-4): its limit is the accepted cells'
# largest (olmoe's), three times the first reading, and its second readings
# are the three wrong losses (0.0011, 0.0024, 0.498).  ``router_logits_rms``
# is the router's ARITHMETIC alone (0 | 0; a router in bf16 reads 1.66e-3).
# ``hidden_token_median`` has no second precision (both sides are the
# program; 0.0052-0.032 | 0.125 for a head over the clean half).
# ``step_grad_norms`` is over every leaf BUT the routers' gates (0.016-0.096
# | 0.55 without 1/p, 0.32 for the clean half's head); the gates' own
# reading, ``step_gate_grad_norms``, is reported and has no limit: two
# compiled programs of one step route the positions nearest a tie otherwise,
# and a gate's gradient, the small difference of eight pulls a position,
# moves with them (0.09-0.27 for the program itself, 0.28-0.59 for the wrong
# losses: no room for a limit); the gates' update is held by
# ``update_norm`` with every other leaf's (0.048-0.159 | 1, which is what a
# leaf left as it was reads, with the more room above the first reading):
# by how much the worst group moved LESS than the plain rule says.  By how
# much the worst group moved MORE, ``update_over_rule``, is REPORTED with
# its parts and not held: seed 5700000502 reads 1.04 (twice the rule's
# norm) in one stack of experts, both times it was run, and the parts say
# why (my chip run, PR 57): ONE expert of the 32 whose gradient is 1e-7 of
# its neighbours' (a row or two reach it) moves 47 times as far in the step
# as the rule moves it, while the step moves each of the 31 others 0.23 of
# the rule's norm.  Adafactor's factored second moments make of a rank-one
# gradient an update of exactly +-1 an element, and of that gradient plus a
# second row of a hundredth its size (0.6 % of the squares here: a position
# at a tie that the compiled step routes here and the chain does not) a
# ratio of near-normal numbers an element, heavy-tailed, rms 25; the rule
# clips a LEAF to an rms of 1, so the one expert's tail shrinks the whole
# stack by 4.5.  Both are the optimizer's rule applied once; neither side is
# wrong, and no limit a near-empty expert cannot cross exists for one group.
# What an update applied twice or at twice the rate must cross is
# ``update_total``, the WHOLE tree's change against the rule's, either way
# (0.0008-0.083 over eight seeds | 1.72 at twice the rate, the eighth wrong
# program, which reads 1.0 exactly in float32; ``update_over_rule`` reads
# 0.04-0.14 in seven of those seeds, 1.04 in the eighth and 1.86 at twice
# the rate).
# ``near_tie_share`` guards the comparison itself: at least two fifths of
# the positions are compared in every layer (0.31-0.51 at this MARGIN).
# ``noise_mismatches`` is an equality, not a tolerance.
TOLERANCES = {"layers_rms": 1e-1, "attention_rms": 2e-2,
              "router_logits_rms": 1e-4, "logits_rms": 1e-2,
              "logits_p999": 3e-2, "logits_token_median": 1e-2, "loss": 1e-3,
              "hidden_token_median": 1e-1, "near_tie_share": 0.6,
              "grads_rms": 3e-1, "grad_stream_rms": 3e-2,
              "step_grad_norms": 1.5e-1, "update_norm": 5e-1,
              "update_total": 5e-1,
              # the program's noised row and weights against the reference's
              # restatement from the same draws: ids that differ, weights
              # that differ by more than 1e-6 of their size
              "noise_mismatches": 0.0}
# A position whose 8th and 9th largest router logits lie closer than this in
# the reference, ONE OF THE TWO A HELD EXPERT, is not compared in that
# layer: the program's router reads the bf16 stream its bf16 attention left,
# so its logits differ from the reference's
# (``router_logits_abs_rms_on_the_references_stream``), and which of the two
# experts it takes there is no error of either side.  Chosen among three
# from this cell's readings (the guard's share | ``layers_rms``): 2^-7
# 0.36-0.47 | 0.025-0.035; 2^-8 0.24-0.28 | 0.034-0.040; 2^-9 0.14-0.18 |
# 0.052-0.063.  The program's logits differ from the reference's by 1.1e-3
# to 2.1e-3 rms, so this is four to seven of those; at 2^-9, one to two,
# three runs of 26 compared positions that had flipped (see TOLERANCES).  A
# quarter of the experts are held, so 44 % of the near ties count (an
# eighth held: 12 %), and the set-up leaves the routers the tokens' own
# small parts of the stream to decide on: no margin keeps three quarters of
# the positions AND only positions that are decided, so the guard is 0.6.
MARGIN = 2.0 ** -7
# what every step of the window keeps to: 2,048 blocks a row draw p around 0.5
MASKED_SHARE = (0.45, 0.55)
# programs that must fall outside the limits, by name: what
# ``compare_with_reference(.., wrong=name)`` puts in the program's place
WRONG_PROGRAMS = {
    "the program under a causal mask over the doubled row": {"wrong": "causal_mask"},
    "the program whose noised blocks see their own clean block": {"wrong": "no_offset"},
    "the program with positions 0..2S-1": {"wrong": "positions_2s"},
    "the loss without 1/p": {"wrong": "unweighted_loss"},
    "the loss on shifted targets": {"wrong": "shifted_targets"},
    "the head over the clean half": {"wrong": "clean_half_head"},
    # what ``update_total`` is for: every leaf moved too far
    "the step at twice its learning rate": {"wrong": "doubled_rate"},
    # and the one that names ``router_logits_rms``'s limit
    "the program, its router's logits in bfloat16": {"wrong": "bf16_router"},
}
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "local_rows_over_level", "held_experts_empty",
                 "masked_share", "loss_weight_mean",
                 "attention_admitted_pairs", "attention_visited_pairs")
EXTRA_SCOPES = ("noise",)
# how many leading directions of a router's input the set-up takes out of
# its gate (:func:`route_like_a_trained_model`): the mean, the masked
# positions' own, and the few that a row's commonest ids share; at 8 the
# largest expert's rows read under 2 of the mean (1, 2, 4, 8, 16 directions
# read 3.2-5.5, 2.2-3.4, 1.8-2.3, 1.6-1.9, 1.6-1.7 at a width of 1,024 on
# the CPU)
SHARED_DIRECTIONS = 8
# the noise key of the two losses ``train_recipe_share.run`` compares
FIXED_NOISE_SEED = 57
# the set-up phase of :func:`route_like_a_trained_model`, as SETUP names it
SETUP_PHASE = "remake_gates_and_place_experts"


def _check_sizes(config: dict, cfg) -> None:
    import jax.numpy as jnp

    from learning_at_home_tpu.models import transformer

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    layers = [cfg.attention_layer(i) for i in range(cfg.n_layers)]
    got["windowed_layers"] = [
        i for i, a in enumerate(layers) if a.window is not None]
    got["rotated_layers"] = [i for i, a in enumerate(layers) if a.rotary]
    got["mixture_layers"] = cfg.mixture_layers()
    got["shared_experts"] = cfg.shared_experts
    got["router_bias"] = cfg.router_bias
    got["mask_token_id"] = cfg.vocab_size - 1  # the program's: the last id
    got["p_floor"] = transformer.DIFFUSION_P_FLOOR  # the program's: a constant
    got["positions_run"] = 2 * cfg.seq_len
    want = dict(
        config, windowed_layers=[],
        rotated_layers=list(range(config["n_layers"])),
        mixture_layers=config["n_layers"],  # decoder_sparse_step 1
        shared_experts=0, router_bias=False,
    )
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise BenchError("the runner knows the stack whose every layer routes")
    if config["use_sliding_window"] or config["rope_scaling"] is not None:
        raise BenchError("the runner knows no window and no rope scaling")
    wrong = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def share_problems(counters: dict, masked_share: tuple = MASKED_SHARE) -> list:
    """What the share and the noising must read in every step of the
    window.  The loads are the data's (the model has no bias to level):
    only the buffer bounds them."""
    problems = []
    dropped = counters.get("dropped_fraction", [1.0])
    if any(x != 0.0 for x in dropped):
        problems.append(
            f"the sorted-row buffer overflowed: dropped_fraction up to "
            f"{max(dropped):.3e}")
    masked = counters.get("masked_share", [math.nan])
    low, high = masked_share
    if not all(low <= x <= high for x in masked):
        problems.append(
            f"masked_share {min(masked):.3f}..{max(masked):.3f} outside "
            f"{low}..{high}")
    return problems


def reference_sizes(config: dict) -> dict:
    """What the reference is given: the FILE's sizes, not the program's."""
    return dict(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        held=(config["first_held_expert"], config["num_experts"]),
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
        block_length=config["block_length"], p_floor=config["p_floor"],
        mask_token_id=config["mask_token_id"],
    )


def data_vocab(cfg) -> int:
    """How many ids the traffic draws from: the vocabulary less its last
    id, the mask id, which no data token carries."""
    return cfg.vocab_size - 1


def route_like_a_trained_model(model, params, token_batches: list):
    """``params`` with every router's gate as a TRAINED model's deployment
    would leave it, and what each layer's rows here over the level share
    were before and after, on ``token_batches`` (the pool a run trains on,
    noised under the set-up's own key).  A set-up call, outside any step;
    it stands in for two things seeded weights lack, a layer at a time on
    the stream the layers before it leave:

    - **a router that does not route on what the positions share.**  Under
      seeded weights broad attention over Zipf ids leaves every position
      the same large vector (four times the token's own part of the
      stream) and the masked positions, a quarter of the row, one more, so
      every position of a layer chooses the same 8 experts
      (``expert_load_max_over_mean`` 15.7 of a possible 16, 27 of the 32
      held experts empty; my chip runs, PR 57; the mean direction alone
      taken out left 12.9).  The components of each gate column along the
      ``SHARED_DIRECTIONS`` leading directions of the router's input over
      the pool are taken out: ``G' = G - V (V^T G)``, ``V`` the leading
      eigenvectors of the input's second moment (orthogonal iteration, on
      the device).
    - **experts placed on chips by load.**  A quarter of the positions
      carry ONE id (the mask's) and go to one set of 8 experts whatever the
      router; how many of those a chip holds decides its rows (0 to 8: 0 to
      twice the buffer).  A deployment places experts on chips by measured
      load; here the gate's columns are permuted so that the held experts'
      load is one of the level shares of a longest-first deal over the
      ``E / held`` chips.  The experts' weights are independent draws, so
      a permutation of the gate's columns IS a placement.

    The reference is given the same weights: nothing here enters
    ``correct`` but through them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = model.cfg
    d, k = cfg.d_model, cfg.k
    held, first, experts = cfg.held_experts, cfg.first_held_expert, cfg.num_experts
    kind = cfg.attention_layer(0)
    key = jax.random.key(FIXED_NOISE_SEED)
    noised = jax.jit(lambda ids, key: model.noised_row(
        ids, *model.noise_draws(key, ids.shape[0]))[0])
    embed = jax.jit(lambda table, row: table[row].astype(cfg.dtype))
    attend = jax.jit(lambda lp, x: model._attention_block(lp, x, kind)[0])
    finish = jax.jit(lambda lp, h: model._ffn_block(lp, h, None, 0)[0])

    def routed(lp, h):  # what the router reads, float32
        return model._norm(lp["ln2"], h).reshape(-1, d).astype(jnp.float32)

    @jax.jit
    def moment_of(lp, h):
        m = routed(lp, h)
        return jnp.dot(m.T, m, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def without_the_shared(gate, moment):
        """The gate less its part along the moment's leading directions
        (a few rounds of orthogonal iteration: the leading eigenvalues are
        far apart, and which direction comes last matters little)."""
        v = jnp.linalg.qr(jax.random.normal(
            jax.random.key(0), (d, SHARED_DIRECTIONS), jnp.float32))[0]
        for _ in range(16):
            v = jnp.linalg.qr(jnp.dot(
                moment, v, precision=jax.lax.Precision.HIGHEST))[0]
        plain = gate.astype(jnp.float32)
        return (plain - jnp.dot(
            v, jnp.dot(v.T, plain, precision=jax.lax.Precision.HIGHEST),
            precision=jax.lax.Precision.HIGHEST)).astype(gate.dtype)

    @jax.jit
    def counts_of(lp, gate, h):
        logits = model.moe.router_logits({"gate": gate}, routed(lp, h))
        chosen = jax.lax.top_k(logits, k)[1].reshape(-1)
        return jnp.zeros(experts, jnp.float32).at[chosen].add(1.0)

    streams = [embed(params["embed"], noised(ids, jax.random.fold_in(key, i)))
               for i, ids in enumerate(token_batches)]
    layers, loads = list(params["layers"]), []
    for i, lp in enumerate(layers):
        streams = [attend(lp, x) for x in streams]
        gate = lp["moe"]["gate"]
        levelled = without_the_shared(
            gate, sum(moment_of(lp, h) for h in streams))
        before, after = (
            np.asarray(sum(counts_of(lp, g, h) for h in streams), np.float64)
            for g in (gate, levelled))
        # longest first, each expert to the chip with the least load so far
        # that still has room; this chip is the first
        chips = [[] for _ in range(experts // held)]
        for e in np.argsort(-after, kind="stable"):
            open_chips = [chip for chip in chips if len(chip) < held]
            min(open_chips, key=lambda chip: after[chip].sum()).append(int(e))
        here, others = sorted(chips[0]), sorted(sum(chips[1:], []))
        order = others[:first] + here + others[first:]
        # placed as the gate was: a step compiled for these parameters is
        # the step every later call runs
        placed = jax.device_put(levelled[:, np.asarray(order)], gate.sharding)
        lp = layers[i] = {**lp, "moe": {**lp["moe"], "gate": placed}}
        level = after.sum() * held / experts
        # rows here over level, the largest expert's rows over the mean:
        # each as the seed left it, then as a run starts
        loads.append([float(before[first:first + held].sum() / level),
                      float(after[here].sum() / level),
                      float(before.max() / before.mean()),
                      float(after.max() / after.mean())])
        streams = [finish(lp, h) for h in streams]
    return {**params, "layers": tuple(layers)}, loads


def _beside(name: str):
    """A runner beside this one, as a module of its own."""
    return harness.load_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), name + ".py"))


def _core_under(model, mask_function):
    """An attention core ``(q, k, v) -> out`` under ``mask_function(q_ids,
    kv_ids)``, a wrong program's: the blocked kernel at the program's own
    tiles where the program runs it, else full scores under the boolean
    array."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.models import trunk

    def core(q, k, v):
        _, s, h, hd = q.shape
        sizes = trunk.flash_block_sizes(q.shape, jax.default_backend())
        if model.attn_impl != "flash" or sizes is None:
            ids = jnp.arange(s, dtype=jnp.int32)
            return jax.nn.dot_product_attention(
                q, k, v, mask=mask_function(ids[:, None], ids[None, :])[None, None])
        from jax.experimental.pallas.ops.tpu import splash_attention as splash
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_mask as mask_lib,
        )

        class WrongMask(mask_lib._ComputableMask):
            def __eq__(self, other):
                return self is other

            def __hash__(self):
                return id(self)

        kernel = splash.make_splash_mha_single_device(
            mask=splash.MultiHeadMask([WrongMask((s, s), mask_function)] * h),
            block_sizes=sizes, residual_checkpoint_name=trunk.FLASH_RESIDUALS)

        def heads_first(x):
            return x.transpose(0, 2, 1, 3)

        return heads_first(jax.vmap(kernel)(
            heads_first(q) * (1.0 / hd ** 0.5), heads_first(k), heads_first(v)))

    return core


def _wrong_program(model, wrong: str | None):
    """What stands in the program's place: ``pieces`` the model whose layers
    are compared one at a time, ``whole`` the model whose ``_hidden``,
    ``loss_fn`` and train step are held to those pieces (None where the
    pieces are the wrong ones), and ``gradients``: whether the backward pass
    is compared (a program whose fault a forward reading names is read
    forward alone)."""
    import jax.numpy as jnp
    import numpy as np

    def twin():
        return type(model)(dataclasses.replace(model.cfg), model.mesh)

    program = types.SimpleNamespace(
        pieces=model, whole=model, gradients=True, rate=1.0)
    half, block = model.cfg.seq_len, model.cfg.diffusion_block
    if wrong == "causal_mask":
        program.pieces, program.whole, program.gradients = twin(), None, False
        program.pieces._ring = _core_under(model, lambda q, k: q >= k)
    elif wrong == "no_offset":
        def mask(q_ids, kv_ids):  # the offset dropped: >= where > stands
            q_blk, k_blk = (q_ids % half) // block, (kv_ids % half) // block
            q_noised, k_noised = q_ids < half, kv_ids < half
            return ((q_noised == k_noised) & q_noised & (q_blk == k_blk)) | (
                ~k_noised & (q_blk >= k_blk))
        program.pieces, program.whole, program.gradients = twin(), None, False
        program.pieces._ring = _core_under(model, mask)
    elif wrong == "positions_2s":
        program.pieces, program.whole, program.gradients = twin(), None, False
        program.pieces._qkv = lambda lp, x, positions, rotary: model._qkv(
            lp, x, np.arange(x.shape[1]), rotary)
    elif wrong == "bf16_router":
        def bf16_logits(params, router_x):
            return (router_x.astype(jnp.bfloat16)
                    @ params["gate"].astype(jnp.bfloat16)).astype(jnp.float32)
        program.pieces, program.whole, program.gradients = twin(), None, False
        program.pieces.moe.router_logits = bf16_logits
    elif wrong == "unweighted_loss":
        program.whole = twin()
        program.whole.noised_row = lambda ids, u, t: (
            lambda row, weights: (row, (weights > 0).astype(jnp.float32)))(
                *model.noised_row(ids, u, t))
    elif wrong == "shifted_targets":
        program.whole = twin()
        whole_ce = program.whole._chunked_ce
        program.whole._chunked_ce = lambda x, head, targets, **how: whole_ce(
            x, head, jnp.roll(targets, -1, axis=1), **how)
    elif wrong == "clean_half_head":
        program.whole = twin()
        whole_hidden = program.whole._hidden

        def halves_swapped(params, row):
            x, aux = whole_hidden(params, row)
            return jnp.roll(x, x.shape[1] // 2, axis=1), aux
        program.whole._hidden = halves_swapped
    elif wrong == "doubled_rate":
        program.rate = 2.0  # of the recipe's rule, in the timed step's place
    elif wrong is not None:
        raise BenchError(f"no wrong program {wrong!r}")
    return program


# (model, optimizer, step) of each train step made while :func:`run` runs
_MADE_STEPS: list = []


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


GRADIENT_READINGS = ("grads_rms", "grad_stream_rms", "step_grad_norms",
                     "step_gate_grad_norms", "update_norm", "update_over_rule",
                     "update_total")


def compare_gradients(program, model, params, reference, config, sizes, ids,
                      row, weights, got_layer, got_logits, streams,
                      decided_at, x_final, operand_dtype) -> dict:
    """The backward pass and the update against the reference, as the
    forward pass is compared: a layer at a time ON THE PROGRAM'S OWN STREAM
    AND ITS OWN COTANGENT, from the loss down (``train_recipe_qwen3next``'s
    four readings, its plain first step and its rule for small leaves).

    ``grads_rms``: each layer's ``jax.vjp`` of the program's pieces against
    the reference's, for the cotangent the program's chain brought there,
    zero at the positions that layer does not compare; the worst LEAF of
    the tree by the difference's norm over the reference's.
    ``grad_stream_rms``: the same for what a layer hands the layer below.
    ``step_grad_norms``: ONE call of the timed train step from an empty
    optimizer state (whose noise is the comparison's: the same key), whose
    second moments are then its gradients' mean squares: each leaf's norm
    against the chain's, the worst leaf's ``|ratio - 1|``, the routers'
    gates read apart as ``step_gate_grad_norms``.
    ``update_norm``: the norm of each leaf's change over that step against
    what the plain rule makes of the chain's gradient, ``1 - ratio`` where
    the leaf moved less than the rule says: a leaf left as it was reads 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _beside("train_recipe_blocks")
    plain = _beside("train_recipe_qwen3next")
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

    def parts(a, p):
        """``a`` over leaf ``p``'s elements, an expert stack ([E, ., .]) an
        expert at a time and any other leaf whole: [parts, elements]."""
        return a.reshape((p.shape[0], -1) if p.ndim == 3 else (1, -1))

    def chain_stats(p_tree, g_tree):
        """A leaf, [parts, 3]: its gradient's sum of squares; what the plain
        rule's first step changes it by (sum of squares, elements moved)."""
        rows = []
        for p, g in zip(jax.tree_util.tree_leaves(p_tree),
                        jax.tree_util.tree_leaves(g_tree)):
            after = plain._first_step(p, g.astype(p.dtype), learning_rate)
            rows.append(jnp.stack([
                jnp.sum(jnp.square(parts(f32(g), p)), axis=1),
                jnp.sum(jnp.square(parts(f32(after) - f32(p), p)), axis=1),
                jnp.sum(parts(after != p, p), axis=1).astype(jnp.float32)],
                axis=1))
        return rows

    def host(rows):
        return [np.asarray(a, np.float64) for a in rows]

    # ---- the timed step, once, from an empty optimizer state --------------
    stepped = None
    if program.whole is not None and operand_dtype is None:
        from learning_at_home_tpu.parallel.mesh import batch_sharding

        if program.rate == 1.0:
            optimizer, step = _timed_step(program.whole, config)
        else:  # a wrong program's: the recipe's rule at another rate
            from learning_at_home_tpu.ops.fused_adafactor import fused_adafactor

            optimizer = fused_adafactor(program.rate * learning_rate)
            step = program.whole.make_train_step(optimizer)
        placed = batch_sharding(model.mesh)  # as the window's batches are
        new, opt_state, _, _ = step(
            jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))(
                params),  # the step donates
            model.init_opt_state(optimizer, params),
            jax.device_put(ids, placed), jax.device_put(ids, placed))
        if not hasattr(opt_state, "v_row"):
            raise BenchError("the step's gradients are read off Adafactor's "
                             f"second moments; the state is {type(opt_state)}")
        leaf_names = names(params, "")

        @jax.jit
        def read_step(new, old, v_row, v):
            """A leaf, [parts, 3]: as :func:`chain_stats`, of the step."""
            rows = []
            for after, p, by_row, whole in zip(
                    jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(v_row), jax.tree_util.tree_leaves(v)):
                moments = whole if whole.shape == p.shape else by_row
                of_part = parts(f32(after) - f32(p), p)
                rows.append(jnp.stack([
                    jnp.mean(f32(moments).reshape(of_part.shape[0], -1), axis=1)
                    * of_part.shape[1],
                    jnp.sum(jnp.square(of_part), axis=1),
                    jnp.sum(parts(after != p, p), axis=1).astype(jnp.float32)],
                    axis=1))
            return rows

        stepped = dict(zip(leaf_names, host(read_step(
            new, params, opt_state.v_row, opt_state.v))))
        del new, opt_state

    # ---- the head: the loss's gradient on the noised half, in blocks ------
    def head_gradients(logits_fn, head_params, x):
        @jax.jit
        def one_block(head_params, xb, tb, wb):
            return jax.grad(lambda hp, xb: reference.ce_sum_of_logits(
                logits_fn(hp, xb).astype(jnp.float32), tb, wb) / s,
                argnums=(0, 1))(head_params, xb)

        total, cotangent = None, []
        for start in range(0, s, block):
            part = slice(start, start + block)
            g, c = one_block(
                head_params, x[:, part], ids[:, part], weights[:, part])
            total = f32(g) if total is None else jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), total, g)
            cotangent.append(c)
        # the clean half's final stream feeds nothing
        cotangent = jnp.concatenate(cotangent, axis=1)
        return total, jnp.concatenate(
            [cotangent, jnp.zeros_like(cotangent)], axis=1)

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
    chain = dict(zip(names(head_params, ""), host(
        jax.jit(chain_stats)(head_params, got_head))))
    del got_head, want_head, want_cotangent

    # ---- the layers, from the last: one compiled pair ---------------------
    def got_side(lp, x, c, mask):
        def pieces(lp, x):
            y, _, aux, z, _, _ = got_layer(lp, x)
            return y, jnp.float32(aux), jnp.float32(z)

        y, back = jax.vjp(pieces, lp, x)
        ones = tuple(jnp.float32(w) for w in side)
        grads, below = back((c.astype(y[0].dtype), *ones))
        compared, compared_below = back(((c * mask).astype(y[0].dtype), *ones))
        return below, chain_stats(lp, grads), compared, compared_below

    def want_side(lp, x, c, got_grads, got_below):
        def plain_layer(lp, x):  # a part's intermediates at a time
            h = jax.checkpoint(lambda lp, x: reference.attention_part(
                lp, x, sizes))(lp, x)
            return jax.checkpoint(lambda lp, h: reference.ffn_part(
                lp, h, sizes))(lp, h)

        _, back = jax.vjp(plain_layer, f32(lp), f32(x))
        grads, below = back((f32(c), *(jnp.float32(w) for w in side)))
        return against(got_grads, grads), against(got_below, below)

    got_side, want_side = jax.jit(got_side), jax.jit(want_side)
    for index in reversed(range(n_layers)):
        lp, x = params["layers"][index], streams[index]
        mask = jnp.asarray(decided_at[index], x.dtype).reshape(1, -1, 1)
        below, stats, compared, compared_below = got_side(lp, x, cotangent, mask)
        leaf_sums, below_sums = want_side(
            lp, x, cotangent * mask, compared, compared_below)
        leaf_names = names(lp, f"['layers'][{index}]")
        record(leaf_names, leaf_sums)
        chain.update(zip(leaf_names, host(stats)))
        stream_rms.append(whole_rms(below_sums))
        cotangent = below
        del compared, compared_below
    embed = jnp.zeros(params["embed"].shape, jnp.float32).at[row[0]].add(
        cotangent[0].astype(jnp.float32))
    chain["['embed']"] = host(jax.jit(chain_stats)(
        {"embed": params["embed"]}, {"embed": embed}))[0]

    worst = max(leaf_rms, key=lambda n: (np.isnan(leaf_rms[n]), leaf_rms[n]))
    read = {
        "grads_rms": float(leaf_rms[worst]), "grads_rms_worst_leaf": worst,
        "grad_stream_rms": float(np.max(stream_rms)),
        "grad_stream_layers_rms": stream_rms[::-1],  # the embedding's first
        "step_grad_norms": 0.0, "step_gate_grad_norms": 0.0, "update_norm": 0.0,
        "update_over_rule": 0.0, "update_total": 0.0,
    }
    if stepped is None:
        return read
    if set(stepped) != set(chain):
        raise BenchError("the step's leaves are not the chain's: "
                         f"{sorted(set(stepped) ^ set(chain))}")
    total = {n: (stepped[n].sum(axis=0), chain[n].sum(axis=0)) for n in chain}
    norms = {n: abs(math.sqrt(got[0] / want[0]) - 1.0)
             for n, (got, want) in total.items()}
    # the routers' gates apart: a gate's gradient is the small difference of
    # eight gates' pulls a position, and two compiled programs of the step
    # route the positions nearest a tie otherwise (see TOLERANCES)
    for reading, gates in (("step_grad_norms", False), ("step_gate_grad_norms", True)):
        leaves = [n for n in norms if n.endswith("['moe']['gate']") == gates]
        worst = max(leaves, key=lambda n: (np.isnan(norms[n]), norms[n]))
        read.update({reading: float(norms[worst]), reading + "_worst_leaf": worst})
    # the change: a leaf of its own where the plain rule moves enough of it
    groups = {}
    for n, (got, want) in total.items():
        group = n if want[2] >= plain.CHANGED_ELEMENTS_MIN else "the small leaves"
        was = groups.get(group, (0.0, 0.0))
        groups[group] = (was[0] + got[1], was[1] + want[1])
    # the norm of a group's change over what the plain rule makes of the
    # chain's gradient: ``update_norm`` by how much the worst group fell
    # short (a leaf left as it was reads 1); ``update_over_rule`` by how much
    # the worst group went over, REPORTED with its parts and not held (see
    # TOLERANCES: a near-empty expert); ``update_total`` the whole tree's,
    # either way (a doubled rate, every leaf applied twice)
    ratios = {n: (math.sqrt(got / want) if want else (math.inf if got else 1.0))
              for n, (got, want) in groups.items()}
    for reading, sign in (("update_norm", -1.0), ("update_over_rule", 1.0)):
        worst = max(ratios, key=lambda n: (
            np.isnan(ratios[n]), sign * (ratios[n] - 1.0)))
        read.update({reading: max(0.0, sign * (ratios[worst] - 1.0)),
                     reading + "_worst_leaf": worst})
    got, want = (sum(group[side] for group in groups.values()) for side in (0, 1))
    read["update_total"] = abs(math.sqrt(got / want) - 1.0)
    # what went over, a part at a time (an expert stack's experts): whether
    # the step moved MORE elements than the rule or moved them FURTHER
    over = read["update_over_rule_worst_leaf"]
    if over in chain:
        read["update_over_rule_parts"] = {
            name: [side[over][:, column].tolist() for side in (stepped, chain)]
            for column, name in enumerate((
                "grad_sq_step_and_chain", "change_sq_step_and_rule",
                "elements_moved_step_and_rule"))}
    read["update_groups"] = len(groups)
    return read


def compare_with_reference(model, params, reference, config, ids, targets=None,
                           operand_dtype=None, wrong=None) -> dict:
    """The program against the reference on ``ids`` [1, S], noised as the
    timed step noises that row from an empty optimizer state, a layer at a
    time ON THE PROGRAM'S OWN STREAM and the noised half's logits a block of
    positions at a time.  ``targets`` is not read (the row predicts itself).
    With ``operand_dtype`` the REFERENCE at that precision takes the
    program's place (what a too-low precision would read); with ``wrong``
    one of ``WRONG_PROGRAMS`` does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _beside("train_recipe_blocks")
    plain = _beside("train_recipe_qwen3next")
    sizes = reference_sizes(config)
    head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_layers, s = len(params["layers"]), ids.shape[1]
    cfg = model.cfg
    if wrong is not None and operand_dtype is not None:
        raise BenchError("a wrong program or the reference's precision, not both")

    def f32(a):
        return a.astype(jnp.float32)

    # the noise the timed step draws for this row from an empty optimizer
    # state (a count of 0); the draws are the reference's INPUTS, and its own
    # restatement makes its row
    noise_key = jax.jit(lambda ids: model.noise_key(
        {"count": jnp.zeros((), jnp.int32)}, ids))(ids)
    u, t = jax.jit(lambda key: model.noise_draws(key, ids.shape[0]))(noise_key)
    row, weights = reference.noised_row(ids, u, t, sizes)
    got_row, got_weights = jax.jit(model.noised_row)(ids, u, t)
    noise_problems = float(jnp.sum(got_row != row) + jnp.sum(
        jnp.abs(got_weights - weights) > 1e-6 * weights))

    if operand_dtype is None:
        program = _wrong_program(model, wrong)
        got_model = program.pieces
        x = params["embed"][row].astype(cfg.dtype)  # what _hidden starts from

        def got_layer(lp, x):
            """The program's layer from its own pieces (what ``_layer``
            composes; ``hidden_token_median`` holds ``_hidden`` to it):
            ``(y, the attention's output, aux, z, the router's logits, what
            the router read)``."""
            h, _, _ = got_model._attention_part(lp, x, cfg.attention_layer(0))
            y, aux = got_model._ffn_block(lp, h, None, 0)
            m = got_model._norm(lp["ln2"], h).reshape(-1, h.shape[-1])
            logits = got_model.moe.router_logits(lp["moe"], m)
            return y, h - x, aux["aux_loss"], aux["router_z_loss"], logits, m

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        program = types.SimpleNamespace(whole=None, gradients=True)  # pieces alone
        x = reference.embed(params, row)

        def got_layer(lp, x):
            h = reference.attention_part(lp, x, sizes, operand_dtype)
            y, aux, z = reference.ffn_part(lp, h, sizes, operand_dtype)
            m = reference.norm(h, lp["ln2"], sizes["norm_eps"])
            return (y, h - x, aux, z, reference.router_logits(lp, h, sizes),
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

    @jax.jit
    def one_layer(lp, x):
        got, out, got_aux, got_z, logits, m = got_layer(lp, x)
        h = reference.attention_part(lp, f32(x), sizes)
        want, aux, z = reference.ffn_part(lp, h, sizes)
        # the router's arithmetic alone: the reference's product on what the
        # program's router read; and its logits against the reference's own,
        # whose input the reference's attention left (absolute: MARGIN's measure)
        with jax.default_matmul_precision("highest"):
            same_input = reference.router(
                {"gate": f32(lp["moe"]["gate"])}, f32(m), sizes)[0]
        stream_diff = logits - reference.router_logits(lp, h, sizes)
        return (got.astype(x.dtype), position_sums(got, want),
                reference.router_margin(lp, h, sizes),
                (rel_rms(logits, same_input),
                 jnp.sqrt(jnp.mean(stream_diff * stream_diff))),
                rel_rms(out, h - f32(x)), (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    at_margins = {m: ([], []) for m in (MARGIN / 2, MARGIN / 4)}  # what MARGIN was chosen among
    # the embedding, then the layers
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, row)), slice(None))]
    near_tie, logits_rms, stream_rms, attention_rms = [], [], [], []
    got_aux = got_z = aux = z = 0.0
    streams, decided_at = [], []  # what each layer read; where it is compared
    for lp in params["layers"]:
        streams.append(x)
        x, sums, margin, router_rms, attn_rms, got_side, want_side = one_layer(lp, x)
        decided = np.asarray(margin) >= MARGIN
        decided_at.append(decided)
        near_tie.append(1.0 - float(decided.mean()))
        logits_rms.append(float(router_rms[0]))
        stream_rms.append(float(router_rms[1]))
        layers_rms.append(decided_rms(sums, decided))
        for narrower, (shares, rms) in at_margins.items():
            firm = np.asarray(margin) >= narrower
            shares.append(1.0 - float(firm.mean()))
            rms.append(decided_rms(sums, firm))
        attention_rms.append(float(attn_rms))
        got_aux, got_z = got_aux + float(got_side[0]), got_z + float(got_side[1])
        aux, z = aux + float(want_side[0]), z + float(want_side[1])

    @jax.jit
    def block_sums(head_params, x, tgt, w):
        want = reference.head(head_params, f32(x), sizes)
        got = f32(got_logits(head_params, x))
        diff = jnp.abs(got - want)
        above = jax.lax.map(lambda edge: jnp.sum(diff > edge), edges)
        return (position_sums(got, want), above,
                reference.ce_sum_of_logits(want, tgt, w),
                reference.ce_sum_of_logits(got, tgt, w))

    block = min(blocks.LOGIT_BLOCK, s)
    if s % block:
        raise BenchError(f"seq_len {s} is no multiple of {block}")
    x_noised = x[:, :s]  # the head reads the noised half alone
    want_ce = got_ce = 0.0
    diff_sq, want_sq = [], []  # a position, float64
    above = [0] * len(blocks.EDGES)
    for start in range(0, s, block):
        part = slice(start, start + block)
        (d2, w2), counts, wce, gce = block_sums(
            head_params, x_noised[:, part], ids[:, part], weights[:, part])
        diff_sq.append(np.asarray(d2, np.float64))
        want_sq.append(np.asarray(w2, np.float64))
        want_ce, got_ce = want_ce + float(wce), got_ce + float(gce)
        above = [a + int(c) for a, c in zip(above, counts)]
    diff_sq, want_sq = np.concatenate(diff_sq), np.concatenate(want_sq)
    elements = s * config["vocab_size"]
    want_loss = reference.total_loss(want_ce / s, aux, z, n_layers, sizes)
    if operand_dtype is None and program.whole is not None:
        # the program WHOLE, as loss_fn composes it under the same key
        got_loss, whole = jax.jit(lambda p, i, key: (
            program.whole.loss_fn(p, i, i, key)[0],
            program.whole._hidden(p, program.whole.noised_row(
                i, *program.whole.noise_draws(key, i.shape[0]))[0])[0]))(
                    params, ids, noise_key)
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
        with plain._kept_out_of_the_compile_cache():
            gradients = compare_gradients(
                program, model, params, reference, config, sizes, ids, row,
                weights, got_layer, got_logits, streams, decided_at, x_noised,
                operand_dtype)
    return {
        **gradients,
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        "attention_rms": float(np.max(attention_rms)),
        "router_logits_rms": float(np.max(logits_rms)),
        "logits_rms": math.sqrt(diff_sq.sum() / elements) / scale,
        "logits_p999": blocks.quantile_from_counts(above, elements, 0.999) / scale,
        "logits_token_median": float(np.median(np.sqrt(diff_sq / want_sq))),
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "hidden_token_median": hidden_median,
        "near_tie_share": max(near_tie),
        "noise_mismatches": noise_problems,
        "reference_loss": want_loss,
        "reference_logits_rms": scale,
        "masked_share_of_the_row": float(jnp.mean(weights > 0)),
        "embed_and_layers_rms": layers_rms,
        "near_tie_shares": near_tie,
        # a layer: the share of its positions the mixture's forward and
        # backward comparisons DO read (the rest lie nearer a tie than MARGIN)
        "compared_position_shares": [1.0 - share for share in near_tie],
        "router_logits_layers_rms": logits_rms,
        "router_logits_abs_rms_on_the_references_stream": stream_rms,
        "attention_layers_rms": attention_rms,
        "near_tie_share_and_layers_rms_at_narrower_margins": {
            str(m): [max(shares), max(rms)] for m, (shares, rms) in at_margins.items()},
    }


class _StepOutsideTheWindow:
    """The program's train step as ``train_recipe_share.run`` calls it.  The
    first call and the calls after the window hand back, as the loss, the
    program's ``loss_fn`` on the parameters and batch they were given under
    ONE FIXED noise key (taken before the step runs: the step donates); the
    step itself runs as ever.  Every other call IS the step.  ``fixed_on``
    keeps the batches those losses were taken on: :func:`run` holds them to
    what it assumes of that file's ``run`` and fails by name otherwise."""

    def __init__(self, model, step, window):
        import jax

        self.step, self.window, self.calls, self.fixed_on = step, window, 0, []
        self.lower = step.lower  # the compiled step's text is the step's
        key = jax.random.key(FIXED_NOISE_SEED)
        self.fixed_loss = jax.jit(
            lambda params, ids: model.loss_fn(params, ids, ids, key)[0])

    def __call__(self, params, opt_state, ids, targets):
        self.calls += 1
        if self.calls > 1 and not self.window.over:
            return self.step(params, opt_state, ids, targets)
        self.fixed_on.append(ids)
        loss = self.fixed_loss(params, ids)
        params, opt_state, _, metrics = self.step(params, opt_state, ids, targets)
        return params, opt_state, loss, metrics


class _SetUpPhaseNamed:
    """The set-up clock, ``train_recipe_share.run``'s phase
    ``level_router_bias`` filed under what this runner does there."""

    def __init__(self, clock):
        self._clock = clock

    def mark(self, phase: str) -> None:
        self._clock.mark(SETUP_PHASE if phase == "level_router_bias" else phase)

    def __getattr__(self, name):
        return getattr(self._clock, name)


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    manifest = harness.load_manifest(args.manifest)
    share = harness.load_module(manifest, "runners", "train_recipe_share")
    window = types.SimpleNamespace(over=False)
    timed = []  # the one step ``share.run`` makes

    def with_the_runners_set_up(model):
        """THIS run's model, the instance alone (no other model of the
        process, not the class), answers ``share.run``'s two calls with the
        runner's: the set-up call (the model has no bias to level) with
        :func:`route_like_a_trained_model`, and ``make_train_step`` with the
        program's own step, remembered and wrapped."""
        make = model.make_train_step

        def remembered(optimizer, *args, **kwargs):
            step = make(optimizer, *args, **kwargs)
            _MADE_STEPS.append((model, optimizer, step))
            timed.append(_StepOutsideTheWindow(model, step, window))
            return timed[-1]

        model.make_train_step = remembered
        model.level_router_bias = lambda params, token_batches: (
            route_like_a_trained_model(model, params, token_batches))

    def load_module(manifest, kind, name):
        module = harness.load_module(manifest, kind, name)
        if (kind, name) == ("runners", "train_recipe"):
            zipf = module.zipf_batches
            module.zipf_batches = lambda rng, vocab, *how: zipf(
                rng, vocab - 1, *how)  # :func:`data_vocab`
        if (kind, name) == ("runners", "train_step"):
            # where ``share.run`` first hands the model it made to a name
            # this runner can replace, before either of the two calls
            check_layout = module._check_layout

            def check_layout_of(model, *rest):
                with_the_runners_set_up(model)
                return check_layout(model, *rest)
            module._check_layout = check_layout_of
        return module

    @contextlib.contextmanager
    def quiet_gc():
        with harness.quiet_gc():
            yield
        window.over = True

    # this process's own copy of the module: its run() looks these up
    share.harness = types.SimpleNamespace(**{
        **vars(harness), "load_module": load_module, "quiet_gc": quiet_gc})
    share.CFG_FIELDS = CFG_FIELDS
    share._check_sizes = _check_sizes
    share.compare_with_reference = compare_with_reference
    share.TOLERANCES = TOLERANCES  # its over_tolerance and REFERENCE line read it
    share.MARGIN = MARGIN
    # a rehearsal's rows have 8 blocks, not 2,048: any share of them is masked
    share.share_problems = share_problems if not config.get("tiny") else (
        lambda counters: share_problems(counters, (0.0, 1.0)))
    share.STEP_COUNTERS = STEP_COUNTERS
    share.EXTRA_SCOPES = EXTRA_SCOPES
    try:
        result = share.run(cell, config, traffic, args, _SetUpPhaseNamed(clock))
    finally:
        _MADE_STEPS.clear()
    # what the fixed-key loss assumes of ``share.run``: one step, whose first
    # call and whose one call after the window are the two it compares, both
    # on the first pool batch.  A run that calls otherwise says so here.
    fixed_on = timed[0].fixed_on if len(timed) == 1 else []
    if len(fixed_on) != 2 or fixed_on[0] is not fixed_on[1]:
        raise BenchError(
            "train_recipe_share.run no longer calls the step as this runner "
            f"assumes: {len(timed)} step(s) made, the fixed-key loss taken "
            f"{len(fixed_on)} time(s)")
    return result
