"""Runner ``train_recipe_hybrid``: ``train_recipe_share``'s run for a share
whose layers are ONE mixer each: Mamba-2 state-space layers, attention
layers and mixture layers of un-gated experts
(``nemotron-labs-twotower-30b-a3b``).

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is, so the set-up (the
levelling call included), the warm-up, the window, the share's checks on
every step (``dropped_fraction`` 0, ``local_rows_over_level`` 0.5-1.5,
``expert_load_max_over_mean`` under its limit), the Zipf generator and the
printed lines are that file's own code, not a copy.  The names its ``run``
looks up in its module are replaced, in this process's private copy of it,
with what this file defines:

- ``CFG_FIELDS`` / ``_check_sizes``: the configuration file restates the
  sizes under the ``nemotron_h`` key names; ``n_routed_experts`` is the
  experts HELD and ``n_routed_experts_published`` the router's width; the
  first ``n_layers`` characters of ``hybrid_override_pattern`` are
  compared with the program's ``mixer_pattern`` layer by layer, the
  state-space mixer's sizes, its chunk and its time-step range with the
  program's, and no layer may rotate.
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``:
  ``train_recipe_share``'s comparison, a layer at a time ON THE PROGRAM'S
  OWN STREAM, for layers of one mixer: a state-space layer compares the
  mixer's output at every position AND the recurrent state after the last
  one (the program's chunked scan against the reference's scan over the
  positions); a mixture layer leaves out the positions whose 6th and 7th
  largest ``score + bias`` lie within ``MARGIN`` in the reference, one of
  the two a held expert (the router reads the layer's own input: there is
  no attention before it); an attention layer leaves out none.
- ``STEP_COUNTERS``: ``ssm_decay_min`` joins the step's counters.
  ``LOAD_MAX_OVER_MEAN``: this cell's own (see there).
- ``EXTRA_SCOPES``: the state-space mixer's scopes (``ssm/in_proj``,
  ``ssm/conv``, ``ssm/scan``, ``ssm/gate_norm``, ``ssm/out_proj``, and
  ``ssm`` for what lies under none of them: the layer's norm).
"""

from __future__ import annotations

import math
import os

import harness
from harness import BenchError

# the file's key (nemotron_h's config.json, then this repo's) -> the
# program's config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "seq_len": "seq_len", "n_routed_experts_published": "num_experts",
    "n_routed_experts": "held_experts", "first_held_expert": "first_held_expert",
    "num_experts_per_tok": "k", "moe_intermediate_size": "expert_ffn_dim",
    "moe_shared_expert_intermediate_size": "shared_expert_dim",
    "n_shared_experts": "shared_experts",
    "mamba_num_heads": "ssm_heads", "mamba_head_dim": "ssm_head_dim",
    "ssm_state_size": "ssm_state_dim", "n_groups": "ssm_groups",
    "conv_kernel": "ssm_conv_kernel", "chunk_size": "ssm_chunk",
    "time_step_floor": "ssm_dt_floor",
    "norm_topk_prob": "renormalize", "router_score": "router_score",
    "routed_scaling_factor": "routed_scale", "router_bias": "router_bias",
    "router_bias_rate": "router_bias_rate", "norm_eps": "norm_eps",
    "layer_norm_epsilon": "norm_eps", "mlp_hidden_act": "expert_kind",
    "tie_word_embeddings": "tie_embeddings",
    "norm": "norm", "positions": "positions", "expert_kind": "expert_kind",
    "routing": "routing", "router_input": "router_input",
    "aux_loss_weight": "aux_loss_weight", "router_z_weight": "router_z_weight",
    "remat": "remat", "scan_layers": "scan_layers",
    "stack_layers": "stack_layers",
}
MIXERS = {"M": "ssm", "*": "attention", "E": "moe"}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 39): the largest the program gave over its seeds,
# and the reference itself with every matmul operand rounded to
# float8_e4m3 (the nearest precision below the configuration's bf16), run
# through this same comparison in the program's place, which must fall
# outside.  ``ssm_rms`` and ``ssm_state_rms`` are the state-space layers'
# own: the mixer's output (without the residual stream, which both sides
# share) and the state after the last position, the worst layer; the
# program's scan with its decays computed in bf16 falls outside them.
# ``hidden_token_median`` has no second precision (both sides are the
# program): a ``_hidden`` that composes another stack than the layers run
# reads tens of percent (tests/test_nemotron_hybrid.py).
# ``near_tie_share`` guards the comparison itself: at least three quarters
# of the positions are compared in every layer.
TOLERANCES = {"layers_rms": 3e-2, "ssm_rms": 1.5e-2, "ssm_state_rms": 2e-2,
              "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2, "loss": 2e-4,
              "hidden_token_median": 2e-2, "near_tie_share": 0.25}
# train_recipe_share's margin, for its reason, from this cell's reading: a
# token whose 6th and 7th largest ``sigmoid score + bias`` lie closer than
# this in the reference, one of the two a held expert, is not compared in
# that layer.  The program's scores differ from the reference's by
# ``router_score_rms`` (the REFERENCE line reports it: 2.0e-4 on the chip,
# so this is five of those); at glm's 2**-9 this stack's levelled routers
# leave out 9 to 20 % of the positions, the deeper the layer the more (a
# quarter of each layer's experts is held, and nothing but mixers of the
# same stream lies between two routers): too near the guard's 25 %.
MARGIN = 2.0 ** -10
# ``expert_load_max_over_mean`` (over ALL 128 experts, mean over the four
# mixture layers) in every step of the window: from this cell's own seeds
# (PERF.md section 2, PR 39: 1.11 to 1.16 in every step of every run; a
# collapse reads 21.3); the limit does not transfer between cells (PERF.md
# section 6, PR 37: glm's reads 2.2 to 4.2).  Here a state-space layer's
# output at a position depends on the whole prefix, so the occurrences of
# one id do not share their router scores as they do after global
# attention over seeded weights, and the levelled loads stay level.
LOAD_MAX_OVER_MEAN = 2.0
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "local_rows_over_level", "router_bias_abs_max",
                 "ssm_decay_min")
EXTRA_SCOPES = ("shared_expert", "router_bias", "ssm/in_proj", "ssm/conv",
                "ssm/scan", "ssm/gate_norm", "ssm/out_proj", "ssm")


def _check_sizes(config: dict, cfg) -> None:
    import jax.numpy as jnp

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    got["hybrid_override_pattern"] = cfg.mixer_pattern
    got["time_step_min"], got["time_step_max"] = cfg.ssm_dt_range
    got["rotated_layers"] = [
        i for i in range(cfg.n_layers) if cfg.attention_layer(i).rotary]
    got["sliding_window"] = [
        i for i in range(cfg.n_layers)
        if cfg.attention_layer(i).window is not None]
    want = dict(
        config,
        hybrid_override_pattern=tuple(
            MIXERS.get(c, c)
            for c in config["hybrid_override_pattern"][: config["n_layers"]]),
        sliding_window=[] if config["sliding_window"] is None
        else config["sliding_window"],
    )
    wrong = {k: (want.get(k), v) for k, v in got.items() if want.get(k) != v}
    if wrong:
        raise BenchError(
            f"configuration file and program disagree (file, program): "
            f"{wrong}"
        )


def reference_sizes(config: dict) -> dict:
    """What the reference is given: the FILE's sizes, not the program's."""
    return dict(
        pattern=config["hybrid_override_pattern"][: config["n_layers"]],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_state_size=config["ssm_state_size"],
        n_groups=config["n_groups"], conv_kernel=config["conv_kernel"],
        experts_per_token=config["num_experts_per_tok"],
        norm_eps=config["norm_eps"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        held=(config["first_held_expert"], config["n_routed_experts"]),
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
    )


def _blocks():
    return harness.load_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "train_recipe_blocks.py"))


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None, decay_dtype=None) -> dict:
    """The program against the reference on ``ids`` [1, S], a layer at a
    time ON THE PROGRAM'S OWN STREAM and the logits a block of positions
    at a time.  With ``operand_dtype`` the REFERENCE at that precision
    takes the program's place (what a too-low precision would read); with
    ``decay_dtype`` the program's own scan computes its decays in that
    dtype (what a scan without float32 decays would read)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _blocks()
    sizes = reference_sizes(config)
    head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_sparse = reference.sparse_layers(params, sizes)

    def f32(a):
        return a.astype(jnp.float32)

    if operand_dtype is None:
        from learning_at_home_tpu.models.trunk import ssm_mixer

        cfg = model.cfg
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def got_ssm(lp, x):
            """The program's mixer alone: its output and its last state."""
            out, state, _ = ssm_mixer(
                lp["ssm"], model._norm(lp["norm"], x), cfg.ssm_heads,
                cfg.ssm_groups, cfg.ssm_chunk, cfg.norm_eps,
                **({} if decay_dtype is None else {"decay_dtype": decay_dtype}))
            return out, state

        def got_layer(lp, x, index):
            y, aux = model._layer(lp, x, index, None, cfg.attention_layer(index))
            if aux is None or "aux_loss" not in aux:
                return y, 0.0, 0.0
            return y, aux["aux_loss"], aux["router_z_loss"]

        def got_scores(lp, x):
            """The program's router scores plus bias, on its own stream."""
            m = model._norm(lp["norm"], x).reshape(-1, x.shape[-1])
            return jax.nn.sigmoid(
                model.moe.router_logits(lp["moe"], m)) + lp["moe"]["router_bias"]

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        x = reference.embed(params, ids)
        got_scores = None

        def got_ssm(lp, x):
            return reference.ssm_part(lp, x, sizes, operand_dtype)

        def got_layer(lp, x, index):
            return reference.layer(lp, x, sizes, index, operand_dtype)

        def got_logits(head_params, x):
            return reference.head(head_params, x, sizes, operand_dtype)

    def position_sums(got, want):
        """Sums of squares a position: of the difference, of the reference."""
        diff = f32(got) - want
        return (jnp.sum(diff * diff, axis=-1).ravel(),
                jnp.sum(want * want, axis=-1).ravel())

    def rel_rms(got, want):
        diff = f32(got) - want
        return jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(want * want))

    every = jnp.full((ids.shape[0] * ids.shape[1],), jnp.inf)  # all decided

    def one_layer(lp, x, index):
        """Layer ``index`` on the stream ``x``: the stream it leaves, its
        sums against the reference's, the margin a position is decided by,
        the router's score error, both sides' losses, and for a
        state-space layer the mixer's and the state's own errors."""
        which = reference.kind(sizes, index)
        zero = jnp.float32(0)
        if which == "M":
            out, state = got_ssm(lp, x)
            got = (x + out.astype(x.dtype)) if operand_dtype is None else x + out
            want_out, want_state = reference.ssm_part(lp, f32(x), sizes)
            want = f32(x) + want_out
            return (got, position_sums(got, want), every, zero, (0.0, 0.0),
                    (0.0, 0.0), (rel_rms(out, want_out),
                                 rel_rms(state, want_state)))
        got, got_aux, got_z = got_layer(lp, x, index)
        want, aux, z = reference.layer(lp, f32(x), sizes, index)
        margin, scores_sq = every, zero
        if which == "E":
            margin = reference.router_margin(lp, f32(x), sizes)
            if got_scores is not None:
                scores_sq = jnp.mean((got_scores(lp, x) - reference.router_scores(
                    lp, f32(x), sizes)) ** 2)
        return (got, position_sums(got, want), margin, scores_sq,
                (got_aux, got_z), (aux, z), (zero, zero))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, then the layers: one compiled pair a KIND of layer
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, ids)), slice(None))]
    near_tie, score_rms, ssm_rms, state_rms = [], [], [], []
    compiled = {}
    got_aux = got_z = aux = z = 0.0
    for index, lp in enumerate(params["layers"]):
        which = reference.kind(sizes, index)
        if which not in compiled:
            compiled[which] = jax.jit(
                lambda lp, x, index=index: one_layer(lp, x, index))
        x, sums, margin, scores_sq, got_side, want_side, ssm = compiled[which](lp, x)
        decided = np.asarray(margin) >= MARGIN
        layers_rms.append(decided_rms(sums, decided))
        if which == "E":
            near_tie.append(1.0 - float(decided.mean()))
            score_rms.append(math.sqrt(float(scores_sq)))
        if which == "M":
            ssm_rms.append(float(ssm[0]))
            state_rms.append(float(ssm[1]))
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
    if operand_dtype is None and decay_dtype is None:
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
        got_loss = reference.total_loss(got_ce / s, got_aux, got_z, n_sparse,
                                        sizes)
        hidden_median = 0.0
    scale = math.sqrt(want_sq.sum() / elements)
    return {
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        "ssm_rms": float(np.max(ssm_rms)),
        "ssm_state_rms": float(np.max(state_rms)),
        "logits_rms": math.sqrt(diff_sq.sum() / elements) / scale,
        "logits_p999": blocks.quantile_from_counts(above, elements, 0.999) / scale,
        "logits_token_median": float(np.median(np.sqrt(diff_sq / want_sq))),
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "hidden_token_median": hidden_median,
        "near_tie_share": max(near_tie),
        "reference_loss": want_loss,
        "reference_logits_rms": scale,
        "embed_and_layers_rms": layers_rms,
        "ssm_layers_rms": ssm_rms,
        "ssm_states_rms": state_rms,
        "near_tie_shares": near_tie,
        "router_score_rms": score_rms,
    }


def run(cell: dict, config: dict, traffic: dict, args, clock) -> dict:
    manifest = harness.load_manifest(args.manifest)
    share = harness.load_module(manifest, "runners", "train_recipe_share")
    # this process's own copy of the module: its run() looks these up
    share.CFG_FIELDS = CFG_FIELDS
    share._check_sizes = _check_sizes
    share.compare_with_reference = compare_with_reference
    share.TOLERANCES = TOLERANCES  # its over_tolerance and REFERENCE line read it
    share.MARGIN = MARGIN
    share.LOAD_MAX_OVER_MEAN = LOAD_MAX_OVER_MEAN
    share.STEP_COUNTERS = STEP_COUNTERS
    share.EXTRA_SCOPES = EXTRA_SCOPES
    return share.run(cell, config, traffic, args, clock)
