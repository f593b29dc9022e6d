"""Runner ``train_recipe_latent``: ``train_recipe_share``'s run for a share
whose attention is expanded from latents and whose stack is followed by a
block that predicts the next-but-one token with a loss of its own
(``glm-4.7-flash``).

It IS ``train_recipe_share``'s run: that module is loaded through
``harness`` and its ``run`` is called as it is, so the set-up (the
levelling call included), the warm-up, the window, the share's checks on
every step (``dropped_fraction`` 0, ``local_rows_over_level`` 0.5-1.5,
``expert_load_max_over_mean`` under its limit), the Zipf generator and the printed
lines are that file's own code, not a copy.  The names its ``run`` looks
up in its module are replaced, in this process's private copy of it, with
what this file defines:

- ``CFG_FIELDS`` / ``_check_sizes``: the configuration file restates the
  sizes under GLM-4.7-Flash's key names; ``n_routed_experts`` is the
  experts HELD and ``n_routed_experts_published`` the router's width; the
  head's size is ``qk_nope_head_dim + qk_rope_head_dim`` and the values'
  ``v_head_dim``; ``first_k_dense_replace`` is compared with the program's
  ``ffn_pattern``.
- ``compare_with_reference`` / ``TOLERANCES`` / ``MARGIN``:
  ``train_recipe_share``'s comparison, a layer at a time ON THE PROGRAM'S
  OWN STREAM, with the prediction block as one more layer: its combine
  (``[rms(embed[next]) ; rms(h)] W_eh``) on the program's final normalized
  stream and the row's next ids, its layer on the program's combine, and
  BOTH heads' logits a block of positions at a time, both losses.  A
  mixture layer (the block's too) leaves out the positions whose 4th and
  5th largest ``score + bias`` lie within ``MARGIN`` in the reference, one
  of the two a held expert.
- ``STEP_COUNTERS``: ``ce_mtp`` joins the step's counters.
  ``LOAD_MAX_OVER_MEAN``: this block's own (see there).
- ``EXTRA_SCOPES`` and the scope table (``_blocks``): the latent's scopes
  (``latent_down``, ``latent_up``, ``rope``) come out of ``attention``'s
  time as scopes of their own; what lies under ``mtp`` and under no other
  scope (the combine, the block's norms) is ``mtp``; and the table gains
  ``mtp_s``, the device self time of EVERYTHING under ``mtp`` (its
  attention, experts and loss pass included, which the scopes above also
  count under their own names).
"""

from __future__ import annotations

import math
import os
import re
import types

import harness
from harness import BenchError

# the file's key (GLM-4.7-Flash's config.json, then this repo's) -> the
# program's config field
CFG_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "n_layers": "n_layers", "num_attention_heads": "n_heads",
    "q_lora_rank": "q_latent_dim", "kv_lora_rank": "kv_latent_dim",
    "qk_rope_head_dim": "rope_head_dim", "v_head_dim": "head_dim",
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
    "norm": "norm", "positions": "positions", "expert_kind": "expert_kind",
    "routing": "routing", "router_input": "router_input",
    "aux_loss_weight": "aux_loss_weight", "router_z_weight": "router_z_weight",
    "remat": "remat", "scan_layers": "scan_layers",
    "stack_layers": "stack_layers",
}

# Each limit sits between two readings on the chip at 16,384 tokens
# (PERF.md section 2, PR 37): the largest the program gave over its seeds,
# and the reference itself with every matmul operand rounded to
# float8_e4m3 (the nearest precision below the configuration's bf16), run
# through this same comparison in the program's place, which must fall
# outside.  ``mtp_*`` are the prediction block's head, ``loss`` the
# weighted sum of both cross-entropies (the accepted cells' limit: the
# program reads 0.9e-5 to 1.0e-4 over 18 readings, float8 2.6e-4 to 3.2e-4).  The block's
# own ``ce_mtp`` is reported and has no limit: the precision hardly moves
# it (the program up to 1.9e-4, float8 from 2.8e-4), and ``loss`` holds it.
# ``hidden_token_median`` has no second precision (both sides are the
# program): a ``_hidden`` that composes another stack than the layers run
# reads tens of percent (tests/test_glm47.py).  ``near_tie_share`` guards
# the comparison itself: at least three quarters of the positions are
# compared in every layer.
TOLERANCES = {"layers_rms": 3e-2, "logits_rms": 1e-2, "logits_p999": 3e-2,
              "logits_token_median": 1e-2, "mtp_logits_rms": 1e-2,
              "mtp_logits_p999": 3e-2, "mtp_logits_token_median": 1e-2,
              "loss": 2e-4,
              "hidden_token_median": 2e-2, "near_tie_share": 0.25}
# train_recipe_share's margin, for its reason, from this cell's reading: a
# token whose 4th and 5th largest ``sigmoid score + bias`` lie closer than
# this in the reference, one of the two a held expert, is not compared in
# that layer.  The program's scores differ from the reference's by
# ``router_score_rms`` (the REFERENCE line reports it: 2.6e-4 to 2.9e-4 on
# the chip, so this is seven of those); at k-exaone's 2**-8 this block's
# levelled routers leave out 23 to 28 % of the positions, over the guard.
MARGIN = 2.0 ** -9
# ``expert_load_max_over_mean`` (over ALL 64 experts, mean over the five
# mixture layers) in every step of the window.  Not k-exaone's 3: under
# seeded weights this block's attention is every layer's global one, whose
# near-uniform average over thousands of keys leaves the occurrences of one
# id with near-identical router scores (k-exaone's first layers see 128
# keys), so after the first updates a Zipf row's commonest id (9.6 % of it:
# 1,568 rows where an expert's level share is 1,024) goes to its four
# experts whole: 1 + 1.53 = 2.53 a layer, 3.3 with the second id; the
# chip's own rows stay level (``local_rows_over_level``) and nothing drops.
# Read on the chip, the largest step of a run: 3.11 to 4.23 over 14
# seeds (one layer's commonest ids do share experts); fresh seeds read
# higher, and a collapse reads 16.  PERF.md section 6, PR 37.
LOAD_MAX_OVER_MEAN = 6.0
STEP_COUNTERS = ("dropped_fraction", "expert_load_max_over_mean",
                 "local_rows_over_level", "router_bias_abs_max", "ce_mtp")
EXTRA_SCOPES = ("shared_expert", "dense_ffn", "router_bias",
                "latent_down", "latent_up", "rope")
MTP = re.compile(r"[/(]mtp[/)]")


def _check_sizes(config: dict, cfg) -> None:
    import jax.numpy as jnp

    got = {name: getattr(cfg, field) for name, field in CFG_FIELDS.items()}
    got["dtype"] = jnp.dtype(cfg.dtype).name
    got["param_dtype"] = jnp.dtype(cfg.param_dtype).name
    got["num_key_value_heads"] = cfg.n_kv_heads or cfg.n_heads
    got["qk_nope_head_dim"] = cfg.head_dim - cfg.rope_head_dim
    pattern = cfg.ffn_pattern or ("moe",) * cfg.n_layers
    dense = config["first_k_dense_replace"]
    got["first_k_dense_replace"] = (
        dense if pattern == ("dense",) * dense + ("moe",) * (cfg.n_layers - dense)
        else pattern)
    got["layer_pattern"] = cfg.layer_pattern  # every layer global and rotated
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
        first_k_dense_replace=config["first_k_dense_replace"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        held=(config["first_held_expert"], config["n_routed_experts"]),
        aux_loss_weight=config["aux_loss_weight"],
        router_z_weight=config["router_z_weight"],
        mtp_loss_weight=config["mtp_loss_weight"],
    )


def _here(name: str):
    return harness.load_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), name))


def compare_with_reference(model, params, reference, config, ids, targets,
                           operand_dtype=None) -> dict:
    """The program against the reference on ``ids`` [1, S] and their
    ``targets`` (each position's next id), a layer at a time ON THE
    PROGRAM'S OWN STREAM, the prediction block as one more layer, and both
    heads' logits a block of positions at a time.  With ``operand_dtype``
    the REFERENCE at that precision takes the program's place (what a
    too-low precision would read)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blocks = _here("train_recipe_blocks.py")
    sizes = reference_sizes(config)
    mp = params["mtp"]
    n_layers = len(params["layers"])
    heads = {"": {"ln_f": params["ln_f"], "lm_head": params["lm_head"]},
             "mtp_": reference.mtp_head_params(params)}
    edges = jnp.asarray(blocks.EDGES, jnp.float32)
    n_sparse = reference.sparse_layers(params, sizes)

    def f32(a):
        return a.astype(jnp.float32)

    if operand_dtype is None:
        cfg = model.cfg
        x = params["embed"][ids].astype(cfg.dtype)  # what _hidden starts from

        def got_layer(lp, x, index):
            y, aux = model._layer(lp, x, index, None, cfg.attention_layer(index))
            if aux is None:
                return y, 0.0, 0.0
            return y, aux["aux_loss"], aux["router_z_loss"]

        def got_scores(lp, x, index):
            """The program's router scores plus bias, on its own stream."""
            h, _ = model._attention_block(lp, x, cfg.attention_layer(index))
            m = model._norm(lp["ln2"], h).reshape(-1, h.shape[-1])
            return jax.nn.sigmoid(
                model.moe.router_logits(lp["moe"], m)) + lp["moe"]["router_bias"]

        def got_final_norm(ln_f, x):
            return model._norm(ln_f, x)

        def got_combine(mp, table, hf, next_ids):
            return model._mtp_input(mp, hf, next_ids, table)

        def got_logits(head_params, x):
            return model._logits(model._norm(head_params["ln_f"], x),
                                 model._head(head_params))
    else:
        x = reference.embed(params, ids)
        got_scores = None

        def got_layer(lp, x, index):
            return reference.layer(lp, x, sizes, index, operand_dtype)

        def got_final_norm(ln_f, x):
            return reference.final_norm({"ln_f": ln_f}, x, sizes)

        def got_combine(mp, table, hf, next_ids):
            return reference.mtp_input(mp, table, hf, next_ids, sizes,
                                       operand_dtype)

        def got_logits(head_params, x):
            return reference.head(head_params, x, sizes, operand_dtype)

    def position_sums(got, want):
        """Sums of squares a position: of the difference, of the reference."""
        diff = f32(got) - want
        return (jnp.sum(diff * diff, axis=-1).ravel(),
                jnp.sum(want * want, axis=-1).ravel())

    def one_layer(lp, x, index):
        got, got_aux, got_z = got_layer(lp, x, index)
        h = reference.attention_part(lp, f32(x), sizes, index)
        want, aux, z = reference.ffn_part(lp, h, sizes, index)
        if "moe" in lp:
            margin = reference.router_margin(lp, h, sizes)
            scores_sq = jnp.float32(0) if got_scores is None else jnp.mean(
                (got_scores(lp, x, index) - reference.router_scores(
                    lp, h, sizes)) ** 2)
        else:  # a dense layer routes nothing: every position is decided
            margin = jnp.full((x.shape[0] * x.shape[1],), jnp.inf)
            scores_sq = jnp.float32(0)
        return (got, position_sums(got, want), margin, scores_sq,
                (got_aux, got_z), (aux, z))

    def decided_rms(sums, decided) -> float:
        d2, w2 = (np.asarray(a, np.float64) for a in sums)
        return math.sqrt(d2[decided].sum() / w2[decided].sum())

    # the embedding, the layers, the block's combine, the block's layer: one
    # compiled pair a KIND of layer (dense, mixture)
    layers_rms = [decided_rms(
        jax.jit(position_sums)(x, reference.embed(params, ids)), slice(None))]
    near_tie, score_rms = [], []
    compiled = {}
    sums_of = {"got": [0.0, 0.0], "want": [0.0, 0.0]}  # aux, z

    def run_layer(lp, x, index):
        dense = reference.is_dense(sizes, index)
        if dense not in compiled:
            compiled[dense] = jax.jit(
                lambda lp, x, index=index: one_layer(lp, x, index))
        y, sums, margin, scores_sq, got_side, want_side = compiled[dense](lp, x)
        decided = np.asarray(margin) >= MARGIN
        near_tie.append(1.0 - float(decided.mean()))
        score_rms.append(math.sqrt(float(scores_sq)))
        layers_rms.append(decided_rms(sums, decided))
        for side, pair in (("got", got_side), ("want", want_side)):
            sums_of[side] = [a + float(b) for a, b in zip(sums_of[side], pair)]
        return y

    for index, lp in enumerate(params["layers"]):
        x = run_layer(lp, x, index)

    @jax.jit
    def combine(mp, ln_f, table, x, next_ids):
        hf = got_final_norm(ln_f, x)
        got = got_combine(mp, table, hf, next_ids)
        want = reference.mtp_input(mp, table, f32(hf), next_ids, sizes)
        return got, position_sums(got, want)

    inner = {k: v for k, v in mp.items() if k != "layer"}
    z_mtp, sums = combine(inner, params["ln_f"], params["embed"], x, targets)
    layers_rms.append(decided_rms(sums, slice(None)))
    z_mtp = run_layer(mp["layer"], z_mtp, n_layers)

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
    elements = s * config["vocab_size"]
    read, ce = {}, {}
    # each head on the program's stream, against its own targets (the
    # block's: the row's shifted by one, none at the last position)
    for name, stream, tgt in (("", x, targets),
                              ("mtp_", z_mtp, reference.after_next(targets))):
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
    if operand_dtype is None:
        # the program WHOLE, as loss_fn composes it: both streams, both
        # cross-entropies
        def whole(p, i, t):
            loss, metrics = model.loss_fn(p, i, t)
            return loss, metrics["ce_mtp"], model._hidden(p, i, next_ids=t)[:2]

        got_loss, got_ce_mtp, streams = jax.jit(whole)(params, ids, targets)
        got_loss, got_ce_mtp = float(got_loss), float(got_ce_mtp)
        layered = jax.jit(lambda ln_f, x, out, z: (
            f32(model._norm(ln_f, x)), f32(model._norm(out, z))))(
                params["ln_f"], x, mp["out_norm"], z_mtp)
        hidden_median = 0.0
        for got_stream, want_stream in zip(streams, layered):
            h2, l2 = jax.jit(position_sums)(got_stream, want_stream)
            hidden_median = max(hidden_median, float(np.median(np.sqrt(
                np.asarray(h2, np.float64) / np.asarray(l2, np.float64)))))
    else:
        got_loss, got_ce_mtp = total(1, sums_of["got"]), ce["mtp_"][1]
        hidden_median = 0.0
    return {
        "layers_rms": float(np.max(layers_rms)),  # a nan stays one
        **read,
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "ce_mtp": abs(got_ce_mtp - ce["mtp_"][0]) / abs(ce["mtp_"][0]),
        "hidden_token_median": hidden_median,
        "near_tie_share": max(near_tie),
        "reference_loss": want_loss,
        "reference_ce_mtp": ce["mtp_"][0],
        # the embedding, the stack's layers, the block's combine, its layer
        "embed_and_layers_rms": layers_rms,
        "near_tie_shares": near_tie,
        "router_score_rms": score_rms,
    }


def _blocks_with_mtp():
    """``train_recipe_blocks`` as ``train_recipe_share.run`` uses it, its
    scope table taking in the prediction block: scope ``mtp`` after every
    other (what lies under it and under none of them), and ``mtp_s``, the
    device self time of everything under it."""
    import trace_reduce

    blocks = _here("train_recipe_blocks.py")

    def make_scope_times(base):
        table_of = blocks.make_scope_times(types.SimpleNamespace(
            SCOPES=base.SCOPES + (("mtp", MTP),),
            GROUPED_MATMUL=base.GROUPED_MATMUL,
            GROUPED_MATMUL_LAYOUT=base.GROUPED_MATMUL_LAYOUT,
        ))

        def scope_times(ops: list, hlo_text: str) -> dict:
            table = table_of(ops, hlo_text)
            op_name = blocks.op_names(hlo_text)
            table["mtp_s"] = sum(
                ns for name, ns in trace_reduce.self_times(ops).items()
                if MTP.search("/" + op_name.get(name, "") + "/")) / 1e9
            return table

        return scope_times

    return types.SimpleNamespace(make_scope_times=make_scope_times)


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
    share._blocks = _blocks_with_mtp
    return share.run(cell, config, traffic, args, clock)
