#!/usr/bin/env python3
"""Two readings a one-chip recipe's configuration rests on
(``smallthinker-21b-a3b`` by default; ``k-exaone-236b-a23b``,
``glm-4.7-flash`` and ``nemotron-labs-twotower-30b-a3b`` by name).

    python tools/smallthinker_probe.py memory [recipe]
    chiprun -- python tools/smallthinker_probe.py float8 [config.json] [seed ...]
    chiprun -- python tools/smallthinker_probe.py ssd [seq_len] [accuracy_len]
    chiprun -- python tools/smallthinker_probe.py conv [rows x channels ...]
    chiprun -- python tools/smallthinker_probe.py conv gated [rows x channels x strip ...]
    chiprun -- python tools/smallthinker_probe.py gate_norm [rows x strip ...]
    chiprun -- python tools/smallthinker_probe.py streams [rows x strip x chunk x stats_rows ...]
    chiprun -- python tools/smallthinker_probe.py delta [seq_len] [accuracy_len] [calls] [form ...] [config ...]
    chiprun -- python tools/smallthinker_probe.py delta split [seq_len] [chunk]
    chiprun -- python tools/smallthinker_probe.py delta parts [seq_len] [chunk] [calls] [config ...]

``memory`` (here, no chip): the whole train step of a recipe of
``__graft_entry__`` (``smallthinker_one_chip``, or the one named, such as
``k_exaone_one_chip``, ``glm_4_7_flash_one_chip`` or
``nemotron_labs_twotower_one_chip``) at published widths, compiled for a described v5e
chip; prints the compiler's ``memory_analysis()`` against the chip's
16,909,334,528 bytes, the tiles each of the step's grouped-matmul
instructions was compiled at (PERF.md section 3), the blocked attention
kernel's calls with the blocks and the grid each got, by layer kind, the
bytes remat keeps of them across the backward pass (PR 38:
``kept_residual_bytes``), the bytes it keeps of the attention part's matrix
products (``kept_product_bytes``) and how many of those products the
compiled step still runs a second time (``recomputed_attention_products``:
none, or the latent form's three products up from the latents a layer; PR
53), the scan
kernel's calls and what remat keeps of those (PR 40), and
how many products of the head's the compiled loss layer holds (three since
PR 34).  Nothing runs.

``float8`` (on the chip): the benchmark runner's own comparison of a
configuration (``benchmarks/configs/smallthinker-21b-a3b.json``, or the
file named), on seeded weights after as many train steps as the cell's
window leaves them (the configuration's ``probe_steps``, 48 by default,
over the cell's pool of 8 seeded rows of Zipf ids; the routers' biases
levelled first where the recipe has them), of the program and then, in
the program's place, of the configuration's plain reference with every
matmul operand rounded to float8_e4m3: the reading that the runner's
tolerances must refuse (PERF.md section 2).  bf16 operands follow, which
they must pass.  Where the runner's comparison takes a ``decay_dtype``
(``train_recipe_hybrid``: a state-space scan), one more reading follows:
the PROGRAM with its scan's decays computed in bf16, which the state-space
layer's limits must refuse.  Where the runner names ``WRONG_PROGRAMS``
(``train_recipe_qwen3next``: bf16 decays, a bf16 router, either gate
missing; a ``_hidden`` of another rule, a step on half the loss, a step
that leaves a leaf as it was), each is read in the program's place too (PR
55).  Words after the seeds choose the readings whose names hold one of
them (``float8 config.json 7 float8 _hidden "the step"``); the program
itself is always read.

``ssd`` (on the chip): the chunked scan of ``ops/ssd.py`` at the
Nemotron cell's shape (``[1, seq_len, 64, 64]``, state 128, 8 groups,
chunks of 128, bf16; ``seq_len`` 16,384 by default), the plain form
against the kernel: milliseconds a call forward and forward + backward
(a loss that reads the output and the last state), and at
``accuracy_len`` (4,096 by default: the float32 form's backward holds
every intermediate) the relative rms of each form's output, last state
and five gradients against the plain form in float32 at the highest
matmul precision (PERF.md section 6, PR 40).

``conv`` (on the chip): the mixer's causal convolution with its SiLU
(``ops/ssm_conv.py``) at the Nemotron cell's shape (``[1, 16384, 6144]``
bf16, four taps), the plain form against the kernel (``ssm_conv_fwd`` /
``ssm_conv_bwd``): milliseconds a call forward and forward + backward (a
``jax.vjp`` under a given bf16 cotangent: no loss of the probe's own in
the time), GB/s on the LEAST bytes a pass moves (``x`` in and ``y`` out;
``x`` and ``dy`` in and ``dx`` out), and the kernel's output and three
gradients against the plain form's.  ``512x1024``-like arguments time the
kernel at those blocks (rows x channels) too (PERF.md section 6, PR 41).
``conv gated``: the same for the gated short convolution that is LFM2's
whole mixer (``ops/short_conv.py``: ``short_conv_fwd`` / ``short_conv_bwd``) at
its cell's shape (``[1, 16384, 3 x 2048]`` bf16, three taps), the least
bytes ``[B | C | u]`` in and ``y`` out forward and those, ``dy`` in and ``d[B
| C | u]`` out backward; blocks as ``512x512x32`` (rows x channels x strip;
PERF.md section 6, PR 61).

``gate_norm`` (on the chip): the mixers' gate and grouped RMSNorm
(``ops/gate_norm.py``) as three cells call it (Nemotron: ``[1,
16384, 4096]`` bf16, groups of 512 under a scale a channel, the gate first,
``z`` at column 0 of ``[.., 10304]``, with the skip ``y + D x``;
Olmo-Hybrid: ``[1, 16384, 5760]``, groups of 192 under one shared scale,
the norm first, ``z`` at column 11,520 of ``[.., 17340]``; Ling-3.0, PR 67:
``[1, 16384, 4096]``, heads of 128 under one shared scale, the norm first,
the gate ONE float32 number a head ``[1, 16384, 32]`` under a sigmoid: the
least bytes are ``y`` in and the result out, and the cotangent in and ``dy``
out backward), the plain form
against the kernel (``gate_norm_fwd`` / ``gate_norm_bwd``): milliseconds a
call forward and forward + backward (a ``jax.vjp`` under a given bf16
cotangent), GB/s on the LEAST bytes a pass moves (forward ``y``, ``z``
(and ``x``) in and the result out; backward those and the cotangent in,
``dy``, ``dz`` (and ``dx``) out), and the kernel's output and gradients
against the plain form's.  ``256x32``-like arguments time the kernel at
those row blocks and strips too, a cell's name among the arguments reads
that cell alone (``gate_norm ling3 64x64 256x32``; PERF.md section 6, PR
47 and PR 67).

``streams`` (on the chip): the residual streams' three stages
(``ops/stream_mix.py``) at the ``xing4.0-29b-a4b`` cell's shape (``[1,
16384, 4, 3584]`` bf16, ``phi`` [14336, 24]), the plain form (on [B, S, n,
C] arguments, as the step before PR 65 held them) against the kernels (on
FOLDED arguments [B, S, n C], as the step hands them on since: a bitcast
of the kernels' view): milliseconds a call forward and forward + backward
(a ``jax.vjp`` under given cotangents), GB/s on the LEAST bytes a pass
moves (the write: ``x`` and ``y`` in and ``x'`` out, then those and the
cotangent in, ``dx`` and ``dy`` out; the read: ``x`` in and ``h`` out, then
``x`` and ``dh`` in and ``dx`` out; the statistics: ``x`` in, then ``x`` in
and ``dx`` out), and the kernels' results and gradients against the plain
form's.  ``64x16x128x256``-like arguments time the kernels at those blocks
too (the mixing kernels' rows, strip and chunk of lanes, the statistics'
rows; PERF.md section 6, PR 65).

``delta`` (on the chip): the chunked gated delta rule of
``ops/delta_rule.py`` at the Olmo-Hybrid cell's shape (``[1, seq_len, 30,
96 / 192]`` bf16; ``seq_len`` 16,384 by default), or at that of each
configuration of ``benchmarks/configs/`` named (``qwen3-next-80b-a3b``:
``[1, seq_len, 32, 128 / 128]``, the rule over the value heads), as its
kernels (``kernel``: ``delta_chunk_fwd`` / ``delta_chunk_bwd``, PR 46) and
in plain form for each way of inverting ``I + A`` (``blocks``, ``product``,
``triangular``; or the forms named), at chunks of 32, 64 and 128:
milliseconds a call forward, forward + backward and the backward call
alone (a ``jax.vjp``'s pullback under given cotangents: what a layer's
backward pass runs once remat keeps the forward's results, PR 58), and at
``accuracy_len`` the relative rms of the
output, the last state and the five gradients against the rule a position
at a time in float32 (PERF.md section 6, PR 45 and PR 46).  ``delta split
[seq_len] [chunk]``: where the PLAIN rule's time goes (the whole rule, the
solve alone, the diagonal blocks' inverses alone, the ``lax.scan`` over
the chunks alone, the rule with its solve stubbed out): the reading the
kernel's design started from (PR 46).  ``delta parts [seq_len] [chunk]
[calls] [config ...]``: where the KERNELS' time goes, by ablation: the
calls whole, then with one part replaced by a stand-in of this file at a
time (the 16-blocks' row steps, the joins, ``W`` and ``U``'s exact
products, the backward's four products through the solve, the state's
chain: :func:`_delta_stand_ins`), forward, forward + backward and the
backward call alone, and the digest of the whole calls' results (PERF.md
section 6, PR 62).
"""

import collections
import contextlib
import inspect
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]

CHIP_BYTES = 16_909_334_528  # memory_stats()["bytes_limit"] of a v5e chip
CONFIG = "benchmarks/configs/smallthinker-21b-a3b.json"


@contextlib.contextmanager
def no_compile_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _equations(jaxpr, primitive: str, path: str = ""):
    """(scope path, equation) of every ``primitive`` under ``jaxpr``; an
    inner equation's name stack is relative to the equation that holds it."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == primitive:
            yield here, eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)  # closed or open
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, primitive, here)


def _bytes(aval) -> int:
    import numpy as np

    return int(np.prod(aval.shape)) * aval.dtype.itemsize


def kept_residual_bytes(jaxpr, name: str | None = None) -> int:
    """The bytes remat's policy keeps across the backward pass under
    ``name``: the sum of the arrays the traced step names
    ``trunk.FLASH_RESIDUALS`` (the default: the blocked kernel's output
    and row sums, once a kernel layer, in the forward),
    ``ssd.SSD_RESIDUALS`` (the scan kernel's output and the states
    entering its chunks, once a state-space layer),
    ``delta_rule.DELTA_RESIDUALS`` (the delta rule's kernel's output and
    the state entering each of its grid steps, once a delta layer; PR 58) or
    ``trunk.ATTENTION_PRODUCTS`` (the results of the attention part's
    matrix products, once an attention layer; PR 53).  What they add to the
    compiled step's live bytes is at most this: the compiler reuses."""
    from learning_at_home_tpu.models.trunk import FLASH_RESIDUALS

    return sum(
        _bytes(eqn.outvars[0].aval) for _, eqn in _equations(jaxpr, "name")
        if eqn.params["name"] == (name or FLASH_RESIDUALS)
    )


def recomputed_attention_products(compiled_text: str) -> int:
    """How many matrix products of the attention part a compiled program
    runs a second time: its fusions rooted in a ``dot_general`` whose
    ``op_name`` lies under ``rematted_computation``, the attention scope
    and one of its products' stages (``proj``, ``out_proj``; the latent
    form's ``latent_down``, ``latent_up``).  Four a layer under a remat
    that keeps no product (six in the latent form); none where the policy
    keeps ``trunk.ATTENTION_PRODUCTS`` (PR 53), but for the latent form's
    three products up from the latents, which are not named."""
    return len(re.findall(
        r'^\s*%\S+ = [^\n]* fusion\([^\n]*op_name="[^"\n]*rematted_computation/'
        r'(?:[^"\n]*/)?attention/(?:[^"\n]*/)?'
        r'(?:proj|out_proj|latent_down|latent_up)/[^"\n]*dot_general"',
        compiled_text, re.M))


def scan_kernel_calls(compiled_text: str, kernels: str = "ssd_chunk",
                      scope: str = "ssm/scan") -> dict:
    """By name of the scan's kernels (``ssd_chunk_fwd``, ``ssd_chunk_bwd``;
    or another pair's, such as the convolution's ``ssm_conv`` under
    ``ssm/conv``): how many instructions a compiled program's text holds
    (the step's are ``%ssd_chunk_fwd.<n>``; a bare ``jax.grad`` of the
    kernel names them ``%jvp_ssd_chunk_fwd_``), and how many of them carry
    the scope in their ``op_name`` (the benchmark's scope table files a
    call that lost the path under ``other``)."""
    calls: dict = {}
    under = "under_" + scope.replace("/", "_")
    for name, rest in re.findall(
            rf"^\s*%\w*?({kernels}_(?:fwd|bwd))[\w.]* = [^\n]*custom-call\((.*?)(?=^\s*%|\Z)",
            compiled_text, re.M | re.S):
        entry = calls.setdefault(name, {"calls": 0, under: 0})
        entry["calls"] += 1
        op_name = re.search(r'op_name="([^"]*)"', rest)
        entry[under] += bool(
            op_name and re.search(rf"[/(]{scope}[/)]", op_name.group(1)))
    return calls


def moe_rows_kernel_calls(compiled_text: str) -> dict:
    """The sorted expert layer's row movements in a compiled program's
    text: how many ``moe_rows_sum`` instructions (``ops/moe_rows.py``) it
    holds and under which of the layer's two scopes, and how many row
    gathers and scatters of bf16 or float32 ``[rows, d]`` arrays are left
    under them (a dropless layer of the kernel form: five gathers, the
    sort's forward twice under remat, and no scatter)."""
    found = {"moe_rows_sum": {"calls": 0, "under_moe_sort": 0, "under_moe_combine": 0},
             "row_gathers": 0, "row_scatters": 0}
    for name, rest in re.findall(
            r"^\s*%([\w.\-]+) = (?:bf16|f32)\[\d+,\d+\][^\n]* (?:custom-call|fusion|scatter)"
            r"\((.*?)(?=^\s*%|\Z)", compiled_text, re.M | re.S):
        op_name = re.search(r'op_name="([^"]*)"', rest)
        scope = op_name and re.search(r"[/(](moe_sort|moe_combine)[/)]", op_name.group(1))
        if not scope:
            continue
        if name.startswith("moe_rows_sum"):
            found["moe_rows_sum"]["calls"] += 1
            found["moe_rows_sum"]["under_" + scope.group(1)] += 1
        elif op_name.group(1).endswith("/gather"):
            found["row_gathers"] += 1
        elif "scatter" in op_name.group(1).rsplit("/", 1)[-1]:
            found["row_scatters"] += 1
    return found


def stream_kernel_calls(compiled_text: str, jaxpr) -> dict:
    """The residual streams' kernels (``ops/stream_mix.py``; PR 65) in a
    compiled step: by scope (``hc/coeff``, ``hc/pre``, ``hc/post``) and
    kernel name how many instructions the text holds forward, under remat
    (``rematted_computation`` in the ``op_name``) and backward, with the
    block of rows and the grid each got in the traced step; under
    ``outside_the_scopes`` the calls whose ``op_name`` lost the scope (the
    benchmark's ``xing4.hc_*`` readings would miss them); and under
    ``stream_sized_results_of_xla`` the results of four streams' size that
    XLA's own instructions still write under scope ``hc`` (the copy of one
    stream to the four, a shape the rule refused: the plain form's), by
    the scope's last two names.  Twelve parts a step: twelve forward calls a
    stage, twelve under remat (fewer of the write: a layer's last is not
    read again) and twelve backward where the rule took every call."""
    found: dict = {}
    outside = 0
    for name, rest in re.findall(
            r"^\s*%(stream_(?:stats|read|write)_(?:fwd|bwd))[\w.]* = [^\n]*custom-call\((.*?)(?=^\s*%|\Z)",
            compiled_text, re.M | re.S):
        op_name = re.search(r'op_name="([^"]*)"', rest)
        scope = op_name and re.search(
            r"/(hc/(?:coeff|pre|post))/", _bare(op_name.group(1)))
        if not scope:
            outside += 1
            continue
        way = ("backward" if name.endswith("bwd") else
               "remat" if "rematted_computation" in op_name.group(1) else "forward")
        entry = found.setdefault(scope.group(1), {}).setdefault(name, {})
        entry[way] = entry.get(way, 0) + 1
    for path, eqn in _equations(jaxpr, "pallas_call"):
        scope = re.search(r"(hc/(?:coeff|pre|post))", _bare(path))
        name = eqn.params["name"]
        if scope and name in found.get(scope.group(1), {}):
            mapping = eqn.params["grid_mapping"]
            found[scope.group(1)][name].update(
                rows=mapping.block_mappings[0].block_shape[0].block_size,
                grid=list(mapping.grid))
    sized = collections.Counter()
    entry = compiled_text[compiled_text.find("\nENTRY"):]  # not the fusions' bodies
    for dims, op_name in re.findall(
            r'^\s*%\S+ = (?:bf16|f32)\[([\d,]+)\][^\n]* (?:fusion|copy|reshape|broadcast)\('
            r'[^\n]*op_name="([^"\n]*)"', entry, re.M):
        scope = re.search(r"/(hc/\w+)/", _bare(op_name))
        if scope and _stream_sized(dims):
            sized[scope.group(1)] += 1
    return {"calls": found, "outside_the_scopes": outside,
            "stream_sized_results_of_xla": dict(sized)}


def _bare(op_name: str) -> str:
    """An ``op_name`` without the transformations' brackets: ``jvp(hc)/pre``
    and ``transpose(jvp(hc))/pre`` are both ``hc/pre``."""
    return re.sub(r"\w+\(|\)", "", op_name)


def _stream_sized(dims: str, tokens: int = 16384, width: int = 4 * 3584) -> bool:
    import numpy as np

    return int(np.prod([int(n) for n in dims.split(",")])) == tokens * width


# the attention kernels' instruction names begin with one of these: the
# blocked kernel's (splash attention) and the band kernel's
# (``ops/band_attention.py``: a window shorter than the key block, PR 63)
ATTENTION_KERNELS = ("splash_mha", "band_attention")


def attention_kernel_calls(compiled_text: str) -> dict:
    """How many instructions of each of the attention kernels' names (the
    blocked kernel's ``splash_mha_fwd_residuals``, ``_dkv_no_residuals``,
    ``_dq_no_residuals``; the band kernel's ``band_attention_fwd``,
    ``_bwd``) a compiled program's text holds."""
    names = "|".join(ATTENTION_KERNELS)
    return dict(collections.Counter(re.findall(
        rf"^\s*%((?:{names})\w*?)(?:\.\d+)? = [^\n]*custom-call\(",
        compiled_text, re.M)))


def attention_stages(compiled_text: str) -> dict:
    """Where a compiled program's instructions under the attention scope
    lie, fused ones too, as ``tools/scope_tree.py`` folds their paths:
    under ``stages`` the stages they name (``norm``, ``proj``, ``qk_norm``,
    ``rope``, ``flash``, ``flash/layout``, ``out_proj``; PR 52), under
    ``kernel_scopes`` the folded paths of the attention kernels' calls
    (instructions ``splash_mha*`` and ``band_attention*``), the kind of
    layer folded too."""
    import harness

    tree = harness.load_path(os.path.join(REPO, "tools", "scope_tree.py"))
    blocks = harness.load_path(os.path.join(
        REPO, "benchmarks", "runners", "train_recipe_blocks.py"))
    names = blocks.op_names(compiled_text)
    return {
        "stages": sorted(tree.attention_stages(names.values())),
        "kernel_scopes": sorted({
            "/".join(tree.fold(op_name, also=tree.ATTENTION_KINDS)[0])
            for name, op_name in names.items() if name.startswith(ATTENTION_KERNELS)}),
    }


def attention_kernel_tilings(jaxpr) -> dict:
    """By layer kind (the scope ``attention/<kind>``, or ``attention``) and
    kernel name: how many calls the traced step makes, the query and key
    blocks each got (what ``trunk.flash_block_sizes`` answered for its
    mask; the band kernel's one block of positions), the grid it walks
    (the key-block axis already shrunk to the mask where the blocked
    kernel can; the band kernel's has none) and its largest result in
    bytes (the fused backward's is the queries' gradient once a key
    block)."""
    tilings = {}
    for path, eqn in _equations(jaxpr, "pallas_call"):
        kind = re.search(r"attention(?:/(global|window))?[/)]", path)
        if not kind or not eqn.params["name"].startswith(ATTENTION_KERNELS):
            continue
        mapping = eqn.params["grid_mapping"]
        q_block, k_block = (m.block_shape for m in mapping.block_mappings[:2])
        if eqn.params["name"].startswith("band_attention"):
            # q first, [g * hd, block], and k, [hd, block]: positions last
            bq, bkv = q_block[-1], k_block[-1]
        else:
            # q comes first, [heads, block_q, hd]; k second, [heads, block_kv,
            # hd]; a batch of more than one row (vmap) puts its axis before
            bq, bkv = q_block[-2], k_block[-2]
        entry = tilings.setdefault(kind.group(1) or "attention", {}).setdefault(
            eqn.params["name"], {
                "calls": 0, "block_q": bq.block_size, "block_kv": bkv.block_size,
                "grid": list(mapping.grid),
                "largest_result_bytes": max(
                    _bytes(a) for a in eqn.params["out_avals"]),
            })
        entry["calls"] += 1
    return tilings


def step_memory(chip, recipe: str = "smallthinker_one_chip") -> dict:
    """The compiler's memory analysis of the whole train step of
    ``__graft_entry__.<recipe>`` compiled for ``chip``, a described v5e
    device (the caller makes ``jax.default_backend()`` answer ``tpu``, as
    on the chip), under ``grouped_matmul_tilings`` how many of its
    grouped-matmul instructions run at which ``tm,tk,tn``, under
    ``attention_kernel_tilings`` what :func:`attention_kernel_tilings`
    reads off the traced step, under ``attention_kernel_calls`` how
    many instructions of each of the kernel's names the compiled step
    holds (one forward a kernel layer since PR 38: remat keeps the
    kernel's residuals), under ``attention_stages`` which stages of the
    attention part its instructions name (:func:`attention_stages`; PR
    52), under ``kept_residual_bytes`` what that costs
    (:func:`kept_residual_bytes`), under ``kept_product_bytes`` the
    results of the attention part's matrix products that remat keeps
    beside them and under ``recomputed_attention_products`` how many of
    those products the compiled step still runs a second time
    (:func:`recomputed_attention_products`: none, or the latent form's
    three up a layer; PR 53), under ``moe_rows_kernel_calls`` the
    sorted expert layer's row movements (:func:`moe_rows_kernel_calls`;
    PR 50), under ``stream_kernel_calls`` the residual streams' kernels by
    scope and direction with the block each got
    (:func:`stream_kernel_calls`; PR 65), under ``scan_kernel_calls`` and
    ``kept_scan_bytes`` the same two for the state-space scan's kernels
    (:func:`scan_kernel_calls`; PR 40), under ``delta_kernel_calls`` the
    delta rule's kernels under ``delta/core`` (one forward a delta layer
    since PR 58: remat keeps the kernel's output and a state a grid step,
    ``kept_delta_bytes``) and under
    ``loops_under_delta_core`` the ``while`` instructions that scope still
    holds (none where the kernel runs: PR 46), under ``conv_kernel_calls`` the
    convolution's kernels under ``ssm/conv`` and under
    ``float32_arrays_under_ssm_conv`` the float32 ``[1, S (+ 3), C]``
    arrays, ``C`` the channels of ``x B C`` or of one of the three, that
    scope still writes (none: PR 41), and under ``loss_layer_products`` how
    many of its fusions under scope ``ce`` are matmuls (the logits' einsum
    and its transposes: the head's products)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import __graft_entry__
    from learning_at_home_tpu.models.trunk import ATTENTION_PRODUCTS
    from learning_at_home_tpu.ops.delta_rule import DELTA_RESIDUALS
    from learning_at_home_tpu.ops.ssd import SSD_RESIDUALS
    from learning_at_home_tpu.parallel.mesh import (
        batch_sharding,
        opt_state_shardings,
    )

    mesh = Mesh(np.array([chip]), ("expert",))
    model, cfg, optimizer, batch = getattr(__graft_entry__, recipe)(mesh)
    assert model.attn_impl == "flash"

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            tree, shardings,
        )

    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    shard = model.param_shardings(shapes)
    p = placed(shapes, shard)
    o = jax.eval_shape(optimizer.init, p)
    o = placed(o, opt_state_shardings(o, shard, p, mesh))
    ids = jax.ShapeDtypeStruct(
        (batch, cfg.seq_len), jnp.int32, sharding=batch_sharding(mesh))
    t0 = time.perf_counter()
    with no_compile_cache():
        traced = model.make_train_step(optimizer).trace(p, o, ids, ids)
        compiled = traced.lower().compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gate_norm = "delta/gate_norm" if "delta/gate_norm" in text else "ssm/gate_norm"
    # params and optimizer state are donated: outputs alias the arguments
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return {
        "compile_s": time.perf_counter() - t0,
        "parameters": sum(int(np.prod(leaf.shape))
                          for leaf in jax.tree_util.tree_leaves(shapes)),
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "live_bytes": live, "chip_bytes": CHIP_BYTES,
        "share_of_chip": live / CHIP_BYTES,
        "grouped_matmul_tilings": dict(collections.Counter(re.findall(
            r'^\s*%ragged-dot-none[.\d]* = [^\n]*ragged_dot_tiling="([\d,]+)"',
            text, re.M))),
        "attention_kernel_tilings": attention_kernel_tilings(traced.jaxpr.jaxpr),
        "attention_kernel_calls": attention_kernel_calls(text),
        "attention_stages": attention_stages(text),
        "moe_rows_kernel_calls": moe_rows_kernel_calls(text),
        "stream_kernel_calls": stream_kernel_calls(text, traced.jaxpr.jaxpr),
        "kept_residual_bytes": kept_residual_bytes(traced.jaxpr.jaxpr),
        "kept_product_bytes": kept_residual_bytes(
            traced.jaxpr.jaxpr, ATTENTION_PRODUCTS),
        "recomputed_attention_products": recomputed_attention_products(text),
        "scan_kernel_calls": scan_kernel_calls(text),
        "conv_kernel_calls": scan_kernel_calls(text, "ssm_conv", "ssm/conv"),
        "float32_arrays_under_ssm_conv": sorted(set(re.findall(
            r'^\s*%\S+ = (f32\[1,1638[47],(?:6144|4096|1024)\])[^\n]*op_name="[^"\n]*ssm/conv',
            text, re.M))),
        "kept_scan_bytes": kept_residual_bytes(traced.jaxpr.jaxpr, SSD_RESIDUALS),
        "gate_norm_kernel_calls": scan_kernel_calls(text, "gate_norm", gate_norm),
        "float32_arrays_beside_gate_norm": sorted({
            shape for shape, dims in re.findall(
                r'^\s*%\S+ = (f32\[1,16384,([\d,]+)\])[^\n]*op_name="[^"\n]*'
                r'(?:ssm/gate_norm|delta/gate_norm|ssm/scan)[/)]', text, re.M)
            if np.prod([int(n) for n in dims.split(",")]) >= 1024}),
        "delta_kernel_calls": scan_kernel_calls(text, "delta_chunk", "delta/core"),
        "kept_delta_bytes": kept_residual_bytes(traced.jaxpr.jaxpr, DELTA_RESIDUALS),
        "loops_under_delta_core": len(re.findall(
            r'^\s*%\S+ = [^\n]* while\([^\n]*op_name="[^"\n]*delta/core', text, re.M)),
        "loss_layer_products": len(re.findall(
            r'^\s*%\S+ = [^\n]* fusion\([^\n]*'
            r'op_name="[^"\n]*[/(]ce[/)][^"\n]*dot_general"', text, re.M)),
    }


def memory(recipe: str = "smallthinker_one_chip") -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # the chip is described, not attached: the recipe must resolve as on it
    jax.default_backend = lambda: "tpu"
    print(json.dumps(step_memory(topo.devices[0], recipe)))


def float8(seeds: list, config_path: str = CONFIG, only: list = ()) -> None:
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()  # a reading compiles what the one before it did
    import jax
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__
    import harness
    from learning_at_home_tpu.parallel.mesh import batch_sharding, make_mesh

    manifest = harness.load_manifest("BENCHMARK.json")
    config = harness.load_json(os.path.join(REPO, config_path))
    runner = harness.load_module(manifest, "runners", config["runner"])
    recipe = harness.load_module(manifest, "runners", "train_recipe")
    reference = harness.load_path(os.path.join(REPO, config["reference"]))
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    model, cfg, optimizer, rows = getattr(__graft_entry__, config["recipe"])(mesh)
    step = model.make_train_step(optimizer)
    for seed in seeds:
        words = harness.seed_words(seed, 4)
        params = model.init_params(jnp.asarray(words[:2], jnp.uint32))
        opt_state = model.init_opt_state(optimizer, params)
        # a runner whose traffic keeps ids back (a mask id) says how many are drawn
        vocab = getattr(runner, "data_vocab", lambda cfg: cfg.vocab_size)(cfg)
        batches = recipe.zipf_batches(
            np.random.default_rng(words[2:]), vocab, rows, cfg.seq_len, 8)
        pool = [tuple(jax.device_put(a, batch_sharding(mesh)) for a in pair)
                for pair in batches]
        if cfg.router_bias:  # the runner's set-up call
            params, _ = model.level_router_bias(params, [ids for ids, _ in pool])
        if hasattr(runner, "route_like_a_trained_model"):  # or its own
            params, _ = runner.route_like_a_trained_model(
                model, params, [ids for ids, _ in pool])
        steps = config.get("probe_steps", 48)
        for i in [0, 1] + [(2 + j) % 8 for j in range(steps - 3)] + [0]:
            params, opt_state, _, _ = step(params, opt_state, *pool[i])
        del opt_state
        ids, tgt = batches[0][0][:1], batches[0][1][:1]
        readings = [("the program", {})] + [
            (jnp.dtype(dtype).name, {"operand_dtype": dtype})
            for dtype in (jnp.float8_e4m3fn, jnp.bfloat16)]
        if "decay_dtype" in inspect.signature(
                runner.compare_with_reference).parameters:
            readings.append(("the program, its scan's decays in bfloat16",
                             {"decay_dtype": jnp.bfloat16}))
        # what a runner names as programs that must fall outside its limits
        readings += list(getattr(runner, "WRONG_PROGRAMS", {}).items())
        if only:  # the program itself, and the readings whose names hold a word
            readings = readings[:1] + [
                r for r in readings[1:] if any(word in r[0] for word in only)]
        for operands, how in readings:
            read = runner.compare_with_reference(
                model, params, reference, config, jnp.asarray(ids),
                jnp.asarray(tgt), **how)
            print("REFERENCE_AT " + json.dumps({
                "seed": seed,
                "operands": operands,
                **read,
                "limits": runner.TOLERANCES,
                "outside": [k for k, lim in runner.TOLERANCES.items()
                            if not read[k] <= lim],
            }), flush=True)
        del params


def _ms(fn, args, calls: int) -> float:
    """Milliseconds a call of ``fn(*args)``, after one call that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


def _rel_rms(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def ssd(seq_len: int = 16384, accuracy_len: int = 4096, calls: int = 10) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from learning_at_home_tpu.ops import ssd as ops

    config = harness.load_json(os.path.join(
        REPO, "benchmarks/configs/nemotron-labs-twotower-30b-a3b.json"))
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n, chunk = config["n_groups"], config["ssm_state_size"], config["chunk_size"]
    f32 = jnp.float32

    def inputs(s, dtype):
        rs = np.random.default_rng(4000000007)
        dt = np.exp(rs.uniform(np.log(config["time_step_min"]),
                               np.log(config["time_step_max"]), (1, s, h)))
        return (jnp.asarray(rs.standard_normal((1, s, h, p)), dtype),
                jnp.asarray(dt, f32), -jnp.asarray(rs.uniform(1, 16, h), f32),
                jnp.asarray(0.5 * rs.standard_normal((1, s, g, n)), dtype),
                jnp.asarray(0.5 * rs.standard_normal((1, s, g, n)), dtype))

    def loss_of(form, s):
        rs = np.random.default_rng(40)
        wy = jnp.asarray(rs.standard_normal((1, s, h, p)), f32)
        wf = jnp.asarray(rs.standard_normal((1, h, p, n)), f32)

        def loss(*args):
            y, final = form(*args, chunk)
            return jnp.sum(y.astype(f32) * wy) + jnp.sum(final * wf)
        return loss

    forms = {"plain": ops.ssd_chunked_plain, "kernel": ops.ssd_chunked_kernel}
    names = ("y", "state", "dx", "ddt", "dA", "dB", "dC")

    def everything(form, s):
        return jax.jit(lambda *a: (
            *form(*a, chunk),
            *jax.grad(loss_of(form, s), argnums=(0, 1, 2, 3, 4))(*a)))

    with jax.default_matmul_precision("highest"):
        want = jax.device_get(everything(ops.ssd_chunked_plain, accuracy_len)(
            *inputs(accuracy_len, f32)))
    for name, form in forms.items():
        got = jax.device_get(everything(form, accuracy_len)(
            *inputs(accuracy_len, jnp.bfloat16)))
        args = inputs(seq_len, jnp.bfloat16)
        print("SSD " + json.dumps({
            "form": name, "seq_len": seq_len, "accuracy_len": accuracy_len,
            "forward_ms": _ms(jax.jit(lambda *a, f=form: f(*a, chunk)), args, calls),
            "forward_backward_ms": _ms(jax.jit(jax.grad(
                loss_of(form, seq_len), argnums=(0, 1, 2, 3, 4))), args, calls),
            "rms_against_float32": {
                k: _rel_rms(a, b) for k, a, b in zip(names, got, want)},
        }), flush=True)


def conv(blocks: list, calls: int = 20) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from learning_at_home_tpu.ops import ssm_conv as ops

    config = harness.load_json(os.path.join(
        REPO, "benchmarks/configs/nemotron-labs-twotower-30b-a3b.json"))
    s, taps = config["seq_len"], config["conv_kernel"]
    c = (config["mamba_num_heads"] * config["mamba_head_dim"]
         + 2 * config["n_groups"] * config["ssm_state_size"])
    rs = np.random.default_rng(4100000007)
    x = jnp.asarray(rs.standard_normal((1, s, c)), jnp.bfloat16)
    dy = jnp.asarray(rs.standard_normal((1, s, c)), jnp.bfloat16)
    w = jnp.asarray(0.5 * rs.standard_normal((c, taps)), jnp.float32)
    b = jnp.asarray(0.1 * rs.standard_normal(c), jnp.float32)

    def both(form):
        def fn(x, w, b, dy):
            y, back = jax.vjp(form, x, w, b)
            return (y, *back(dy))
        return jax.jit(fn)

    least = x.size * x.dtype.itemsize  # one pass over [1, S, C] bf16
    want = None
    forms = [("plain", ops.causal_conv_silu_plain, None), ("kernel",
             ops.causal_conv_silu_kernel, (ops._ROWS, ops._CHANNELS))] + [
        ("kernel", ops.causal_conv_silu_kernel, tuple(int(n) for n in a.split("x")))
        for a in blocks]
    for name, form, at in forms:
        if at:
            ops._ROWS, ops._CHANNELS = at
        try:
            got = jax.device_get(both(form)(x, w, b, dy))
            forward = _ms(jax.jit(form), (x, w, b), calls)
            forward_backward = _ms(both(form), (x, w, b, dy), calls)
        except Exception as e:  # a block the compiler refuses
            print("CONV " + json.dumps({"form": name, "blocks": at,
                                        "refused": str(e)[:300]}), flush=True)
            continue
        want = want or got
        print("CONV " + json.dumps({
            "form": name, "blocks": at, "shape": list(x.shape), "taps": taps,
            "forward_ms": forward, "forward_backward_ms": forward_backward,
            "forward_gb_s_on_least_bytes": 2 * least / forward / 1e6,
            "forward_backward_gb_s_on_least_bytes":
                5 * least / forward_backward / 1e6,
            "y_max_abs_against_plain": float(np.max(np.abs(
                np.asarray(got[0], np.float32) - np.asarray(want[0], np.float32)))),
            "rms_against_plain": {
                k: _rel_rms(a, b_) for k, a, b_ in zip(("y", "dx", "dw", "db"), got, want)},
        }), flush=True)


def conv_gated(blocks: list, calls: int = 20) -> None:
    """``conv gated [rows x channels x strip ...]``: the gated short
    convolution that is LFM2's conv mixer (``ops/short_conv.py``) at the
    cell's shape ``[1, 16384, 3 x 2048]`` bf16, 3 taps: the plain form
    against the kernel at its own blocks and at each named."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from learning_at_home_tpu.ops import short_conv as ops

    config = harness.load_json(os.path.join(
        REPO, "benchmarks/configs/lfm2-8b-a1b.json"))
    s, c, taps = config["seq_len"], config["hidden_size"], config["conv_L_cache"]
    rs = np.random.default_rng(4100000007)
    bcu = jnp.asarray(rs.standard_normal((1, s, 3 * c)), jnp.bfloat16)
    dy = jnp.asarray(rs.standard_normal((1, s, c)), jnp.bfloat16)
    w = jnp.asarray(0.5 * rs.standard_normal((c, taps)), jnp.float32)

    def both(form):
        def fn(bcu, w, dy):
            y, back = jax.vjp(form, bcu, w)
            return (y, *back(dy))
        return jax.jit(fn)

    least = dy.size * dy.dtype.itemsize  # one pass over [1, S, C] bf16
    want = None
    own = (ops._ROWS, ops._CHANNELS, ops._STRIP)
    forms = [("plain", ops.gated_short_conv_plain, None),
             ("kernel", ops.gated_short_conv_kernel, own)] + [
        ("kernel", ops.gated_short_conv_kernel,
         tuple(int(n) for n in a.split("x"))) for a in blocks]
    for name, form, at in forms:
        if at:
            ops._ROWS, ops._CHANNELS, ops._STRIP = at
        try:
            got = jax.device_get(both(form)(bcu, w, dy))
            forward = _ms(jax.jit(form), (bcu, w), calls)
            forward_backward = _ms(both(form), (bcu, w, dy), calls)
        except Exception as e:  # a block the compiler refuses
            print("CONV " + json.dumps({"form": name, "blocks": at,
                                        "refused": str(e)[:300]}), flush=True)
            continue
        want = want or got
        print("CONV " + json.dumps({
            "form": name, "blocks": at, "shape": list(bcu.shape), "taps": taps,
            "forward_ms": forward, "forward_backward_ms": forward_backward,
            # [B | C | u] read and y written; those, dy and d[B | C | u]
            "forward_gb_s_on_least_bytes": 4 * least / forward / 1e6,
            "forward_backward_gb_s_on_least_bytes":
                11 * least / forward_backward / 1e6,
            "rms_against_plain": {
                k: _rel_rms(a, b_) for k, a, b_ in zip(("y", "dbcu", "dw"), got, want)},
        }), flush=True)
    ops._ROWS, ops._CHANNELS, ops._STRIP = own


def gate_norm(words: list, calls: int = 20) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.ops import gate_norm as ops

    s = 16384
    rs = np.random.default_rng(4700000007)

    def normal(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rs.standard_normal(shape), dtype)

    # (cell, C, the gate's array's width and dtype, z's column, group, the
    # scale's length, eps, gate first, heads of the skip, the gate's function)
    bf16, f32 = jnp.bfloat16, jnp.float32
    cells = [("nemotron", 4096, 10304, bf16, 0, 512, 4096, 1e-5, True, 64, "silu"),
             ("olmo-hybrid", 5760, 17340, bf16, 11520, 192, 192, 1e-6, False, 0, "silu"),
             ("ling3", 4096, 32, f32, 0, 128, 128, 1e-6, False, 0, "sigmoid")]
    named = [w for w in words if w in {cell[0] for cell in cells}]
    blocks = [tuple(int(n) for n in w.split("x")) for w in words if w not in named]
    for cell, c, wide, of_gate, first, group, n_scale, eps, gate_first, heads, gate in cells:
        if named and cell not in named:
            continue
        a_group = wide == c // group
        # which of the module's blocks this call reads: a gate a group has
        # its own rows and no strips (``256x0``: the second number is not read)
        at_names = ("_GROUP_ROWS",) if a_group else ("_ROWS", "_STRIP")
        committed = tuple(getattr(ops, name) for name in at_names)
        # the projection's width rounded up to whole lane tiles: an ARGUMENT
        # of a width that is none gets a layout with the positions minor, and
        # a transposing copy of it in front of the kernel (in the step the
        # in-projection writes it channels minor).  A gate a group is so
        # narrow that it is MADE inside the timed program, as the step makes
        # it: ``z`` is then the two factors of a small product ([1, S, 128]
        # by [128, C / group], float32 out), in both forms alike
        width = 128 if a_group else wide + -wide % 128
        y, z, dout = normal(1, s, c), normal(1, s, width), normal(1, s, c)
        if a_group:
            z = (z, 0.2 * normal(width, wide))
        scale = 1.0 + 0.1 * normal(n_scale, dtype=jnp.float32)
        skip = (normal(1, s, c), normal(heads, dtype=jnp.float32)) if heads else None

        def gate_of(z):
            if not a_group:
                return z
            return jnp.einsum("bsd,dh->bsh", *z, preferred_element_type=of_gate)

        def plain(y, z, scale, skip):
            z = gate_of(z)
            return ops.gated_rms_norm_plain(
                y, z if a_group else z[..., first:first + c], scale, group, eps,
                gate_first, skip, gate)

        def kernel(y, z, scale, skip):
            return ops.gated_rms_norm_kernel(
                y, gate_of(z), scale, group, eps, gate_first, first, skip, gate)

        def both(form):
            def fn(y, z, scale, skip, dout):
                out, back = jax.vjp(form, y, z, scale, skip)
                return (out, *jax.tree_util.tree_leaves(back(dout)))
            return jax.jit(fn)

        least = y.size * y.dtype.itemsize  # one pass over [1, S, C] bf16
        # forward; forward + backward (a gate a group is 1/64 of a pass)
        passes = (4, 11) if heads else (2, 5) if a_group else (3, 8)
        want = None
        forms = [("plain", plain, None), ("kernel", kernel, committed)] + [
            ("kernel", kernel, at) for at in blocks]
        for name, form, at in forms:
            for at_name, n in zip(at_names, at or ()):
                setattr(ops, at_name, n)
            try:
                got = jax.device_get(both(form)(y, z, scale, skip, dout))
                # a function of its own a block: ``jax.jit(form)`` would hand
                # back the program it compiled at the first block
                forward = _ms(
                    jax.jit(lambda *a, form=form: form(*a)), (y, z, scale, skip), calls)
                forward_backward = _ms(both(form), (y, z, scale, skip, dout), calls)
            except Exception as e:  # a block the compiler refuses
                print("GATE_NORM " + json.dumps({
                    "cell": cell, "form": name, "rows_strip": at,
                    "refused": str(e)[:300]}), flush=True)
                continue
            want = want or got
            names = ("out", "dy", "dz", "dz_weights", "dscale") if a_group else (
                "out", "dy", "dz", "dscale", "dx", "dD")
            print("GATE_NORM " + json.dumps({
                "cell": cell, "form": name, "rows_strip": at, "shape": list(y.shape),
                "gate": [[1, s, wide], jnp.dtype(of_gate).name, gate],
                "forward_ms": forward, "forward_backward_ms": forward_backward,
                "backward_ms": forward_backward - forward,
                "forward_gb_s_on_least_bytes": passes[0] * least / forward / 1e6,
                "backward_gb_s_on_least_bytes":
                    (passes[1] - passes[0]) * least / (forward_backward - forward) / 1e6,
                "rms_against_plain": {
                    k: _rel_rms(a, b_) for k, a, b_ in zip(names, got, want)},
            }), flush=True)
        for at_name, n in zip(at_names, committed):
            setattr(ops, at_name, n)


def streams(blocks: list, calls: int = 10, shape=(1, 16384, 4, 3584)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.ops import stream_mix as ops

    b, s, n, c = shape
    o = 2 * n + n * n
    rs = np.random.default_rng(6500000007)
    names = ("_ROWS", "_STRIP", "_CHUNK", "_STATS_ROWS")
    committed = tuple(getattr(ops, name) for name in names)

    def normal(*shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(scale * rs.standard_normal(shape), dtype)

    def uniform(*shape):
        return jnp.asarray(rs.uniform(0.1, 1.0, shape), jnp.float32)

    x, y = normal(b, s, n, c), normal(b, s, c)
    f32 = jnp.float32
    # (stage, plain, kernel, operands, the cotangent, passes over ONE stream
    # [B, S, C] of the least bytes forward and backward, the results' names)
    stages = [
        ("write", ops.stream_write_plain, ops.stream_write_kernel,
         (x, y, uniform(n, b, s), uniform(n, n, b, s)), normal(b, s, n, c),
         (2 * n + 1, 3 * n + 2), ("out", "dx", "dy", "dpost", "dres")),
        ("read", ops.stream_read_plain, ops.stream_read_kernel,
         (x, uniform(n, b, s)), normal(b, s, c),
         (n + 1, 2 * n + 1), ("out", "dx", "dpre")),
        ("stats", ops.token_stats_plain, ops.token_stats_kernel,
         (x, normal(n * c, o, dtype=f32, scale=(n * c) ** -0.5)),
         (normal(b, s, dtype=f32), normal(o, b, s, dtype=f32)),
         (n, 2 * n), ("ms", "m", "dx", "dphi")),
    ]
    least = y.size * y.dtype.itemsize  # one pass over [B, S, C] bf16

    def fold_of(a):
        return a.reshape(b, s, n * c) if a.shape == (b, s, n, c) else a

    def folded(form):
        """``form`` between FOLDED streams [B, S, n C], as the step hands
        them on (``Transformer._hc_read``): unfolded inside the program, a
        bitcast of the kernels' own view; an ARGUMENT [B, S, n, C] comes in
        that shape's tiling and is copied to the kernels' in front of
        every call, two passes that no call in the step pays."""
        def fn(fold, *rest):
            return jax.tree_util.tree_map(
                fold_of, form(fold.reshape(b, s, n, c), *rest))
        return fn

    def both(form):
        def fn(operands, cotangent):
            out, back = jax.vjp(form, *operands)
            return (*jax.tree_util.tree_leaves(out), *back(cotangent))
        return jax.jit(fn)

    for stage, plain, kernel, operands, cotangent, passes, results in stages:
        want = None
        forms = [("plain", plain, None), ("kernel", kernel, committed)] + [
            ("kernel", kernel, tuple(int(k) for k in a.split("x"))) for a in blocks]
        for name, form, at in forms:
            for key, value in zip(names, at or ()):
                setattr(ops, key, value)
            args, given = operands, cotangent
            if name == "kernel":
                form = folded(form)
                args = (fold_of(operands[0]), *operands[1:])
                given = jax.tree_util.tree_map(fold_of, cotangent)
            try:
                got = jax.device_get(both(form)(args, given))
                forward = _ms(jax.jit(lambda *a, form=form: form(*a)), args, calls)
                forward_backward = _ms(both(form), (args, given), calls)
            except Exception as e:  # a block the compiler refuses
                print("STREAMS " + json.dumps({
                    "stage": stage, "form": name, "rows_strip_chunk_stats_rows": at,
                    "refused": str(e)[:300]}), flush=True)
                continue
            finally:
                for key, value in zip(names, committed):
                    setattr(ops, key, value)
            got = [a.reshape(w.shape) for a, w in zip(got, want or got)]
            want = want or got
            print("STREAMS " + json.dumps({
                "stage": stage, "form": name, "rows_strip_chunk_stats_rows": at,
                "shape": list(x.shape),
                "forward_ms": forward, "forward_backward_ms": forward_backward,
                "backward_ms": forward_backward - forward,
                "forward_gb_s_on_least_bytes": passes[0] * least / forward / 1e6,
                "backward_gb_s_on_least_bytes":
                    passes[1] * least / (forward_backward - forward) / 1e6,
                "rms_against_plain": {
                    k: _rel_rms(a, b_) for k, a, b_ in zip(results, got, want)},
            }), flush=True)


def _delta_shape(config: str) -> tuple:
    """``(heads, a head's keys, a head's values)`` of the rule as a
    configuration's mixer calls it: over the VALUE heads (the key heads are
    repeated a value head each before the rule, ``trunk.delta_mixer``)."""
    import harness

    cell = harness.load_json(os.path.join(REPO, "benchmarks/configs", config + ".json"))
    return (cell["linear_num_value_heads"], cell["linear_key_head_dim"],
            cell["linear_value_head_dim"])


def _delta_inputs(s: int, h: int, dk: int, dv: int, dtype) -> tuple:
    """``(q, k, v, g, beta)`` as the mixer hands them to the rule: SiLU'd,
    unit-length keys and queries (they share a positive mean: the solve's
    hard case), strengths in (0, 2), decays as layer 0's seeded weights
    give."""
    import jax.numpy as jnp
    import numpy as np

    rs = np.random.default_rng(4500000007)

    def unit(a):
        a = a * (1.0 / (1.0 + np.exp(-a)))
        return a / np.sqrt(np.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    g = -rs.uniform(1, 16, h) * np.exp(rs.uniform(
        np.log(1e-3), np.log(1e-1), (1, s, h)))
    return (jnp.asarray(unit(rs.standard_normal((1, s, h, dk))) / dk ** 0.5, dtype),
            jnp.asarray(unit(rs.standard_normal((1, s, h, dk))), dtype),
            jnp.asarray(rs.standard_normal((1, s, h, dv)), dtype),
            jnp.asarray(g, jnp.float32),
            jnp.asarray(2.0 / (1.0 + np.exp(-rs.standard_normal((1, s, h)))),
                        jnp.float32))


def delta(seq_len: int = 16384, accuracy_len: int = 1024, calls: int = 5,
          forms=("kernel", "blocks", "product", "triangular"),
          config: str = "olmo-hybrid-7b") -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.ops import delta_rule as ops

    h, dk, dv = _delta_shape(config)
    f32 = jnp.float32

    def inputs(s, dtype):
        return _delta_inputs(s, h, dk, dv, dtype)

    def loss_of(form, s):
        rs = np.random.default_rng(45)
        wo = jnp.asarray(rs.standard_normal((1, s, h, dv)), f32)
        wf = jnp.asarray(rs.standard_normal((1, h, dk, dv)), f32)

        def loss(*args):
            o, final = form(*args)
            return jnp.sum(o.astype(f32) * wo) + jnp.sum(final * wf)
        return loss

    names = ("o", "state", "dq", "dk", "dv", "dg", "dbeta")

    def everything(form, s):
        return jax.jit(lambda *a: (
            *form(*a), *jax.grad(loss_of(form, s), argnums=(0, 1, 2, 3, 4))(*a)))

    def backward_alone(form, s):
        """The backward call by itself: the pullback of a ``jax.vjp`` taken
        before the clock starts, under given cotangents (for the kernels:
        ``delta_chunk_bwd`` and XLA's transposes and sums around it)."""
        rs = np.random.default_rng(46)
        cotangents = (jnp.asarray(rs.standard_normal((1, s, h, dv)), jnp.bfloat16),
                      jnp.asarray(rs.standard_normal((1, h, dk, dv)), f32))
        pullback = jax.jit(lambda *a: jax.vjp(form, *a)[1])(*inputs(s, jnp.bfloat16))
        return _ms(jax.jit(lambda pull, ct: pull(ct)), (pullback, cotangents), calls)

    want = jax.device_get(everything(ops.gated_delta_recurrent, accuracy_len)(
        *inputs(accuracy_len, f32)))
    for solve in forms:
        for chunk in (32, 64, 128):
            def form(*a, solve=solve, chunk=chunk):
                if solve == "kernel":  # delta_chunk_fwd / delta_chunk_bwd
                    return ops.gated_delta_kernel(*a, chunk)
                return ops.gated_delta_plain(*a, chunk, solve=solve)

            line = {"solve": solve, "chunk": chunk, "seq_len": seq_len,
                    "accuracy_len": accuracy_len, "shape": [1, seq_len, h, dk, dv]}
            try:
                got = jax.device_get(everything(form, accuracy_len)(
                    *inputs(accuracy_len, jnp.bfloat16)))
                args = inputs(seq_len, jnp.bfloat16)
                line.update({
                    "forward_ms": _ms(jax.jit(form), args, calls),
                    "forward_backward_ms": _ms(jax.jit(jax.grad(
                        loss_of(form, seq_len), argnums=(0, 1, 2, 3, 4))), args, calls),
                    "backward_alone_ms": backward_alone(form, seq_len),
                    "rms_against_the_recurrence_in_float32": {
                        k: _rel_rms(a, b) for k, a, b in zip(names, got, want)},
                })
            except Exception as e:  # a form the compiler or the memory refuses
                line["refused"] = str(e)[:300]
            print("DELTA " + json.dumps(line), flush=True)


def delta_split(seq_len: int = 16384, chunk: int = 64, calls: int = 5) -> None:
    """Where the PLAIN chunked rule's time goes at the cell's shape: the
    whole rule, then with the solve stubbed out (the right-hand side
    returned as it stands), the solve alone over every chunk at once, the
    diagonal blocks' inverses alone, and the ``lax.scan`` over the chunks
    alone; forward and forward + backward (a sum as the loss)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from learning_at_home_tpu.ops import delta_rule as ops

    config = harness.load_json(os.path.join(
        REPO, "benchmarks/configs/olmo-hybrid-7b.json"))
    h, dk, dv = (config["linear_num_key_heads"], config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    f32, bf16 = jnp.float32, jnp.bfloat16
    rs = np.random.default_rng(46)
    nc = seq_len // chunk
    nb = chunk // ops.SOLVE_BLOCK

    def normal(shape, dtype, scale=1.0):
        return jnp.asarray(scale * rs.standard_normal(shape), dtype)

    q, k = (normal((1, seq_len, h, dk), bf16, dk ** -0.5) for _ in "qk")
    v = normal((1, seq_len, h, dv), bf16)
    g = -jnp.abs(normal((1, seq_len, h), f32, 0.05))
    beta = jnp.asarray(rs.uniform(0, 2, (1, seq_len, h)), f32)
    a = jnp.tril(normal((1, nc, h, chunk, chunk), f32, 0.1), -1)
    rhs = normal((1, nc, h, chunk, dk + dv), f32)
    blocks = jnp.tril(normal((1, nc, h, nb, 16, 16), f32, 0.1), -1)
    w, kt = (normal((nc, 1, h, chunk, dk), bf16) for _ in "wk")
    u = normal((nc, 1, h, chunk, dv), f32)
    decay = jnp.asarray(rs.uniform(0.5, 1, (nc, 1, h, 1, 1)), f32)

    def scan(w, u, kt, decay):
        def one_chunk(state, of_chunk):
            w_c, u_c, k_c, decay_c = of_chunk
            entering = state.astype(bf16)
            new = (u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, entering,
                                    preferred_element_type=f32)).astype(bf16)
            return decay_c * state + jnp.einsum(
                "bhik,bhiv->bhkv", k_c, new, preferred_element_type=f32), (
                    entering, new)
        return jax.lax.scan(one_chunk, jnp.zeros((1, h, dk, dv), f32),
                            (w, u, kt, decay))

    def rule(*args):
        return ops.gated_delta_plain(*args, chunk)

    def both(fn, args, argnums):
        def loss(*x):
            return sum(jnp.sum(t.astype(f32)) for t in jax.tree.leaves(fn(*x)))
        return {"forward_ms": _ms(jax.jit(fn), args, calls),
                "forward_backward_ms": _ms(
                    jax.jit(jax.grad(loss, argnums=argnums)), args, calls)}

    line = {"chunk": chunk, "seq_len": seq_len, "shape": [1, seq_len, h, dk, dv]}
    line["rule"] = both(rule, (q, k, v, g, beta), (0, 1, 2, 3, 4))
    line["solve"] = both(ops.solve_unit_lower, (a, rhs), (0, 1))
    line["block_inverses"] = both(ops._substituted_inverse, (blocks,), (0,))
    line["scan"] = both(scan, (w, u, kt, decay), (0, 1, 2, 3))
    whole_solve = ops.solve_unit_lower
    ops.solve_unit_lower = lambda a, rhs, how: rhs + 0.0 * jnp.sum(
        a, axis=-1, keepdims=True)
    try:
        line["rule_without_solve"] = both(rule, (q, k, v, g, beta), (0, 1, 2, 3, 4))
    finally:
        ops.solve_unit_lower = whole_solve
    print("DELTA_SPLIT " + json.dumps(line), flush=True)


class _Unchained:
    """A kernel's scratch Ref of carried states whose reads of ONE state
    (``ref[h]``, ``ref[n, h]``) give zeros: every write stays, and every
    product that read a state is still made, but no chunk waits for the
    chunk before it.  Whole reads and all writes go to the Ref."""

    def __init__(self, ref):
        self.ref, self.shape, self.dtype = ref, ref.shape, ref.dtype

    def __getitem__(self, at):
        import jax.numpy as jnp

        if at is Ellipsis:
            return self.ref[...]
        at = at if isinstance(at, tuple) else (at,)
        return jnp.zeros(self.shape[len(at):], self.dtype)

    def __setitem__(self, at, value):
        self.ref[at] = value


def _delta_stand_ins(ops) -> dict:
    """``{part: {a name of ops/delta_rule.py: its stand-in}}``: what
    ``delta parts`` swaps in, a part at a time.  Each stand-in still reads
    what the part read (no other part falls dead with it) and costs next to
    nothing beside it: the blocks' inverses as ``I - a`` inside the blocks
    (no row step), a join that joins nothing, and ONE bf16 pass in place of
    ``W`` and ``U``'s three and of each of the backward's four products at
    the highest precision (the pass stays, what the precision adds goes);
    the chain's reads of a carried state as zeros (:class:`_Unchained`)."""
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    dot = ops._dot
    plain_dims = inspect.signature(dot).parameters["dims"].default

    def unsolved_blocks(a, i, j, block):
        return jnp.where(
            i // block == j // block, jnp.where(i == j, 1.0, 0.0) - a, 0.0)

    def solve_products_in_one_pass(a, b, dims=plain_dims, precision=None):
        if precision is None or dims == plain_dims:  # a join's, or not the highest
            return dot(a, b, dims, precision)
        return dot(a.astype(bf16), b.astype(bf16), dims)

    def unchained(kernel, carried: int):
        def stub(*refs, **static):
            return kernel(*refs[:-carried], *map(_Unchained, refs[-carried:]),
                          **static)
        return stub

    return {
        "row_steps": {"_block_inverses": unsolved_blocks},
        "joins": {"_join": lambda x, a, i, j, width: x},
        "w_u": {"_exact_dot": lambda a, b: dot(a.astype(bf16), b.astype(bf16))},
        "solve_products": {"_dot": solve_products_in_one_pass},
        "chain": {"_fwd_kernel": unchained(ops._fwd_kernel, 1),
                  "_bwd_kernel": unchained(ops._bwd_kernel, 2)},
    }


def delta_parts(seq_len: int = 16384, chunk: int = 64, calls: int = 10,
                config: str = "olmo-hybrid-7b") -> None:
    """The kernels' calls at a configuration's shape, whole and with one
    part stood in for at a time: ms forward, forward + backward and the
    backward call alone; ``saves_ms`` is the whole call less that."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.ops import delta_rule as ops

    h, dk, dv = _delta_shape(config)
    f32, bf16 = jnp.float32, jnp.bfloat16
    rs = np.random.default_rng(6200000007)
    args = _delta_inputs(seq_len, h, dk, dv, bf16)
    cotangents = (jnp.asarray(rs.standard_normal((1, seq_len, h, dv)), bf16),
                  jnp.asarray(rs.standard_normal((1, h, dk, dv)), f32))

    def read() -> dict:
        def form(*a):  # made anew a reading: what it traces is what stands in ops now
            return ops.gated_delta_kernel(*a, chunk)

        def loss(*a):
            o, final = form(*a)
            return (jnp.sum(o.astype(f32) * cotangents[0].astype(f32))
                    + jnp.sum(final * cotangents[1]))

        both = jax.jit(lambda *a: (*form(*a), *jax.grad(
            loss, argnums=(0, 1, 2, 3, 4))(*a)))
        pullback = jax.jit(lambda *a: jax.vjp(form, *a)[1])(*args)
        digest = hashlib.sha256()
        for result in jax.device_get(both(*args)):
            digest.update(np.asarray(result).tobytes())
        return {"forward_ms": _ms(jax.jit(form), args, calls),
                "forward_backward_ms": _ms(both, args, calls),
                "backward_alone_ms": _ms(
                    jax.jit(lambda pull, ct: pull(ct)), (pullback, cotangents), calls),
                "sha256_of_the_results": digest.hexdigest()[:16]}

    line = {"shape": [1, seq_len, h, dk, dv], "chunk": chunk, "calls": calls}
    whole = read()
    print("DELTA_PARTS " + json.dumps({**line, "stood_in": None, **whole}), flush=True)
    for part, stand_ins in _delta_stand_ins(ops).items():
        real = {name: getattr(ops, name) for name in stand_ins}
        for name, stand_in in stand_ins.items():
            setattr(ops, name, stand_in)
        try:
            got = read()
        finally:
            for name, fn in real.items():
                setattr(ops, name, fn)
        del got["sha256_of_the_results"]  # a stand-in's results mean nothing
        print("DELTA_PARTS " + json.dumps({
            **line, "stood_in": part, **got, "saves_ms": {
                k: whole[k] - v for k, v in got.items()}}), flush=True)


if __name__ == "__main__":
    if sys.argv[1:2] == ["memory"]:
        memory(*sys.argv[2:3])
    elif sys.argv[1:3] == ["conv", "gated"]:
        conv_gated(sys.argv[3:])
    elif sys.argv[1:2] == ["conv"]:
        conv(sys.argv[2:])
    elif sys.argv[1:2] == ["gate_norm"]:
        gate_norm(sys.argv[2:])
    elif sys.argv[1:2] == ["streams"]:
        streams(sys.argv[2:])
    elif sys.argv[1:2] == ["ssd"]:
        ssd(*(int(a) for a in sys.argv[2:4]))
    elif sys.argv[1:3] == ["delta", "split"]:
        delta_split(*(int(a) for a in sys.argv[3:5]))
    elif sys.argv[1:3] == ["delta", "parts"]:
        for config in [a for a in sys.argv[3:] if not a.isdigit()] or ["olmo-hybrid-7b"]:
            delta_parts(*(int(a) for a in sys.argv[3:] if a.isdigit()), config=config)
    elif sys.argv[1:2] == ["delta"]:
        named = [a for a in sys.argv[2:] if not a.isdigit()]
        configs = [a for a in named if os.path.exists(os.path.join(
            REPO, "benchmarks/configs", a + ".json"))]
        forms = [a for a in named if a not in configs]
        for config in configs or ["olmo-hybrid-7b"]:
            delta(*(int(a) for a in sys.argv[2:] if a.isdigit()), config=config,
                  **({"forms": forms} if forms else {}))
    elif sys.argv[1:2] == ["float8"]:
        named = [a for a in sys.argv[2:] if a.endswith(".json")]
        words = [a for a in sys.argv[2:] if a not in named and not a.isdigit()]
        float8([int(s) for s in sys.argv[2:] if s.isdigit()] or [3100000007],
               *(named[:1] or [CONFIG]), only=words)
    else:
        sys.exit(__doc__)
