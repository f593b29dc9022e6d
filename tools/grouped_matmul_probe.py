#!/usr/bin/env python3
"""The dropless expert layer's grouped matmul on the chip: what
``ops/moe_dispatch.py`` ``grouped_matmul_tiles`` rests on.

    python tools/grouped_matmul_probe.py tiles collapsed   # ragged_dot under the attribute
    python tools/grouped_matmul_probe.py tiles uniform
    python tools/grouped_matmul_probe.py tiles collapsed 128   # other row tiles than 256, 512, 1024
    python tools/grouped_matmul_probe.py gmm collapsed     # megablox at the same tiles
    python tools/grouped_matmul_probe.py sizes             # fewer rows: where the gain starts
    python tools/grouped_matmul_probe.py accuracy          # the program's call against float32
    python tools/grouped_matmul_probe.py rows              # the row movements around it, a pass at a time
    python tools/grouped_matmul_probe.py rows uniform 256 1024 --tokens 16384 --choices 6 --width 2560
    # a share's row movements (glm-4.7-flash's: 32 of 64 experts held, a buffer of 65,536 rows):
    python tools/grouped_matmul_probe.py rows --tokens 16384 --choices 4 --width 2048 --groups 64 \
        --buffer-rows 65536
    # another cell's shapes (here smallthinker-21b-a3b-train-zipf16k's):
    python tools/grouped_matmul_probe.py tiles skewed 256 --rows 98304 --widths 2560x768,768x2560
    # a share's buffer, a width no multiple of the lanes divides (nemotron-labs-twotower's):
    python tools/grouped_matmul_probe.py tiles uniform 256 512 --rows 49152 --counted-rows 24576 \
        --groups 32 --widths 2688x1856,1856x2688

``--rows``, ``--groups`` and ``--widths`` (``k``x``n``, comma-separated)
give every subcommand its shapes; the default is the OLMoE cell's.  One
process a subcommand (a chip belongs to one process), one ``ROW`` line of
JSON a reading, written to ``chiprun_out/grouped_matmul_probe.jsonl`` as
well.  Every time is the host clock around ``ITERS`` launches that end in ``block_until_ready``,
after the compile; a setting the compiler refuses is a row with
``refused``.  PERF.md section 6 ("PR 30", "PR 32") holds the readings
the tile rule was set from.

The three kinds of call a train step makes of one ``grouped_matmul``
(``m`` rows in 64 groups, widths ``k`` x ``n``):

- ``forward``:  ``[m, k] x [64, k, n] -> [m, n]``;
- ``drows``:    the gradient to the rows, ``[m, n] x [64, n, k] -> [m, k]``
  (the same kind of call on the transposed weights);
- ``dweights``: the gradient to the weights, ``[m, k], [m, n] -> [64, k, n]``,
  which contracts over the ragged dimension.

The two cells that run it: ``olmoe-1b-7b-train-zipf4k``, ``m`` 131,072
(16,384 tokens x 8), ``k x n`` 2048 x 1024 (gate and up) and 1024 x 2048
(down); ``smallthinker-21b-a3b-train-zipf16k``, ``m`` 98,304 (16,384 x 6),
2560 x 768 and 768 x 2560; both 64 groups.  Group sizes ``collapsed`` as
the OLMoE cell has them (8 of 64 experts hold nearly every row: largest
over mean 7.76), ``skewed`` as the SmallThinker cell has them (a power
law over the experts whose largest over mean is ``--max-over-mean``, 5.6:
the cell reads 5.4 to 5.9) or ``uniform``.

- ``tiles``: ``jax.lax.ragged_dot`` as it is, then under the frontend
  attribute ``ragged_dot_tiling`` for tm in 256, 512, 1024 and, for tk
  and tn, every multiple of 128 from 384 up that divides its dimension
  (512, 1024, 2048 at OLMoE's widths; 512, 640, 1280, 2560 and 384, 768
  at SmallThinker's; a tile that does not divide is padded, PR 30, and
  left out); ``compiled_tiling`` is what the compiled instruction
  carries.
- ``gmm``: megablox ``gmm`` (``transpose_rhs`` for ``drows``) and ``tgmm``
  (with the 537 MB transpose of the rows it needs, and without) at the
  same tiles.
- ``sizes``: the default against ``grouped_matmul_tiles``'s choice at 512 ...
  65,536 rows, both kinds of group sizes: where ``GROUPED_MATMUL_MIN_ROWS``
  comes from.
- ``rows``: the sorted layer's row movements (``ops/moe_dispatch.py``
  ``sort_tokens`` / ``unsort_combine``, ``ops/moe_rows.py``) at ``--tokens``
  x ``--choices`` rows of ``--width`` bf16 under a random routing: each
  gather alone, then each of the four passes (sort and combine, forward
  and backward) in the parent's form and as ``ops/moe_rows.py``'s rules
  have it, with the sum alone in both its forms (the kernel at the token
  blocks given as well) and the backward's scale and dot products alone;
  ms a call, GB/s on the bytes a pass has to move, and each result
  against the first form's.  With ``--buffer-rows R`` the SHARE path's
  (``share_sort_tokens`` / ``share_combine``: ``--groups`` experts scored,
  as many held as make ``R`` twice the level share): the combine's forward,
  the sort's backward and the combine's backward, each alone, as a
  scatter-add of ``R`` rows and as a gather of the ``--tokens`` x
  ``--choices`` assignments' rows and a sum (``share_gather_fits`` is set
  from these), with the gather and the sum alone.
- ``accuracy``: ``grouped_matmul`` (the program's call, its tiles) and
  plain ``ragged_dot``, result and both gradients, each against float32
  operands at ``Precision.HIGHEST``: rms of the difference over rms of
  the exact result.
"""

from __future__ import annotations

import argparse
import importlib
import itertools
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 10
# the defaults: olmoe-1b-7b-train-zipf4k (experts; 16,384 tokens x 8;
# gate and up, down); main() takes another cell's from the command line
GROUPS, ROWS = 64, 131072
WIDTHS = ((2048, 1024), (1024, 2048))
# ``skewed``: the smallthinker cell's expert_load_max_over_mean reads 5.4-5.9
MAX_OVER_MEAN = 5.6
KINDS = ("forward", "drows", "dweights")
ROW_TILES = (256, 512, 1024)
OUT = os.path.join(REPO, "chiprun_out", "grouped_matmul_probe.jsonl")


def row(**facts) -> None:
    line = json.dumps(facts)
    print("ROW " + line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def require_tpu():
    import jax

    d = jax.devices()[0]
    print(f"# device: {d.platform} [{d.device_kind}] jax {jax.__version__}",
          flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"grouped_matmul_probe needs a TPU, found {d.platform!r}")


def group_sizes(how: str, m: int = ROWS, groups: int = GROUPS,
                max_over_mean: float = MAX_OVER_MEAN):
    """``collapsed``: an eighth of the experts with 97 % of the rows
    between them, the rest spread over the others (largest over mean
    7.76, as the olmoe cell's ``expert_load_max_over_mean`` reads);
    ``skewed``: sizes that fall as a power of the expert's rank, the
    power found so that the largest over the mean is ``max_over_mean``;
    ``uniform``: m / groups each."""
    import numpy as np

    if how == "uniform":
        sizes = np.full(groups, m // groups)
    elif how == "skewed":
        ranks = np.arange(1, groups + 1, dtype=np.float64)
        lo, hi = 0.0, 8.0  # largest over mean grows with the power: bisect
        for _ in range(60):
            power = (lo + hi) / 2
            share = ranks ** -power
            lo, hi = (power, hi) if share[0] / share.mean() < max_over_mean else (lo, power)
        sizes = np.floor(m * share / share.sum()).astype(np.int64)
    else:
        hot = int(7.76 * m / groups)
        sizes = np.full(groups, (m - groups // 8 * hot) // (groups - groups // 8))
        sizes[::8] = hot
    sizes[-1] += m - sizes.sum()
    assert sizes.sum() == m and (sizes >= 0).all()
    return sizes.astype(np.int32)


def operands(k: int, n: int, m: int = ROWS, groups: int = GROUPS):
    """Unit-variance rows and row cotangents, weights of variance 1 / k."""
    import jax
    import jax.numpy as jnp

    kx, kw, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (groups, k, n), jnp.float32) / k ** 0.5).astype(jnp.bfloat16)
    g = jax.random.normal(kg, (m, n), jnp.float32).astype(jnp.bfloat16)
    return x, w, g


def width_tiles(dim: int) -> list:
    """The tiles tried along a width: every multiple of the 128 lanes,
    from 384 up, that divides it; or, for a width no multiple of the lanes
    divides (1,856 is 14.5 of them), that divides its cover, the next
    multiple of 128 (1,920: 384, 640, 1,920): a tile that does not divide
    is padded."""
    cover = -(-dim // 128) * 128
    return [t for t in range(384, cover + 1, 128) if cover % t == 0]


def ragged_call(kind: str, tiles):
    """One of the three calls through ``jax.lax.ragged_dot``, under the
    attribute (``tiles``) or as it is (``None``): the operations autodiff
    makes of ``grouped_matmul``."""
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.ops.moe_dispatch import (
        WEIGHTS_GRADIENT,
        ragged_dot_tiling,
    )

    def call(x, w, g, sizes):
        with ragged_dot_tiling(tiles):
            if kind == "forward":
                return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)
            if kind == "drows":
                return jax.lax.ragged_dot(
                    g, jnp.swapaxes(w, 1, 2), sizes, preferred_element_type=g.dtype)
            return jax.lax.ragged_dot_general(
                x, g, sizes, WEIGHTS_GRADIENT, preferred_element_type=x.dtype)

    return jax.jit(call)


def gmm_call(kind: str, tiles, transposed_rows: bool = False):
    """The same call through megablox.  ``tgmm`` takes the rows as
    ``[k, m]``: ``transposed_rows`` hands them over so (the kernel
    alone); otherwise the transpose is inside the timed program."""
    import jax
    import jax.numpy as jnp
    # the package's ``gmm`` is the differentiable wrapper: the kernels'
    # module has to be asked for by its path
    mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

    def call(x, w, g, sizes):
        if kind == "forward":
            return mb.gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=tiles)
        if kind == "drows":
            return mb.gmm(g, w, sizes, preferred_element_type=g.dtype, tiling=tiles,
                          transpose_rhs=True)
        xt = x if transposed_rows else jnp.swapaxes(x, 0, 1)
        return mb.tgmm(xt, g, sizes, preferred_element_type=x.dtype, tiling=tiles)

    return jax.jit(call)


def program_tiles(kind: str, m: int, k: int, n: int, dtype):
    """What ``grouped_matmul`` runs that kind of call at."""
    from learning_at_home_tpu.ops.moe_dispatch import grouped_matmul_tiles

    return grouped_matmul_tiles(
        *((m, n, k) if kind == "drows" else (m, k, n)), dtype,
        weights_gradient=kind == "dweights")


def timed(what: str, settings: dict, fn, *args) -> None:
    """Compile ``fn`` for ``args``, read the tiling off the compiled
    ragged-dot instruction, time ``ITERS`` launches."""
    import jax

    try:
        compiled = fn.lower(*args).compile()
        carried = sorted(set(re.findall(
            r'%ragged-dot-none[^\n]*ragged_dot_tiling="([0-9,]+)"', compiled.as_text())))
        jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        out = None
        for _ in range(ITERS):
            out = compiled(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / ITERS * 1e3
        facts = dict(ms=round(ms, 3))
        if carried:
            facts["compiled_tiling"] = carried
        row(what=what, **settings, **facts)
    except Exception as e:  # the compiler's own refusal (VMEM), or the kernel's
        text = f"{type(e).__name__}: {e}"
        short = re.search(r"Scoped allocation with size \S+ and limit \S+", text)
        row(what=what, **settings,
            refused=short.group(0) if short else text.splitlines()[0][:300])


def sweep(what: str, make_call, shape) -> None:
    """Every setting of the row tiles given (or ``ROW_TILES``) with
    ``width_tiles`` of the call's two widths for tk and tn (the rows'
    gradient contracts ``n`` and makes ``k``)."""
    import jax.numpy as jnp

    require_tpu()
    how, m = shape.group_sizes, shape.rows
    # a share's buffer holds more rows than its groups count
    sizes = jnp.asarray(group_sizes(
        how, shape.counted_rows or m, shape.groups, shape.max_over_mean))
    for k, n in shape.widths:
        x, w, g = operands(k, n, m, shape.groups)
        for kind in KINDS:
            a, b = (n, k) if kind == "drows" else (k, n)
            settings = list(itertools.product(
                shape.row_tiles or ROW_TILES, width_tiles(a), width_tiles(b)))
            base = dict(kind=kind, groups=how, m=m, k=k, n=n)
            if what == "ragged_dot":
                timed(what, dict(base, tiles=None), make_call(kind, None), x, w, g, sizes)
            for tiles in settings:
                timed(what, dict(base, tiles=tiles), make_call(kind, tiles), x, w, g, sizes)
            if what == "gmm" and kind == "dweights":
                xt = jnp.swapaxes(x, 0, 1)
                for tiles in settings:
                    timed("gmm_rows_transposed_before", dict(base, tiles=tiles),
                          gmm_call(kind, tiles, transposed_rows=True), xt, w, g, sizes)


def tiles(shape) -> None:
    sweep("ragged_dot", ragged_call, shape)


def gmm(shape) -> None:
    sweep("gmm", gmm_call, shape)


def sizes(shape) -> None:
    """Where the gain starts: the compiler's own tiles against
    ``grouped_matmul_tiles``'s at fewer rows."""
    import jax.numpy as jnp

    from learning_at_home_tpu.ops import moe_dispatch

    require_tpu()
    moe_dispatch.GROUPED_MATMUL_MIN_ROWS = 0  # ask the rule below its threshold
    hows = dict.fromkeys(("uniform", shape.group_sizes))
    for m, how in itertools.product((512, 2048, 8192, 32768, 65536), hows):
        group = jnp.asarray(group_sizes(how, m, shape.groups, shape.max_over_mean))
        for k, n in shape.widths:
            x, w, g = operands(k, n, m, shape.groups)
            for kind in KINDS:
                for setting in (None, program_tiles(kind, m, k, n, x.dtype)):
                    timed("ragged_dot", dict(kind=kind, groups=how, m=m, k=k, n=n,
                                             tiles=setting),
                          ragged_call(kind, setting), x, w, g, group)


def accuracy(shape) -> None:
    import jax
    import jax.numpy as jnp

    from learning_at_home_tpu.ops.moe_dispatch import grouped_matmul

    require_tpu()
    worst, m = 0.0, shape.rows
    hows = dict.fromkeys((shape.group_sizes, "uniform"))
    for (k, n), how in itertools.product(shape.widths, hows):
        group = jnp.asarray(group_sizes(how, m, shape.groups, shape.max_over_mean))
        x, w, g = operands(k, n, m, shape.groups)

        def all_three(fn, x, w, g):
            out, vjp = jax.vjp(lambda x, w: fn(x, w, group), x, w)
            return (out,) + vjp(g.astype(out.dtype))

        def plain(x, w, sizes):
            return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)

        def exact(x, w, sizes):
            return jax.lax.ragged_dot(x, w, sizes, precision=jax.lax.Precision.HIGHEST)

        got = {
            "grouped_matmul": jax.jit(lambda *a: all_three(grouped_matmul, *a))(x, w, g),
            "ragged_dot": jax.jit(lambda *a: all_three(plain, *a))(x, w, g),
        }
        want = jax.jit(lambda *a: all_three(exact, *a))(
            *(a.astype(jnp.float32) for a in (x, w, g)))

        def rel(a, ref):
            a, ref = a.astype(jnp.float32), ref.astype(jnp.float32)
            return float(jnp.sqrt(jnp.mean((a - ref) ** 2) / jnp.mean(ref ** 2)))

        for i, kind in enumerate(KINDS):
            read = dict(
                grouped_matmul_vs_exact=rel(got["grouped_matmul"][i], want[i]),
                ragged_dot_vs_exact=rel(got["ragged_dot"][i], want[i]),
                grouped_matmul_vs_ragged_dot=rel(
                    got["grouped_matmul"][i], got["ragged_dot"][i]),
            )
            worst = max(worst, read["grouped_matmul_vs_exact"])
            row(what="accuracy", kind=kind, groups=how, m=m, k=k, n=n,
                tiles=program_tiles(kind, m, k, n, x.dtype), **read)
    # one bf16 rounding of the result reads 0.17 % rms
    row(what="accuracy_verdict", ok=worst < 0.005, worst_vs_exact=worst, limit=0.005)
    if not worst < 0.005:
        raise SystemExit("grouped_matmul_probe: over 0.5 % from the float32 result")


def time_passes(passes, **shape_facts) -> None:
    """``(what, form, call, arguments, least bytes)`` a pass: compile, time
    ``ITERS`` launches, and hold each result against the first form's."""
    import jax
    import jax.numpy as jnp

    want = {}
    for what, form, call, args, least in passes:
        try:
            compiled = jax.jit(call).lower(*args).compile()
            got = jax.block_until_ready(compiled(*args))
            t0 = time.perf_counter()
            for _ in range(ITERS):
                out = compiled(*args)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / ITERS * 1e3
        except Exception as e:  # a block the compiler refuses
            row(what=what, form=form, refused=f"{type(e).__name__}: {e}"[:300])
            continue
        facts = dict(ms=round(ms, 3), gb_s_on_least_bytes=round(least / ms / 1e6, 1))
        first = want.setdefault(what, got)
        if first is not got:
            facts["rms_against_first"] = [
                float(jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)
                               / jnp.mean(b.astype(jnp.float32) ** 2)))
                for a, b in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(first))]
        row(what=what, form=form, **shape_facts, **facts)


def share_rows(shape) -> None:
    """A share's row movements: each pass as the scatter-add of the buffer's
    rows and as the gather of the assignments' rows and a sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.ops import moe_dispatch as md
    from learning_at_home_tpu.ops import moe_rows

    require_tpu()
    n, k, d, buffer_rows = shape.tokens, shape.choices, shape.width, shape.buffer_rows
    scored = shape.groups
    held = buffer_rows * scored // (2 * n * k)  # the buffer is twice the level share
    if md.share_buffer_rows(n, k, held, scored) != buffer_rows:
        raise SystemExit(
            f"no whole number of {scored} experts makes {buffer_rows} rows "
            f"twice the level share of {n} x {k} assignments")
    rs = np.random.default_rng(60)
    plan = jax.jit(
        lambda logits: md.share_routing(logits, k, scored // 4, held, buffer_rows)
    )(jnp.asarray(rs.standard_normal((n, scored)), jnp.float32))
    bf16 = jnp.bfloat16
    x = jnp.asarray(rs.standard_normal((n, d)), bf16)
    ys = jnp.asarray(rs.standard_normal((buffer_rows, d)), bf16)
    buffer, tokens, picked = buffer_rows * d * 2, n * d * 2, n * k * d * 2  # bytes, bf16
    row(what="share", tokens=n, choices=k, width=d, buffer_rows=buffer_rows,
        scored=scored, held=held, routed_here=int(plan.routed_here),
        dropped=int(plan.routed_here) - int(plan.group_sizes.sum()),
        rule=md.share_gather_fits(n, k, buffer_rows, d, bf16, "tpu"),
        sum_kernel=moe_rows.sum_rows_fits(n, k, d, bf16, "tpu"))

    def combine(gathered):
        return lambda ys, weight: md._share_combine(
            ys, weight, plan.token, plan.valid, plan.slot, plan.slot_weight,
            bf16, gathered)

    def parent_combine(ys, weight):
        return md._share_scatter_add(ys, weight, plan.token, plan.valid, n).astype(bf16)

    def selected_combine(ys, weight):  # the sentinel's other cure: a pass that zeroes what it read
        return moe_rows.sum_rows(
            jnp.where(held_here, md._rows_of_slots(ys, plan.slot), 0),
            plan.slot_weight, n, k, bf16)

    def parent_sort(x):
        return jnp.where(plan.valid[:, None], x[plan.token], 0)

    def rule_sort(x):
        return md._share_rows_to_buffer(x, plan.token, plan.valid, plan.slot)

    def back(form):
        return lambda g, *primals: jax.vjp(form, *primals)[1](g)

    gathered = md._rows_of_slots(ys, plan.slot)
    flat = plan.slot.reshape(-1)
    held_here = (flat < buffer_rows)[:, None]
    time_passes([
        ("gather_to_buffer", "xla", parent_sort, (x,), tokens + buffer),
        ("gather_of_slots", "rule", lambda ys: md._rows_of_slots(ys, plan.slot), (ys,),
         buffer + picked),
        # every assignment with no row reads the buffer's last
        ("gather_of_slots", "one_row", lambda ys: ys[jnp.minimum(flat, buffer_rows - 1)],
         (ys,), buffer + picked),
        ("gather_of_slots", "selected",
         lambda ys: jnp.where(held_here, md._rows_of_slots(ys, plan.slot), 0), (ys,),
         buffer + picked),
        ("sum", "rule", lambda p, w: moe_rows.sum_rows(p, w, n, k, bf16, masked=True),
         (gathered, plan.slot_weight), picked + tokens),
        ("sum", "plain", lambda p, w: moe_rows.sum_rows_plain(p, w, n, k, bf16, masked=True),
         (gathered, plan.slot_weight), picked + tokens),
        # the dropless layer's two calls: a weight broadcast over the lanes in the kernel, and none
        ("sum", "not_masked", lambda p, w: moe_rows.sum_rows(p, w, n, k, bf16),
         (gathered, plan.slot_weight), picked + tokens),
        ("sum", "no_weights", lambda p: moe_rows.sum_rows(p, None, n, k, bf16),
         (gathered,), picked + tokens),
        ("combine_forward", "scatter_add", parent_combine, (ys, plan.weight), buffer + tokens),
        ("combine_forward", "gather", combine(True), (ys, plan.weight), buffer + tokens),
        ("combine_forward", "gather_selected", selected_combine,
         (ys, plan.weight), buffer + tokens),
        ("sort_backward", "scatter_add", back(parent_sort), (ys, x), buffer + tokens),
        ("sort_backward", "gather", back(rule_sort), (ys, x), buffer + tokens),
        ("combine_backward", "autodiff", back(parent_combine), (x, ys, plan.weight),
         2 * buffer + tokens),
        ("combine_backward", "rule", back(combine(False)), (x, ys, plan.weight),
         2 * buffer + tokens),
    ], tokens=n, choices=k, width=d, buffer_rows=buffer_rows)


def rows(shape) -> None:
    """The sorted layer's row movements, the parent's forms and the rule's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.ops import moe_dispatch as md
    from learning_at_home_tpu.ops import moe_rows

    require_tpu()
    n, k, d = shape.tokens, shape.choices, shape.width
    rs = np.random.default_rng(50)
    # every token picks k distinct experts of GROUPS, as a router would
    picks = np.argsort(rs.random((n, shape.groups)), axis=1)[:, :k]
    order = jnp.asarray(np.argsort(picks.reshape(-1), kind="stable"), jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    weights = jnp.asarray(rs.random((n, k)), jnp.float32)
    x = jnp.asarray(rs.standard_normal((n, d)), jnp.bfloat16)
    ys = jnp.asarray(rs.standard_normal((n * k, d)), jnp.bfloat16)
    picked, scale = ys[inverse], weights.reshape(-1)[order]
    small, large = n * d * 2, n * k * d * 2  # bytes of [n, d] and [n*k, d] bf16
    bf16 = jnp.bfloat16

    def scale_and_dots(rows, others, scale):  # the combine's backward, behind its gather
        wide = rows.astype(jnp.float32)
        return ((scale[:, None] * wide).astype(bf16),
                jnp.sum(wide * others.astype(jnp.float32), axis=-1))

    def parent_combine(ys, weights):
        return moe_rows.sum_rows_plain(
            md._rows_from_sorted(ys, order, inverse), weights, n, k, bf16)

    def combine(ys, weights):
        return md._combine_sorted(ys, weights, order, inverse, bf16)

    def back(form):
        return lambda g, *primals: jax.vjp(form, *primals)[1](g)

    def sort_back(sum_rows):
        return lambda g: sum_rows(g[inverse], None, n, k, g.dtype)

    def sum_at(tokens):
        def call(picked, weights):
            moe_rows._TOKENS = tokens  # read while the call is traced
            try:
                return moe_rows.sum_rows_kernel(picked, weights, n, k, bf16)
            finally:
                moe_rows._TOKENS = committed
        return call

    committed = moe_rows._TOKENS
    passes = [  # (what, form, its call, arguments, bytes it has to move)
        ("gather_to_sorted", "xla", lambda x: x[order // k], (x,), small + large),
        ("gather_from_sorted", "xla", lambda ys: ys[inverse], (ys,), 2 * large),
        ("sum", "plain", lambda p, w: moe_rows.sum_rows_plain(p, w, n, k, bf16),
         (picked, weights), large + small),
        *[("sum", f"kernel@{t}", sum_at(t), (picked, weights), large + small)
          for t in [committed, *shape.row_tiles]],
        ("scale_and_dots", "xla", scale_and_dots, (picked, ys, scale), 3 * large),
        ("sort_backward", "parent", sort_back(moe_rows.sum_rows_plain), (ys,), large + small),
        ("sort_backward", "rule", sort_back(moe_rows.sum_rows), (ys,), large + small),
        ("combine_forward", "parent", parent_combine, (ys, weights), large + small),
        ("combine_forward", "rule", combine, (ys, weights), large + small),
        ("combine_backward", "parent", back(parent_combine), (x, ys, weights), 2 * large + small),
        ("combine_backward", "rule", back(combine), (x, ys, weights), 2 * large + small),
    ]
    time_passes(passes, tokens=n, choices=k, width=d)


def main() -> None:
    def widths(text: str):
        return tuple(tuple(map(int, pair.split("x"))) for pair in text.split(","))

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("command", choices=("tiles", "gmm", "sizes", "accuracy", "rows"))
    ap.add_argument("group_sizes", nargs="?", default="collapsed",
                    choices=("collapsed", "skewed", "uniform"))
    ap.add_argument("row_tiles", nargs="*", type=int,
                    help=f"tiles, gmm: row tiles to sweep (default {ROW_TILES}); "
                         "rows: token blocks of the kernel beside the rule's")
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--groups", type=int, default=GROUPS)
    ap.add_argument("--widths", type=widths, default=WIDTHS,
                    help="kxn of each grouped matmul, comma-separated")
    ap.add_argument("--counted-rows", type=int, default=None,
                    help="tiles, gmm: the rows the groups count, where the "
                         "buffer (--rows) holds more (a share's)")
    ap.add_argument("--max-over-mean", type=float, default=MAX_OVER_MEAN,
                    help="skewed: the largest group's rows over the mean")
    ap.add_argument("--tokens", type=int, default=16384, help="rows: n")
    ap.add_argument("--choices", type=int, default=8, help="rows: k")
    ap.add_argument("--width", type=int, default=2048, help="rows: d")
    ap.add_argument("--buffer-rows", type=int, default=None,
                    help="rows: a share's buffer, R; its passes in place of the dropless layer's")
    shape = ap.parse_args()
    {"tiles": tiles, "gmm": gmm, "sizes": sizes, "accuracy": accuracy,
     "rows": share_rows if shape.buffer_rows else rows}[shape.command](shape)


if __name__ == "__main__":
    main()
