#!/usr/bin/env python3
"""The attention core on the chip: what ``models/trunk.py`` rests on.

    python tools/attention_probe.py splash     # the blocked kernel's tiles
    python tools/attention_probe.py tiles      # the other blocked kernel's
    python tools/attention_probe.py cores      # blocked against xla, by length
    python tools/attention_probe.py accuracy   # blocked against xla, results
    python tools/attention_probe.py window     # the blocked kernel's tiles under a window
    python tools/attention_probe.py latent     # the blocked kernel's tiles at heads of 256
    python tools/attention_probe.py blockdiff  # the blocked kernel's tiles under block diffusion's mask

One process a subcommand (a chip belongs to one process), one ``ROW``
line of JSON a reading, written to ``chiprun_out/attention_probe.jsonl``
as well.  Every time is the host clock around ``ITERS`` launches that end
in ``block_until_ready``, after a first call that compiles; a setting the
kernel or Mosaic refuses is a row with ``refused``.  PERF.md section 6
("PR 28") holds the readings ``trunk.flash_block_sizes`` and ``auto``'s
threshold were set from.

- ``splash``: ``splash_attention`` under a causal mask at the OLMoE
  cell's ``[4, 16, 4096, 128]`` bf16, forward and forward + backward,
  fused backward or not, then each pass's tiles under the best of the
  other: the kernel behind ``attention_core``'s ``impl="flash"``.
- ``tiles``: ``flash_attention`` at the same shape, causal: the forward
  kernel, the dK/dV kernel and the dQ kernel each over their own tile
  sizes (the backward kernels through the module's private entry points:
  the public call runs all three).  The kernel the repo had; at its best
  tiles 3.3 % of the cell's step behind splash.
- ``cores``: ``attention_core`` ``flash`` against ``xla``, forward and
  forward + backward, at the cell's shape and at 8,192 tokens a call for
  S = 256 ... 8,192 and head sizes 64 and 128: where ``auto``'s
  threshold comes from.
- ``window``: ``splash_attention`` at the two 16,384-token cells' shapes,
  grouped heads, forward and forward + backward.  At K-EXAONE's ``[1, 64
  over 8, 16384, 128]`` under ``LocalMask(127, 0)``: the baseline (1024,
  1024, 512, fused) beside ``block_q`` in {256, 512, 1024, 2048} x
  ``block_kv`` in {128, 256, 512} with the compute tile at the block and
  at half of it, unfused (a dK/dV and a dQ kernel over grids the mask
  shrinks, no per-key-block partials of the queries' gradient), then each
  backward kernel's own tiles under the best of the others, a few
  settings past the sweep's edges (``window beyond``), then the kernel
  under ``flash_block_sizes``'s answer for the window (``window rule``).
  At SmallThinker's ``[1, 28 over 4, 16384, 128]`` under a window of 4,096
  and under the causal mask: the baseline against the unfused form at the
  same blocks.  PERF.md section 6 ("PR 36") holds the table the rule's
  short-window regime was set from.  ``window band``: the band kernel
  (``ops/band_attention.py``, what ``attention_core`` hands a window
  shorter than the key block since PR 63) beside that regime at
  K-EXAONE's shape under windows of 128, 256 and 512, each kernel on
  arrays in its own layout (heads first; positions last), and the band
  kernel at 64 over 16 and 64 over 64 heads (groups of 4 and of 1);
  PERF.md section 6 ("PR 63").
- ``latent``: ``splash_attention`` under a causal mask at GLM-4.7-Flash's
  expanded latent attention, ``[1, 20, 16384, 256]`` bf16 (queries, keys
  and values of 256 a head), forward and forward + backward: the blocks of
  heads of 128 and their neighbours, fused backward and not, then the
  kernel under ``flash_block_sizes``'s answer (``latent rule``).  PERF.md
  section 6 ("PR 37") holds the table the rule's regime for heads of 256
  was set from.  ``latent all xing4``: the same at Xing4.0's and Ling-3.0's
  ``[1, 32, 16384, 192 | 128]``, where the backward is the repo's ONE call
  (``ops/attention_backward.py``) read beside the library's unfused pair
  and its fused kernel; PERF.md section 6 ("PR 64", "PR 69").
- ``blockdiff``: ``splash_attention`` under block diffusion's mask
  (``trunk.block_diffusion_mask``, the computable form ``attention_core``
  builds, blocks of 4) at the SDAR cell's doubled row, ``[1, 32 over 4,
  16384, 128]`` bf16, forward and forward + backward: the causal rule's
  blocks and their neighbours (narrower blocks visit fewer pairs the mask
  empties, wider ones fewer grid steps), fused backward and not, each row
  with the pairs its forward visits over the 67,141,632 the mask admits;
  the same blocks under the causal mask for scale; then the kernel under
  ``flash_block_sizes``'s answer (``blockdiff rule``).  PERF.md section 6
  ("PR 57") holds the table.
- ``accuracy``: ``attention_core`` ``flash`` against ``xla`` at the
  cell's shape, output and the three input gradients: rms of the
  difference over rms of the ``xla`` result (limit 1 %), beside what bf16
  itself costs (each against float32 operands at ``Precision.HIGHEST``).
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 10
CELL = (4, 16, 4096, 128)  # olmoe-1b-7b-train-zipf4k: batch, heads, S, head
OUT = os.path.join(REPO, "chiprun_out", "attention_probe.jsonl")


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
        raise SystemExit(f"attention_probe needs a TPU, found {d.platform!r}")


def ms(fn, *args) -> float:
    """Milliseconds a call, compiled before the clock starts."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(ITERS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / ITERS * 1e3


def timed(what: str, settings: dict, fn, *args) -> float | None:
    """The row of one reading; its ms, None where it was refused."""
    try:
        took = ms(fn, *args)
    except Exception as e:  # the kernel's own refusal, or Mosaic's
        text = f"{type(e).__name__}: {e}"
        row(what=what, **settings, refused=text.splitlines()[0][:300])
        return None
    row(what=what, **settings, ms=round(took, 3))
    return took


def qkv(shape):
    """Unit-variance bf16 q, k, v and an output cotangent of ``shape``."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return tuple(
        jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
        for k in keys
    )


def major_minor(majors, minors):
    return [(a, b) for a in majors for b in minors if b <= a]


def tiles() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    require_tpu()
    b, h, s, hd = CELL
    q, k, v, do = qkv(CELL)
    scale = 1.0 / hd ** 0.5

    def forward(bq, bkm, bk, bb):
        sizes = fa.BlockSizes(block_q=bq, block_k_major=bkm, block_k=bk, block_b=bb)
        return jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, sm_scale=scale, block_sizes=sizes))

    timed("forward", dict(block_q=128, block_k_major=128, block_k=128, block_b=1),
          forward(128, 128, 128, 1), q, k, v)
    for bq, (bkm, bk), bb in itertools.product(
        (256, 512, 1024, 2048),
        major_minor((256, 512, 1024, 2048), (256, 512, 1024)), (1, 2),
    ):
        timed("forward", dict(block_q=bq, block_k_major=bkm, block_k=bk, block_b=bb),
              forward(bq, bkm, bk, bb), q, k, v)

    # the residuals the backward kernels take, as _flash_attention_bwd does
    o, l, m = jax.jit(lambda q, k, v: fa._flash_attention_impl(
        q, k, v, None, None, True, True, scale, 1, 512, 512, 512, False))(q, k, v)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    def dkv(bqm, bq, bkm, bk):
        return jax.jit(lambda *a: fa._flash_attention_bwd_dkv(
            *a, block_q_major=bqm, block_q=bq, block_k_major=bkm, block_k=bk,
            sm_scale=scale, causal=True, mask_value=fa.DEFAULT_MASK_VALUE,
            debug=False))

    def dq(bqm, bkm, bk):
        return jax.jit(lambda *a: fa._flash_attention_bwd_dq(
            *a, block_q_major=bqm, block_k_major=bkm, block_k=bk,
            sm_scale=scale, causal=True, mask_value=fa.DEFAULT_MASK_VALUE,
            debug=False))

    args = (q, k, v, None, None, l, m, do, di)
    timed("dkv", dict(block_q_major_dkv=128, block_q_dkv=128,
                      block_k_major_dkv=128, block_k_dkv=128),
          dkv(128, 128, 128, 128), *args)
    pairs = major_minor((256, 512, 1024, 2048), (256, 512, 1024))
    for (bqm, bq), (bkm, bk) in itertools.product(pairs, pairs):
        if bq * bk > 512 * 1024:  # four float32 [bq, bk] temporaries a step
            continue
        timed("dkv", dict(block_q_major_dkv=bqm, block_q_dkv=bq,
                          block_k_major_dkv=bkm, block_k_dkv=bk),
              dkv(bqm, bq, bkm, bk), *args)
    timed("dq", dict(block_q_dq=128, block_k_major_dq=128, block_k_dq=128),
          dq(128, 128, 128), *args)
    for bqm, (bkm, bk) in itertools.product((256, 512, 1024, 2048), pairs):
        if bqm * bk > 512 * 1024:
            continue
        timed("dq", dict(block_q_dq=bqm, block_k_major_dq=bkm, block_k_dq=bk),
              dq(bqm, bkm, bk), *args)


def _splash(forward, backward, fused, heads, s, window=None, dq=None,
            mask=None):
    """``forward``, ``backward``: (block_q, block_kv, block_kv_compute);
    ``dq``: the unfused backward's dQ kernel's (block_q, block_kv), the
    backward's blocks where none is given; ``mask``: a head's mask where it
    is neither causal nor a window."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    (bq, bkv, bkvc), (bq_b, bkv_b, bkvc_b) = forward, backward
    bq_dq, bkv_dq = (None, None) if fused else dq or (bq_b, bkv_b)
    sizes = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
        block_q_dkv=bq_b, block_kv_dkv=bkv_b, block_kv_dkv_compute=bkvc_b,
        block_q_dq=bq_dq, block_kv_dq=bkv_dq,
        use_fused_bwd_kernel=fused,
    )
    one = mask if mask is not None else (
        sm.CausalMask((s, s)) if window is None
        else sm.LocalMask((s, s), (window - 1, 0), offset=0))
    return sk.make_splash_mha_single_device(
        mask=sm.MultiHeadMask([one] * heads), block_sizes=sizes)


def splash() -> None:
    import jax

    require_tpu()
    b, h, s, hd = CELL
    q, k, v, do = qkv(CELL)
    scale = 1.0 / hd ** 0.5
    both_ways = [(sizes, sizes, fused) for sizes, fused in itertools.product(
        ((512, 512, 512), (512, 1024, 512), (1024, 1024, 512),
         (1024, 1024, 1024), (1024, 2048, 512), (1024, 2048, 1024),
         (2048, 2048, 512), (2048, 2048, 1024), (512, 2048, 512)),
        (False, True),
    )]
    # then each pass's own tiles under the best of the other
    best = (1024, 1024, 512)
    backward_only = [(best, sizes, True) for sizes in (
        (512, 1024, 256), (512, 2048, 512), (1024, 512, 512), (1024, 1024, 256),
        (2048, 512, 512), (2048, 1024, 256), (2048, 1024, 512), (2048, 2048, 256),
        (4096, 512, 512), (4096, 1024, 256),
    )]
    forward_only = [(sizes, best, True) for sizes in (
        (1024, 1024, 256), (1024, 512, 512), (2048, 1024, 512), (2048, 512, 512),
        (2048, 1024, 256), (4096, 512, 512),
    )]
    for forward, backward, fused in both_ways + backward_only + forward_only:
        settings = dict(forward=forward, backward=backward, fused_bwd=fused)
        try:
            kernel = _splash(forward, backward, fused, h, s)
        except Exception as e:
            row(what="splash", **settings, refused=f"{type(e).__name__}: {e}"[:300])
            continue
        fwd = jax.jit(lambda q, k, v: jax.vmap(kernel)(q * scale, k, v))

        def both(q, k, v, do):
            out, vjp = jax.vjp(fwd, q, k, v)
            return out, vjp(do)

        if backward == best or forward == backward:
            timed("splash_forward", settings, fwd, q, k, v)
        timed("splash_forward_backward", settings, jax.jit(both), q, k, v, do)


# batch, query heads, key/value heads, S, head size, window
KEXAONE = (1, 64, 8, 16384, 128, 128)       # k-exaone-236b-a23b-train-zipf16k
SMALLTHINKER = (1, 28, 4, 16384, 128, 4096)  # smallthinker-21b-a3b-train-zipf16k
BASELINE = (1024, 1024, 512)  # trunk._FLASH_TILES: PR 28's sweep, causal
BAND_WINDOWS = (128, 256, 512)  # what ops.band_attention.band_kernel_fits takes


def _window_reading(cell, window, forward, backward, fused, dq, args,
                    forward_too=True, mask=None, **also):
    """Rows ``window_forward`` (where ``forward_too``) and
    ``window_forward_backward`` of one setting at ``cell``'s shape; their
    ms in that order, None where the kernel or Mosaic refused."""
    import jax

    b, h, hkv, s, hd, _ = cell
    dq = None if fused else dq or backward[:2]
    settings = dict(
        shape=[b, h, hkv, s, hd], window=window, forward=forward,
        backward=backward, dq=dq, fused_bwd=fused, **also)
    t0 = time.perf_counter()
    try:
        kernel = _splash(forward, backward, fused, h, s, window, dq, mask)
    except Exception as e:
        row(what="window_forward_backward", **settings,
            refused=f"{type(e).__name__}: {e}"[:300])
        return None
    settings["mask_tables_s"] = round(time.perf_counter() - t0, 2)
    scale = 1.0 / hd ** 0.5
    fwd = jax.jit(lambda q, k, v: jax.vmap(kernel)(q * scale, k, v))

    def both(q, k, v, do):
        out, vjp = jax.vjp(fwd, q, k, v)
        return out, vjp(do)

    read = [timed("window_forward", settings, fwd, *args[:3])] if forward_too else []
    read.append(timed("window_forward_backward", settings, jax.jit(both), *args))
    return None if None in read else tuple(read)


def _rule_reading(cell, window, args, **also):
    """:func:`_window_reading` of the blocked kernel under
    ``flash_block_sizes``'s answer for ``window``, as ``attention_core``
    builds it where the band kernel does not take the call."""
    from learning_at_home_tpu.models import trunk

    b, h, _, s, hd, _ = cell
    sizes = trunk.flash_block_sizes((b, s, h, hd), "tpu", window)
    fused = sizes.use_fused_bwd_kernel
    return _window_reading(
        cell, window,
        (sizes.block_q, sizes.block_kv, sizes.block_kv_compute),
        (sizes.block_q_dkv, sizes.block_kv_dkv, sizes.block_kv_dkv_compute),
        fused, None if fused else (sizes.block_q_dq, sizes.block_kv_dq), args,
        **also)


def _band_reading(cell, window, **also):
    """Rows ``window_forward`` and ``window_forward_backward`` of the band
    kernel (``ops/band_attention.py``) at ``cell``'s shape, in ITS layout."""
    import jax

    from learning_at_home_tpu.ops.band_attention import band_attention

    b, h, hkv, s, hd, _ = cell
    # [B, heads * hd, S], positions last, as the step's projections and
    # rotations leave them on the chip: the transposes to heads and back
    # are no instruction, so the reading is the kernel's alone, as the
    # blocked kernel's is in its layout
    q, do, _, _ = qkv((b, h * hd, s))
    k, v, _, _ = qkv((b, hkv * hd, s))
    settings = dict(shape=[b, h, hkv, s, hd], window=window, kernel="band", **also)

    def heads(x):
        return x.reshape(b, -1, hd, s).transpose(0, 3, 1, 2)

    fwd = jax.jit(lambda q, k, v: band_attention(
        heads(q), heads(k), heads(v), window).transpose(0, 2, 3, 1).reshape(q.shape))

    def both(q, k, v, do):
        out, vjp = jax.vjp(fwd, q, k, v)
        return out, vjp(do)

    return (timed("window_forward", settings, fwd, q, k, v),
            timed("window_forward_backward", settings, jax.jit(both), q, k, v, do))


def _grouped_qkv(cell):
    """q [B, H, S, hd], k [B, Hkv, S, hd], v [B, Hkv, S, hd_v] and the
    output's cotangent [B, H, S, hd_v]; ``hd_v`` is ``VALUE_DIM``'s for the
    cell, else ``hd``."""
    b, h, hkv, s, hd, _ = cell
    hd_v = VALUE_DIM.get(cell, hd)
    q, k, _, _ = qkv((b, h, s, hd))
    k = k[:, :hkv]
    v, do, _, _ = qkv((b, h, s, hd_v))
    return q, k, v[:, :hkv], do


def window(which: str = "all") -> None:
    """``which``: ``all``; ``kexaone`` (``sweep``, ``beyond``, ``rule`` and
    ``band``, or one of the four alone); ``smallthinker``."""
    require_tpu()
    parts = {"all": ("sweep", "beyond", "rule", "band", "smallthinker"),
             "kexaone": ("sweep", "beyond", "rule", "band")}.get(which, (which,))
    if set(parts) & {"sweep", "beyond", "rule"}:
        cell, w = KEXAONE, KEXAONE[-1]
        args = _grouped_qkv(cell)

        def read(forward, backward, fused, dq=None, **also):
            return _window_reading(cell, w, forward, backward, fused, dq, args, **also)

    if "sweep" in parts:
        for fused in (True, False):  # what unfusing alone is worth
            read(BASELINE, BASELINE, fused, stage="baseline")
        grid = [(bq, bkv, c) for bq in (256, 512, 1024, 2048)
                for bkv in (128, 256, 512) for c in (bkv, bkv // 2) if c >= 128]
        same = {t: read(t, t, False, stage="same") for t in grid}
        same = {t: r for t, r in same.items() if r}
        best_fwd = min(same, key=lambda t: same[t][0])
        best_bwd = min(same, key=lambda t: same[t][1] - same[t][0])
        # each backward kernel's own tiles under the best of the others
        dkv = {t: read(best_fwd, t, False, best_bwd[:2], forward_too=False,
                       stage="dkv") for t in grid}
        best_dkv = min((t for t in dkv if dkv[t]), key=lambda t: dkv[t][0])
        dqs = {t: read(best_fwd, best_dkv, False, t, forward_too=False, stage="dq")
               for t in sorted({t[:2] for t in grid})}
        best_dq = min((t for t in dqs if dqs[t]), key=lambda t: dqs[t][0])
        row(what="window_best", shape=list(cell[:5]), window=w, forward=best_fwd,
            backward=best_dkv, dq=best_dq, forward_ms=round(same[best_fwd][0], 3),
            forward_backward_ms=round(dqs[best_dq][0], 3))
    if "beyond" in parts:
        # past the sweep's edges: key blocks as wide as the baseline's under
        # narrower query blocks, unfused; and the fused backward at the
        # sweep's best blocks (32 partials of the queries' gradient, 8.6 GB)
        for t in ((256, 1024, 512), (512, 1024, 512), (512, 1024, 1024)):
            read(t, t, False, stage="beyond")
        read((512, 512, 512), (512, 512, 512), True, stage="beyond")
    if "rule" in parts:
        # the rule's own answer, as attention_core would build it
        if "sweep" not in parts:
            read(BASELINE, BASELINE, True, stage="baseline")
        _rule_reading(cell, w, args, stage="rule")
    if "band" in parts:
        # the band kernel beside the blocked kernel's short-window regime,
        # at the cell's shape under each window the band's rule takes and
        # at its other group sizes under the cell's
        for w in BAND_WINDOWS:
            _rule_reading(KEXAONE, w, _grouped_qkv(KEXAONE), stage="band")
            _band_reading(KEXAONE, w, stage="band")
        for hkv in (16, 64):  # groups of 4 and of 1
            cell = (*KEXAONE[:2], hkv, *KEXAONE[3:])
            _band_reading(cell, cell[-1], stage="band")
    if "smallthinker" in parts:
        cell = SMALLTHINKER
        args = _grouped_qkv(cell)
        for w in (cell[-1], None):  # the window layers' mask, the global layer's
            for fused in (True, False):
                _window_reading(cell, w, BASELINE, BASELINE, fused, None, args,
                                stage="baseline")


GLM47 = (1, 20, 20, 16384, 256, None)  # glm-4.7-flash-train-zipf16k
# smallthinker's 28 heads of 128 with a key head each (its cell has 4: the
# one backward call takes no groups), for the kernels' rates at that size
MHA128 = (1, 28, 28, 16384, 128, None)
XING4 = (1, 32, 32, 16384, 192, None)  # xing4.0-29b-a4b-train-zipf16k
VALUE_DIM = {XING4: 128}  # a cell whose values are narrower than its keys


def latent(which: str = "all", config: str = "glm47") -> None:
    """``which``: ``all``, ``sweep``, ``rule`` or ``resident``: the ONE
    backward call of ``ops/attention_backward.py`` (PR 69), alone and with
    the library's forward.  ``config``: ``glm47`` (heads of 256) and
    ``mha128`` (28 heads of 128, a key head each), where ``resident`` reads
    it at three settings beside the library's fused backward at the rule's
    tiles and ``all`` leaves it out; or ``xing4`` (queries and keys of 192
    over values of 128, ``[1, 32, 16384, 192 | 128]``: the fused backward's
    partials of the queries' gradient, ``S / block_kv_dkv`` float32 copies
    of ``[32, 16384, 192]``, are 8 GB at key blocks of 1,024 and do not fit
    the step, so the library's sweep is the unfused backward's, with the
    fused one at key blocks of 2,048 and 1,024 beside it; ~4 min, PR 64),
    where ``resident`` is both grid orders over the blocks (~5 min), ``all``
    reads it first, and ``rule`` runs what ``resident_backward_fits``
    answers."""
    from learning_at_home_tpu.models import trunk

    require_tpu()
    cell = {"glm47": GLM47, "xing4": XING4, "mha128": MHA128}[config]
    args = _grouped_qkv(cell)
    if config == "xing4":
        return _latent_xing4(which, cell, args)

    def read(forward, backward, fused, **also):
        return _window_reading(cell, None, forward, backward, fused, None, args,
                               **also)

    if which == "resident":
        # the ONE backward call (PR 69) at sizes no rule hands it, beside
        # the library's fused backward at the rule's tiles: a table for a
        # later issue, no rule changes by it
        b, h, _, s, hd, _ = cell
        sizes = trunk.flash_block_sizes((b, s, h, hd), "tpu")
        forward = (sizes.block_q, sizes.block_kv, sizes.block_kv_compute)
        read(forward, (sizes.block_q_dkv, sizes.block_kv_dkv,
                       sizes.block_kv_dkv_compute), True, stage="rule")
        for blocks in ((1024, 1024, 256), (1024, 1024, 512), (512, 1024, 256)):
            _resident_reading(cell, forward, blocks, True, args, stage="resident")
        return
    if which in ("all", "sweep"):
        grid = ((1024, 1024, 512), (1024, 1024, 1024), (512, 1024, 512),
                (512, 512, 512), (1024, 512, 512), (512, 2048, 512),
                (1024, 2048, 512), (2048, 1024, 512), (2048, 512, 512),
                (256, 1024, 512), (512, 1024, 256), (1024, 1024, 256))
        same = {(t, fused): read(t, t, fused, stage="same")
                for t in grid for fused in (True, False)}
        same = {t: r for t, r in same.items() if r}
        best_fwd = min(same, key=lambda t: same[t][0])[0]
        # the backward's own tiles under the best forward's
        for t in grid:
            for fused in (True, False):
                if t != best_fwd:
                    read(best_fwd, t, fused, forward_too=False, stage="backward")
    if which in ("all", "rule"):
        b, h, _, s, hd, _ = cell
        sizes = trunk.flash_block_sizes((b, s, h, hd), "tpu")
        if sizes is None:
            row(what="latent_rule", refused="flash_block_sizes has no tiles")
            return
        read((sizes.block_q, sizes.block_kv, sizes.block_kv_compute),
             (sizes.block_q_dkv, sizes.block_kv_dkv, sizes.block_kv_dkv_compute),
             sizes.use_fused_bwd_kernel, stage="rule")


def _resident_reading(cell, forward, blocks, keys_outer, args, **also):
    """Rows ``resident_backward`` (the ONE backward call of
    ``ops/attention_backward.py`` alone, on a forward's kept output and row
    sums) and ``window_forward_backward`` (the library's forward at
    ``forward`` and that backward, with the scale's two products, as
    ``attention_backward.resident_attention``'s ``custom_vjp`` runs them) at
    ``blocks`` = (query block, key block, keys of one product), key blocks
    outer or query blocks outer; their ms, None where Mosaic refused."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    from learning_at_home_tpu.ops import attention_backward as ab

    b, h, hkv, s, hd, _ = cell
    sizes = sk.BlockSizes(
        block_q=forward[0], block_kv=forward[1], block_kv_compute=forward[2])
    settings = dict(
        shape=[b, h, hkv, s, hd], window=None, forward=forward,
        backward=blocks, bwd="resident", keys_outer=keys_outer, **also)
    scale = 1.0 / hd ** 0.5

    def backward(q, k, v, o, lse, do):
        return ab.attention_backward(q, k, v, o, lse, do, blocks, keys_outer)

    def both(q, k, v, do):
        q = q * scale
        o, lse = ab._forward(q, k, v, sizes, None, False)
        dq, dk, dv = backward(q, k, v, o, lse, do)
        return o, (dq * scale, dk, dv)

    q, k, v, do = args
    o, lse = jax.jit(lambda q, k, v: ab._forward(
        q * scale, k, v, sizes, None, False))(q, k, v)
    read = (timed("resident_backward", settings, jax.jit(backward),
                  q * scale, k, v, o, lse, do),
            timed("window_forward_backward", settings, jax.jit(both), *args))
    return None if None in read else read


def _latent_xing4(which, cell, args) -> None:
    from learning_at_home_tpu.models import trunk
    from learning_at_home_tpu.ops import attention_backward as ab

    def read(forward, backward, fused, dq=None, **also):
        return _window_reading(cell, None, forward, backward, fused, dq, args,
                               **also)

    if which in ("all", "resident"):
        # the ONE backward call (PR 69): both grid orders, the blocks swept
        forward = (1024, 1024, 256)
        for blocks in ((1024, 1024, 512), (1024, 1024, 256), (1024, 1024, 1024),
                       (512, 1024, 512), (1024, 512, 512), (512, 512, 512),
                       (2048, 1024, 512), (1024, 2048, 512), (2048, 2048, 512),
                       (512, 2048, 512), (2048, 512, 512)):
            for keys_outer in (True, False):
                _resident_reading(cell, forward, blocks, keys_outer, args,
                                  stage="resident")

    if which in ("all", "sweep"):
        forwards = ((1024, 1024, 512), (1024, 1024, 1024), (1024, 1024, 256),
                    (512, 1024, 512), (2048, 1024, 512), (1024, 2048, 512),
                    (2048, 2048, 512))
        same = {t: read(t, (1024, 1024, 512), False, stage="forward")
                for t in forwards}
        same = {t: r for t, r in same.items() if r}
        best = min(same, key=lambda t: same[t][0])
        for t in ((1024, 1024, 1024), (1024, 1024, 256), (512, 1024, 512),
                  (1024, 512, 512), (2048, 1024, 512), (1024, 2048, 512),
                  (2048, 2048, 512), (512, 2048, 512)):
            read(best, t, False, forward_too=False, stage="unfused")
        # the dQ kernel's own blocks under the best of the rest
        for dq in ((512, 1024), (2048, 1024), (1024, 2048), (2048, 2048)):
            read(best, (1024, 1024, 512), False, dq, forward_too=False,
                 stage="unfused_dq")
        for t in ((1024, 2048, 512), (1024, 2048, 1024), (2048, 2048, 512),
                  (1024, 4096, 512), (1024, 1024, 512)):
            read(best, t, True, forward_too=False, stage="fused")
    if which in ("all", "rule"):
        b, h, _, s, hd, _ = cell
        sizes = trunk.flash_block_sizes(
            (b, s, h, hd), "tpu", value_dim=VALUE_DIM[cell])
        if sizes is None:
            row(what="latent_rule", refused="flash_block_sizes has no tiles")
            return
        forward = (sizes.block_q, sizes.block_kv, sizes.block_kv_compute)
        if ab.resident_backward_fits(
                (b, s, h, hd), cell[2], VALUE_DIM[cell], None, None, "tpu"):
            # what ``attention_core`` runs since PR 69: the ONE backward call
            _resident_reading(cell, forward, ab._BLOCKS, True, args, stage="rule")
            return
        read(forward,
             (sizes.block_q_dkv, sizes.block_kv_dkv, sizes.block_kv_dkv_compute),
             True, stage="rule")


def _core_both(impl):
    """Forward + backward of ``attention_core`` on [B, S, H, hd]."""
    import jax

    from learning_at_home_tpu.models.trunk import attention_core

    def both(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: attention_core(q, k, v, impl), q, k, v)
        return out, vjp(do)

    return jax.jit(both)


def cores() -> None:
    import jax

    from learning_at_home_tpu.models.trunk import attention_core

    require_tpu()
    b, h, s, hd = CELL
    shapes = [(b, s, h, hd)] + [  # the cell's, then 8,192 tokens a call
        (8192 // s, s, 16 if hd == 128 else 8, hd)
        for hd, s in itertools.product((64, 128), (256, 512, 1024, 2048, 4096, 8192))
    ]
    for shape in shapes:
        _, s, _, hd = shape
        q, k, v, do = qkv(shape)
        for impl in ("xla", "flash"):
            settings = dict(impl=impl, batch=shape[0], seq=s, heads=shape[2], head=hd)
            timed("core_forward", settings,
                  jax.jit(lambda q, k, v, impl=impl: attention_core(q, k, v, impl)),
                  q, k, v)
            timed("core_forward_backward", settings, _core_both(impl), q, k, v, do)


def accuracy() -> None:
    import jax
    import jax.numpy as jnp

    require_tpu()
    b, h, s, hd = CELL
    shape = (b, s, h, hd)
    q, k, v, do = qkv(shape)

    def exact(q, k, v, do):  # float32 operands, no bf16 pass on the MXU
        def core(q, k, v):
            hi = jax.lax.Precision.HIGHEST
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / hd ** 0.5
            mask = jnp.tril(jnp.ones((s, s), bool))
            w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=hi)

        out, vjp = jax.vjp(core, q, k, v)
        return out, vjp(do)

    def flat(result):
        out, (dq, dk, dv) = result
        return dict(out=out, dq=dq, dk=dk, dv=dv)

    got = {impl: flat(_core_both(impl)(q, k, v, do)) for impl in ("xla", "flash")}
    f32 = [a.astype(jnp.float32) for a in (q, k, v, do)]
    one = jax.jit(exact)
    rows = [flat(one(*(a[i:i + 1] for a in f32))) for i in range(b)]  # 1 GB of scores a row
    want = {name: jnp.concatenate([r[name] for r in rows]) for name in rows[0]}

    def rel(a, ref):
        a, ref = a.astype(jnp.float32), ref.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean((a - ref) ** 2) / jnp.mean(ref ** 2)))

    worst = 0.0
    for name in ("out", "dq", "dk", "dv"):
        read = dict(
            flash_vs_xla=rel(got["flash"][name], got["xla"][name]),
            xla_vs_exact=rel(got["xla"][name], want[name]),
            flash_vs_exact=rel(got["flash"][name], want[name]),
        )
        worst = max(worst, read["flash_vs_xla"])
        row(what="accuracy", tensor=name, shape=list(shape), **read)
    row(what="accuracy_verdict", ok=worst < 0.01, worst_flash_vs_xla=worst, limit=0.01)
    if not worst < 0.01:
        raise SystemExit("attention_probe: flash is over 1 % from xla")


SDAR = (1, 32, 4, 16384, 128, None)  # sdar-30b-a3b-train-zipf8k: the doubled row
SDAR_BLOCK = 4


def blockdiff(which: str = "all") -> None:
    """``which``: ``all``, ``sweep`` or ``rule``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
        splash_attention_mask_info as mask_info,
    )

    from learning_at_home_tpu.models import trunk

    require_tpu()
    cell = SDAR
    b, h, _, s, hd, _ = cell
    args = _grouped_qkv(cell)
    mask = trunk._block_diffusion_splash_mask(s, SDAR_BLOCK)
    admitted = trunk.block_diffusion_admitted_pairs(s // 2, SDAR_BLOCK)

    def read(forward, backward, fused, causal=False, **also):
        info, _ = mask_info.process_mask(
            sm.MultiHeadMask([sm.CausalMask((s, s)) if causal else mask]),
            forward[:2])
        visited = int((info.block_mask != 0).sum()) * forward[0] * forward[1]
        return _window_reading(
            cell, None, forward, backward, fused, None, args,
            mask=None if causal else mask,
            mask_name="causal" if causal else "block_diffusion",
            forward_visited_over_admitted=round(visited / admitted, 4), **also)

    if which in ("all", "sweep"):
        read(BASELINE, BASELINE, True, causal=True, stage="causal")
        grid = ((1024, 1024, 512), (1024, 1024, 1024), (512, 1024, 512),
                (512, 512, 512), (1024, 512, 512), (2048, 1024, 512),
                (1024, 2048, 512), (256, 512, 512), (512, 256, 256),
                (256, 256, 256))
        same = {(t, fused): read(t, t, fused, stage="same")
                for t in grid for fused in (True, False)}
        same = {t: r for t, r in same.items() if r}
        best_fwd = min(same, key=lambda t: same[t][0])[0]
        best_both = min(same, key=lambda t: same[t][1])
        row(what="blockdiff_best", shape=list(cell[:5]), forward=best_fwd,
            forward_ms=round(min(r[0] for r in same.values()), 3),
            forward_backward=list(best_both),
            forward_backward_ms=round(same[best_both][1], 3))
        # the fused backward's own tiles under the best forward's
        for t in grid[:6]:
            if t != best_fwd:
                read(best_fwd, t, True, forward_too=False, stage="backward")
    if which in ("all", "rule"):
        sizes = trunk.flash_block_sizes((b, s, h, hd), "tpu")
        read((sizes.block_q, sizes.block_kv, sizes.block_kv_compute),
             (sizes.block_q_dkv, sizes.block_kv_dkv, sizes.block_kv_dkv_compute),
             sizes.use_fused_bwd_kernel, stage="rule")


if __name__ == "__main__":
    {"tiles": tiles, "splash": splash, "cores": cores, "accuracy": accuracy,
     "window": window, "latent": latent, "blockdiff": blockdiff,
     }[sys.argv[1]](*sys.argv[2:])
