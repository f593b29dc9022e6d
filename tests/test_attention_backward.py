"""The ONE backward call of the blocked attention kernel
(``ops/attention_backward.py``) in Pallas interpret mode on the CPU,
against ``jax.grad`` of the plain mathematics in float32 and against the
library's unfused pair, and the rule that hands it a call.  What Mosaic
makes of it, and how fast, is the chip's to say (``tools/attention_probe.py
latent all xing4``); that it takes the call at the ``xing4`` cell's shape
is ``tests/test_attention_backward_chip.py``'s AOT compile.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.ops import attention_backward as ab

FWD = "splash_mha_fwd_residuals"  # the library's forward that keeps its row sums
PAIR = ("splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals")


def _plain(q, k, v):
    """Causal attention on heads-first arrays, the scale already on q; the
    output and the logsumexp [B, H, S]."""
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v, precision="highest")
    return out, jax.nn.logsumexp(scores, axis=-1)


def _operands(hd, hdv, s, h, rows, dtype):
    """q (scaled), k, v and an output cotangent that differs from position
    to position and head to head."""
    keys = jax.random.split(jax.random.PRNGKey(hd + s + h), 4)
    q, k, v, do = (
        jax.random.normal(key, (rows, h, s, width), jnp.float32)
        for key, width in zip(keys, (hd, hd, hdv, hdv)))
    return tuple(x.astype(dtype) for x in (q / hd ** 0.5, k, v, do))


def _close(got, want, dtype, what):
    # float32: sums in another order; bf16: the probabilities and their
    # gradient are rounded to 8 bits where the reference rounds nothing
    tolerance = 1e-5 if dtype == jnp.float32 else 1.5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0,
        atol=tolerance * float(jnp.abs(want.astype(jnp.float32)).max()),
        err_msg=what)


# queries' and keys' head, values', S, heads, batch rows, operands, (query
# block, key block, keys of one product), key blocks outer: three or four
# blocks a head so that the resident accumulator is revisited, blocks of unequal
# lengths either way, a product of half a key block, one block of the whole
CASES = [
    (192, 128, 384, 1, 1, jnp.float32, (128, 128, 128), True),
    (192, 128, 384, 2, 1, jnp.bfloat16, (128, 128, 64), True),
    (192, 128, 512, 1, 1, jnp.bfloat16, (256, 256, 128), False),
    (24, 16, 512, 2, 2, jnp.float32, (128, 128, 128), True),
    (24, 16, 512, 2, 1, jnp.bfloat16, (128, 256, 128), True),
    (24, 16, 512, 2, 1, jnp.float32, (256, 128, 128), True),
    (24, 16, 384, 2, 2, jnp.float32, (128, 128, 128), False),
    (24, 16, 512, 1, 1, jnp.float32, (128, 256, 64), False),
    (24, 16, 512, 1, 1, jnp.bfloat16, (256, 128, 128), False),
    (24, 16, 256, 1, 1, jnp.float32, (1024, 1024, 512), True),
]
IDS = [
    f"{hd}over{hdv}-s{s}-h{h}-b{b}-{jnp.dtype(d).name}-"
    f"{'x'.join(map(str, blocks))}-{'keys' if outer else 'queries'}"
    for hd, hdv, s, h, b, d, blocks, outer in CASES
]


@pytest.mark.parametrize("hd, hdv, s, h, rows, dtype, blocks, outer", CASES, ids=IDS)
def test_the_three_gradients_are_the_plain_mathematics(
        hd, hdv, s, h, rows, dtype, blocks, outer):
    """``dq``, ``dk``, ``dv`` of the one call from a forward's output and
    row sums, against ``jax.grad`` of the float32 mathematics."""
    q, k, v, do = _operands(hd, hdv, s, h, rows, dtype)
    wide = [x.astype(jnp.float32) for x in (q, k, v)]
    (o, lse), vjp = jax.vjp(lambda *x: _plain(*x), *wide)
    want = vjp((do.astype(jnp.float32), jnp.zeros_like(lse)))
    got = ab.attention_backward(
        q, k, v, o.astype(dtype), lse, do, blocks, outer, interpret=True)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == b.shape
        _close(a, b, dtype, name)


@pytest.mark.parametrize("s, bq, bkv, pairs", [
    (16384, 1024, 1024, 136), (16384, 1024, 2048, 72), (16384, 512, 1024, 272),
    (512, 128, 128, 10), (512, 256, 128, 6), (512, 128, 256, 6), (256, 256, 256, 1),
])
@pytest.mark.parametrize("outer", [True, False], ids=["keys", "queries"])
def test_the_table_holds_the_pairs_the_mask_leaves_anything_in(
        s, bq, bkv, pairs, outer):
    """Every pair whose last query sees its first key, once, none other,
    sorted by the outer axis: the kernel reads a block's first and last
    visit from the pair's own indices."""
    kv_of, q_of = ab.block_pairs(s, bq, bkv, outer)
    assert len(kv_of) == pairs
    seen = np.zeros((s // bkv, s // bq), bool)
    seen[kv_of, q_of] = True
    last_query = np.arange(s // bq)[None, :] * bq + bq - 1
    admitted = last_query >= np.arange(s // bkv)[:, None] * bkv  # its first key
    assert (seen == admitted).all()
    outer_axis, inner_axis = (kv_of, q_of) if outer else (q_of, kv_of)
    order = list(zip(outer_axis.tolist(), inner_axis.tolist()))
    assert order == sorted(order)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("hd, hdv, h", [(192, 128, 1), (24, 16, 2)])
def test_the_call_is_the_librarys_unfused_pair(hd, hdv, h, dtype, monkeypatch):
    """``resident_attention`` (the library's forward, the one backward call
    at three blocks a head) against the library's own forward and unfused
    backward at the same blocks: the output bit for bit, the gradients to
    the operands' rounding."""
    monkeypatch.setattr(ab, "_BLOCKS", (128, 128, 128))
    s = 384
    q, k, v, do = _operands(hd, hdv, s, h, 1, dtype)
    tiles = dict(block_q=128, block_kv=128, block_kv_compute=128)
    pair = splash.make_splash_mha_single_device(
        mask=splash.MultiHeadMask([splash.CausalMask((s, s))] * h),
        block_sizes=splash.BlockSizes(
            **tiles, block_q_dkv=128, block_kv_dkv=128, block_kv_dkv_compute=128,
            block_q_dq=128, block_kv_dq=128, use_fused_bwd_kernel=False),
        interpret=True)
    want_out, vjp = jax.vjp(jax.vmap(pair), q, k, v)
    want = vjp(do)
    got_out, vjp = jax.vjp(
        lambda q, k, v: ab.resident_attention(
            q, k, v, splash.BlockSizes(**tiles), interpret=True), q, k, v)
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
    for name, a, b in zip(("dq", "dk", "dv"), vjp(do), want):
        assert a.dtype == dtype
        _close(a, b, dtype, name)


@pytest.mark.parametrize("shape, kv, hdv, window, block, backend, itemsize, fits", [
    ((1, 16384, 32, 192), 32, 128, None, None, "tpu", 2, True),   # xing4, ling-3.0
    ((4, 4096, 8, 192), 8, 128, None, None, "tpu", 2, True),
    ((2, 512, 4, 192), 4, 128, None, None, "tpu", 4, True),       # one block
    ((1, 16384, 32, 192), 32, 128, None, None, "cpu", 2, False),  # Mosaic: a TPU only
    ((1, 16384, 32, 192), 32, 128, None, None, "gpu", 2, False),
    ((1, 16384, 32, 192), 32, 128, 4096, None, "tpu", 2, False),  # a window
    ((1, 16384, 32, 192), 32, 128, 16384, None, "tpu", 2, False),
    ((1, 16384, 32, 192), 32, 128, None, 4, "tpu", 2, False),     # block diffusion
    ((1, 16384, 32, 192), 8, 128, None, None, "tpu", 2, False),   # fewer key heads
    ((1, 16384, 32, 192), 1, 128, None, None, "tpu", 2, False),
    ((1, 16384, 32, 192), 32, 192, None, None, "tpu", 2, False),  # pairs never run
    ((1, 16384, 20, 256), 20, 256, None, None, "tpu", 2, False),  # glm-4.7-flash
    ((1, 16384, 28, 128), 28, 128, None, None, "tpu", 2, False),
    ((4, 4096, 16, 128), 16, 128, None, None, "tpu", 2, False),   # olmoe
    ((1, 16384, 64, 64), 64, 64, None, None, "tpu", 2, False),
    ((1, 16384, 32, 24), 32, 16, None, None, "tpu", 2, False),
    ((1, 1536, 32, 192), 32, 128, None, None, "tpu", 2, False),   # no block divides it
    ((2, 13, 32, 192), 32, 128, None, None, "tpu", 2, False),     # a prompt of any length
    ((1, 32768, 32, 192), 32, 128, None, None, "tpu", 2, False),  # dq of a head: 58.7 MB
    ((1, 16384, 32, 192), 32, 128, None, None, "tpu", 4, True),   # float32: 46.1 MB
    ((1, 24576, 32, 192), 32, 128, None, None, "tpu", 4, False),  # 69.2 MB
])
def test_the_rule_reads_the_call(shape, kv, hdv, window, block, backend, itemsize, fits):
    assert ab.resident_backward_fits(
        shape, kv, hdv, window, block, backend, itemsize) is fits
    assert trunk.resident_backward_fits is ab.resident_backward_fits


def test_the_resident_arrays_are_inside_the_vmem_the_call_asks_for():
    """At the cell's shape the head's float32 ``dq^T`` [192, S] and its
    output block [S, 192] twice over, rows of 192 as two 128-lane tiles:
    29.4 MB of the 96 the call asks for, which the chip's 128 hold."""
    assert ab._resident_bytes(16384, 192, 2) == 16384 * (192 * 4 + 2 * 256 * 2)
    assert ab._resident_bytes(16384, 192, 2) <= ab._VMEM_RESIDENT < ab._VMEM
    assert ab._VMEM <= 128 * 2 ** 20


def _calls(jaxpr, name, found=None):
    """The ``pallas_call`` equations named ``name`` in a jaxpr, inner ones too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, name, found)
    return found


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "not-kept"])
def test_a_checkpoint_that_keeps_the_residuals_holds_one_forward_call(kept):
    """Under ``save_only_these_names(FLASH_RESIDUALS)``, the layer's remat
    (``DMoETransformerLM._hidden``): the output and the logsumexp are kept,
    so the backward pass holds the one backward call and no second forward;
    under a policy that keeps nothing the forward runs twice."""
    q, k, v, _ = _operands(24, 16, 256, 2, 1, jnp.float32)
    sizes = splash.BlockSizes(block_q=128, block_kv=128, block_kv_compute=128)

    def layer(q, k, v):
        out = ab.resident_attention(
            jnp.sin(q), k, v, sizes, trunk.FLASH_RESIDUALS, interpret=True)
        return jnp.sum(jnp.cos(out))

    policy = (jax.checkpoint_policies.save_only_these_names(trunk.FLASH_RESIDUALS)
              if kept else jax.checkpoint_policies.nothing_saveable)
    jaxpr = jax.make_jaxpr(jax.grad(
        jax.checkpoint(layer, policy=policy), argnums=(0, 1, 2)))(q, k, v).jaxpr
    assert len(_calls(jaxpr, FWD)) == (1 if kept else 2)
    assert len(_calls(jaxpr, ab.NAME)) == 1
    assert not any(_calls(jaxpr, name) for name in PAIR)


def test_the_kernels_name_is_one_the_benchmarks_reader_files_as_backward():
    """``benchmarks/runners/train_recipe_blocks.py`` finds the attention
    kernel's calls by the prefix ``splash_mha`` and files a call as backward
    unless its name has ``_fwd``."""
    assert ab.NAME.startswith("splash_mha") and "_fwd" not in ab.NAME


def _core_grads(monkeypatch, shape, hdv, **how):
    """The jaxpr of ``attention_core``'s three gradients under ``flash`` as
    on a TPU, the kernels interpreted where they are the repo's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        trunk, "resident_attention",
        functools.partial(ab.resident_attention, interpret=True))
    b, s, h, hd = shape
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (b, s, h, width), jnp.float32)
               for key, width in zip(keys, (hd, hd, hdv)))

    def loss(q, k, v):
        return jnp.sum(jnp.cos(trunk.attention_core(q, k, v, "flash", **how)))

    return (q, k, v), loss


@pytest.mark.parametrize("scale", [None, 0.11], ids=["plain", "yarn"])
def test_the_core_hands_the_one_backward_its_calls(scale, monkeypatch):
    """``attention_core`` under ``flash`` at queries of 192 over values of
    128 under a causal mask, YaRN's ``scale`` or none: the library's forward
    once, the one backward call, no call of the pair; the result and the
    gradients are the ``xla`` core's."""
    monkeypatch.setattr(ab, "_BLOCKS", (128, 128, 128))
    (q, k, v), loss = _core_grads(monkeypatch, (1, 256, 2, 192), 128, scale=scale)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    assert len(_calls(jaxpr, FWD)) == 1 and len(_calls(jaxpr, ab.NAME)) == 1
    assert not any(_calls(jaxpr, name) for name in PAIR)
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(jnp.cos(
            trunk.attention_core(q, k, v, "xla", scale=scale))),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        _close(a, b, jnp.float32, name)


@pytest.mark.parametrize("shape, kv, hdv, how, backward", [
    ((1, 512, 4, 128), 4, 128, {}, ("splash_mha_dkv_no_residuals",)),  # fused
    ((1, 512, 4, 64), 4, 64, {}, ("splash_mha_dkv_no_residuals",)),
    ((1, 512, 2, 256), 2, 256, {}, ("splash_mha_dkv_no_residuals",)),
    ((1, 512, 4, 192), 2, 128, {}, ("splash_mha_dkv_no_residuals",)),  # fewer key heads
    ((1, 2048, 2, 192), 2, 128, dict(window=600), PAIR),  # a window: the pair
    ((1, 1024, 2, 128), 2, 128, dict(diffusion_block=4),
     ("splash_mha_dkv_no_residuals",)),
], ids=["128", "64", "256", "192-grouped", "192-window", "blockdiff"])
def test_every_other_call_is_the_librarys(shape, kv, hdv, how, backward, monkeypatch):
    """Where the rule refuses, the library's kernels as before: traced, not
    run (Mosaic lowers them for a TPU alone)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        trunk, "resident_attention",
        lambda *a, **k: pytest.fail("the one backward call was built"))
    b, s, h, hd = shape
    q = jnp.zeros(shape, jnp.bfloat16)
    k, v = jnp.zeros((b, s, kv, hd), q.dtype), jnp.zeros((b, s, kv, hdv), q.dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(trunk.attention_core(
            q, k, v, "flash", **how).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v).jaxpr
    assert not _calls(jaxpr, ab.NAME)
    for name in PAIR:
        assert len(_calls(jaxpr, name)) == (1 if name in backward else 0)
