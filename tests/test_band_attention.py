"""The band attention kernels (``ops/band_attention.py``) in Pallas
interpret mode on the CPU, against the plain mathematics in float32
(``tests/test_smallthinker_attention.py``'s ``_naive_attention``), and the
rule that hands them a call.  What Mosaic makes of them, and how fast, is
the chip's to say (``tools/attention_probe.py window band``); that it takes
them at K-EXAONE's shape is ``tests/test_kexaone_chip.py``'s AOT compile.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_smallthinker_attention import _naive_attention

from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.ops import band_attention as band

# window, S, H, Hkv, batch rows, operands: windows 1, 128, 200, 256 and 512
# (halos of 128, 256 and 512 rows), groups of 1, 4 and 8, one block (its
# halo read clamped and masked whole) and several
CASES = [
    (1, 512, 4, 1, 1, jnp.float32),
    (128, 512, 8, 1, 1, jnp.float32),
    (128, 1024, 8, 1, 2, jnp.bfloat16),
    (200, 1536, 4, 4, 1, jnp.float32),
    (200, 1024, 8, 2, 2, jnp.bfloat16),
    (256, 1024, 2, 2, 1, jnp.float32),
    (512, 1536, 4, 1, 1, jnp.float32),
    (512, 1024, 8, 1, 1, jnp.bfloat16),
]
IDS = [f"w{w}-s{s}-{h}over{kv}-b{b}-{jnp.dtype(d).name}" for w, s, h, kv, b, d in CASES]


def _qkv(s, h, kv, rows, dtype):
    keys = jax.random.split(jax.random.PRNGKey(s + h + kv), 3)
    return tuple(
        jax.random.normal(key, (rows, s, n, 128), jnp.float32).astype(dtype)
        for key, n in zip(keys, (h, kv, kv)))


def _close(got, want, dtype, what):
    # float32: sums in another order; bf16: the probabilities and the
    # scaled keys are rounded to 8 bits where the reference rounds nothing
    tolerance = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=0,
        atol=tolerance * float(jnp.abs(want).max()), err_msg=what)


def _naive_logsumexp(q, k, window):
    b, s, h, hd = q.shape
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = jnp.where(((j <= i) & (j > i - window))[None, None], scores, -jnp.inf)
    return jax.nn.logsumexp(scores, axis=-1)


@pytest.mark.parametrize("window, s, h, kv, rows, dtype", CASES, ids=IDS)
def test_forward_is_the_plain_mathematics(window, s, h, kv, rows, dtype):
    """Output and logsumexp [B, H, S] of the forward kernel."""
    q, k, v = _qkv(s, h, kv, rows, dtype)
    out, lse = band.band_attention_lse(q, k, v, window, interpret=True)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    wide = [x.astype(jnp.float32) for x in (q, k, v)]
    _close(out, _naive_attention(*wide, window), dtype, "out")
    _close(lse, _naive_logsumexp(*wide[:2], window), dtype, "logsumexp")


@pytest.mark.parametrize("window, s, h, kv, rows, dtype", CASES, ids=IDS)
def test_backward_is_the_plain_mathematics(window, s, h, kv, rows, dtype):
    """The three gradients of the one backward kernel, under a cotangent
    that differs from position to position and head to head."""
    q, k, v = _qkv(s, h, kv, rows, dtype)
    do = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    got = jax.vjp(
        lambda q, k, v: band.band_attention(q, k, v, window, interpret=True),
        q, k, v)[1](do.astype(dtype))
    want = jax.vjp(
        lambda q, k, v: _naive_attention(q, k, v, window),
        *(x.astype(jnp.float32) for x in (q, k, v)))[1](
            do.astype(dtype).astype(jnp.float32))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        _close(a, b, dtype, name)


@pytest.mark.parametrize("shape, kv, window, backend, fits", [
    ((1, 16384, 64, 128), 8, 128, "tpu", True),    # k-exaone's window layers
    ((1, 16384, 64, 128), 8, 1, "tpu", True),
    ((1, 16384, 64, 128), 8, 200, "tpu", True),
    ((1, 16384, 64, 128), 8, 256, "tpu", True),
    ((1, 16384, 64, 128), 8, 512, "tpu", True),
    ((4, 512, 8, 128), 2, 128, "tpu", True),       # one block; groups of 4
    ((2, 1536, 16, 128), 16, 300, "tpu", True),    # groups of 1
    ((1, 16384, 64, 128), 8, 513, "tpu", False),   # a halo beyond one block
    ((1, 16384, 64, 128), 8, 1024, "tpu", False),
    ((1, 16384, 28, 128), 4, 4096, "tpu", False),  # smallthinker's window layers
    ((1, 16384, 64, 128), 8, None, "tpu", False),  # the global layer
    ((1, 16384, 64, 128), 8, 0, "tpu", False),
    ((1, 16384, 64, 128), 8, 128, "cpu", False),   # Mosaic lowers for a TPU only
    ((1, 16384, 64, 128), 8, 128, "gpu", False),
    ((1, 16384, 64, 64), 8, 128, "tpu", False),    # heads it was never run at
    ((1, 16384, 20, 256), 20, 128, "tpu", False),
    ((1, 16384, 64, 128), 32, 128, "tpu", False),  # groups of 2: never run
    ((1, 16384, 64, 128), 4, 128, "tpu", False),   # groups of 16: VMEM
    ((1, 16384, 64, 128), 24, 128, "tpu", False),  # no whole group
    ((1, 256, 8, 128), 1, 128, "tpu", False),      # shorter than a block
    ((1, 1280, 8, 128), 1, 128, "tpu", False),     # a length no block divides
    ((2, 13, 8, 128), 1, 4, "tpu", False),         # a prompt of any length
])
def test_the_rule_reads_the_call(shape, kv, window, backend, fits):
    assert band.band_kernel_fits(shape, kv, window, backend) is fits
    assert trunk.band_kernel_fits is band.band_kernel_fits


@pytest.mark.parametrize("window, halo", [
    (1, 128), (2, 128), (128, 128), (129, 128), (130, 256), (200, 256),
    (257, 256), (258, 512), (300, 512), (512, 512),
])
def test_the_halo_covers_the_window_and_divides_the_block(window, halo):
    assert band._halo(window) == halo >= window - 1 and band._BLOCK % halo == 0


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
    (``DMoETransformerLM._hidden``): the output and the logsumexp are
    kept, so the backward pass holds the backward kernel and no second
    forward; under a policy that keeps nothing the forward runs twice."""
    q, k, v = _qkv(512, 4, 1, 1, jnp.float32)

    def layer(q, k, v):
        out = band.band_attention(
            jnp.sin(q), k, v, 128, trunk.FLASH_RESIDUALS, interpret=True)
        return jnp.sum(jnp.cos(out))

    policy = (jax.checkpoint_policies.save_only_these_names(trunk.FLASH_RESIDUALS)
              if kept else jax.checkpoint_policies.nothing_saveable)
    jaxpr = jax.make_jaxpr(jax.grad(
        jax.checkpoint(layer, policy=policy), argnums=(0, 1, 2)))(q, k, v).jaxpr
    assert len(_calls(jaxpr, "band_attention_fwd")) == (1 if kept else 2)
    assert len(_calls(jaxpr, "band_attention_bwd")) == 1


def test_the_core_hands_the_band_kernel_its_calls(monkeypatch):
    """``attention_core`` under ``flash`` where the rule fits: the band
    kernel's result and gradients (interpreted), no blocked kernel, no
    ``flash/layout``; and the named residuals reach the caller's
    checkpoint."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        trunk, "band_attention", functools.partial(band.band_attention, interpret=True))
    monkeypatch.setattr(
        splash, "make_splash_mha_single_device",
        lambda *a, **k: pytest.fail("the blocked kernel was built"))
    q, k, v = _qkv(1024, 8, 2, 1, jnp.float32)

    def both(core):
        out, vjp = jax.vjp(core, q, k, v)
        return (out,) + vjp(jnp.cos(out))

    got = both(lambda q, k, v: trunk.attention_core(q, k, v, "flash", 200))
    want = both(lambda q, k, v: _naive_attention(q, k, v, 200))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        _close(a, b, jnp.float32, name)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: trunk.attention_core(q, k, v, "flash", 200))(q, k, v)
    scopes = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    assert scopes and all(
        scope.startswith("flash") and "layout" not in scope for scope in scopes)
