"""The attention cores at SmallThinker's heads: grouped key/value heads and a
window through the ``xla`` core and through the blocked kernel.  A module
apart from ``tests/test_smallthinker.py`` (the block against its reference),
so that ``--dist loadfile`` can spread the two.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_at_home_tpu.models import trunk


def _naive_attention(q, k, v, window):
    b, s, h, hd = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    allowed = (j <= i) if window is None else (j <= i) & (j > i - window)
    scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def _grouped_qkv(s, h, kv, hd):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(keys[0], (1, s, h, hd), jnp.float32),
            jax.random.normal(keys[1], (1, s, kv, hd), jnp.float32),
            jax.random.normal(keys[2], (1, s, kv, hd), jnp.float32))


@pytest.mark.parametrize("window", [None, 1, 5, 24, 100])
def test_xla_core_takes_grouped_heads_and_a_window(window):
    q, k, v = _grouped_qkv(24, 6, 2, 16)
    np.testing.assert_allclose(
        np.asarray(trunk.attention_core(q, k, v, "xla", window)),
        np.asarray(_naive_attention(q, k, v, window)), atol=2e-6)


@pytest.mark.parametrize("window, seq, tile", [
    (None, 512, 128), (300, 512, 128),
    # through the rule as it is: a window shorter than the key block, so
    # 512-wide blocks of queries and keys and the unfused backward's two
    # kernels over grids shrunk to the mask
    (200, 1024, None),
])
def test_blocked_kernel_takes_grouped_heads_and_a_window(window, seq, tile, monkeypatch):
    """The kernel itself (interpreted on the CPU), over several blocks:
    two key/value heads under six query heads, uncopied, under the causal
    and the local mask, forward and backward, against the plain
    mathematics."""
    import functools

    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if tile:
        monkeypatch.setattr(trunk, "_FLASH_TILES", {k: tile for k in trunk._FLASH_TILES})
    else:
        sizes = trunk.flash_block_sizes((1, seq, 6, 64), "tpu", window)
        assert not sizes.use_fused_bwd_kernel
        assert window < sizes.block_kv < seq and sizes.block_q < seq
    monkeypatch.setattr(
        splash, "make_splash_mha_single_device",
        functools.partial(splash.make_splash_mha_single_device, interpret=True))
    q, k, v = _grouped_qkv(seq, 6, 2, 64)

    def both(core):
        out, vjp = jax.vjp(core, q, k, v)
        return (out,) + vjp(jnp.cos(out))

    got = both(lambda q, k, v: trunk.attention_core(q, k, v, "flash", window))
    want = both(lambda q, k, v: _naive_attention(q, k, v, window))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-3 * float(jnp.abs(b).max()),
            err_msg=name)
