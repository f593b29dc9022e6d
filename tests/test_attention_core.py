"""The attention core's choice (``trunk.attention_core``): which core
runs, with which tiles, read from the call's shape, the backend and the
mask's window alone.

CPU only: what the kernel computes and how fast is the chip's to say
(``tools/attention_probe.py``); that Mosaic takes the tiles is an AOT
compile in ``tests/test_olmoe.py``, beside the topology fixture.
"""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.models.transformer import (
    FLASH_MIN_SEQ_LEN,
    DMoETransformerConfig,
    DMoETransformerLM,
    auto_attn_impl,
)
from learning_at_home_tpu.parallel.mesh import make_mesh


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("head", [64, 128])
@pytest.mark.parametrize("seq", [256, 512, 1536, 4096, 8192, 16384])
def test_tiles_fit_the_shape_or_the_kernel_is_refused(seq, head, batch):
    """Every answer is a ``BlockSizes`` the kernel's own checks accept
    (its ``__post_init__``), with the backward sizes, each no longer than
    the sequence and dividing it, the compute tile dividing its block; or
    None."""
    sizes = trunk.flash_block_sizes((batch, seq, 16, head), "tpu")
    largest = max(trunk._FLASH_TILES.values())
    if seq % min(largest, seq):
        assert sizes is None
        return
    assert isinstance(sizes, BlockSizes) and sizes.has_backward_blocks
    tiles = _tiles(sizes)
    assert len(tiles) == 6  # forward and the fused backward, three each
    assert all(t % 128 == 0 and seq % t == 0 for t in tiles.values()), tiles
    assert sizes.block_kv % sizes.block_kv_compute == 0
    assert sizes.block_kv_dkv % sizes.block_kv_dkv_compute == 0
    # a pure function of the shape: nothing else is read
    assert sizes == trunk.flash_block_sizes((batch, seq, 2, head), "tpu")


def _tiles(sizes):
    return {
        f.name: getattr(sizes, f.name) for f in dataclasses.fields(sizes)
        if f.name.startswith("block_") and getattr(sizes, f.name) is not None
    }


# the answer for every call until PR 36, and since then for a causal mask
# and a window no shorter than the key block
def _parents(seq):
    return BlockSizes(
        block_q=min(1024, seq), block_kv=min(1024, seq),
        block_kv_compute=min(512, seq), block_q_dkv=min(1024, seq),
        block_kv_dkv=min(1024, seq), block_kv_dkv_compute=min(512, seq),
        use_fused_bwd_kernel=True,
    )


@pytest.mark.parametrize("head", [64, 128])
@pytest.mark.parametrize("seq", [512, 4096, 16384])
@pytest.mark.parametrize("window", [None, 128, 256, 1024, 4096])
def test_tiles_read_the_window(window, seq, head):
    """The rule's answer under a window: a ``BlockSizes`` the kernel's
    ``__post_init__`` accepts, every tile a multiple of the 128 lanes
    that divides S, the compute tile dividing its block.  No window, or
    one no shorter than the key block: the parent's answer field for
    field (fused backward, six tiles).  A shorter window: key blocks that
    cover it, no narrower than the measured tile and no wider than the
    parent's, and an unfused backward with its dQ kernel's tiles (eight
    tiles).  Neither the batch nor the head count is read.  Since PR 63
    the short-window tiles are what a call gets that the band kernel does
    not take: the core asks ``band_kernel_fits`` first, which takes the
    windows up to 512 at heads of 128 over lengths its block of 512
    divides (K-EXAONE's window layers: 128 at 16,384)."""
    assert trunk.band_kernel_fits((1, seq, 64, head), 8, window, "tpu") is (
        window in (128, 256) and head == 128)
    assert not trunk.band_kernel_fits((1, seq, 64, head), 8, window, "cpu")
    sizes = trunk.flash_block_sizes((1, seq, 64, head), "tpu", window)
    assert isinstance(sizes, BlockSizes) and sizes.has_backward_blocks
    tiles = _tiles(sizes)
    assert all(t % 128 == 0 and seq % t == 0 for t in tiles.values()), tiles
    assert sizes.block_kv % sizes.block_kv_compute == 0
    assert sizes.block_kv_dkv % sizes.block_kv_dkv_compute == 0
    parent = _parents(seq)
    if window is None or window >= min(1024, seq):
        assert sizes == parent and len(tiles) == 6
    else:
        assert not sizes.use_fused_bwd_kernel and len(tiles) == 8
        for name in ("block_kv", "block_kv_dkv", "block_kv_dq"):
            assert window <= tiles[name] <= parent.block_kv, (name, tiles)
            assert tiles[name] == min(trunk._FLASH_WINDOW_TILE, seq)
    assert sizes == trunk.flash_block_sizes((4, seq, 2, head), "tpu", window)


@pytest.mark.parametrize("seq, window", [
    (16384, None), (16384, 4096), (4096, None),  # the three cells' other calls
    (16384, 1024), (512, 512), (512, 4096),
])
def test_a_window_no_shorter_than_the_key_block_changes_nothing(seq, window):
    """``olmoe`` (no window), ``smallthinker`` (4,096) and the global
    layers get the ``BlockSizes`` they had, field for field."""
    assert not trunk.band_kernel_fits((1, seq, 28, 128), 4, window, "tpu")
    sizes = trunk.flash_block_sizes((1, seq, 28, 128), "tpu", window)
    assert sizes == _parents(seq)  # a dataclass: every field compared
    assert sizes == trunk.flash_block_sizes((1, seq, 28, 128), "tpu")


@pytest.mark.parametrize("seq, window, key_block, band", [
    (16384, 128, 512, True),   # k-exaone's window layers: the band kernel's
    (16384, 1, 512, True), (16384, 300, 512, True), (16384, 512, 512, True),
    (4096, 128, 512, True),
    # 640, 768 and 896 divide no power of two: still unfused, and past the
    # band kernel's halo of one block
    (16384, 513, 1024, False),
    (15360, 600, 640, False), (15360, 700, 768, False),
    (1024, 200, 512, True), (512, 128, 512, True),
    (256, 128, 256, False),    # shorter than the band kernel's block
    (1536, 128, None, True),   # 512 divides it; the blocked kernel's 1024 does not
])
def test_key_blocks_cover_the_window_and_the_measured_tile(seq, window, key_block, band):
    """Key blocks are the window's cover, no narrower than the tile the
    sweep put first (a grid step's fixed cost: 128-wide key blocks under a
    window of 128 ran no faster than 1024-wide ones).  ``band``: whether
    the core hands the call to the band kernel before it asks for tiles
    (PR 63; eight query heads over one key/value head here)."""
    assert trunk.band_kernel_fits((1, seq, 8, 128), 1, window, "tpu") is band
    sizes = trunk.flash_block_sizes((1, seq, 8, 128), "tpu", window)
    if key_block is None:  # the window does not make a length divisible
        assert sizes is None and trunk.flash_block_sizes((1, seq, 8, 128), "tpu") is None
        return
    assert (sizes.block_kv, sizes.block_kv_dkv, sizes.block_kv_dq) == (key_block,) * 3
    assert not sizes.use_fused_bwd_kernel
    assert sizes.block_q == sizes.block_q_dkv == sizes.block_q_dq == min(
        trunk._FLASH_WINDOW_TILE, seq)


@pytest.mark.parametrize("shape, kv, value_dim, window, diffusion, compute, resident", [
    ((1, 16384, 32, 192), 32, 128, None, None, 256, True),    # xing4, ling-3.0
    ((4, 4096, 16, 128), 16, None, None, None, 512, False),   # olmoe
    ((1, 16384, 28, 128), 4, None, None, None, 512, False),   # smallthinker
    ((1, 16384, 28, 128), 4, None, 4096, None, 512, False),
    ((1, 16384, 64, 128), 8, None, None, None, 512, False),   # k-exaone
    ((1, 16384, 20, 256), 20, None, None, None, 256, False),  # glm-4.7-flash
    ((1, 16384, 32, 128), 2, None, None, None, 512, False),   # nemotron
    ((1, 16384, 16, 256), 2, None, None, None, 256, False),   # qwen3-next
    ((1, 16384, 32, 128), 4, None, None, 4, 512, False),      # sdar
    ((1, 16384, 32, 64), 8, None, None, None, 512, False),    # lfm2
])
def test_the_one_backward_call_is_for_heads_of_192_over_128_alone(
        shape, kv, value_dim, window, diffusion, compute, resident):
    """The cells' calls of the blocked kernel: every one keeps the fused
    backward's ``BlockSizes`` it had; ``xing4``'s and ``ling-3.0``'s pair of
    sizes, which ran the unfused pair until PR 69, now gets the forward's
    tiles with no dQ kernel's, and ``resident_backward_fits`` takes it and
    no other."""
    sizes = trunk.flash_block_sizes(shape, "tpu", window, value_dim)
    assert sizes.use_fused_bwd_kernel and sizes.block_q_dq is None
    assert sizes == dataclasses.replace(_parents(shape[1]), block_kv_compute=compute)
    assert trunk.resident_backward_fits(
        shape, kv, value_dim or shape[3], window, diffusion, "tpu") is resident


@pytest.mark.parametrize("shape, backend", [
    ((4, 4096, 16, 128), "cpu"),   # Mosaic lowers for a TPU only
    ((4, 4096, 16, 128), "gpu"),
    ((2, 13, 16, 128), "tpu"),     # a prompt of any length reaches prefill
    ((2, 4096 + 64, 16, 128), "tpu"),
    ((2, 4096, 16, 96), "tpu"),    # a head size the kernel has no rule for
])
def test_kernel_is_refused_where_it_cannot_run(shape, backend):
    assert trunk.flash_block_sizes(shape, backend) is None


@pytest.mark.parametrize("backend, n_devices, seq, want", [
    ("tpu", 1, FLASH_MIN_SEQ_LEN, "flash"),
    ("tpu", 1, 4 * FLASH_MIN_SEQ_LEN, "flash"),
    ("tpu", 1, FLASH_MIN_SEQ_LEN // 2, "xla"),
    ("tpu", 1, 256, "xla"),                      # dmoe256's length
    ("tpu", 1, FLASH_MIN_SEQ_LEN + 64, "xla"),   # long enough, not divisible
    ("tpu", 4, 4 * FLASH_MIN_SEQ_LEN, "xla"),    # Mosaic partitions no kernel
    ("cpu", 1, FLASH_MIN_SEQ_LEN, "xla"),
    ("cpu", 1, 16 * FLASH_MIN_SEQ_LEN, "xla"),
    ("gpu", 1, FLASH_MIN_SEQ_LEN, "xla"),
])
@pytest.mark.parametrize("head", [64, 128])
def test_auto_is_a_rule_over_backend_mesh_and_shape(
    backend, n_devices, seq, head, want
):
    assert auto_attn_impl(backend, n_devices, seq, head) == want


def _qkv(shape):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    return [jax.random.normal(k, shape, jnp.float32) for k in keys]


@pytest.mark.parametrize("shape, backend", [
    ((2, 128, 2, 64), "cpu"),   # tiles would fit: the backend refuses
    ((2, 24, 2, 64), "tpu"),    # the backend would do: the length refuses
])
def test_flash_falls_back_to_the_xla_core(shape, backend, monkeypatch):
    """``impl="flash"`` where the kernel cannot run is the ``xla`` core's
    result bit for bit, not an error."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q, k, v = _qkv(shape)
    got = trunk.attention_core(q, k, v, "flash")
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(trunk.attention_core(q, k, v, "xla"))
    )


def test_only_the_kernel_branch_is_scoped(monkeypatch):
    """The scope ``flash`` names the kernel's operations and nothing of
    the ``xla`` branch: no ``attention/flash`` in a step means the ``xla``
    core ran.  (No hash holds a scope: the lowered text the step hashes of
    ``tests/test_olmoe.py`` are taken of carries no debug information.)"""
    q, k, v = _qkv((1, 128, 2, 64))

    def scopes(impl):
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: trunk.attention_core(q, k, v, impl)
        )(q, k, v)
        return [str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns]

    on_xla = scopes("xla")
    assert not any("flash" in scope for scope in on_xla)
    assert scopes("flash") == on_xla  # the CPU: one branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert all(scope.startswith("flash") for scope in scopes("flash"))
    assert scopes("xla") == on_xla


@pytest.mark.parametrize("window", [None, 64], ids=["causal", "window"])
def test_the_kernel_branch_names_its_layout_apart_from_the_kernel(
    monkeypatch, window
):
    """Under ``flash``: the four ``heads_first`` transposes and the scale
    on q read ``flash/layout``, and every other equation (the kernel's
    call under its ``vmap``) reads ``flash`` and not ``layout``, so a
    reader by scope can tell the layout's time from the kernel's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v = _qkv((1, 128, 2, 64))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: trunk.attention_core(q, k, v, "flash", window)
    )(q, k, v)
    by_scope = collections.Counter(
        (str(e.source_info.name_stack), e.primitive.name)
        for e in jaxpr.jaxpr.eqns)
    layout = {key: n for key, n in by_scope.items() if key[1] in ("transpose", "mul")}
    assert layout == {("flash/layout", "transpose"): 4, ("flash/layout", "mul"): 1}
    kernel = [scope for scope, name in by_scope if name not in ("transpose", "mul")]
    assert kernel and all(
        scope.startswith("flash") and "layout" not in scope for scope in kernel)


def test_cached_decode_under_flash_matches_the_full_forward(monkeypatch):
    """``_generate_cached``'s prefill passes a prompt of any length
    through ``attention_core`` under the model's ``attn_impl``: a model
    that resolved to the kernel (as on the chip) decodes a prompt the
    kernel has no tiles for."""
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    cfg = DMoETransformerConfig(
        vocab_size=64, d_model=128, n_layers=2, n_heads=2,  # heads of 64
        seq_len=FLASH_MIN_SEQ_LEN, num_experts=8, k=2, dtype=jnp.float32,
        capacity_factor=8.0,
    )
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        model = DMoETransformerLM(cfg, mesh)
    assert model.attn_impl == "flash"
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3, 4, 5], [9, 8, 7, 6, 5]], jnp.int32)
    full = model.generate(params, prompt, max_new_tokens=6)
    cached = model.generate(params, prompt, max_new_tokens=6, use_cache=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


def test_kernel_branch_matches_the_xla_core_in_the_interpreter(monkeypatch):
    """The wiring around the kernel (scale on q, layouts, causal mask, its
    backward) in Pallas interpret mode on the CPU: output and the three
    input gradients equal the ``xla`` core's.  What Mosaic makes of the
    kernel, and how fast, is the chip's to say."""
    import functools

    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        splash, "make_splash_mha_single_device",
        functools.partial(splash.make_splash_mha_single_device, interpret=True),
    )
    q, k, v = _qkv((2, 256, 2, 64))

    def loss(impl):
        return lambda q, k, v: (trunk.attention_core(q, k, v, impl) ** 2).sum()

    want = jax.value_and_grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=2e-5 * float(np.abs(b).max())
        )
