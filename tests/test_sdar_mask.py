"""Block diffusion's attention mask (``trunk.block_diffusion_mask``) over the
doubled row ``[x_t | x_0]``: the computable form the blocked kernel takes
against the boolean array of the three published terms, for every ``(i,
j)``, through the ``xla`` core and through the kernel under ``interpret``;
what the mask lets a position see (perturbations through the tiny stack);
and that with blocks of ONE token the clean half is the causal model.

Tiny sizes on the CPU.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import harness  # noqa: E402  (benchmarks/harness.py: imports no jax)

from __graft_entry__ import sdar_one_chip  # noqa: E402
from learning_at_home_tpu.models import trunk  # noqa: E402
from learning_at_home_tpu.models.transformer import DMoETransformerLM  # noqa: E402
from learning_at_home_tpu.parallel.mesh import make_mesh  # noqa: E402

reference = harness.load_path(os.path.join(
    REPO, "benchmarks", "configs", "sdar_30b_a3b_reference.py"))


def _published(half: int, block: int) -> np.ndarray:
    """``M`` from the three published terms, an element at a time."""
    i = np.arange(2 * half)
    blk, noised = (i % half) // block, i < half
    bq, bk, nq, nk = blk[:, None], blk[None, :], noised[:, None], noised[None, :]
    return (((bq == bk) & (nq == nk)) | ((bq > bk) & nq & ~nk)
            | ((bq >= bk) & ~nq & ~nk))


@pytest.mark.parametrize("half, block", [
    (8, 1), (8, 2), (12, 3), (16, 4), (24, 6), (32, 32), (20, 5)])
def test_the_computable_mask_is_the_published_one(half, block):
    """Every ``(i, j)``, on numpy and on jax arrays, shifts and divisions
    alike; the reference's own array; and the count of admitted pairs."""
    want = _published(half, block)
    i = np.arange(2 * half)
    got = trunk.block_diffusion_mask(i[:, None], i[None, :], half, block)
    assert (np.asarray(got) == want).all()
    on_device = trunk.block_diffusion_mask(
        jnp.asarray(i)[:, None], jnp.asarray(i)[None, :], half, block)
    assert (np.asarray(on_device) == want).all()
    assert (np.asarray(reference.mask(half, block)) == want).all()
    assert want.sum() == trunk.block_diffusion_admitted_pairs(half, block)
    assert want.diagonal().all()  # every query sees itself: no empty row
    assert not want[half:, :half].any()  # a clean query sees no noised key


def _weights_of(core_out, count):
    """With q = k = 0 and v the identity the core's output IS the mask over
    each query's count of admitted keys."""
    return np.asarray(core_out)[0, :, 0, :] * count[:, None]


@pytest.mark.parametrize("half, block", [(8, 2), (16, 4), (12, 3)])
def test_the_xla_core_admits_exactly_the_masks_pairs(half, block):
    s = 2 * half
    want = _published(half, block)
    zeros = jnp.zeros((1, s, 1, s), jnp.float32)
    v = jnp.eye(s, dtype=jnp.float32)[None, :, None, :]
    out = jax.jit(lambda q, v: trunk.attention_core(
        q, q, v, "xla", None, block))(zeros, v)
    np.testing.assert_allclose(
        _weights_of(out, want.sum(1)), want.astype(np.float32), atol=1e-5)
    with pytest.raises(ValueError, match="no window"):
        trunk.attention_core(zeros, zeros, v, "xla", 4, block)


@pytest.mark.parametrize("half, block", [(128, 4), (128, 32), (256, 8)])
def test_the_kernel_admits_exactly_the_masks_pairs(half, block, monkeypatch):
    """The blocked kernel under ``interpret`` with the computable mask, at
    lengths of two and four 128-wide tiles: every ``(i, j)`` (uniform
    weights over the admitted keys), and the same output as the ``xla``
    core on random heads with grouped key/value heads."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        splash, "make_splash_mha_single_device", functools.partial(
            splash.make_splash_mha_single_device, interpret=True))
    s = 2 * half
    want = _published(half, block)
    assert trunk.flash_block_sizes((1, s, 1, 128), "tpu") is not None
    zeros = jnp.zeros((1, s, 1, 128), jnp.float32)
    # v the identity, 128 columns at a time: a head size the kernel takes
    for start in range(0, s, 128):
        v = jnp.eye(s, dtype=jnp.float32)[None, :, None, start:start + 128]
        out = trunk.attention_core(zeros, zeros, v, "flash", None, block)
        np.testing.assert_allclose(
            _weights_of(out, want.sum(1)),
            want[:, start:start + 128].astype(np.float32), atol=1e-5)
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, s, 4, 128), jnp.float32)
    k, v = (jnp.asarray(rs.randn(1, s, 2, 128), jnp.float32) for _ in range(2))
    np.testing.assert_allclose(
        np.asarray(trunk.attention_core(q, k, v, "flash", None, block)),
        np.asarray(trunk.attention_core(q, k, v, "xla", None, block)),
        atol=2e-5)
    visited = trunk.block_diffusion_visited_pairs(q.shape, "flash", "tpu", block)
    assert trunk.block_diffusion_admitted_pairs(half, block) <= visited <= s * s
    assert trunk.block_diffusion_visited_pairs(q.shape, "xla", "tpu", block) == s * s
    # at the cell's shape and tiles of 1024: 8 diagonal blocks of the
    # noised-to-noised quadrant, 36 of each block-triangular one, none of
    # the clean-to-noised quadrant's 64
    assert trunk.block_diffusion_visited_pairs(
        (1, 16384, 32, 128), "flash", "tpu", 4) == 80 * 1024 * 1024
    assert trunk.block_diffusion_admitted_pairs(8192, 4) == 67_141_632


# ---- what a position sees, through the tiny stack ----


@pytest.fixture(scope="module")
def tiny():
    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    model, cfg, _, batch = sdar_one_chip(mesh, tiny=True)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab_size - 1, (batch, cfg.seq_len)).astype(np.int32)
    u, t = model.noise_draws(jax.random.key(1), batch)
    row, _ = model.noised_row(jnp.asarray(ids), u, t)
    hidden = jax.jit(lambda p, row: model._hidden(p, row)[0])
    return model, cfg, params, np.asarray(row), hidden


def _moved(hidden, params, row, changed):
    before = np.asarray(hidden(params, jnp.asarray(row)))
    after = np.asarray(hidden(params, jnp.asarray(changed)))
    return np.abs(after - before).max(axis=(0, 2)) > 0  # a position


def test_a_clean_block_moves_no_noised_block_before_or_at_it(tiny):
    """Another token in clean block ``b``: the noised blocks up to and
    including ``b`` and the clean blocks before ``b`` stay to the bit; the
    noised blocks after it and the clean ones from it on move."""
    model, cfg, params, row, hidden = tiny
    s, length = cfg.seq_len, cfg.diffusion_block
    b = 3
    changed = row.copy()
    at = s + b * length + 1  # a clean position of block b
    changed[:, at] = (changed[:, at] + 1) % (cfg.vocab_size - 1)
    moved = _moved(hidden, params, row, changed)
    assert not moved[: (b + 1) * length].any()  # noised blocks 0..b
    assert moved[(b + 1) * length: s].all()  # noised blocks after b
    assert not moved[s: s + b * length].any()  # clean blocks before b
    assert moved[s + b * length:].all()


def test_a_noised_position_moves_its_own_noised_block_alone(tiny):
    model, cfg, params, row, hidden = tiny
    s, length = cfg.seq_len, cfg.diffusion_block
    b = 2
    changed = row.copy()
    at = b * length + 3  # the block's last position: both directions inside
    changed[:, at] = (changed[:, at] + 1) % (cfg.vocab_size - 1)
    moved = _moved(hidden, params, row, changed)
    assert moved[b * length: (b + 1) * length].all()
    moved[b * length: (b + 1) * length] = False
    assert not moved.any()


def test_with_blocks_of_one_token_the_clean_half_is_the_causal_stack(tiny):
    """Block length 1: clean position ``i`` sees the clean positions up to
    its own at rotary positions ``0..S-1``, which is the causal model on
    the same ids; its stream equals that model's ``_hidden`` (the same
    weights, ``objective='next_token'``)."""
    model, cfg, params, row, _ = tiny
    s = cfg.seq_len
    ones = DMoETransformerLM(
        dataclasses.replace(cfg, diffusion_block=1), model.mesh)
    causal = DMoETransformerLM(
        dataclasses.replace(cfg, objective="next_token"), model.mesh)
    doubled = jax.jit(lambda p, row: ones._hidden(p, row)[0])(
        params, jnp.asarray(row))
    plain = jax.jit(lambda p, ids: causal._hidden(p, ids)[0])(
        params, jnp.asarray(row[:, s:]))
    np.testing.assert_allclose(
        np.asarray(doubled[:, s:]), np.asarray(plain), atol=2e-6)
    # and the noised half is NOT it: a noised position sees no earlier noised one
    assert np.abs(np.asarray(doubled[:, :s]) - np.asarray(plain)).max() > 1e-3
