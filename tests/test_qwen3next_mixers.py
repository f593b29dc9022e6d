"""Qwen3-Next's mixers in the pod step, each alone against the reference's
equations: (b) the delta rule with fewer key heads than value heads, (c) the
gated attention and its partial rotation, (d) the shares of the mixture that
add up.  A module apart from ``tests/test_qwen3next.py`` (the whole stack and
the runner's comparison), so that ``--dist loadfile`` can spread the two.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_qwen3next import (  # noqa: F401  (``tiny`` is a fixture)
    SIZES,
    _close,
    _one_device_mesh,
    reference,
    tiny,
)
from learning_at_home_tpu.models import trunk
from learning_at_home_tpu.models.transformer import DMoETransformerLM
from learning_at_home_tpu.ops import delta_rule
from learning_at_home_tpu.ops.delta_rule import gated_delta_chunked
from learning_at_home_tpu.ops.gate_norm import gated_rms_norm
from learning_at_home_tpu.ops.ssm_conv import causal_conv_silu
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts


# ---- (b) the delta rule with fewer key heads than value heads ----


def _parents_delta_mixer(p, x, n_heads, chunk, eps):
    """``trunk.delta_mixer`` as PR 53 left it (one head count, ``beta = 2
    sigmoid(b)``), operation for operation: what equal heads must still
    give, to the bit."""
    b, s, _ = x.shape
    f32 = jnp.float32
    d_v = p["w_out"].shape[0]
    d_qk = p["conv_w"].shape[0] - d_v
    dk, dv = d_qk // (2 * n_heads), d_v // n_heads
    w_in = p["w_in"].astype(x.dtype)
    proj = x @ w_in
    write, step = jnp.split(jnp.einsum(
        "bsd,dn->bsn", x, w_in[:, d_qk + 2 * d_v:],
        preferred_element_type=f32), 2, axis=-1)
    qk = causal_conv_silu(proj, p["conv_w"][:d_qk], None)
    v = causal_conv_silu(proj, p["conv_w"][d_qk:], None, first=d_qk)
    qk = qk.reshape(b, s, 2, n_heads, dk)
    beta = 2.0 * jax.nn.sigmoid(write)
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        step + p["dt_bias"].astype(f32))
    o, state = gated_delta_chunked(
        qk[:, :, 0], qk[:, :, 1], v.reshape(b, s, n_heads, dv), g, beta,
        chunk, jnp.float32, unit=True)
    y = gated_rms_norm(
        o.reshape(b, s, d_v), proj, p["gate_norm"]["scale"], dv, eps,
        gate_first=False, first=d_qk + d_v)
    return y @ p["w_out"].astype(x.dtype), state


def _delta_params(rs, d, hk, hv, dk, dv):
    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rs.randn(*shape), jnp.float32)

    d_qk, d_v = 2 * hk * dk, hv * dv
    return {
        "w_in": normal(d, d_qk + 2 * d_v + 2 * hv, scale=d ** -0.5),
        "conv_w": normal(d_qk + d_v, 4, scale=0.5), "dt_bias": normal(hv),
        "A_log": jnp.log(jnp.asarray(rs.uniform(1, 16, hv), jnp.float32)),
        "gate_norm": {"scale": 1.0 + normal(dv, scale=0.2)},
        "w_out": normal(d_v, d, scale=d_v ** -0.5)}


@pytest.mark.parametrize("chunk", [16])
def test_the_delta_mixer_shares_a_key_head_between_two_value_heads(tiny, chunk):
    """The program's mixer against the reference's scan over the positions
    (each key head read by two value heads, ``beta = sigmoid(b)``): output
    and the state after the last position, one state a VALUE head."""
    _, cfg, params, _, _ = tiny
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.seq_len, cfg.d_model))
    a = trunk.rms_norm(lp["ln1"], x, cfg.norm_eps)
    got, state, decay_min, beta_max = jax.jit(lambda p: trunk.delta_mixer(
        p, a, cfg.n_heads, chunk, cfg.norm_eps, neg_eigval=False))(
            lp["delta"])
    want, want_state = jax.jit(lambda lp: reference.delta_part(lp, x, SIZES))(lp)
    assert state.shape == (2, cfg.delta_value_heads, cfg.delta_key_dim,
                           cfg.delta_value_dim)
    _close(got, want)
    _close(state, want_state)
    assert 0.0 <= float(decay_min) < 1.0 and 0.0 < float(beta_max) <= 1.0
    doubled, _, _, top = jax.jit(lambda p: trunk.delta_mixer(
        p, a, cfg.n_heads, chunk, cfg.norm_eps))(lp["delta"])
    assert float(top) == pytest.approx(2 * float(beta_max))
    assert np.abs(np.asarray(doubled - got)).max() > 1e-3


def test_equal_heads_give_the_parents_mixer_to_the_bit():
    """As many value heads as key heads and ``beta = 2 sigmoid(b)``: the
    mixer Olmo-Hybrid runs, bit for bit what it was before this model."""
    rs = np.random.RandomState(3)
    p = _delta_params(rs, d=32, hk=4, hv=4, dk=8, dv=16)
    x = jnp.asarray(rs.randn(2, 64, 32), jnp.float32)
    got, state, _, _ = jax.jit(
        lambda p, x: trunk.delta_mixer(p, x, 4, 16, 1e-6))(p, x)
    want, want_state = jax.jit(
        lambda p, x: _parents_delta_mixer(p, x, 4, 16, 1e-6))(p, x)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(state), np.asarray(want_state))


def test_the_rules_kernels_run_the_shared_heads_at_keys_and_values_of_128(
        monkeypatch, llvm_optimised):
    """The mixer at heads of 128/128, two value heads a key head, its rule
    as ``delta_chunk_fwd`` / ``delta_chunk_bwd`` under ``interpret`` (the
    tiles the cell's shape gets: two heads a grid row): output, state and
    every gradient against the plain form's.  Both sides keep LLVM's
    optimised code: a leaf of two numbers, each a sum over all 128 positions,
    reads 1.1e-4 of its largest under the suite's cheap code generation
    against the limit of 1e-4 (my CPU run, PR 70)."""
    rs = np.random.RandomState(5)
    d, hk, hv, dk, dv, s = 32, 1, 2, 128, 128, 128
    assert delta_rule._grid(hv, s, 64, dk, dv) == (2, 128)
    assert delta_rule._grid(32, 16384, 64, 128, 128) == (2, 256)
    p = _delta_params(rs, d, hk, hv, dk, dv)
    x = jnp.asarray(rs.randn(1, s, d), jnp.float32)

    def loss(p, x):
        out, state, *_ = trunk.delta_mixer(p, x, hk, 64, 1e-6, neg_eigval=False)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(state), (out, state)

    plain, (out, state) = llvm_optimised(
        jax.grad(loss, argnums=(0, 1), has_aux=True))(p, x)
    calls = []

    def through_the_kernel(q, k, v, g, beta, chunk, decay_dtype, unit):
        assert delta_rule.kernel_fits(q.shape, v.shape, chunk, "tpu")
        calls.append((q.shape, v.shape))
        return delta_rule.gated_delta_kernel(
            q, k, v, g, beta, chunk, interpret=True, unit=unit)

    monkeypatch.setattr(trunk, "gated_delta_chunked", through_the_kernel)
    through, (got, got_state) = llvm_optimised(
        jax.grad(loss, argnums=(0, 1), has_aux=True))(p, x)
    assert calls == [((1, s, hv, dk), (1, s, hv, dv))]  # the rule sees value heads
    _close(got, out)
    _close(got_state, state)
    for g, w in zip(jax.tree_util.tree_leaves(through), jax.tree_util.tree_leaves(plain)):
        _close(g, w, 1e-4)


# ---- (c) the gated attention and its partial rotation ----


def _attention_layer(rs, d=32, heads=4, kv=2, hd=16, gate=True):
    def w(*shape):
        return jnp.asarray(rs.randn(*shape) / np.sqrt(shape[0]), jnp.float32)

    return {"wq": w(d, (2 if gate else 1) * heads * hd), "wk": w(d, kv * hd),
            "wv": w(d, kv * hd), "wo": w(heads * hd, d),
            "q_norm": {"offset": jnp.asarray(rs.uniform(-0.3, 0.3, hd), jnp.float32)},
            "k_norm": {"offset": jnp.asarray(rs.uniform(-0.3, 0.3, hd), jnp.float32)}}


def test_the_rotation_leaves_three_quarters_of_a_head_unchanged_to_the_bit():
    """``rotary_dim`` 4 of 16: columns 4..15 of q and k are what the
    projections (and the norm) made, bit for bit; columns 0..3 are rotated
    in pairs (j, j + 2) at frequencies over 4, as the reference rotates."""
    rs = np.random.RandomState(1)
    lp = _attention_layer(rs)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    positions = jnp.arange(24, dtype=jnp.int32)
    q0, k0, v0, g0 = trunk.gated_qkv_projections(lp, x, 4, None, norm_eps=1e-6)
    q, k, v, g = trunk.gated_qkv_projections(
        lp, x, 4, positions, 1e7, 1e-6, rotary_dim=4)
    for got, plain in ((q, q0), (k, k0)):
        assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(plain[..., 4:]))
        assert np.abs(np.asarray(got[..., :4] - plain[..., :4])).max() > 1e-2
        assert np.array_equal(np.asarray(got[:, 0]), np.asarray(plain[:, 0]))
        _close(got, reference.rope_first(plain, 1e7, 4), 1e-6)
    assert np.array_equal(np.asarray(v), np.asarray(v0))
    assert np.array_equal(np.asarray(g), np.asarray(g0)) and g.shape == q.shape
    whole, _, _, _ = trunk.gated_qkv_projections(lp, x, 4, positions, 1e7, 1e-6)
    assert np.abs(np.asarray(whole[..., 4:] - q0[..., 4:])).max() > 1e-2


def test_the_attention_gate_is_live(tiny):
    """A head's second half of ``wq``'s columns is its gate: zeroed, every
    gate is ``sigmoid(0)`` and the mixer gives half of what it gives with
    no gate at all; as drawn it gives what the reference's equations do."""
    model, cfg, params, _, _ = tiny
    lp = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.seq_len, cfg.d_model))
    kind = cfg.attention_layer(3)
    part = jax.jit(lambda lp: model._attention_part(lp, x, kind))
    h, a, extremes = part(lp)
    _close(h - x, jax.jit(lambda lp: reference.attention_mixer(lp, x, SIZES))(lp), 1e-5)
    assert 0.3 < float(extremes["attention_gate_mean"]) < 0.7
    hd = cfg.head_dim
    columns = lp["wq"].reshape(cfg.d_model, cfg.n_heads, 2, hd)
    zeroed = {**lp, "wq": columns.at[:, :, 1].set(0.0).reshape(lp["wq"].shape)}
    half, _, at_zero = part(zeroed)
    assert float(at_zero["attention_gate_mean"]) == 0.5
    ungated = DMoETransformerLM(
        dataclasses.replace(cfg, attention_gate=False), model.mesh)
    no_gate = {**lp, "wq": columns[:, :, 0].reshape(cfg.d_model, cfg.n_heads * hd)}
    full, _, none = ungated._attention_part(no_gate, x, kind)
    assert none == {}
    _close(half - x, 0.5 * (full - x), 1e-6)
    assert np.abs(np.asarray((h - x) - (full - x))).max() > 1e-2


def test_the_shared_experts_gate_is_live(tiny):
    """``w_g`` zeroed, the shared expert is added at ``sigmoid(0)``: half;
    as drawn, the layer's mixture is the reference's."""
    model, cfg, params, _, _ = tiny
    lp = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.seq_len, cfg.d_model))
    block = jax.jit(lambda lp: model._ffn_block(lp, h, None, 0))
    y, aux = block(lp)
    want, want_aux, _ = jax.jit(lambda lp: reference.ffn_part(lp, h, SIZES))(lp)
    _close(y, want, 1e-5)
    assert float(aux["aux_loss"]) == pytest.approx(float(want_aux), rel=1e-5)
    at_zero, aux_zero = block(
        {**lp, "shared_gate": jnp.zeros_like(lp["shared_gate"])})
    assert float(aux_zero["shared_gate_mean"]) == 0.5
    no_shared, _ = block(
        {k: v for k, v in lp.items() if k not in ("shared", "shared_gate")})
    ungated, _ = block({k: v for k, v in lp.items() if k != "shared_gate"})
    _close(at_zero - no_shared, 0.5 * (ungated - no_shared), 1e-6)
    assert np.abs(np.asarray(y - ungated)).max() > 1e-2


# ---- (d) the shares add up ----


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts all 8 shares give (each its own 4 of the 32
    experts, through the program's share path), with the shared expert and
    its gate counted once, equal the uncut reference's layer; so do the
    reference's own shares.  No share's buffer overflows and none renormalises
    over the experts it holds."""
    rs = np.random.RandomState(5)
    d, f, experts, held, k, n = 32, 16, 32, 4, 6, 128

    def w(*shape):
        return jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)

    moe = {"gate": w(d, experts), "w_gate": w(experts, d, f),
           "w_up": w(experts, d, f), "w_down": w(experts, f, d)}
    lp = {"ln2": {"offset": jnp.asarray(rs.uniform(-0.3, 0.3, d), jnp.float32)},
          "moe": moe, "shared_gate": w(d, 1),
          "shared": {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}}
    h = jnp.asarray(rs.randn(1, n, d), jnp.float32)
    sizes = dict(SIZES, experts_per_token=k, held=None)
    want, _, _ = reference.ffn_part(lp, h, sizes)
    m = reference.norm(h, lp["ln2"], sizes["norm_eps"]).reshape(-1, d)
    once = reference.shared_gate(lp, m) * trunk.gated_mlp(lp["shared"], m)
    total, ref_total = once, once

    def share_of(first):
        cut = {name: moe[name][first:first + held]
               for name in ("w_gate", "w_up", "w_down")}
        return {**moe, **cut}

    empty = 0
    for j in range(experts // held):
        share = ShardedMixtureOfExperts(
            _one_device_mesh(), hidden_dim=d, num_experts=experts, k=k,
            dtype=jnp.float32, ffn_dim=f, expert_kind="gated_silu",
            routing="dropless", held_experts=held, first_held_expert=j * held)
        part, aux = jax.jit(share)(share_of(j * held), m)
        assert float(aux["dropped_fraction"]) == 0.0, j
        empty += float(aux["held_experts_empty"])
        total = total + part
        ref_total = ref_total + reference.routed_part(
            share_of(j * held), m, dict(sizes, held=(j * held, held)))
    _, _, rank, gates = reference.router(moe, m, sizes)
    assert empty == float(((rank < k).sum(axis=0) == 0).sum())
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    scale = np.abs(np.asarray(want - h)).max()
    for got in (total, ref_total):
        np.testing.assert_allclose(
            np.asarray(h + got.reshape(h.shape)), np.asarray(want), rtol=0,
            atol=1e-5 * scale)
