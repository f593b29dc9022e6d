"""DMoE-Transformer language model — the flagship ([BJ] config 3/5).

The reference's headline experiment: a Transformer LM whose FFN layers are
mixtures of experts (256-expert grid on WikiText-103 — SURVEY.md §3.5).
Two deployment modes share this module:

- **pod mode** (this file's train step): MoE FFNs are
  ``ShardedMixtureOfExperts`` — experts sharded over the mesh's ``expert``
  axis, dispatch via ``lax.all_to_all`` inside one compiled program.
- **swarm mode**: the same trunk with ``RemoteMixtureOfExperts`` FFNs
  calling DHT-discovered servers (see ``experiments/``).

Design notes for the MXU: everything is einsum-shaped, params in float32
with bfloat16 compute, static shapes throughout, optional per-layer remat
(``jax.checkpoint``) to trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, ClassVar

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learning_at_home_tpu.models.trunk import (
    ATTENTION_PRODUCTS,
    FLASH_RESIDUALS,
    SHORT_CONV_RESULT,
    RopeScaling,
    attention_core,
    block_diffusion_admitted_pairs,
    block_diffusion_visited_pairs,
    delta_mixer,
    head_gate,
    flash_block_sizes,
    gate_activation,
    gated_mlp,
    gated_qkv_projections,
    hc_coefficients,
    hc_post,
    hc_pre,
    latent_qkv_projections,
    layer_norm,
    one_query_attention,
    output_projection,
    rms_norm,
    short_conv_mixer,
    ssm_mixer,
    yarn_scales,
)
from learning_at_home_tpu.ops.delta_rule import DELTA_RESIDUALS, channel_decay_fits
from learning_at_home_tpu.ops.moe_dispatch import balanced_bias, level_bias
from learning_at_home_tpu.ops.ssd import SSD_RESIDUALS
from learning_at_home_tpu.parallel.mesh import batch_sharding
from learning_at_home_tpu.parallel.sharded_moe import ShardedMixtureOfExperts

Params = Any
# what a layer reports beside the stream and the router's sums (a recurrent
# layer's extremes, a gate's mean, a share's empty experts), and how the
# stack's layers' readings join in the step's metrics
_EXTREMES = {"ssm_decay_min": jnp.min, "delta_decay_min": jnp.min,
             "delta_beta_max": jnp.max, "attention_gate_mean": jnp.mean,
             "shared_gate_mean": jnp.mean, "held_experts_empty": jnp.max,
             "shortconv_out_rms": jnp.min,
             # hyper-connections: the largest |row or column sum - 1| of any
             # part's mixing matrix, and the largest over the smallest rms
             # of the streams entering a final sum
             "hc_res_marginal_error": jnp.max, "hc_stream_rms_spread": jnp.max}


@dataclasses.dataclass(frozen=True)
class AttentionLayer:
    """What one layer's attention is, where layers of a stack differ
    (``DMoETransformerConfig.layer_pattern``).  ``window``: None = every
    earlier key (global), w = the w keys that end with the query's own.
    ``rotary``: whether the layer's queries and keys are rotated.
    ``mixer``: ``'softmax'`` (the causal softmax attention the two fields
    above describe), ``'delta'`` (linear attention by the gated delta
    rule, ``trunk.delta_mixer``: a recurrent state a head and a short
    convolution in place of scores over the keys, no window, no
    rotation) or ``'conv'`` (a gated short convolution that is the whole
    mixer, ``trunk.short_conv_mixer``: no scores, no state, no window, no
    rotation)."""

    window: int | None = None
    rotary: bool = True
    mixer: str = "softmax"


@dataclasses.dataclass(frozen=True)
class DMoETransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    seq_len: int = 256
    num_experts: int = 256
    k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    router_z_weight: float = 1e-3  # ST-MoE router z-loss
    # Switch-style multiplicative routing noise (deterministic pattern;
    # see ops.moe_dispatch.router_jitter).  Essential for byte-level
    # corpora where near-identical rows otherwise collapse onto the same
    # experts (measured 0.73 init dropped fraction on the 256-expert
    # flagship).  Default OFF: the fixed row↦noise map is not
    # permutation-invariant, so it would break exact zigzag/contiguous
    # sequence-layout equivalence; trainers opt in (train_lm
    # --router-jitter).
    router_jitter: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # Not options: the stack has ONE layout, a tuple of per-layer trees run
    # by the unrolled loop.  The two names are what the CFG_FIELDS tables of
    # benchmarks/runners/{train_step,train_recipe,train_recipe_blocks,
    # train_recipe_share,train_recipe_latent,train_recipe_hybrid}.py read
    # off a config and compare with their files' `false`, their only
    # readers; ROADMAP.md Speed 6(f) drops the keys from the benchmark's
    # files, and then these two lines go.
    scan_layers: ClassVar[bool] = False
    stack_layers: ClassVar[bool] = False
    tie_embeddings: bool = True
    # sequence/context parallelism: attention runs as a ring over the
    # mesh's 'seq' axis (parallel/ring_attention.py).  The MoE stays
    # data+expert sharded; XLA inserts the reshard at the boundary.
    seq_parallel: bool = False
    # "zigzag" balances causal work across the ring (~2× fewer attention
    # FLOPs at scale); "contiguous" is the plain ring
    seq_layout: str = "zigzag"
    # token-chunk size of the cross-entropy (peak logits memory =
    # ce_chunk × vocab × 4 bytes; see loss_fn)
    ce_chunk: int = 1024
    # ---- the block's shape: an architecture's description, not tuning
    # switches.  The defaults are the DMoE-Transformer of the seed paper;
    # OLMoE is rmsnorm / rope / qk_norm / gated_silu of width 1024 /
    # dropless / renormalize False (__graft_entry__.olmoe_one_chip);
    # SmallThinker is rmsnorm (eps 1e-6) / rope (theta 1.5e6) on three
    # layers in four / 28 heads over 4 key/value heads of 128 / a window of
    # 4,096 on the same three / gated_relu of width 768 / dropless /
    # router on the attention's input (smallthinker_one_chip); K-EXAONE is
    # rmsnorm / rope (theta 1e6) on the window layers (128 keys) of
    # L L L G / 64 heads over 8 key/value heads of 128 with a norm over
    # each head / a dense first layer of width 18,432 / then a shared
    # expert beside gated_silu experts of width 2048, 8 of 128 by sigmoid
    # scores with a selection bias, weights renormalised times 2.5 /
    # dropless, a share of the experts held (k_exaone_one_chip);
    # GLM-4.7-Flash is rmsnorm / rope (theta 1e6) / 20 heads of 256 whose
    # queries, keys and values are expanded from latents of 768 and 512,
    # the last 64 of a head rotated / a dense first layer of width 10,240 /
    # then a shared expert beside gated_silu experts of width 1536, 4 of 64
    # by sigmoid scores with a selection bias, renormalised times 1.8 /
    # dropless, a share held / one block that predicts the next-but-one
    # token with a loss of its own (glm_4_7_flash_one_chip);
    # Nemotron-Labs-TwoTower's hybrid tower is rmsnorm / no positions /
    # layers that are ONE mixer each, M E M E M * E M E: Mamba-2 (64 heads
    # of 64, state 128, 8 groups, chunks of 128), 32 heads over 2 key/value
    # heads of 128, or un-gated squared-ReLU experts of width 1856, 6 of
    # 128 by sigmoid scores with a selection bias, renormalised times 2.5,
    # beside a shared expert of width 3712 / dropless, a share held
    # (nemotron_labs_twotower_one_chip); Olmo-Hybrid-7B is rmsnorm (eps
    # 1e-6) on each part's OUTPUT / no positions / L L L F: three layers of
    # the gated delta rule (30 heads, keys of 96, values of 192, 4 taps,
    # chunks of 64) to each of 30 heads of 128 with a norm over the whole
    # queries and keys / a dense gated_silu block of width 11,008 in every
    # layer, no mixture at all (olmo_hybrid_7b_one_chip);
    # Qwen3-Next-80B-A3B is rmsnorm_offset (eps 1e-6) / L L L F: three
    # layers of the gated delta rule (16 key heads under 32 value heads, keys
    # and values of 128, beta = sigmoid(b), 4 taps, chunks of 64) to each of
    # 16 heads over 2 key/value heads of 256 with a norm over each head, the
    # first 64 of a head rotated (theta 1e7), the output gated / in every
    # layer a shared gated_silu expert of width 512 under a gate of its own
    # beside experts of the same width, 10 of 512 by softmax, renormalised /
    # dropless, a share held (qwen3_next_one_chip); LFM2-8B-A1B is rmsnorm
    # (eps 1e-5) / rope (theta 1e6) / C A C C C: a gated short convolution
    # of 3 taps that is the whole mixer (C * conv(B * u), no bias, no
    # activation, no state) in three layers of four, 32 heads over 8
    # key/value heads of 64 with a norm over each head in the fourth / a
    # dense gated_silu block of width 7,168 in the leading layer, then
    # gated_silu experts of width 1,792, 4 of 32 by sigmoid scores with a
    # selection bias, renormalised, every expert held / a tied head
    # (lfm2_8b_a1b_one_chip); Xing4.0-29B-A4B is rmsnorm (eps 1e-6) / rope
    # (theta 1e4) under YaRN (factor 64 over 4,096) / FOUR residual streams
    # a token, every part reading and writing them through
    # hyper-connections whose 4 x 4 matrix 20 Sinkhorn iterations make
    # doubly stochastic / 32 heads whose queries, keys and values are
    # expanded from latents of 768 and 512, queries and keys of 192 (the
    # last 64 rotated) over values of 128 / a dense leading layer of width
    # 9,216, then a shared expert beside gated_silu experts of width 1,024,
    # 4 of 64 by sigmoid scores with a selection bias, renormalised times
    # 2 / dropless, a share held / one block that predicts the
    # next-but-one token (xing4_0_29b_a4b_one_chip).
    # 'layernorm' (scale and bias), 'rmsnorm' (scale only) or
    # 'rmsnorm_offset' (Qwen3-Next: the multiplier is 1 + w, w zero from the
    # seed; every norm of the stack but the delta rule's gate-and-norm)
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    # where a part's norm sits: 'input', x + Part(norm(x)); or 'output',
    # x + norm(Part(x)), the part reading the stream as it is (the Olmo
    # family's order).  The attention, the delta rule and the feed-forward
    # part honour it alike; the final norm before the head is there in both
    norm_place: str = "input"
    # 'learned': a [seq_len, d] table added to the embeddings; 'rope':
    # no table, rotary embedding of the queries and keys of every layer
    # (or of the layers that layer_pattern says)
    positions: str = "learned"
    rope_theta: float = 10000.0
    # key/value heads (query head h reads key/value head h // (n_heads /
    # n_kv_heads)); None = n_heads
    n_kv_heads: int | None = None
    # a head's size; None = d_model // n_heads
    head_dim: int | None = None
    # latent attention (trunk.latent_qkv_projections): the keys and values
    # of every head are expanded from one normalized latent of
    # kv_latent_dim a token, the queries from one of q_latent_dim (None
    # beside a kv_latent_dim: no query latent and no norm of it, the
    # queries one plain product, DeepSeek-V2-Lite's form); of a head's
    # head_dim the last rope_head_dim are rotated, and the keys' rotated
    # part is one a token, shared by the heads.  kv_latent_dim None = the
    # plain projections
    kv_latent_dim: int | None = None
    q_latent_dim: int | None = None
    rope_head_dim: int | None = None
    # the plain projections rotate the FIRST rotary_dim columns of a head
    # alone (Qwen3-Next's partial_rotary_factor: 64 of 256); None = all
    rotary_dim: int | None = None
    # a softmax layer's output is multiplied by sigmoid(gate) before wo, the
    # gate a second half of wq's columns a head (Qwen3-Next's full-attention
    # layers, trunk.gated_qkv_projections); 'head': by ONE number a head a
    # token, sigmoid(x w_gate) of a projection [d, H] of its own, on the
    # plain or the latent projections and on a channel-decayed delta layer
    # alike (Ling-3.0's gated_attention_proj_granularity_type 'head_wise')
    attention_gate: bool | str = False
    # where the layers of the stack differ: one AttentionLayer a layer,
    # or one period of them, repeated; None = every layer global, rotated
    # where positions == 'rope'
    layer_pattern: tuple[AttentionLayer, ...] | None = None
    # RMSNorm (own scales) of the queries and keys: True, over the whole
    # d-wide projections, before the split into heads; 'head', over each
    # head's own head_dim, one scale shared by the heads
    qk_norm: bool | str = False
    # 'gelu' (w1/b1/w2/b2), 'gated_silu' / 'gated_relu'
    # (w_gate/w_up/w_down, no biases; SiLU or ReLU on the gate branch), or
    # 'relu2' (w_up/w_down, no gate branch, no biases: relu(x Wu)^2 Wd)
    expert_kind: str = "gelu"
    # an expert's hidden width; None = 4 * d_model
    expert_ffn_dim: int | None = None
    # 'capacity': [E, C, d] slots, overflow dropped (capacity_factor);
    # 'dropless': sort by expert + grouped matmul, nothing dropped
    routing: str = "capacity"
    # top-k gate weights renormalised to sum to 1, or as the softmax
    # over all experts gives them
    renormalize: bool = True
    # what the router reads: 'moe_input', the normalized stream the
    # experts compute on, or 'attention_input', the layer's normalized
    # input that the attention block reads (a router placed before the
    # attention: its top-k is known before the attention has run)
    router_input: str = "moe_input"
    # a layer's feed-forward part, one entry a layer: 'moe' (the mixture)
    # or 'dense' (one gated block of width dense_ffn_dim that every token
    # passes, no router); None = every layer 'moe'
    ffn_pattern: tuple[str, ...] | None = None
    dense_ffn_dim: int | None = None
    # experts every token passes beside the routed ones, as one block of
    # the experts' kind added to the routed sum, of width shared_expert_dim
    # (None = shared_experts * expert_ffn_dim)
    shared_experts: int = 0
    shared_expert_dim: int | None = None
    # the shared expert's output is multiplied by sigmoid(x w_g), one number
    # a token (Qwen3-Next's shared_expert_gate)
    shared_expert_gate: bool = False
    # 'softmax': gates from a softmax over all experts; 'sigmoid': every
    # expert scored on its own, the k largest renormalised (renormalize)
    # and multiplied by routed_scale
    router_score: str = "softmax"
    routed_scale: float = 1.0
    # a per-expert bias [E] added to the sigmoid scores for the CHOICE of
    # experts alone: no gradient reaches it, and the train step moves it by
    # router_bias_rate toward level loads (arXiv:2408.15664)
    router_bias: bool = False
    router_bias_rate: float = 0.0
    # (n_group, topk_group): the sigmoid router chooses its k inside the
    # topk_group best of n_group groups of consecutive experts, a group
    # scored by the sum of its two best selection scores (DeepSeek-V3's
    # group-limited routing, ops.moe_dispatch.group_limited); None = none
    router_groups: tuple[int, int] | None = None
    # this program holds held_experts of the num_experts each router
    # scores, from first_held_expert on: one chip's share of layers whose
    # experts no chip holds whole (None = all of them)
    held_experts: int | None = None
    first_held_expert: int = 0
    # blocks after the stack that predict the next-but-one token (0 or 1):
    # z = [rms(embed[t_{i+1}]) ; rms(h_i)] W_eh on the stack's final
    # normalized stream h, one more layer of the model's own kind (a
    # mixture layer with its own parameters), a norm, and the model's own
    # head; its mean CE against t_{i+2} joins the loss times
    # mtp_loss_weight
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.0
    # what a layer IS, one entry a layer: None = every layer an attention
    # block followed by a feed-forward part (ffn_pattern says which); or
    # layers that are ONE mixer each behind ONE norm, x + Mixer(norm(x)):
    # 'ssm' (the Mamba-2 state-space mixer, trunk.ssm_mixer), 'attention',
    # or 'moe' (the mixture, with its shared expert)
    mixer_pattern: tuple[str, ...] | None = None
    # the state-space mixer: ssm_heads heads of ssm_head_dim, a state of
    # ssm_state_dim a head channel, B and C shared by the heads of each of
    # ssm_groups groups, a causal depthwise convolution of ssm_conv_kernel
    # taps, the recurrence computed ssm_chunk positions at a time; dt's
    # bias drawn so that softplus(dt_bias) is log-uniform in ssm_dt_range
    # and at least ssm_dt_floor
    ssm_heads: int | None = None
    ssm_head_dim: int | None = None
    ssm_state_dim: int | None = None
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_dt_range: tuple[float, float] = (1e-3, 1e-1)
    ssm_dt_floor: float = 1e-4
    # a layer whose AttentionLayer.mixer is 'delta': n_heads heads with
    # keys of delta_key_dim and values of delta_value_dim, causal depthwise
    # convolutions of delta_conv_kernel taps on q, k and v, the recurrence
    # computed delta_chunk positions at a time; its decays' bias drawn as
    # the state-space mixer's (ssm_dt_range)
    delta_key_dim: int | None = None
    delta_value_dim: int | None = None
    delta_conv_kernel: int = 4
    delta_chunk: int = 64
    # the rule's value heads where they are more than its n_heads query/key
    # heads (Qwen3-Next: 32 over 16, value head j reading query/key head
    # j // 2); None = n_heads
    delta_value_heads: int | None = None
    # beta = 2 sigmoid(b) (Olmo-Hybrid's linear_allow_neg_eigval) or, False,
    # sigmoid(b) (Qwen3-Next)
    delta_neg_eigval: bool = True
    # None: one decay a head, g = -exp(A_log) softplus(a + dt_bias), out of
    # the one in-projection (Gated DeltaNet).  A number (Kimi Delta
    # Attention, arXiv:2510.26692; Ling-3.0's kda_lower_bound -5 under
    # kda_safe_gate): a decay a KEY CHANNEL from a projection [d, H dk] of
    # its own, g = floor * sigmoid(exp(A_log) (f + dt_bias)) in (floor, 0),
    # dt_bias a channel; the in-projection is [q | k | v], the write
    # strengths a projection [d, H] of their own, and the output's gate one
    # number a head (attention_gate 'head') after the norm
    delta_decay_floor: float | None = None
    # what a training row is and what the loss asks of it.  'next_token':
    # the row's ids, a causal mask, the mean CE of each position's next
    # token.  'block_diffusion' (BD3-LMs, arXiv:2503.09573, as SDAR trains):
    # the stack runs a row of 2 * seq_len positions, a noised copy then the
    # clean copy of the same seq_len ids at rotary positions 0..seq_len-1
    # twice, under ``trunk.block_diffusion_mask`` with blocks of
    # diffusion_block tokens; a block draws t ~ U(0, 1) and each of its
    # tokens becomes the mask id (the vocabulary's last id) with
    # probability p = DIFFUSION_P_FLOOR + (1 - DIFFUSION_P_FLOOR) t; the
    # loss is over the noised copy, in place: sum over masked positions of
    # CE(logits_i, ids_i) / p over batch * seq_len.  seq_len counts the
    # DATA tokens of a row, under either objective
    objective: str = "next_token"
    diffusion_block: int = 4
    # a layer whose AttentionLayer.mixer is 'conv': a causal depthwise
    # convolution of short_conv_kernel taps over d_model channels between
    # two gates (LFM2's conv_L_cache)
    short_conv_kernel: int = 3
    # manifold-constrained hyper-connections (arXiv:2512.24880 over
    # arXiv:2409.19606): the residual stream is hc_streams streams a token,
    # [B, S, n, d]; the embedding is copied to them, every part of a layer
    # (its attention, its feed-forward part; the prediction block's two)
    # reads a learned, input-dependent mix of them and writes back through
    # a doubly stochastic n x n matrix made by hc_sinkhorn_iters
    # Sinkhorn iterations a token a part (trunk.hc_coefficients, hc_pre,
    # hc_post: hc_eps is what the iterations' sums are kept from zero by,
    # hc_res_clamp what the matrix's logits are clipped to before their
    # exponential), and the streams are summed before the final norm.
    # None = one stream, x + Part(norm(x)), to the bit
    hc_streams: int | None = None
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # latent attention's values a head where they are not as wide as its
    # queries and keys (head_dim): 128 under keys of 192; None = head_dim
    v_head_dim: int | None = None
    # YaRN's numbers (trunk.RopeScaling): the rotated part's frequencies
    # are interpolated a pair at a time and the softmax's scale grows by
    # trunk.yarn_scales' second; None = the plain frequencies
    rope_scaling: RopeScaling | None = None

    def mixture_layers(self) -> int:
        """How many of the stack's layers route (hold a mixture)."""
        if self.mixer_pattern is not None:
            return self.mixer_pattern.count("moe")
        return (self.ffn_pattern or ("moe",) * self.n_layers).count("moe")

    def attention_layer(self, i: int) -> AttentionLayer:
        """Layer ``i``'s attention."""
        if self.layer_pattern is None:
            return AttentionLayer(rotary=self.positions == "rope")
        return self.layer_pattern[i % len(self.layer_pattern)]


# The shortest sequence at which the blocked kernel, at its tuned tiles,
# beats the xla core on a TPU v5e for heads of 64 and of 128 alike (heads
# of 256 were measured at 16,384 alone, where xla's scores do not fit)
# (forward + backward of the core alone, bf16, 8,192 tokens a call, ms
# xla / kernel; tools/attention_probe.py cores; PERF.md section 6 "PR 28"):
#   S       heads of 64 (8)    heads of 128 (16)
#     256    0.60 /  0.76        2.61 /  2.00   (xla still wins at 64:
#     512    1.34 /  0.74        4.85 /  1.93    dmoe256's shape)
#   1,024    3.14 /  1.05        9.03 /  2.77
#   2,048    5.86 /  1.48       17.21 /  3.65
#   4,096   16.13 /  2.27       33.26 /  5.16
#   8,192  241.06 /  3.73      485.28 /  8.21   (xla's [B,H,S,S] scores
#                                                 fall off the HBM cliff)
FLASH_MIN_SEQ_LEN = 512
# the least masking probability a block draws under objective
# 'block_diffusion' (BD3-LMs' and LLaDA's floor): 1 / p is the loss's weight
DIFFUSION_P_FLOOR = 1e-3


def auto_attn_impl(
    backend: str, n_devices: int, seq_len: int, head_dim: int,
    value_dim: int | None = None,
) -> str:
    """The attention core a model runs (``DMoETransformerLM.attn_impl``),
    from the backend, the mesh and the training shape alone.  ``'flash'``:
    the TPU Pallas blocked kernel (splash attention under a causal mask,
    O(S) memory) at the tiles ``trunk.flash_block_sizes`` gives for the
    call's shape, where it can run and the sequence is long enough for it
    to win.  ``'xla'``: ``jax.nn.dot_product_attention`` (materializes
    [B,H,S,S]), everywhere else.  The kernel can run on the ``tpu``
    backend specifically, not merely "not cpu" (Mosaic lowering), at
    tiles that divide the length, and on a mesh of ONE device: Mosaic
    refuses to partition a kernel over a mesh ("wrap the call in a
    shard_map"), so there the step keeps the core XLA can partition."""
    can_run = (
        n_devices == 1
        and flash_block_sizes(
            (1, seq_len, 1, head_dim), backend, value_dim=value_dim) is not None
    )
    return "flash" if can_run and seq_len >= FLASH_MIN_SEQ_LEN else "xla"


class DMoETransformerLM:
    """Functional model: explicit param pytree, jit/pjit-friendly apply."""

    def __init__(self, config: DMoETransformerConfig, mesh: Mesh):
        if config.norm not in ("layernorm", "rmsnorm", "rmsnorm_offset"):
            raise ValueError(
                f"norm must be 'layernorm', 'rmsnorm' or 'rmsnorm_offset', "
                f"got {config.norm!r}"
            )
        if config.positions not in ("learned", "rope"):
            raise ValueError(
                f"positions must be 'learned' or 'rope', got "
                f"{config.positions!r}"
            )
        if config.router_input not in ("moe_input", "attention_input"):
            raise ValueError(
                f"router_input must be 'moe_input' or 'attention_input', "
                f"got {config.router_input!r}"
            )
        if config.norm_place not in ("input", "output"):
            raise ValueError(
                f"norm_place must be 'input' or 'output', got "
                f"{config.norm_place!r}"
            )
        kinds = {config.attention_layer(i) for i in range(config.n_layers)}
        self._delta = any(a.mixer == "delta" for a in kinds)
        self._conv = any(a.mixer == "conv" for a in kinds)
        if any(a.mixer not in ("softmax", "delta", "conv") for a in kinds):
            raise ValueError(
                f"an AttentionLayer's mixer is 'softmax' or 'delta' or 'conv', "
                f"got {sorted({a.mixer for a in kinds})}"
            )
        if self._conv and (
            config.seq_parallel or mesh.devices.size > 1
            or config.norm_place != "input"
        ):
            raise NotImplementedError(
                "a 'conv' layer on a mesh of several chips, under "
                "seq_parallel=True (ring attention, parallel/"
                "ring_attention.py) or behind a norm on a part's output "
                "(norm_place): the short convolution's taps cross a "
                "sequence shard's edge and nothing hands the rows before "
                "it over, its kernel is not partitioned over a mesh, and "
                "the layer is x + Mixer(norm(x))"
            )
        if self._delta and None in (config.delta_key_dim, config.delta_value_dim):
            raise ValueError(
                "a 'delta' layer needs delta_key_dim and delta_value_dim"
            )
        if self._delta and (config.delta_value_heads or 0) % config.n_heads:
            raise ValueError(
                f"delta_value_heads={config.delta_value_heads} must be a "
                f"multiple of the rule's {config.n_heads} key heads (n_heads)"
            )
        if self._delta and config.seq_parallel:
            raise NotImplementedError(
                "seq_parallel=True (ring attention, parallel/"
                "ring_attention.py) with a 'delta' layer: the delta rule's "
                "recurrence and its convolutions cross the ring's sequence "
                "shards, and nothing carries a state from shard to shard"
            )
        if config.layer_pattern is not None:
            if config.n_layers % len(config.layer_pattern):
                raise ValueError(
                    f"layer_pattern has {len(config.layer_pattern)} entries"
                    f", which do not divide n_layers={config.n_layers}"
                )
            if config.positions != "rope" and any(a.rotary for a in kinds):
                raise ValueError(
                    "layer_pattern rotates a layer's queries and keys: "
                    "positions must be 'rope'"
                )
        if config.qk_norm not in (False, True, "head"):
            raise ValueError(
                f"qk_norm must be False, True or 'head', got {config.qk_norm!r}"
            )
        ffns = set(config.ffn_pattern or ("moe",))
        if config.ffn_pattern is not None:
            if len(config.ffn_pattern) != config.n_layers or ffns - {"moe", "dense"}:
                raise ValueError(
                    f"ffn_pattern must name 'moe' or 'dense' for each of "
                    f"the {config.n_layers} layers, got {config.ffn_pattern}"
                )
            if "dense" in ffns and config.dense_ffn_dim is None:
                raise ValueError("a 'dense' layer needs dense_ffn_dim")
        if (config.shared_experts or "dense" in ffns) and (
            config.expert_kind == "gelu"
        ):
            raise ValueError(
                "the dense layer and the shared expert are blocks without "
                "biases (gated, or un-gated for 'relu2'), with the experts' "
                "activation: expert_kind must not be 'gelu'"
            )
        mixers = config.mixer_pattern
        if mixers is not None:
            if len(mixers) != config.n_layers or (
                set(mixers) - {"ssm", "attention", "moe"}
            ) or "moe" not in mixers:
                raise ValueError(
                    f"mixer_pattern must name 'ssm', 'attention' or 'moe' "
                    f"for each of the {config.n_layers} layers, one 'moe' "
                    f"at least (no model asks for single mixers without "
                    f"one), got {mixers}"
                )
            if "ssm" in mixers and None in (
                config.ssm_heads, config.ssm_head_dim, config.ssm_state_dim
            ):
                raise ValueError(
                    "an 'ssm' layer needs ssm_heads, ssm_head_dim and "
                    "ssm_state_dim"
                )
            if (
                config.ffn_pattern is not None or config.mtp_layers
                or config.router_input != "moe_input" or self._delta
                or self._conv or config.norm_place != "input"
            ):
                raise ValueError(
                    "mixer_pattern: a layer that is ONE mixer has no "
                    "feed-forward part beside its attention (ffn_pattern), "
                    "no attention input for its router (router_input), "
                    "no next-but-one-token block built of such layers "
                    "(mtp_layers), no 'delta' layer or 'conv' layer among "
                    "its attention layers and its ONE norm on its input "
                    "(norm_place)"
                )
            if config.seq_parallel:
                raise NotImplementedError(
                    "seq_parallel=True (ring attention, parallel/"
                    "ring_attention.py) with mixer_pattern: a state-space "
                    "layer's recurrence and its convolution cross the "
                    "ring's sequence shards, and nothing carries a state "
                    "from shard to shard"
                )
        latent = (config.kv_latent_dim, config.rope_head_dim, config.head_dim)
        if any(size is not None for size in (
                config.kv_latent_dim, config.q_latent_dim, config.rope_head_dim)):
            if None in latent:  # q_latent_dim None: no query latent
                raise ValueError(
                    "latent attention is kv_latent_dim, rope_head_dim and "
                    "head_dim together (and q_latent_dim, or None for "
                    f"queries of one plain product), got {latent} and "
                    f"q_latent_dim={config.q_latent_dim}"
                )
            if config.n_kv_heads not in (None, config.n_heads) or config.qk_norm:
                raise ValueError(
                    "latent attention expands keys and values for every "
                    "query head and norms its latents: no n_kv_heads, no "
                    "qk_norm"
                )
            if not 0 < config.rope_head_dim < config.head_dim or (
                config.rope_head_dim % 2
            ):
                raise ValueError(
                    f"rope_head_dim={config.rope_head_dim} is the rotated, "
                    f"even part of head_dim={config.head_dim}"
                )
        if config.attention_gate not in (False, True, "head"):
            raise ValueError(
                f"attention_gate must be False, True or 'head', got "
                f"{config.attention_gate!r}"
            )
        if (config.rotary_dim is not None or config.attention_gate is True) and (
            config.kv_latent_dim is not None or config.qk_norm is True
            or config.seq_parallel
        ):
            raise ValueError(
                "rotary_dim and attention_gate belong to the plain "
                "projections with no norm or one over each head: no latent "
                "attention, no norm over the whole queries (qk_norm=True), "
                "no ring (seq_parallel: its projections hand no gate over)"
            )
        if config.attention_gate == "head" and config.seq_parallel:
            raise ValueError(
                "attention_gate='head' under seq_parallel: the ring's "
                "projections hand no gate over"
            )
        channel_decay = config.delta_decay_floor is not None
        if (channel_decay and not self._delta) or (
            self._delta and channel_decay != (config.attention_gate == "head")
        ):
            raise ValueError(
                "delta_decay_floor (a decay a key channel) and "
                "attention_gate='head' go together in a stack with a "
                "'delta' layer: the channel-decayed mixer's output gate is "
                "one number a head, the head-decayed mixer's one a channel "
                f"(got {config.delta_decay_floor} and "
                f"{config.attention_gate!r})"
            )
        if channel_decay and (
            config.delta_value_heads not in (None, config.n_heads)
            or not channel_decay_fits(config.delta_decay_floor)
        ):
            raise ValueError(
                f"delta_decay_floor={config.delta_decay_floor}: a decay a "
                "key channel has as many value heads as key heads and a "
                "floor that ops.delta_rule.channel_decay_fits admits"
            )
        if config.shared_expert_gate and (
            not config.shared_experts or config.norm_place != "input"
        ):
            raise ValueError(
                "shared_expert_gate gates a shared expert (shared_experts) "
                "behind a norm on the part's input"
            )
        if config.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers must be 0 or 1, got {config.mtp_layers}: one "
                "block that predicts the next-but-one token is described"
            )
        if not config.mixture_layers() and (
            config.mtp_layers or config.router_bias
        ):
            raise ValueError(
                "a stack with no 'moe' layer has no router: no selection "
                "bias (router_bias) and no next-but-one-token block, whose "
                "layer is a mixture layer (mtp_layers)"
            )
        if config.objective not in ("next_token", "block_diffusion"):
            raise ValueError(
                f"objective must be 'next_token' or 'block_diffusion', got "
                f"{config.objective!r}"
            )
        self._diffusion = config.objective == "block_diffusion"
        if self._diffusion:
            if (
                config.positions != "rope" or mixers is not None
                or self._delta or self._conv or config.mtp_layers
                or config.seq_parallel
                or any(a.window is not None for a in kinds)
            ):
                raise NotImplementedError(
                    "objective='block_diffusion' runs softmax attention "
                    "with rotary positions in every layer over one doubled "
                    "row: no learned positions (a table of seq_len rows), "
                    "no window, no recurrent mixer (mixer_pattern, a "
                    "'delta' layer: a state would run from the noised copy "
                    "into the clean one; a 'conv' layer's taps likewise), no "
                    "next-but-one-token block and "
                    "no ring (seq_parallel)"
                )
            if config.seq_len % config.diffusion_block:
                raise ValueError(
                    f"diffusion_block={config.diffusion_block} must divide "
                    f"seq_len={config.seq_len}"
                )
        n_kv = config.n_kv_heads or config.n_heads
        if config.n_heads % n_kv:
            raise ValueError(
                f"n_heads={config.n_heads} must be a multiple of "
                f"n_kv_heads={n_kv}"
            )
        # what ring attention and the KV-cache decoder do not take
        self._grouped_or_windowed = n_kv != config.n_heads or any(
            a.window is not None for a in kinds
        )
        # what the KV-cache decoder's one feed-forward part (a mixture that
        # holds its experts) is not
        self._other_ffn = bool(
            "dense" in ffns or config.shared_experts
            or config.held_experts not in (None, config.num_experts)
        )
        if config.seq_parallel and (
            config.kv_latent_dim is not None or config.mtp_layers
        ):
            raise NotImplementedError(
                "seq_parallel=True (ring attention, parallel/"
                "ring_attention.py) has no latent attention (its ring "
                "rotates keys and values, not their latent) and no "
                "next-but-one-token block (mtp_layers: the next ids' "
                "embeddings are not laid out over the ring)"
            )
        if config.seq_parallel and self._grouped_or_windowed:
            raise NotImplementedError(
                "seq_parallel=True (ring attention, parallel/"
                "ring_attention.py) rotates key/value blocks of as many "
                "heads as the queries have under a causal mask alone: it "
                "has no grouped key/value heads and no window"
            )
        if (config.v_head_dim is not None or config.rope_scaling is not None) and (
            config.kv_latent_dim is None
        ):
            raise NotImplementedError(
                "v_head_dim and rope_scaling belong to latent attention "
                "(kv_latent_dim): the plain projections' values are as wide "
                "as their keys (wv's columns over the heads) and their "
                "rotation takes the plain frequencies"
            )
        if config.rope_scaling is not None and config.seq_parallel:
            # (latent attention under the ring is refused above; this names
            # what the ring itself lacks)
            raise NotImplementedError(
                "seq_parallel=True (ring attention, parallel/"
                "ring_attention.py) with rope_scaling: the ring's core "
                "scales its scores by 1 / sqrt(head) alone"
            )
        if config.hc_streams is not None:
            if config.hc_streams < 2:
                raise ValueError(
                    f"hc_streams={config.hc_streams}: hyper-connections mix "
                    "two streams or more (None = one stream)"
                )
            if config.seq_parallel or mesh.devices.size > 1:
                raise NotImplementedError(
                    "hc_streams on a mesh of several chips or under "
                    "seq_parallel=True (ring attention, parallel/"
                    "ring_attention.py): the streams [B, S, n, d] and their "
                    "coefficients are laid out over no mesh axis, and "
                    "nothing of it was run on one"
                )
            if self._diffusion:
                raise NotImplementedError(
                    "hc_streams with objective='block_diffusion': the "
                    "doubled row's two copies would share the streams' "
                    "coefficients' norm, and nothing of it was run"
                )
            if (
                mixers is not None or self._delta or self._conv
                or config.norm_place != "input" or config.shared_expert_gate
            ):
                raise NotImplementedError(
                    "hc_streams wraps an attention part and a feed-forward "
                    "part behind norms on their inputs: no mixer_pattern, "
                    "no 'delta' or 'conv' layer, no norm on a part's output "
                    "(norm_place) and no shared_expert_gate pass through "
                    "the wrapper"
                )
        self.cfg = config
        self.mesh = mesh
        self.attn_impl = auto_attn_impl(
            jax.default_backend(), mesh.devices.size, config.seq_len,
            config.head_dim or config.d_model // config.n_heads,
            config.v_head_dim,
        )
        # the softmax's scale where it is not 1 / sqrt(head): YaRN's
        self._attn_scale = None if config.rope_scaling is None else (
            float(yarn_scales(config.rope_scaling)[1] / config.head_dim ** 0.5))
        # compiled decoders (one per decode path) + the memoized
        # eval-routing twin (see generate / decode_model): without these,
        # every generate() call re-traces its whole decode loop — measured
        # 17.1 s vs 0.07 s compiled for 60 tokens at seq_len 1024 on CPU
        self._gen_jit: dict = {}
        self._decode_model: "DMoETransformerLM | None" = None
        self._gate_act = gate_activation(config.expert_kind)
        # a stack with no mixture layer builds no router and no expert state
        self.moe = None if not config.mixture_layers() else (
            ShardedMixtureOfExperts(
                mesh,
                hidden_dim=config.d_model,
                num_experts=config.num_experts,
                k=config.k,
                capacity_factor=config.capacity_factor,
                dtype=config.dtype,
                param_dtype=config.param_dtype,
                router_jitter=config.router_jitter,
                ffn_dim=config.expert_ffn_dim,
                expert_kind=config.expert_kind,
                routing=config.routing,
                renormalize=config.renormalize,
                router_input=config.router_input == "attention_input",
                held_experts=config.held_experts,
                first_held_expert=config.first_held_expert,
                router_score=config.router_score,
                router_bias=config.router_bias,
                routed_scale=config.routed_scale,
                router_groups=config.router_groups,
            )
        )
        self._ring = None
        self._zig = self._zig_inv = None
        if config.seq_parallel:
            if "seq" not in mesh.axis_names:
                raise ValueError("seq_parallel=True requires a 'seq' mesh axis")
            from learning_at_home_tpu.parallel.ring_attention import (
                make_ring_attention,
                zigzag_indices,
            )

            layout = config.seq_layout
            n_seq = mesh.shape["seq"]
            if layout == "zigzag" and config.seq_len % (2 * n_seq):
                import logging

                logging.getLogger(__name__).warning(
                    "seq_len %d not divisible by 2*%d — falling back to the "
                    "contiguous ring layout (zigzag needs paired chunks)",
                    config.seq_len, n_seq,
                )
                layout = "contiguous"
            if layout == "zigzag":
                # the residual stream is permuted ONCE at the model
                # boundary (see apply); the ring consumes zigzag order
                # directly — 2 gathers per step instead of 4 per layer
                self._zig = zigzag_indices(config.seq_len, n_seq)
                self._zig_inv = np.argsort(self._zig)
            self._ring = make_ring_attention(
                mesh, causal=True, layout=layout,
                pre_permuted=self._zig is not None,
            )

    # ---- parameters ----

    def init_params(self, rng: jax.Array) -> Params:
        """``params["layers"]`` is a tuple of per-layer trees, one a
        layer, which the forward's unrolled loop reads leaf by leaf; what
        a layer's tree holds says what the layer is."""
        cfg = self.cfg
        d, v, s = cfg.d_model, cfg.vocab_size, cfg.seq_len
        hd = cfg.head_dim or d // cfg.n_heads
        d_q, d_kv = cfg.n_heads * hd, (cfg.n_kv_heads or cfg.n_heads) * hd
        dense = jax.nn.initializers.lecun_normal()
        embed_init = jax.nn.initializers.normal(1.0 / np.sqrt(d))
        k_embed, k_pos, k_head, k_layers = jax.random.split(rng, 4)
        pdt = cfg.param_dtype

        def rms(width):
            """An RMSNorm's parameter: what it holds says which it is."""
            if cfg.norm == "rmsnorm_offset":
                return {"offset": jnp.zeros((width,), pdt)}
            return {"scale": jnp.ones((width,), pdt)}

        def ln():
            if cfg.norm != "layernorm":
                return rms(d)
            return {"scale": jnp.ones((d,), pdt), "bias": jnp.zeros((d,), pdt)}

        def dense_block(key, width):
            """A dense block of the experts' kind: un-gated for 'relu2'."""
            kg, ku, kd = jax.random.split(key, 3)
            block = {"w_up": dense(ku, (d, width), pdt),
                     "w_down": dense(kd, (width, d), pdt)}
            if cfg.expert_kind != "relu2":
                block["w_gate"] = dense(kg, (d, width), pdt)
            return block

        def mixture(key):
            """A layer's mixture and, beside it, its shared expert."""
            held = {"moe": self.moe.init_params(key, device_put=False)}
            if cfg.shared_experts:
                held["shared"] = dense_block(
                    jax.random.fold_in(key, 1),
                    cfg.shared_expert_dim
                    or cfg.shared_experts * self.moe.ffn_dim,
                )
            if cfg.shared_expert_gate:
                held["shared_gate"] = dense(jax.random.fold_in(key, 2), (d, 1), pdt)
            return held

        def ssm(key):
            """The state-space mixer: ``dt_bias`` so that ``softplus`` of
            it is log-uniform in ``ssm_dt_range`` and at least
            ``ssm_dt_floor``, ``A_log = log(uniform(1, 16))``, ``D = 1``
            (the three float32 whatever the parameters' dtype, as the
            decays' arithmetic is): decays in a trained model's range."""
            h, n_groups = cfg.ssm_heads, cfg.ssm_groups
            d_inner = h * cfg.ssm_head_dim
            conv_dim = d_inner + 2 * n_groups * cfg.ssm_state_dim
            k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
            low, high = np.log(cfg.ssm_dt_range)
            dt = jnp.maximum(
                jnp.exp(jax.random.uniform(k_dt, (h,), minval=low, maxval=high)),
                cfg.ssm_dt_floor,
            )
            return {
                "w_in": dense(k_in, (d, d_inner + conv_dim + h), pdt),
                # one filter a channel: fan-in the taps
                "conv_w": jax.nn.initializers.lecun_normal(
                    in_axis=-1, out_axis=-2
                )(k_conv, (conv_dim, cfg.ssm_conv_kernel), pdt),
                "conv_b": jnp.zeros((conv_dim,), pdt),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
                "A_log": jnp.log(jax.random.uniform(
                    k_a, (h,), minval=1.0, maxval=16.0)),
                "D": jnp.ones((h,), jnp.float32),
                "gate_norm": {"scale": jnp.ones((d_inner,), pdt)},
                "w_out": dense(k_out, (d_inner, d), pdt),
            }

        def delta(key):
            """The delta-rule mixer.  Head-decayed (``delta_decay_floor``
            None): ``w_in``'s columns are [q | k | v | z | b | a] and
            ``dt_bias`` is one a VALUE head.  Channel-decayed: ``w_in`` is
            [q | k | v], the decays' projection ``w_decay`` [d, H dk], the
            write strengths' ``w_beta`` and the output gate's ``w_gate``
            [d, H] are their own, and ``dt_bias`` is one a CHANNEL.  In
            both the filters of q, k and v are lecun-normal over their taps
            with no bias; ``A_log`` (a head) is drawn as the state-space
            mixer's and so is the head-decayed ``dt_bias``; the
            channel-decayed ``dt_bias`` puts the bounded gate at rest
            uniformly over its range (float32 whatever the parameters'
            dtype, as the decays' arithmetic is); one output-norm scale of
            a head's value size, shared by the heads (the plain form, scale
            1, whatever ``cfg.norm``)."""
            h = cfg.delta_value_heads or cfg.n_heads
            d_qk, d_v = 2 * cfg.n_heads * cfg.delta_key_dim, h * cfg.delta_value_dim
            k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
            low, high = np.log(cfg.ssm_dt_range)
            channel = cfg.delta_decay_floor is not None
            a = jax.random.uniform(k_a, (h,), minval=1.0, maxval=16.0)
            if channel:
                projections = {
                    "w_in": dense(k_in, (d, d_qk + d_v), pdt),
                    "w_decay": dense(
                        jax.random.fold_in(k_in, 1), (d, d_qk // 2), pdt),
                    "w_beta": dense(jax.random.fold_in(k_in, 2), (d, h), pdt),
                    "w_gate": dense(jax.random.fold_in(k_in, 3), (d, h), pdt),
                }
                # the bounded gate AT REST (f = 0), floor * sigmoid(A dt_bias),
                # uniform over the middle 96 % of (floor, 0) a channel.  The
                # softplus draw below would put sigmoid(A dt_bias) at 1e-7 to
                # 0.4: a gate that hardly decays, under which a rule whose
                # sums are kept in a lower precision reads as the program
                # does (PERF.md section 6, PR 66, after the review)
                rest = jax.random.uniform(
                    k_dt, (h, d_qk // (2 * h)), minval=0.02, maxval=0.98)
                dt_bias = ((jnp.log(rest) - jnp.log1p(-rest)) / a[:, None]).ravel()
            else:
                projections = {
                    "w_in": dense(k_in, (d, d_qk + 2 * d_v + 2 * h), pdt)}
                dt = jnp.exp(jax.random.uniform(
                    k_dt, (h,), minval=low, maxval=high))
                dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
            return {
                **projections,
                "conv_w": jax.nn.initializers.lecun_normal(
                    in_axis=-1, out_axis=-2
                )(k_conv, (d_qk + d_v, cfg.delta_conv_kernel), pdt),
                "dt_bias": dt_bias,
                "A_log": jnp.log(a),
                "gate_norm": {"scale": jnp.ones((cfg.delta_value_dim,), pdt)},
                "w_out": dense(k_out, (d_v, d), pdt),
            }

        def short_conv(key):
            """The gated short convolution: ``w_in``'s columns are [B | C |
            u], the filter lecun-normal over its taps, no bias anywhere."""
            k_in, k_conv, k_out = jax.random.split(key, 3)
            return {
                "w_in": dense(k_in, (d, 3 * d), pdt),
                "conv_w": jax.nn.initializers.lecun_normal(
                    in_axis=-1, out_axis=-2
                )(k_conv, (d, cfg.short_conv_kernel), pdt),
                "w_out": dense(k_out, (d, d), pdt),
            }

        def hyper_connection(key):
            """One part's hyper-connection: ``phi`` [n d, 2 n + n^2] normal
            of deviation ``0.5 / (alpha sqrt(n d))`` with ``alpha`` 0.01 for
            each of the three, so that the input-dependent term ``alpha (u
            phi)`` has deviation 0.5 under the unit-rms ``u`` and moves
            every coefficient by tens of percent; ``b`` [2 n + n^2] (float32
            whatever the parameters' dtype, as ``alpha`` is): the read's
            and the write's logits normal(1), the mixing matrix's 1 on the
            diagonal plus normal(0.3): after its Sinkhorn iterations
            neither the identity nor uniform (the diagonal near 0.4, the
            rest near 0.2), and within 1e-5 of doubly stochastic after 20
            iterations (a wider spread of logits converges more slowly:
            deviations of 1 and 0.5 on a diagonal of 2 leave 3e-2)."""
            n = cfg.hc_streams
            k_phi, k_b, k_res = jax.random.split(key, 3)
            alpha = 0.01
            return {
                "phi": (jax.random.normal(k_phi, (n * d, 2 * n + n * n))
                        * (0.5 / (alpha * np.sqrt(n * d)))).astype(pdt),
                "b": jnp.concatenate([
                    jax.random.normal(k_b, (2 * n,)),
                    (jnp.eye(n) + 0.3 * jax.random.normal(
                        k_res, (n, n))).reshape(-1)]),
                "alpha": jnp.full((3,), alpha, jnp.float32),
            }

        def init_mixer_layer(key, mixer):
            """A layer that is ONE mixer behind ONE norm; what it holds
            says which: ``ssm``, ``wq``.., or ``moe`` (and ``shared``)."""
            ks = jax.random.split(key, 5)
            if mixer == "ssm":
                return {"norm": ln(), "ssm": ssm(ks[0])}
            if mixer == "moe":
                return {"norm": ln(), **mixture(ks[4])}
            return {
                "norm": ln(),
                "wq": dense(ks[0], (d, d_q), pdt),
                "wk": dense(ks[1], (d, d_kv), pdt),
                "wv": dense(ks[2], (d, d_kv), pdt),
                "wo": dense(ks[3], (d_q, d), pdt),
            }

        def init_layer(key, ffn="moe", kind=AttentionLayer()):
            ks = jax.random.split(key, 5)
            if kind.mixer == "delta":  # what the layer holds says so
                attention = {"delta": delta(ks[0])}
            elif kind.mixer == "conv":
                attention = {"conv": short_conv(ks[0])}
            elif cfg.kv_latent_dim is None:
                attention = {
                    # gated: a head's columns are its query's, then its gate's
                    "wq": dense(
                        ks[0],
                        (d, 2 * d_q if cfg.attention_gate is True else d_q), pdt),
                    "wk": dense(ks[1], (d, d_kv), pdt),
                    "wv": dense(ks[2], (d, d_kv), pdt),
                    "wo": dense(ks[3], (d_q, d), pdt),
                }
            else:
                # the latents' down-projections and norms, their
                # expansions: a head's columns of wkv_b are [k_nope | v],
                # its values v_head_dim wide (None: as wide as its keys)
                c_q, c_kv = cfg.q_latent_dim, cfg.kv_latent_dim
                rope, nope = cfg.rope_head_dim, hd - cfg.rope_head_dim
                hd_v = cfg.v_head_dim or hd
                attention = {
                    # no query latent: the queries one plain product
                    **({"wq": dense(ks[0], (d, d_q), pdt)} if c_q is None else {
                        "wq_a": dense(ks[0], (d, c_q), pdt),
                        "q_a_norm": {"scale": jnp.ones((c_q,), pdt)},
                        "wq_b": dense(
                            jax.random.fold_in(ks[0], 1), (c_q, d_q), pdt)}),
                    "wkv_a": dense(ks[1], (d, c_kv + rope), pdt),
                    "kv_a_norm": {"scale": jnp.ones((c_kv,), pdt)},
                    "wkv_b": dense(
                        ks[2], (c_kv, cfg.n_heads * (nope + hd_v)), pdt),
                    "wo": dense(ks[3], (cfg.n_heads * hd_v, d), pdt),
                }
            if cfg.attention_gate == "head" and kind.mixer == "softmax":
                attention["w_gate"] = dense(
                    jax.random.fold_in(ks[3], 1), (d, cfg.n_heads), pdt)
            lp = {"ln1": ln(), **attention, "ln2": ln()}
            if cfg.hc_streams is not None:  # one a part
                lp["hc_attn"] = hyper_connection(jax.random.fold_in(key, 11))
                lp["hc_ffn"] = hyper_connection(jax.random.fold_in(key, 12))
            # the layer's feed-forward part is what its parameters hold:
            # 'ffn' (dense), or 'moe' and beside it 'shared'
            if ffn == "dense":
                lp["ffn"] = dense_block(ks[4], cfg.dense_ffn_dim)
            else:
                lp.update(mixture(ks[4]))
            if cfg.qk_norm and kind.mixer == "softmax":
                per_head = cfg.qk_norm == "head"
                lp["q_norm"] = rms(hd if per_head else d_q)
                lp["k_norm"] = rms(hd if per_head else d_kv)
            return lp

        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        ffn_of = cfg.ffn_pattern or ("moe",) * cfg.n_layers
        params: dict = {
            "embed": embed_init(k_embed, (v, d), pdt),
            **(
                {"pos": embed_init(k_pos, (s, d), pdt)}
                if cfg.positions == "learned" else {}
            ),
            "ln_f": ln(),
            "layers": (
                tuple(map(init_mixer_layer, layer_keys, cfg.mixer_pattern))
                if cfg.mixer_pattern is not None
                else tuple(
                    init_layer(k, f, cfg.attention_layer(i))
                    for i, (k, f) in enumerate(zip(layer_keys, ffn_of)))
            ),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(k_head, (d, v), pdt)
        if cfg.mtp_layers:
            k_mtp = jax.random.fold_in(k_layers, cfg.n_layers)
            params["mtp"] = {
                "e_norm": ln(), "h_norm": ln(),
                "w_eh": dense(jax.random.fold_in(k_mtp, 1), (2 * d, d), pdt),
                "layer": init_layer(k_mtp),
                "out_norm": ln(),
            }
        return jax.device_put(params, self.param_shardings(params))

    def param_shardings(self, params_shape: Params) -> Params:
        """Replicated everywhere except the expert stacks."""
        moe = self.moe.param_shardings() if self.moe is not None else {}
        repl = NamedSharding(self.mesh, P())

        def assign(path, leaf):
            for p in path:
                name = getattr(p, "key", getattr(p, "name", None))
                if name == "moe":
                    inner = path[-1]
                    return moe[getattr(inner, "key", None)]
            return repl

        return jax.tree_util.tree_map_with_path(assign, params_shape)

    # ---- forward ----

    def _norm(self, p, x):
        if self.cfg.norm != "layernorm":
            return rms_norm(p, x, self.cfg.norm_eps)
        return layer_norm(p, x, self.cfg.norm_eps)

    def _qkv(self, lp, x, positions, rotary: bool):
        """Finished q, k, v of a layer whose tokens sit at ``positions``
        [S] (read only where the layer is ``rotary``): what every
        attention core (xla, flash, ring, one-query) takes; fourth, the
        output's gate before its sigmoid, or None where the layer has none
        (``trunk.gated_qkv_projections``: ``wq``'s width says)."""
        how = dict(
            positions=jnp.asarray(positions, jnp.int32) if rotary else None,
            rope_theta=self.cfg.rope_theta, norm_eps=self.cfg.norm_eps,
        )
        if "wkv_a" in lp:
            q, k, v = latent_qkv_projections(
                lp, x, self.cfg.n_heads, **how,
                rope_scaling=self.cfg.rope_scaling)
            return q, k, v, head_gate(lp, x)
        q, k, v, gate = gated_qkv_projections(
            lp, x, self.cfg.n_heads, rotary_dim=self.cfg.rotary_dim, **how)
        return q, k, v, head_gate(lp, x) if gate is None else gate

    def _layer(self, lp, x, layer_idx, token_mask, kind: AttentionLayer):
        """One block.  ``kind`` (static) is the layer's attention,
        ``cfg.attention_layer(layer_idx)``.  Returns ``(x, aux)``; ``aux``
        is None for a layer that routes nothing and counts nothing."""
        one_mixer = "norm" in lp  # what the layer holds says what it is
        if "ssm" in lp:
            return self._ssm_block(lp, x)
        if one_mixer and "moe" in lp:
            return self._ffn_block(lp, x, None, layer_idx, token_mask)
        x, attn_in, extremes = self._mixer_part(lp, x, kind)
        if one_mixer:
            return x, None
        x, aux = self._ffn_block(lp, x, attn_in, layer_idx, token_mask)
        both = "hc_res_marginal_error"
        if both in extremes:  # the layer's two parts' larger
            extremes[both] = jnp.maximum(extremes[both], aux[both])
        return x, {**(aux or {}), **extremes} or None

    def _folded_layer(self, lp, x, layer_idx, token_mask, kind: AttentionLayer):
        """:meth:`_layer` as the stack under ``hc_streams`` calls it: the
        streams come FOLDED (:meth:`_hc_fold`; ONE stream [B, S, d], the
        embedding or the prediction block's combine, as it is) and go
        folded, so the fold is what remat keeps of a layer: an array kept
        across the backward pass is written as it is shaped."""
        x, aux = self._layer(lp, x, layer_idx, token_mask, kind)
        return self._hc_fold(x), aux

    @staticmethod
    def _hc_fold(x):
        """The streams [B, S, n, d] as ONE row a token, [B, S, n d]: the
        stream kernels' own tiling (``ops/stream_mix.py``), which [B, S, n,
        d] with its few streams second to last is not.  This and
        :meth:`_hc_unfold` are the layout's one place: what is handed from
        program to program or kept across the backward pass is the fold,
        and :meth:`_hc_read` takes it."""
        return x.reshape(*x.shape[:2], -1)

    def _hc_unfold(self, x):
        """The fold [B, S, n d] as the streams [B, S, n, d]."""
        return x.reshape(*x.shape[:2], self.cfg.hc_streams, self.cfg.d_model)

    def _part_input(self, norm_p, x):
        """What a part of a layer reads: the normalized stream, or the
        stream as it is where the norm is on the part's output."""
        return self._norm(norm_p, x) if self.cfg.norm_place == "input" else x

    def _part_output(self, norm_p, out):
        """What a part of a layer adds to the stream: what it gave, or its
        norm where the norm is on the part's output."""
        return out if self.cfg.norm_place == "input" else self._norm(norm_p, out)

    def _add_part(self, norm_p, x, out):
        """The stream after a part gave ``out``: ``x + out``, or ``x +
        norm(out)`` where the norm is on the part's output."""
        return x + self._part_output(norm_p, out)

    def _hc_read(self, hc, x):
        """The streams a part mixes, what it reads of them and what its
        write needs: the stream itself, twice, and None where it is ONE
        stream (``hc`` None: the layer holds no hyper-connection), else the
        streams [B, S, n, d], ``sum_j pre[j] x[:, :, j]`` and ``(post,
        res, res's marginal error)`` (``trunk.hc_coefficients``).  ``x`` is
        the streams [B, S, n, d], their fold [B, S, n d] (:meth:`_hc_fold`:
        what the stack and the set-up hand on) or ONE stream [B, S, d],
        which is copied to the ``n``.  Every part of every layer passes
        through this and :meth:`_hc_write`: the stack's, the prediction
        block's, and the set-up's (``level_router_bias``)."""
        if hc is None:
            return x, x, None
        cfg = self.cfg
        if x.ndim == 4:
            x = self._hc_fold(x)
        elif x.shape[-1] == cfg.d_model:  # ONE stream comes in: the
            # stack's first layer's embedding, the block's combine.  Copied
            # HERE, inside the layer's checkpoint, so what remat keeps of
            # it is one stream (a fold is ``n`` times as wide a row)
            x = self._hc_fold(self._hc_copy(x))

        # the coefficients, the read and the write each unfold the streams
        # for themselves out of the ONE fold: autodiff then adds their
        # three gradients in the fold, a bitcast of what the kernels give
        # and take; added as [B, S, n, d] the sum and each of its terms is
        # copied to another tiling, six passes a part (PERF.md section 6,
        # PR 65).  The unfolds are named hc/pre: the sum is made where they
        # are transposed, XLA fuses the read's own gradient ``pre[j] dh``
        # into it, and the pass is the mixing's (xing4.hc_mix_share)
        def unfolded():
            with jax.named_scope("hc"), jax.named_scope("pre"):
                return self._hc_unfold(x)

        pre, *write = hc_coefficients(
            hc, unfolded(), cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp, cfg.norm_eps)
        return unfolded(), hc_pre(unfolded(), pre), write

    @staticmethod
    def _hc_write(x, out, write):
        """The streams ``x`` after the part gave ``out`` [B, S, d]."""
        post, res, _ = write
        return hc_post(x, out, post, res)

    def _delta_block(self, lp, x):
        """The stream after the layer's delta-rule mixer, what the mixer
        read, and the two extremes the step's metrics keep."""
        cfg = self.cfg
        with jax.named_scope("delta"):
            mixer_in = self._part_input(lp["ln1"], x)
            out, _, decay_min, beta_max = delta_mixer(
                lp["delta"], mixer_in, cfg.n_heads, cfg.delta_chunk,
                cfg.norm_eps, neg_eigval=cfg.delta_neg_eigval,
                decay_floor=cfg.delta_decay_floor,
            )
            x = self._add_part(lp["ln1"], x, out)
        return x, mixer_in, {
            "delta_decay_min": decay_min, "delta_beta_max": beta_max}

    def _ssm_block(self, lp, x):
        """``x + Mixer(norm(x))``, the Mamba-2 mixer; ``aux`` is the
        smallest decay the layer saw."""
        cfg = self.cfg
        with jax.named_scope("ssm"):
            out, _, decay_min = ssm_mixer(
                lp["ssm"], self._norm(lp["norm"], x), cfg.ssm_heads,
                cfg.ssm_groups, cfg.ssm_chunk, cfg.norm_eps,
            )
        return x + out, {"ssm_decay_min": decay_min}

    def _shortconv_block(self, lp, x):
        """The stream after the layer's gated short convolution, what the
        mixer read, and the rms of what it gave (a dead gate reads 0)."""
        with jax.named_scope("shortconv"):
            mixer_in = self._norm(lp["ln1"], x)
            out = short_conv_mixer(lp["conv"], mixer_in)
            rms = jnp.sqrt(jnp.mean(jnp.square(out.astype(jnp.float32))))
            x = x + out
        return x, mixer_in, {"shortconv_out_rms": rms}

    def _mixer_part(self, lp, x, kind: AttentionLayer):
        """The stream after the layer's token mixer, which its parameters
        name (``delta``, ``conv``, or softmax attention's projections), the
        input the mixer read and what the step's metrics keep of it."""
        if "delta" in lp:
            return self._delta_block(lp, x)
        if "conv" in lp:
            return self._shortconv_block(lp, x)
        return self._attention_part(lp, x, kind)

    def _attention_block(self, lp, x, kind: AttentionLayer):
        """The stream after the layer's attention (or the mixer in its
        place), and the input it read (a router placed before it reads
        that)."""
        return self._mixer_part(lp, x, kind)[:2]

    def _attention_part(self, lp, x, kind: AttentionLayer):
        """:meth:`_attention_block` and, third, what the step's metrics
        keep of the layer: the mean of a gated output's gate."""
        s = x.shape[1]
        # where a stack has both kinds, the scope says which this one is
        scope = "attention" if self.cfg.layer_pattern is None else (
            "attention/global" if kind.window is None else "attention/window"
        )
        streams, x, write = self._hc_read(lp.get("hc_attn"), x)
        with jax.named_scope(scope):
            # a layer of one mixer has ONE norm
            norm_p = lp["ln1" if "ln1" in lp else "norm"]
            with jax.named_scope("norm"):
                attn_in = self._part_input(norm_p, x)
            core = self._ring if self._ring is not None else (
                lambda q, k, v: attention_core(
                    q, k, v, self.attn_impl, kind.window,
                    self.cfg.diffusion_block if self._diffusion else None,
                    self._attn_scale,
                )
            )
            if self._diffusion:  # the doubled row: both copies at 0..s/2-1
                positions = np.arange(s) % (s // 2)
            else:
                # under the zigzag ring the stream is in zigzag order
                positions = np.arange(s) if self._zig is None else self._zig
            q, k, v, gate = self._qkv(lp, attn_in, positions, kind.rotary)
            out = output_projection(lp, core(q, k, v), gate)
            extremes = {}
            if gate is not None:
                with jax.named_scope("gate"):
                    extremes["attention_gate_mean"] = jnp.mean(
                        jax.nn.sigmoid(gate.astype(jnp.float32)))
            with jax.named_scope("norm"):  # the norm, wherever it is placed
                out = self._part_output(norm_p, out)
            if write is None:
                x = x + out  # directly under the attention scope
        if write is not None:
            x = self._hc_write(streams, out, write)
            extremes["hc_res_marginal_error"] = write[2]
        return x, attn_in, extremes

    @staticmethod
    def _ffn_norm(lp):
        """The norm the feed-forward part reads through: the layer's
        second, or its ONE where the layer is the mixture alone."""
        return lp["ln2" if "ln2" in lp else "norm"]

    def _ffn_block(self, lp, x, attn_in, layer_idx, token_mask=None):
        """The layer's feed-forward part, which its parameters name: one
        dense gated block (``ffn``: no router, ``aux`` None), or the
        mixture (``moe``) and beside it the shared expert (``shared``)."""
        streams, x, write = self._hc_read(lp.get("hc_ffn"), x)
        b, s, d = x.shape
        norm_p = self._ffn_norm(lp)
        ffn_in = self._part_input(norm_p, x)
        if "ffn" in lp:
            with jax.named_scope("dense_ffn"):
                out = gated_mlp(lp["ffn"], ffn_in, self._gate_act)
                if write is None:
                    return self._add_part(norm_p, x, out), None
            return self._hc_write(streams, out, write), {
                "hc_res_marginal_error": write[2]}
        moe_in = ffn_in.reshape(b * s, d)
        # layer index salts the router jitter: decorrelates the
        # deterministic noise pattern across layers (round-2 advisor)
        moe_out, aux = self.moe(
            lp["moe"], moe_in, jitter_salt=layer_idx,
            token_mask=None if token_mask is None else token_mask.reshape(b * s),
            router_x=(
                attn_in.reshape(b * s, d)
                if self.cfg.router_input == "attention_input" else None
            ),
        )
        if write is not None:  # the part's sum, then ONE write
            out = moe_out.reshape(b, s, d)
            if "shared" in lp:
                with jax.named_scope("shared_expert"):
                    out = out + gated_mlp(lp["shared"], ffn_in, self._gate_act)
            return self._hc_write(streams, out, write), {
                **aux, "hc_res_marginal_error": write[2]}
        if self.cfg.norm_place == "input":
            x = x + moe_out.reshape(b, s, d)
            if "shared_gate" in lp:
                with jax.named_scope("shared_expert"):
                    shared = gated_mlp(lp["shared"], ffn_in, self._gate_act)
                    with jax.named_scope("shared_gate"):
                        # one number a token, float32 as the router's are
                        gate = jax.nn.sigmoid(jnp.einsum(
                            "bsd,dn->bsn", ffn_in,
                            lp["shared_gate"].astype(ffn_in.dtype),
                            preferred_element_type=jnp.float32))
                        x = x + (gate * shared.astype(jnp.float32)).astype(x.dtype)
                        aux = {**aux, "shared_gate_mean": jnp.mean(gate)}
            elif "shared" in lp:
                with jax.named_scope("shared_expert"):
                    x = x + gated_mlp(lp["shared"], ffn_in, self._gate_act)
            return x, aux
        out = moe_out.reshape(b, s, d)  # ONE norm over all the part gave
        if "shared" in lp:
            with jax.named_scope("shared_expert"):
                out = out + gated_mlp(lp["shared"], ffn_in, self._gate_act)
        return x + self._norm(norm_p, out), aux

    def _hidden(
        self, params: Params, token_ids: jax.Array,
        token_mask: jax.Array | None = None,
        next_ids: jax.Array | None = None,
    ) -> tuple:
        """token_ids [B, S] → final-LN hidden states [B, S, d]; aux scalars.
        Under ``objective='block_diffusion'`` the row is the doubled one,
        ``[x_t | x_0]`` (:meth:`noised_row`), and so is the stream.

        With ``next_ids`` [B, S] (each position's next token: a training
        row's targets; only where the config has the next-but-one-token
        block) a second stream comes between the two: ``(x, x_mtp, aux)``,
        ``x_mtp`` [B, S, d] the block's normalized output (:meth:`_mtp`),
        for the same head; ``aux`` then takes in the block's router
        (the means over one more mixture layer, ``expert_counts`` one more
        row, the last).

        ``token_mask`` [B, S] bool (optional, traced): False marks padding
        positions that must not participate in MoE routing (they claim no
        expert capacity and receive zero MoE output) — used by
        :meth:`generate` so a row's right-padding cannot evict other rows'
        real tokens from expert slots.  Attention needs no mask: causality
        already keeps real positions from attending to future padding."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"][token_ids].astype(cfg.dtype)
            if cfg.positions == "learned":
                x = x + params["pos"][
                    None, : token_ids.shape[1]
                ].astype(cfg.dtype)
        layer_fn = self._layer if cfg.hc_streams is None else self._folded_layer
        if cfg.remat:
            # kind is static: a window or a rotation is part of the program.
            # Kept across the backward pass: the blocked attention
            # kernel's output and row sums, which its backward kernels
            # read, so the recompute holds no forward kernel call (a
            # layer's 68-273 MB against 3-32 ms: PERF.md section 6, PR
            # 38), and the scan kernel's output and entering states
            # (ops/ssd.py; PR 40), and the delta rule's kernel's output
            # and the ONE state entering each of its grid steps, from
            # which its backward kernel rebuilds the chunks' in VMEM
            # (ops/delta_rule.py; a layer's 268-331 MB against a forward
            # call of 8-10 ms: PERF.md section 6, PR 58); and the results
            # of the attention part's matrix products, ``x @ wq``, ``x @
            # wk``, ``x @ wv`` and ``out @ wo`` (trunk.ATTENTION_PRODUCTS;
            # PR 53), which the backward pass reads (the queries' and
            # keys' norm, the kernel, the feed-forward part behind the
            # add) and would otherwise multiply a second time: bf16 [B, S,
            # q + 2 kv + d] a layer, 537 MB in k-exaone for 28 ms (PERF.md
            # section 6, PR 53).  A layer on the xla core keeps its
            # products too; and a conv mixer's gated convolution, which
            # its out-projection's backward reads (trunk.SHORT_CONV_RESULT;
            # PR 61); everything else (norms, the rotation, the mixers' and
            # the experts' products, the dense blocks) is recomputed
            # ... but under hc_streams what a layer keeps is n streams (470
            # MB at 16,384 tokens of 3,584), and the products' 161 MB a
            # layer are what does not fit beside them: they are run again
            # (PERF.md section 6, PR 64)
            kept = (FLASH_RESIDUALS, SSD_RESIDUALS, DELTA_RESIDUALS,
                    ATTENTION_PRODUCTS, SHORT_CONV_RESULT)
            if cfg.hc_streams is not None:
                kept = tuple(n for n in kept if n != ATTENTION_PRODUCTS)
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(4,),
                policy=jax.checkpoint_policies.save_only_these_names(*kept),
            )

        if self._zig is not None:
            if token_ids.shape[1] != len(self._zig):
                raise ValueError(
                    f"zigzag layout was built for seq_len {len(self._zig)}, "
                    f"got {token_ids.shape[1]} — the pre-permuted ring would "
                    "silently misattend on other lengths"
                )
            # zigzag sequence layout for the whole layer stack: attention
            # consumes it natively; MoE and norms are per-token (order-
            # independent); positions were already added above
            x = x[:, self._zig]
            if token_mask is not None:
                token_mask = token_mask[:, self._zig]
        aux_total = None
        counts = []
        extremes: dict = {}  # a recurrent layer's least decay, .., each

        def add(aux):
            """A mixture layer's aux into the stack's sums; its
            assignments per expert stay a row of their own.  What a
            recurrent layer reports (``_EXTREMES``) is kept a layer each."""
            nonlocal aux_total
            aux = dict(aux)
            for name in _EXTREMES:
                if name in aux:
                    extremes.setdefault(name, []).append(aux.pop(name))
            if "expert_counts" in aux:
                counts.append(aux.pop("expert_counts"))
            if aux:
                aux_total = (
                    aux
                    if aux_total is None
                    else {k: aux_total[k] + aux[k] for k in aux_total}
                )

        for i in range(cfg.n_layers):
            with jax.named_scope(f"layer_{i}"):
                x, aux = layer_fn(
                    params["layers"][i], x, i, token_mask,
                    cfg.attention_layer(i),
                )
            if aux is not None:  # a dense layer routes nothing
                add(aux)
        if self._zig is not None:
            x = x[:, self._zig_inv]
        if cfg.hc_streams is not None:
            x, spread = self._hc_sum(self._hc_unfold(x))
            extremes["hc_stream_rms_spread"] = [spread]
        x = self._norm(params["ln_f"], x)
        n_moe = cfg.mixture_layers()
        if next_ids is not None:
            with jax.named_scope("mtp"):
                x_mtp, aux = self._mtp(
                    params["mtp"], x, next_ids, params["embed"], layer_fn,
                    token_mask,
                )
            add(aux)
            n_moe += 1
        # a stack with no mixture layer has no router sums at all
        aux_mean = {k: v / n_moe for k, v in (aux_total or {}).items()}
        if cfg.router_bias:  # [mixture layers, E]: the balancing rule's
            aux_mean["expert_counts"] = jnp.stack(counts)
        for name, values in extremes.items():
            # over the layers: the smallest decay the step saw (neither
            # frozen at 1 nor forgetting everything), the largest write
            aux_mean[name] = _EXTREMES[name](jnp.stack(values))
        if next_ids is not None:
            return x, x_mtp, aux_mean
        return x, aux_mean

    def _hc_copy(self, x):
        """The stream [B, S, d] copied to the ``hc_streams`` streams [B, S,
        n, d] the layers hand on.  Written as the FOLD's copy, ``[x | x |
        ..]`` [B, S, n d], unfolded: what reads the streams folds them
        again (:meth:`_hc_read`), and the copy's transpose is then a sum of
        the fold's ``n`` lane-aligned slices; as a broadcast to [B, S, n,
        d] the gradient's terms were each copied to that shape's tiling
        before the sum (PERF.md section 6, PR 65)."""
        n = self.cfg.hc_streams
        with jax.named_scope("hc"), jax.named_scope("copy"):
            return jnp.concatenate([x] * n, axis=-1).reshape(
                *x.shape[:2], n, x.shape[2])

    @staticmethod
    def _hc_sum(x):
        """The streams [B, S, n, d] summed (float32, rounded to their
        dtype) for the final norm, and the largest over the smallest rms of
        the streams that entered the sum."""
        with jax.named_scope("hc"), jax.named_scope("sum"):
            x32 = x.astype(jnp.float32)
            ms = jnp.mean(x32 * x32, axis=(0, 1, 3))
            return (x32.sum(axis=2).astype(x.dtype),
                    jnp.sqrt(jnp.max(ms) / jnp.min(ms)))

    def _mtp_input(self, mp, h, next_ids, embed):
        """``[rms(embed[next]) ; rms(h)] W_eh``: what the block's layer
        reads, the embedding's half first."""
        with jax.named_scope("combine"):
            e = embed[next_ids].astype(self.cfg.dtype)
            return jnp.concatenate(
                [self._norm(mp["e_norm"], e), self._norm(mp["h_norm"], h)],
                axis=-1,
            ) @ mp["w_eh"].astype(self.cfg.dtype)

    def _mtp(self, mp, h, next_ids, embed, layer_fn, token_mask=None):
        """The next-but-one-token block on the stack's final normalized
        stream ``h`` [B, S, d] and each position's next token: ``z =
        [rms(embed[next]) ; rms(h)] W_eh`` (scope ``combine``), one more
        layer of the model's own kind (``layer_0``: ``layer_fn``, the
        stack's, under remat where it is; under ``hc_streams`` the combine
        is copied to the streams, the layer mixes them through
        hyper-connections of its own, and they are summed), the block's own
        final norm.
        Returns the normalized stream for the model's head and the layer's
        ``aux``."""
        cfg = self.cfg
        z = self._mtp_input(mp, h, next_ids, embed)
        with jax.named_scope("layer_0"):
            z, aux = layer_fn(
                mp["layer"], z, cfg.n_layers, token_mask,
                cfg.attention_layer(cfg.n_layers),
            )
        if cfg.hc_streams is not None:  # hyper-connections of the block's own
            z, spread = self._hc_sum(self._hc_unfold(z))
            aux = {**aux, "hc_stream_rms_spread": spread}
        return self._norm(mp["out_norm"], z), aux

    def _head(self, params: Params) -> jax.Array:
        # compute dtype (bf16 on TPU), NOT f32: the MXU runs bf16 operands
        # at full rate with f32 accumulation (preferred_element_type at
        # the logits matmul); an f32 operand forces the slow multi-pass
        # path — measured as the dominant cost of the chunked CE.
        return (
            params["embed"].T
            if self.cfg.tie_embeddings
            else params["lm_head"]
        ).astype(self.cfg.dtype)

    @staticmethod
    def _logits(x: jax.Array, head: jax.Array) -> jax.Array:
        return jnp.einsum(
            "...d,dv->...v", x, head, preferred_element_type=jnp.float32
        )

    def apply(
        self, params: Params, token_ids: jax.Array,
        token_mask: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        """token_ids [B, S] → logits [B, S, V] (f32); aux dict of scalars.
        ``token_mask``: see :meth:`_hidden` (padding-vs-routing)."""
        x, aux_mean = self._hidden(params, token_ids, token_mask)
        return self._logits(x, self._head(params)), aux_mean

    # ---- autoregressive decoding ----

    def decode_model(self) -> "DMoETransformerLM":
        """The model to EVALUATE/DECODE with: identical weights, routing on
        clean gates.  ``router_jitter`` is a training-only regularizer
        (selection noise), so a jittered model decodes through a twin with
        jitter 0; a clean model is its own decode model.

        Memoized: repeated ``generate()`` calls must reuse the same twin
        (and hence its compiled-decoder cache).
        """
        if not self.cfg.router_jitter:
            return self
        if self._decode_model is None:
            self._decode_model = DMoETransformerLM(
                dataclasses.replace(self.cfg, router_jitter=0.0), self.mesh
            )
        return self._decode_model

    def generate(
        self,
        params: Params,
        prompt_ids: jax.Array,
        max_new_tokens: int,
        temperature: float = 0.0,
        rng: jax.Array | None = None,
        use_cache: bool = False,
    ) -> jax.Array:
        """Greedy (or temperature-sampled) autoregressive decoding.

        prompt_ids: [B, P] int32 with P + max_new_tokens <= seq_len.
        Returns [B, P + max_new_tokens].  Each step re-runs the full
        forward over the fixed-length buffer (static shapes for XLA) —
        the straightforward eval path, not a KV-cache serving stack.
        Routing follows :meth:`decode_model` (no jitter).

        Right-padding is masked out of MoE routing via ``token_mask``:
        causality makes padding inert for *attention*, but capacity
        routing is cross-token (slot claims are token-order over the
        flattened [B*S] buffer), so unmasked padding from earlier rows
        could exhaust expert capacity ahead of later rows' real tokens
        and decode output would silently depend on padding occupancy
        (round-3 advisor finding).

        ``use_cache=True`` switches to the incremental KV-cache decoder
        (:meth:`_generate_cached`): O(S·d) per new token instead of the
        full O(S²·d) re-forward.  Routing note: each decode step routes
        only the B live tokens (per-step capacity), whereas the
        re-forward path routes the whole masked buffer — identical
        whenever capacity never binds (generous ``capacity_factor``),
        and the per-step regime is what a serving stack does anyway.
        """
        if self._diffusion:
            raise NotImplementedError(
                "generate() with objective='block_diffusion': the model "
                "generates a block at a time (denoising steps that each "
                "yield several tokens a sequence, against a cache of the "
                "finished blocks), which is not built; this decoder yields "
                "one token a step under a causal mask"
            )
        b, p = prompt_ids.shape
        s = self.cfg.seq_len
        if p == 0:
            raise ValueError(
                "prompt must have at least one token (p=0 would wrap the "
                "first write to the end of the decode buffer)"
            )
        if p + max_new_tokens > s:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"seq_len {s}"
            )
        if max_new_tokens < 0:
            # almost certainly caller arithmetic gone negative (e.g. a
            # token budget minus the prompt length) — refuse loudly
            raise ValueError(
                f"max_new_tokens must be >= 0, got {max_new_tokens}"
            )
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if temperature > 0 and rng is None:
            raise ValueError("temperature > 0 requires an rng key")
        if max_new_tokens == 0:
            # nothing to decode (validation above still applies); the
            # cached path would otherwise allocate a (b, 0) output
            # buffer and fail at trace time on .at[:, 0]
            return prompt_ids
        if use_cache:
            if self.cfg.hc_streams is not None:
                raise NotImplementedError(
                    "use_cache=True with hc_streams: the KV-cache decoder "
                    "hands ONE residual stream from layer to layer; several "
                    "streams and their mixing a token a part are not built "
                    "in it; decode without the cache"
                )
            if self._conv:
                raise NotImplementedError(
                    "use_cache=True with a 'conv' layer: the KV-cache "
                    "decoder runs softmax attention in every layer; a conv "
                    "layer's cache (the last short_conv_kernel - 1 positions "
                    "of B * u a layer) beside the KV cache is not built; "
                    "decode without the cache"
                )
            if self._delta or self.cfg.norm_place != "input":
                raise NotImplementedError(
                    "use_cache=True with a 'delta' layer or a norm on a "
                    "part's output (norm_place): the KV-cache decoder runs "
                    "softmax attention behind a norm on its input in every "
                    "layer; a delta layer's recurrent state (and its "
                    "convolutions' last inputs) beside the KV cache is not "
                    "built; decode without the cache"
                )
            if self.cfg.mixer_pattern is not None:
                raise NotImplementedError(
                    "use_cache=True with mixer_pattern: the KV-cache "
                    "decoder runs an attention block and a mixture in "
                    "every layer; a state-space layer's recurrent state "
                    "(and its convolution's last inputs) beside the KV "
                    "cache is not built; decode without the cache"
                )
            if (
                self.cfg.attention_gate or self.cfg.rotary_dim is not None
                or self.cfg.shared_expert_gate
            ):
                raise NotImplementedError(
                    "use_cache=True with attention_gate, rotary_dim or "
                    "shared_expert_gate: the KV-cache decoder's attention "
                    "block multiplies no gate onto its output and rotates "
                    "whole heads, and its feed-forward part has no gated "
                    "shared expert; decode without the cache"
                )
            if self.cfg.kv_latent_dim is not None or self.cfg.mtp_layers:
                raise NotImplementedError(
                    "use_cache=True: the KV-cache decoder keeps whole keys "
                    "and values a head and samples from the stack's head "
                    "alone: no latent attention (a cache of the latent and "
                    "the absorbed one-query form are not built) and no "
                    "next-but-one-token block (mtp_layers); decode without "
                    "the cache"
                )
            if (
                self._grouped_or_windowed or self._other_ffn
                or self.cfg.router_input != "moe_input"
            ):
                raise NotImplementedError(
                    "use_cache=True: the KV-cache decoder keeps one "
                    "key/value head a query head, masks by position alone, "
                    "routes on the experts' input and runs a mixture that "
                    "holds its experts in every layer: no grouped "
                    "key/value heads, no window, no router on the "
                    "attention's input, no dense layer, shared expert or "
                    "share of the experts; decode without the cache"
                )
            if self.cfg.seq_parallel:
                raise NotImplementedError(
                    "use_cache=True does not compose with seq_parallel "
                    "(the cache is not ring-sharded); decode on a "
                    "non-seq-parallel mesh"
                )
            from learning_at_home_tpu.parallel.mesh import data_axes

            n_shards = 1
            for a in data_axes(self.mesh):
                n_shards *= self.mesh.shape[a]
            if b % n_shards or (b * p) % n_shards:
                raise ValueError(
                    f"use_cache=True routes B={b} rows per decode step and "
                    f"B*P={b * p} in prefill, which must divide the mesh's "
                    f"{n_shards} token shards — grow the batch or decode "
                    "without the cache (the re-forward path routes the "
                    "whole buffer and is immune)"
                )
        model = self.decode_model()
        # one compiled decoder per path, cached on the decode twin; jit's
        # own shape/static keying handles (b, p, max_new_tokens,
        # temperature) variation.  Eager tracing of the whole decode loop
        # cost 17.1 s where the compiled call takes 0.07 s (60 tokens,
        # seq 1024, CPU).
        fn = model._gen_jit.get(use_cache)
        if fn is None:
            fn = jax.jit(
                model._generate_cached if use_cache else model._generate_full,
                static_argnums=(2, 3),  # max_new_tokens, temperature
            )
            model._gen_jit[use_cache] = fn
        if rng is None:
            rng = jax.random.PRNGKey(0)  # unused at temperature == 0
        return fn(params, prompt_ids, max_new_tokens, float(temperature), rng)

    def _generate_full(
        self,
        params: Params,
        prompt_ids: jax.Array,
        max_new_tokens: int,
        temperature: float,
        rng: jax.Array,
    ) -> jax.Array:
        """Re-forward decoding: every step runs the full masked forward
        over the fixed-length buffer.  Simple and exactly the training
        graph; O(S²·d) per token — prefer ``use_cache=True`` for long
        buffers."""
        b, p = prompt_ids.shape
        s = self.cfg.seq_len
        buf = jnp.zeros((b, s), prompt_ids.dtype).at[:, :p].set(prompt_ids)

        def step(carry, t):
            buf, rng = carry
            # positions <= t hold real tokens this step; the rest is
            # padding and must not compete for expert capacity
            valid = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :] <= t, buf.shape
            )
            logits, _ = self.apply(params, buf, token_mask=valid)
            step_logits = jax.lax.dynamic_index_in_dim(
                logits, t, axis=1, keepdims=False
            )  # [B, V]
            rng, sub = jax.random.split(rng)
            if temperature > 0:  # static: resolved at trace time
                nxt = jax.random.categorical(sub, step_logits / temperature)
            else:
                nxt = jnp.argmax(step_logits, axis=-1)
            nxt = nxt.astype(buf.dtype)
            # all rows write the same column t+1 (static bound covers the
            # scan length; writes are always in range here)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, nxt[:, None], t + 1, axis=1
            )
            return (buf, rng), None

        (buf, _), _ = jax.lax.scan(
            step,
            (buf, rng),
            jnp.arange(p - 1, p - 1 + max_new_tokens, dtype=jnp.int32),
        )
        return buf[:, : p + max_new_tokens]

    # ---- incremental (KV-cache) decoding ----

    @staticmethod
    def _one_query_attention(
        lp, q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, t: jax.Array
    ) -> jax.Array:
        """Attention for ONE query position over the cache — the shared
        :func:`~learning_at_home_tpu.models.trunk.one_query_attention`
        (the swarm KV decoder uses the same function with per-row ``t``,
        so pod and gateway decode steps cannot drift numerically)."""
        return one_query_attention(lp, q, k_cache, v_cache, t)

    def _generate_cached(
        self,
        params: Params,
        prompt_ids: jax.Array,
        max_new_tokens: int,
        temperature: float,
        rng: jax.Array | None,
    ) -> jax.Array:
        """Incremental decode: prefill the KV cache on the prompt, then
        one O(S·d) step per new token.  Called via
        ``generate(use_cache=True)`` on the :meth:`decode_model` (this
        instance already has eval-safe routing)."""
        cfg = self.cfg
        b, p = prompt_ids.shape
        s_cache = p + max_new_tokens
        hd = cfg.head_dim or cfg.d_model // cfg.n_heads
        if rng is None:
            rng = jax.random.PRNGKey(0)  # unused at temperature == 0

        def sample(logits_1d, key):  # [B, V] -> [B]
            if temperature > 0:  # static: resolved at trace time
                return jax.random.categorical(key, logits_1d / temperature)
            return jnp.argmax(logits_1d, axis=-1)

        # ---- prefill: full forward over the prompt, caches filled ----
        x = params["embed"][prompt_ids].astype(cfg.dtype)
        if cfg.positions == "learned":
            x = x + params["pos"][None, :p].astype(cfg.dtype)
        k_caches, v_caches = [], []
        for i in range(cfg.n_layers):
            lp = params["layers"][i]
            h = self._norm(lp["ln1"], x)
            q, k, v, _ = self._qkv(
                lp, h, np.arange(p), cfg.attention_layer(i).rotary
            )
            # same impl as the full forward: the parity guarantee vs the
            # re-forward decoder must survive flash-attention configs
            x = x + output_projection(
                lp, attention_core(q, k, v, self.attn_impl)
            )
            moe_in = self._norm(lp["ln2"], x).reshape(b * p, cfg.d_model)
            moe_out, _ = self.moe(lp["moe"], moe_in, jitter_salt=i)
            x = x + moe_out.reshape(b, p, cfg.d_model)
            kc = jnp.zeros((b, s_cache, cfg.n_heads, hd), k.dtype)
            vc = jnp.zeros_like(kc)
            k_caches.append(jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0)))
            v_caches.append(jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0)))
        x_last = self._norm(params["ln_f"], x[:, -1:])
        logits = self._logits(x_last, self._head(params))[:, 0]  # [B, V]
        rng, sub = jax.random.split(rng)
        next_tok = sample(logits, sub).astype(prompt_ids.dtype)

        out_buf = (
            jnp.zeros((b, max_new_tokens), prompt_ids.dtype)
            .at[:, 0].set(next_tok)
        )

        # ---- decode: one position per step, caches appended in place.
        # Caches stay a TUPLE of per-layer arrays (scan carry leaves): a
        # stacked [L, ...] cache would need .at[i].set, which copies the
        # whole stack per layer per step — measured 2x slower end-to-end.
        def step(carry, t):
            k_caches, v_caches, tok, out_buf, rng = carry
            x = params["embed"][tok].astype(cfg.dtype)  # [B, d]
            if cfg.positions == "learned":
                x = x + jnp.take(
                    params["pos"].astype(cfg.dtype), t, axis=0
                )[None, :]
            x = x[:, None, :]  # [B, 1, d]
            k_caches, v_caches = list(k_caches), list(v_caches)
            for i in range(cfg.n_layers):
                lp = params["layers"][i]
                h = self._norm(lp["ln1"], x)
                q, k, v, _ = self._qkv(
                    lp, h, t[None], cfg.attention_layer(i).rotary
                )
                k_caches[i] = jax.lax.dynamic_update_slice(
                    k_caches[i], k, (0, t, 0, 0)
                )
                v_caches[i] = jax.lax.dynamic_update_slice(
                    v_caches[i], v, (0, t, 0, 0)
                )
                x = x + self._one_query_attention(
                    lp, q, k_caches[i], v_caches[i], t
                )
                moe_in = self._norm(lp["ln2"], x).reshape(b, cfg.d_model)
                moe_out, _ = self.moe(lp["moe"], moe_in, jitter_salt=i)
                x = x + moe_out.reshape(b, 1, cfg.d_model)
            x = self._norm(params["ln_f"], x)
            logits = self._logits(x, self._head(params))[:, 0]
            rng, sub = jax.random.split(rng)
            nxt = sample(logits, sub).astype(tok.dtype)
            out_buf = jax.lax.dynamic_update_slice_in_dim(
                out_buf, nxt[:, None], t - p + 1, axis=1
            )
            return (
                tuple(k_caches), tuple(v_caches), nxt, out_buf, rng
            ), None

        if max_new_tokens > 1:
            (_, _, _, out_buf, _), _ = jax.lax.scan(
                step,
                (tuple(k_caches), tuple(v_caches), next_tok, out_buf, rng),
                jnp.arange(p, p + max_new_tokens - 1, dtype=jnp.int32),
            )
        return jnp.concatenate([prompt_ids, out_buf], axis=1)

    # ---- loss / train step ----

    def noise_draws(self, key: jax.Array, batch: int) -> tuple:
        """The uniform draws block diffusion's noising reads: ``u``
        [batch, seq_len], one a token, and ``t`` [batch, seq_len /
        diffusion_block], one a block."""
        token_key, block_key = jax.random.split(key)
        s = self.cfg.seq_len
        return (
            jax.random.uniform(token_key, (batch, s), jnp.float32),
            jax.random.uniform(
                block_key, (batch, s // self.cfg.diffusion_block), jnp.float32),
        )

    def noised_row(self, token_ids: jax.Array, u: jax.Array, t: jax.Array):
        """The stack's row and the loss's weights from ``token_ids`` [B, S]
        and the draws of :meth:`noise_draws`: a block's ``p = floor + (1 -
        floor) t``; a token is replaced by the mask id where its ``u < p``.
        Returns ``[x_t | x_0]`` [B, 2 S] and the weights [B, S], ``1 / p``
        at a masked position and 0 elsewhere."""
        cfg = self.cfg
        floor = DIFFUSION_P_FLOOR
        p = jnp.repeat(floor + (1.0 - floor) * t, cfg.diffusion_block, axis=1)
        masked = u < p
        mask_id = jnp.asarray(cfg.vocab_size - 1, token_ids.dtype)  # the last
        noised = jnp.where(masked, mask_id, token_ids)
        return (
            jnp.concatenate([noised, token_ids], axis=1),
            jnp.where(masked, 1.0 / p, 0.0),
        )

    def noise_key(self, opt_state, token_ids: jax.Array) -> jax.Array:
        """The key a train step noises its rows with, derived on the device
        from what the step already holds: the optimizer state's ``count``
        (fresh every step) folded into a key made of the rows' own ids (so
        of the seed that made the data; two rows of a pool differ)."""
        counts = [
            leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(opt_state)[0]
            if getattr(path[-1], "name", getattr(path[-1], "key", None))
            == "count"
        ]
        if not counts:
            raise NotImplementedError(
                "objective='block_diffusion' folds the optimizer state's "
                f"'count' into its noise key; {type(opt_state).__name__} "
                "has none"
            )
        ids = token_ids.astype(jnp.uint32).reshape(-1)
        mark = jnp.sum(ids * (2 * jnp.arange(ids.size, dtype=jnp.uint32) + 1))
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), mark), counts[0])

    def _diffusion_loss_fn(self, params, token_ids, noise_key):
        """Block diffusion's training loss (``cfg.objective``): the stack
        over ``[x_t | x_0]``, the head over the noised copy alone, in place
        (position i predicts ``token_ids[i]``, no shift), each masked
        position's CE times ``1 / p`` over ``B * S``; plus the weighted
        router aux and z losses over all ``2 S`` positions."""
        cfg = self.cfg
        b, s = token_ids.shape
        with jax.named_scope("noise"):
            u, t = self.noise_draws(noise_key, b)
            row, weights = self.noised_row(token_ids, u, t)
            masked = (weights > 0).astype(jnp.float32)
            counters = {
                "masked_share": jnp.mean(masked),
                "loss_weight_mean": jnp.sum(weights) / jnp.maximum(
                    jnp.sum(masked), 1.0),
            }
        x, aux = self._hidden(params, row)
        with jax.named_scope("ce"):
            ce = self._chunked_ce(
                x[:, :s], self._head(params), token_ids, weights=weights)
        loss = (
            ce
            + cfg.aux_loss_weight * aux["aux_loss"]
            + cfg.router_z_weight * aux["router_z_loss"]
        )
        head_dim = cfg.head_dim or cfg.d_model // cfg.n_heads
        shape = (b, 2 * s, cfg.n_heads, head_dim)
        counters.update(  # static: what the mask admits, what the core computes
            attention_admitted_pairs=jnp.float32(
                block_diffusion_admitted_pairs(s, cfg.diffusion_block)),
            attention_visited_pairs=jnp.float32(block_diffusion_visited_pairs(
                shape, self.attn_impl, jax.default_backend(),
                cfg.diffusion_block)),
        )
        return loss, {"ce": ce, **aux, **counters}

    def loss_fn(
        self, params: Params, token_ids: jax.Array, targets: jax.Array,
        noise_key: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        """Training loss: mean next-token CE (:meth:`_chunked_ce`)
        plus the weighted router aux and z losses.  Under
        ``objective='block_diffusion'`` the row predicts itself under the
        noise ``noise_key`` draws (:meth:`_diffusion_loss_fn`) and
        ``targets`` is not read."""
        if self._diffusion:
            if noise_key is None:
                raise ValueError(
                    "objective='block_diffusion' noises its rows: loss_fn "
                    "needs a noise_key (a train step derives one: noise_key)"
                )
            return self._diffusion_loss_fn(params, token_ids, noise_key)
        if self.cfg.mtp_layers:
            x, x_mtp, aux = self._hidden(params, token_ids, next_ids=targets)
        else:
            x, aux = self._hidden(params, token_ids)
        with jax.named_scope("ce"):
            head = self._head(params)
            ce = self._chunked_ce(x, head, targets)
        loss = ce
        if "aux_loss" in aux:  # a stack with no mixture has no such term
            loss = (
                ce
                + self.cfg.aux_loss_weight * aux["aux_loss"]
                + self.cfg.router_z_weight * aux["router_z_loss"]
            )
        metrics = {"ce": ce, **aux}
        if self.cfg.mtp_layers:
            # position i of the block's stream against t_{i+2}, the row's
            # targets shifted by one; the last position has no target (-1:
            # masked), so the mean is over B (S - 1) and the shapes stay
            with jax.named_scope("mtp"), jax.named_scope("ce"):
                b, s = targets.shape
                after_next = jnp.concatenate(
                    [targets[:, 1:], jnp.full((b, 1), -1, targets.dtype)],
                    axis=1,
                )
                ce_mtp = self._chunked_ce(
                    x_mtp, head, after_next, b * (s - 1), masked=True
                )
            loss = loss + self.cfg.mtp_loss_weight * ce_mtp
            metrics["ce_mtp"] = ce_mtp
        return loss, metrics

    def _chunked_ce(self, x, head, targets, denominator=None, masked=False,
                    weights=None):
        """Chunked cross-entropy: the [tokens, V] f32 logits are never
        materialized at once.  Token chunks of ``ce_chunk`` go through the
        head + softmax-CE inside a ``lax.scan``, so peak logits memory is
        chunk×V.  Under differentiation the same scan takes each chunk's
        gradients from the logits it has (:func:`_ce_of_chunks`): the
        forward ends holding the gradients with respect to ``x`` and the
        head, the backward only multiplies them by its cotangent, and the
        head is multiplied by three times a step, not four.  At the
        256-expert flagship shape the chunking is what lifts the per-chip
        batch from 16 to 64 — the f32 logits (+ cotangents) were the
        dominant activation term.  ``denominator``: what the summed CEs
        are divided by (None = every token, ``B * S``); ``masked``: a
        target of -1 marks a position that has none and adds nothing;
        ``weights`` [B, S] float32: each position's CE is multiplied by its
        weight before the sum (0 at a position the loss does not ask; None
        = the unweighted code, not a multiply by ones).

        Which path runs is read off the mesh and the shapes:

        * one device: the scan over all tokens (:meth:`_chunked_ce_sum`).
        * several devices, and the batch (with a ``seq`` axis also the
          sequence) divides over the shards of ``batch_sharding(mesh)``
          — every jitted train step: the same scan per shard under
          ``shard_map`` (scope ``ce/shard_map``).  Each device scans its
          own rows against the replicated head, the per-shard sums are
          added outside, and the head's cotangent is summed over the
          shards by the ``shard_map`` transpose.  The loss is per token,
          so any partition that ``x`` and ``targets`` share is right.
        * otherwise (an eager call with a batch that does not divide):
          the global scan.  On a sharded ``x`` the partitioner turns
          its per-chunk ``dynamic_slice`` into gather-then-slice and
          every device computes every chunk: 62 % of the four-chip
          flagship step (PERF.md, PR 25).  Correct, never fast."""
        from learning_at_home_tpu.parallel.mesh import data_axes

        mesh = self.mesh
        b, s = targets.shape
        if denominator is None:
            denominator = b * s
        b_shards = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
        s_shards = mesh.shape.get("seq", 1)
        if mesh.devices.size == 1 or b % b_shards or s % s_shards:
            return self._chunked_ce_sum(
                x, head, targets, denominator, masked, weights)

        from jax import shard_map

        spec = batch_sharding(mesh).spec  # P(batch axes[, "seq"])
        # the weights, where the loss has them, are laid out as the targets
        labels = (targets,) if weights is None else (targets, weights)
        ce_sums = shard_map(  # one sum a shard, laid out like the shards
            lambda xl, hl, *ll: self._chunked_ce_sum(
                xl, hl, ll[0], denominator, masked, *ll[1:]
            ).reshape(
                (1,) * len(spec)
            ),
            mesh=mesh,
            in_specs=(P(*spec, None), P(), *(spec,) * len(labels)),
            out_specs=spec,
            # the scan's carry starts unvarying (a constant 0) and ends
            # varying over the batch axes: the varying-axes check refuses it
            check_vma=False,
        )(x, head, *labels)
        return ce_sums.sum()

    def _chunked_ce_sum(self, x, head, targets, denominator, masked=False,
                        weights=None):
        """Sum (f32) of the token CEs of ``x`` [b, s, d] over
        ``denominator`` (the GLOBAL token count, also where ``x`` is one
        shard's rows), ``ce_chunk`` tokens at a time: :func:`_ce_of_chunks`."""
        n = x.shape[0] * x.shape[1]
        return _ce_of_chunks(
            x.reshape(n, x.shape[-1]), head, targets.reshape(n),
            min(self.cfg.ce_chunk, n), denominator, masked,
            None if weights is None else weights.reshape(n),
        )

    # ---- the routers' selection biases ----

    def _router_biases(self, params: Params) -> list | None:
        """The mixture layers' selection biases, in order; None where
        the routers have none."""
        if not self.cfg.router_bias:
            return None
        return [lp["moe"]["router_bias"]
                for lp in self._routed_layers(params) if "moe" in lp]

    @staticmethod
    def _routed_layers(params: Params) -> list:
        """The per-layer trees in the order of ``expert_counts``' rows: the
        stack's, then the next-but-one-token block's layer."""
        return list(params["layers"]) + (
            [params["mtp"]["layer"]] if "mtp" in params else []
        )

    def _balance(self, params: Params, biases: list | None, counts) -> Params:
        """``params`` with the selection biases as they are after a step:
        no gradient moves them (what the optimizer made of their zero
        gradient is discarded: ``biases`` are the step's own, from
        :meth:`_router_biases`), the balancing rule does,
        ``router_bias_rate`` toward level on the step's ``counts``
        [mixture layers, E]."""
        if biases is None:
            return params
        with jax.named_scope("router_bias"):
            moved = iter([
                balanced_bias(b, c, self.cfg.router_bias_rate)
                for b, c in zip(biases, counts)
            ])

        def put(lp):
            if "moe" not in lp:
                return lp
            return {**lp, "moe": {**lp["moe"], "router_bias": next(moved)}}

        params = {**params, "layers": tuple(map(put, params["layers"]))}
        if "mtp" in params:
            params["mtp"] = {**params["mtp"], "layer": put(params["mtp"]["layer"])}
        return params

    def level_router_bias(self, params: Params, token_batches: list):
        """``params`` with every mixture layer's selection bias levelled on
        ``token_batches`` (a list of [B, S] id arrays: the pool a run
        trains on), and what each layer's largest load over the mean was
        before and after.  A layer at a time: the layer's router scores
        on the stream the layers before it leave, ``level_bias`` on them,
        then the levelled layer's own output on to the next.  Under
        seeded random weights a router sends a token id's every
        occurrence to the same few experts; a trained router's bias has
        levelled that, and this stands in for the training.  A set-up
        call, outside any step."""
        cfg = self.cfg
        if not cfg.router_bias:
            return params, []
        embed = jax.jit(lambda table, ids: table[ids].astype(cfg.dtype))

        def folded(part):
            """``part(lp, x, ..) -> (x, ..)`` giving the streams FOLDED, as
            the step's layers do (:meth:`_folded_layer`; it takes them
            folded as it is): a program's argument or result [B, S, n, d]
            lies in that shape's tiling and is copied to the stream
            kernels' on the way in and on the way out, two arrays of four
            streams more a program."""
            def giving_the_fold(lp, x, *rest):
                x, *others = part(lp, x, *rest)
                return (self._hc_fold(x), *others)
            return part if cfg.hc_streams is None else giving_the_fold

        attend = jax.jit(folded(self._attention_block), static_argnums=(2,))
        finish = jax.jit(folded(self._ffn_block))
        whole = jax.jit(folded(self._layer), static_argnums=(4,))
        scores = jax.jit(lambda lp, x: jax.nn.sigmoid(self.moe.router_logits(
            lp["moe"],
            self._part_input(
                self._ffn_norm(lp), self._hc_read(lp.get("hc_ffn"), x)[1]
            ).reshape(-1, cfg.d_model))))
        # what the block's combine reads: the streams summed, or the stream
        total = (lambda x: x) if cfg.hc_streams is None else jax.jit(
            lambda x: self._hc_sum(self._hc_unfold(x))[0])
        streams = [embed(params["embed"], ids) for ids in token_batches]

        def advance(part):
            """Each batch's stream through ``part`` in its place, TWO
            programs in flight: a list built beside the old one holds both
            generations, and eight programs enqueued at once hold eight
            programs' temporaries (under ``hc_streams`` that was the
            process's peak, 15.7 GB: PERF.md section 6, PR 65)."""
            for j in range(len(streams)):
                streams[j] = part(streams[j])
                if j:  # the one before: the device is never left waiting
                    jax.block_until_ready(streams[j - 1])

        layers, loads = list(params["layers"]), []
        groups = dict(zip(("n_group", "topk_group"), cfg.router_groups or ()))
        if cfg.router_input != "moe_input":
            raise NotImplementedError(
                "level_router_bias reads the router on the experts' input"
            )
        for i, lp in enumerate(layers):
            one_mixer = "norm" in lp  # its router reads the layer's input
            if not one_mixer:
                advance(lambda x: attend(lp, x, cfg.attention_layer(i))[0])
            if "moe" in lp:
                bias, load = level_bias(
                    jnp.concatenate([scores(lp, x) for x in streams]),
                    lp["moe"]["router_bias"], cfg.k, **groups,
                )
                lp = layers[i] = {**lp, "moe": {**lp["moe"], "router_bias": bias}}
                loads.append(load)
            advance(
                (lambda x: whole(lp, x, i, None, cfg.attention_layer(i))[0])
                if one_mixer else (lambda x: finish(lp, x, None, i)[0]))
        params = {**params, "layers": tuple(layers)}
        if "mtp" in params:
            # the block's router too, on what the block's attention leaves
            # of the levelled stack's final stream and each position's next
            # id: the row shifted by one (the last position keeps its own)
            mp = params["mtp"]
            combine = jax.jit(lambda mp, ln_f, table, x, ids: self._mtp_input(
                mp, self._norm(ln_f, total(x)),
                jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1), table))
            for j, ids in enumerate(token_batches):  # as ``advance``
                streams[j] = attend(
                    mp["layer"],
                    combine(mp, params["ln_f"], params["embed"], streams[j], ids),
                    cfg.attention_layer(cfg.n_layers),
                )[0]
                if j:
                    jax.block_until_ready(streams[j - 1])
            lp = mp["layer"]
            bias, load = level_bias(
                jnp.concatenate([scores(lp, x) for x in streams]),
                lp["moe"]["router_bias"], cfg.k, **groups,
            )
            loads.append(load)
            params["mtp"] = {**mp, "layer": {
                **lp, "moe": {**lp["moe"], "router_bias": bias}}}
        return params, loads

    def init_opt_state(
        self, optimizer: optax.GradientTransformation, params: Params
    ):
        """Optimizer state with correct shardings (expert stacks stay
        expert-sharded; scalars replicated) — plain jit(opt.init) leaves
        outputs on one device, which breaks restore + mixed-device steps."""
        from learning_at_home_tpu.parallel.mesh import opt_state_shardings

        abstract = jax.eval_shape(optimizer.init, params)
        shardings = opt_state_shardings(
            abstract, self.param_shardings(params), params, self.mesh
        )
        return jax.jit(optimizer.init, out_shardings=shardings)(params)

    def make_train_step(
        self, optimizer: optax.GradientTransformation, accum_steps: int = 1
    ) -> Callable:
        """Donating, fully-jitted train step; inputs sharded over the mesh.

        ``accum_steps > 1`` returns a step that takes token_ids/targets of
        shape [accum, batch, seq], runs the microbatches sequentially
        through one ``lax.scan`` (sequential execution is what bounds
        live activations to one microbatch — grad_fn is already the
        differentiated function, so no checkpoint wrapper applies),
        averages the gradients, and applies ONE optimizer update —
        effective batch = accum × batch without the activation HBM of
        the large batch."""
        if self._diffusion and accum_steps > 1:
            raise NotImplementedError(
                "accum_steps > 1 with objective='block_diffusion': the "
                "microbatches of one step share the optimizer's count, and "
                "no key is derived a microbatch"
            )
        grad_fn = jax.value_and_grad(self.loss_fn, has_aux=True)
        # FusedOptimizer (ops.fused_adafactor) folds the param add into the
        # optimizer's own final pass — the update tree never hits HBM
        apply_fn = getattr(optimizer, "apply_fused", None)
        if apply_fn is None:
            def apply_fn(params, grads, opt_state):
                # optax transforms expect grads in the param dtype
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype), grads, params
                )
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

        def train_step(params, opt_state, token_ids, targets):
            if self._diffusion:  # the step's own noise, drawn on the device
                with jax.named_scope("noise"):
                    key = self.noise_key(opt_state, token_ids)
                (loss, metrics), grads = grad_fn(
                    params, token_ids, targets, key)
            else:
                (loss, metrics), grads = grad_fn(params, token_ids, targets)
            biases = self._router_biases(params)
            with jax.named_scope("optimizer"):
                params, opt_state = apply_fn(params, grads, opt_state)
            params = self._balance(
                params, biases, metrics.pop("expert_counts", None)
            )
            return params, opt_state, loss, metrics

        def accum_step(params, opt_state, token_ids, targets):
            def micro(carry, xt):
                gsum, lsum, msum = carry
                ids, tgt = xt
                (loss, metrics), grads = grad_fn(params, ids, tgt)
                # accumulate in f32: with the bf16 param_dtype recipe the
                # microbatch grads are bf16, and a bf16 running sum loses
                # ~precision to swamping as accum_steps grows
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads
                )
                msum = jax.tree_util.tree_map(jnp.add, msum, metrics)
                return (gsum, lsum + loss, msum), None

            zeros_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            zeros_m = jax.eval_shape(
                lambda p: grad_fn(p, token_ids[0], targets[0])[0][1], params
            )
            zeros_m = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, l.dtype), zeros_m
            )
            (gsum, lsum, msum), _ = jax.lax.scan(
                micro,
                (zeros_g, jnp.float32(0), zeros_m),
                (token_ids, targets),
            )
            inv = 1.0 / accum_steps
            # stay f32: the fused optimizer consumes f32 grads directly
            # (its state dtypes key off the PARAM dtype); the optax
            # fallback's apply_fn casts to param dtype itself
            grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
            biases = self._router_biases(params)
            with jax.named_scope("optimizer"):
                params, opt_state = apply_fn(params, grads, opt_state)
            # the microbatches' assignments together
            params = self._balance(
                params, biases, msum.pop("expert_counts", None)
            )
            metrics = jax.tree_util.tree_map(lambda m: m * inv, msum)
            return params, opt_state, lsum * inv, metrics

        data_shard = batch_sharding(self.mesh)
        if accum_steps > 1:
            # microbatch axis is leading: prepend None to the batch spec
            data_shard = NamedSharding(
                self.mesh, P(None, *data_shard.spec)
            )
        return jax.jit(
            accum_step if accum_steps > 1 else train_step,
            in_shardings=(None, None, data_shard, data_shard),
            donate_argnums=(0, 1),
        )


# ---- the loss layer: the token CEs a chunk at a time ----


def _softmax_ce(logits: jax.Array, targets: jax.Array, masked: bool = False,
                weights: jax.Array | None = None):
    """Summed CE (f32) of a chunk's rows from its float32 ``logits``
    [c, V], with the two pieces the gradient is made of: the exponentials
    ``e`` [c, V] (of the logits less the row's largest) and their row sums
    ``s`` [c, 1].  ``softmax = e / s``.  ``masked``: a row whose target is
    negative has none and adds nothing to the sum.  ``weights`` [c]: a
    row's CE times its weight."""
    top = logits.max(axis=-1, keepdims=True)
    e = jnp.exp(logits - top)
    s = e.sum(axis=-1, keepdims=True)
    if masked:
        label = jnp.take_along_axis(
            logits, jnp.maximum(targets, 0)[:, None], axis=-1)
        ces = jnp.where(targets[:, None] >= 0, jnp.log(s) + top - label, 0.0)
    else:
        label = jnp.take_along_axis(logits, targets[:, None], axis=-1)
        ces = jnp.log(s) + top - label
    if weights is not None:
        ces = ces * weights[:, None]
    return ces.sum(), e, s


def _over_chunks(step, carry, flat_x, flat_t, chunk: int):
    """``step(carry, (rows, targets)) -> (carry, out)`` over ``flat_x``
    [n, d] and ``flat_t`` [n] (or a tuple of such arrays: the targets and
    the rows' weights), ``chunk`` rows at a time: a ``lax.scan``
    over the divisible prefix (one call where that is a single chunk),
    then one more call for a sub-chunk remainder, so that no call sees
    more than a chunk for EVERY n (an indivisible n must not silently
    materialize full [n, V] logits).  The scan takes its last chunk first,
    the order in which the transpose of a forward scan added the chunks'
    shares of the head's gradient: in the head's dtype that sum depends on
    its order, and a train step keeps the bits it had.  Returns the carry
    and the calls' outputs, their rows in the order of ``flat_x``'s."""
    n = flat_x.shape[0]
    main = (n // chunk) * chunk
    outs = []
    if main > chunk:
        xs = (
            flat_x[:main].reshape(main // chunk, chunk, -1),
            jax.tree_util.tree_map(
                lambda t: t[:main].reshape(main // chunk, chunk), flat_t),
        )
        carry, out = jax.lax.scan(step, carry, xs, reverse=True)
        outs.append(jax.tree_util.tree_map(
            lambda o: o.reshape(main, *o.shape[2:]), out
        ))
    elif main:
        carry, out = step(carry, (flat_x[:main], jax.tree_util.tree_map(
            lambda t: t[:main], flat_t)))
        outs.append(out)
    if n > main:
        carry, out = step(carry, (flat_x[main:], jax.tree_util.tree_map(
            lambda t: t[main:], flat_t)))
        outs.append(out)
    return carry, outs


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ce_of_chunks(flat_x, head, flat_t, chunk: int, denominator: int,
                  masked: bool = False, flat_w=None):
    """Sum (f32) over ``denominator`` of the CEs of the rows ``flat_x``
    [n, d] against ``head`` [d, V] and the targets ``flat_t`` [n]: one
    head product and one softmax a chunk, no [n, V] array at any time.

    Differentiated (:func:`_ce_of_chunks_fwd`), the same pass over the
    same chunks also takes the gradients, from the logits it has: the
    loss is a scalar, so they need nothing a backward pass would bring,
    and the head is multiplied by three times a step (logits, and the
    two gradient products) where recomputing each chunk's logits in the
    backward made it four.  ``masked``: rows whose target is negative have
    none; they add nothing to the sum and get no gradient.  ``flat_w`` [n]
    float32 (None = no weights, the code as it was): a row's CE and its
    gradient times its weight; the weights themselves get no gradient."""

    def step(ce_sum, rows_labels):
        rows, labels = rows_labels
        ce, _, _ = _softmax_ce(
            DMoETransformerLM._logits(rows, head), *_labels(labels, masked))
        return ce_sum + ce, None

    ce_sum, _ = _over_chunks(
        step, jnp.float32(0), flat_x, _with_weights(flat_t, flat_w), chunk)
    return ce_sum / denominator


def _with_weights(flat_t, flat_w):
    """What :func:`_over_chunks` cuts into chunks beside the rows: the
    targets, or the targets and the weights."""
    return flat_t if flat_w is None else (flat_t, flat_w)


def _labels(labels, masked: bool) -> tuple:
    """A chunk of :func:`_with_weights` as ``_softmax_ce``'s arguments
    after the logits: ``(targets, masked, weights or None)``."""
    if isinstance(labels, tuple):
        return labels[0], masked, labels[1]
    return labels, masked, None


def _ce_of_chunks_fwd(flat_x, head, flat_t, chunk, denominator, masked=False,
                      flat_w=None):
    """The value, and as residuals its gradients with respect to
    ``flat_x`` (a chunk's rows from each step, stacked by the scan) and
    ``head`` (a carry of the scan, accumulated in the head's dtype as the
    backward scan's transpose did).  A chunk's ``d = (softmax - onehot) /
    denominator`` is float32, the divisor on it before any cast, and the
    two products are the transposes autodiff gives the logits' einsum:
    float32 operands and results, cast to the rows' and the head's dtype
    afterwards."""
    scale = jnp.float32(1) / denominator

    def step(carry, rows_labels):
        ce_sum, d_head = carry
        rows, labels = rows_labels
        targets, _, weights = _labels(labels, masked)
        # the chunk's rows as an array of their own, as the barrier of
        # jax.checkpoint held them: sliced out of the stack inside each
        # product's fusion instead, they are not prefetched, and the head's
        # gradient product compiles to tiles 29 % slower (PERF.md, PR 34)
        rows = jax.lax.optimization_barrier(rows)
        logits, gradient_products = jax.vjp(
            DMoETransformerLM._logits, rows, head
        )
        ce, e, s = _softmax_ce(logits, targets, masked, weights)
        # a row's weight in the mean: 0 where it has no target
        weight = (
            jnp.where(targets[:, None] >= 0, scale, 0.0) if masked else scale
        )
        if weights is not None:
            weight = weight * weights[:, None]
        softmax = e * (weight / s)
        hit = jnp.arange(logits.shape[-1]) == targets[:, None]
        d_rows, d_head_chunk = gradient_products(
            jnp.where(hit, softmax - weight, softmax)
        )
        return (ce_sum + ce, d_head + d_head_chunk), d_rows

    (ce_sum, d_head), d_rows = _over_chunks(
        step, (jnp.float32(0), jnp.zeros_like(head)), flat_x,
        _with_weights(flat_t, flat_w), chunk
    )
    d_x = d_rows[0] if len(d_rows) == 1 else jnp.concatenate(d_rows)
    return ce_sum / denominator, (
        d_x, d_head, None if flat_w is None else jnp.zeros_like(flat_w))


def _ce_of_chunks_bwd(chunk, denominator, masked, gradients, cotangent):
    """The forward's gradients times the scalar cotangent, in float32
    (a train step's is 1, the mean's divisor being on them already: no
    bit changes); the integer targets have no gradient."""
    d_x, d_head = (
        (g.astype(jnp.float32) * cotangent).astype(g.dtype)
        for g in gradients[:2]
    )
    # neither the integer targets nor the weights move with the loss
    return d_x, d_head, None, gradients[2]


_ce_of_chunks.defvjp(_ce_of_chunks_fwd, _ce_of_chunks_bwd)
