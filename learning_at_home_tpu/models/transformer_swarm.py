"""Swarm-mode DMoE-Transformer: local trunk, network-remote expert FFNs.

This is the reference's headline training setup (SURVEY.md §3.5): the
trainer owns the embeddings/attention/gates and steps them with its own
optimizer; every MoE FFN layer is a ``RemoteMixtureOfExperts`` whose
experts live on DHT-discovered servers and update themselves
asynchronously on each backward RPC.

The remote dispatch rides ``io_callback`` under ``custom_vjp``
(client/moe.py), so the whole step still jits — on the CPU and from a
process that holds the TPU alike (forward+grad dispatches from a
TPU-resident jit against a CPU server: ``tools/chip_probe.py client``,
v5e, jax 0.9.0).  Experts inside one pod use pod mode's
ShardedMixtureOfExperts instead (SURVEY.md §2.2).

Deployment note: run trainers and expert servers in SEPARATE processes
(the normal swarm topology).  In one process they share one XLA runtime,
and a trainer's blocking host callback can occupy the execution slot the
server's own jitted expert computation needs — under concurrency that
degenerates into stalls.  ``background_server`` in-process is fine for
light tests; real training should talk to ``python -m
learning_at_home_tpu.server`` peers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from learning_at_home_tpu.client.moe import RemoteMixtureOfExperts
from learning_at_home_tpu.client.routing import ExpertSource
from learning_at_home_tpu.models.trunk import causal_attention, layer_norm


@dataclasses.dataclass(frozen=True)
class SwarmTransformerConfig:
    vocab_size: int = 258
    d_model: int = 256
    n_layers: int = 2
    n_heads: int = 8
    seq_len: int = 128
    grid_size: tuple = (16, 16)  # 256-expert grid, [BJ] config 3
    k_best: int = 4
    k_min: int = 1
    backward_k_min: int = 1
    uid_prefix: str = "ffn"
    routing: str = "enumerate"
    dtype: Any = jnp.float32
    # generous defaults: first-time XLA compiles per batch bucket happen
    # inside the server's RPC window
    forward_timeout: float = 60.0
    backward_timeout: float = 60.0
    timeout_after_k_min: float = 1.0
    # "bfloat16"/"float16": downcast activation/grad payloads on the wire
    # (both directions; servers compute in f32) — halves the DCN bytes of
    # the large-row dispatches that dominate swarm dispatch p50
    wire_dtype: Any = None
    # wire CODEC pin ("none"/"bf16"/"f16"/"u8"/"blockq8"); None = adaptive
    # per-pool escalation (client/moe.py wire_codec, docs/PROTOCOL.md) —
    # 8-bit codecs quarter the DCN bytes vs f32
    wire_codec: Any = None
    # > 0: debit each expert's SELECTION score by this × its endpoint's
    # RTT EMA (seconds) so routing avoids slow/overloaded peers
    # proactively (see client/moe.py latency_weight); 0 = off
    latency_weight: float = 0.0
    # latency-aware routing cost model (ISSUE 8): bias selection by
    # predicted completion time (RTT EMA + DHT-advertised queue depth +
    # estimated transfer at the negotiated codec), minimized over each
    # expert's replica set.  None falls back to latency_weight; 0 = off
    # (bias=None, selection bitwise the blind gate).  See
    # client/routing.py RoutingCostModel / DEFAULT_COST_WEIGHT.
    routing_cost_weight: Any = None
    # DHT scope of the ``load.<prefix>`` heartbeats the cost model reads
    # (must match the servers' --telemetry-prefix; see utils/telemetry.py)
    telemetry_prefix: str = "swarm"


class SwarmDMoETransformerLM:
    """Trainer-side model; expert parameters never touch this process."""

    def __init__(self, config: SwarmTransformerConfig, source: ExpertSource):
        self.cfg = config
        # one MoE layer object per transformer layer: layers may route to
        # different uid prefixes (ffn0., ffn1., ...) so experts specialize
        self.moes = [
            RemoteMixtureOfExperts(
                in_features=config.d_model,
                grid_size=config.grid_size,
                uid_prefix=f"{config.uid_prefix}{i}",
                source=source,
                k_best=config.k_best,
                k_min=config.k_min,
                backward_k_min=config.backward_k_min,
                routing=config.routing,
                forward_timeout=config.forward_timeout,
                backward_timeout=config.backward_timeout,
                timeout_after_k_min=config.timeout_after_k_min,
                wire_dtype=config.wire_dtype,
                wire_codec=config.wire_codec,
                latency_weight=config.latency_weight,
                routing_cost_weight=config.routing_cost_weight,
                telemetry_prefix=config.telemetry_prefix,
            )
            for i in range(config.n_layers)
        ]

    def init_params(self, rng: jax.Array) -> Any:
        cfg = self.cfg
        d, v, s = cfg.d_model, cfg.vocab_size, cfg.seq_len
        dense = jax.nn.initializers.lecun_normal()
        embed_init = jax.nn.initializers.normal(1.0 / np.sqrt(d))
        keys = iter(jax.random.split(rng, 3 + 6 * cfg.n_layers))

        def ln():
            return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}

        params = {
            "embed": embed_init(next(keys), (v, d)),
            "pos": embed_init(next(keys), (s, d)),
            "ln_f": ln(),
            "layers": [],
        }
        for i in range(cfg.n_layers):
            params["layers"].append(
                {
                    "ln1": ln(),
                    "wq": dense(next(keys), (d, d)),
                    "wk": dense(next(keys), (d, d)),
                    "wv": dense(next(keys), (d, d)),
                    "wo": dense(next(keys), (d, d)),
                    "ln2": ln(),
                    "gate": self.moes[i].init_gate_params(next(keys)),
                }
            )
        return params

    def apply(self, params, token_ids):
        b, s = token_ids.shape
        x = params["embed"][token_ids] + params["pos"][None, :s]
        for i, lp in enumerate(params["layers"]):
            x = x + causal_attention(lp, layer_norm(lp["ln1"], x), self.cfg.n_heads)
            moe_in = layer_norm(lp["ln2"], x).reshape(b * s, self.cfg.d_model)
            moe_out = self.moes[i](moe_in, lp["gate"])
            x = x + moe_out.reshape(b, s, self.cfg.d_model)
        x = layer_norm(params["ln_f"], x)
        return x @ params["embed"].T

    def apply_overlapped(self, params, token_ids, *, overlap: bool = True):
        """ScMoE-style parallel-branch step with communication/compute
        overlap (ISSUE 7; cf. Shortcut-connected Expert Parallelism,
        arXiv:2404.05019).

        Architecture note — this is a DIFFERENT (shortcut) wiring from
        :meth:`apply`: each layer's MoE branch reads ``ln2`` of the layer
        INPUT (not the post-attention residual), so the expert fan-out
        for layer *i* has no data dependency on layer *i*'s attention and
        can be FIRED before it.  The overlapped schedule fires the MoE,
        computes the attention trunk while the RPCs fly, and joins the
        future only where the residual add needs the replies.  Backward
        mirrors it automatically: the join op's bwd fires the grad
        fan-out, the attention backward computes, and the fire op's bwd
        joins (client/moe.py).

        ``overlap=False`` runs the SAME primitive ops in the serial
        schedule (join immediately after fire) — only host-side
        scheduling differs, so serial and overlapped outputs and
        gradients are bitwise identical; that is the contract
        the parity tests rely on."""
        cfg = self.cfg
        b, s = token_ids.shape
        x = params["embed"][token_ids] + params["pos"][None, :s]
        for i, lp in enumerate(params["layers"]):
            moe_in = layer_norm(lp["ln2"], x).reshape(b * s, cfg.d_model)
            pending = self.moes[i].fire(moe_in, lp["gate"])
            try:
                if not overlap:  # serial schedule: eat the wait right here
                    moe_out = self.moes[i].join(*pending)
                x = x + causal_attention(
                    lp, layer_norm(lp["ln1"], x), cfg.n_heads
                )
                if overlap:  # join as late as the data dependency allows
                    moe_out = self.moes[i].join(*pending)
            except Exception:
                # a raise between fire and join must not leak the
                # in-flight fan-out until ticket eviction (no-op if the
                # join already consumed it)
                self.moes[i].discard(*pending)
                raise
            x = x + moe_out.reshape(b, s, cfg.d_model)
        x = layer_norm(params["ln_f"], x)
        return x @ params["embed"].T

    def loss_fn(self, params, token_ids, targets):
        logits = self.apply(params, token_ids)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    def loss_fn_overlapped(self, params, token_ids, targets, *,
                           overlap: bool = True):
        logits = self.apply_overlapped(params, token_ids, overlap=overlap)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    def make_train_step(self, optimizer: optax.GradientTransformation) -> Callable:
        """Eager-host train step: local grads via jax.grad (backward RPCs
        fire inside), optimizer on trunk+gates only."""

        def step(params, opt_state, ids, targets):
            loss, grads = jax.value_and_grad(self.loss_fn)(params, ids, targets)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        return step

    def make_overlapped_train_step(
        self, optimizer: optax.GradientTransformation, *,
        overlap: bool = True,
    ) -> Callable:
        """Train step over the shortcut architecture — ``overlap``
        selects the schedule (overlapped vs serial) without changing a
        single primitive op; see :meth:`apply_overlapped`."""

        def loss(params, ids, targets):
            return self.loss_fn_overlapped(
                params, ids, targets, overlap=overlap
            )

        def step(params, opt_state, ids, targets):
            loss_val, grads = jax.value_and_grad(loss)(params, ids, targets)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss_val

        return step
