"""ShardedMixtureOfExperts: the pod-scale expert-parallel MoE FFN.

This is [BJ] config 5 — the intra-pod realization of the reference's DMoE
(SURVEY.md §2.2 "Expert parallelism", §7 M5): experts live sharded across
the ``expert`` mesh axis as ONE stacked parameter pytree; a token batch,
sharded across all devices, is routed by top-k gating, capacity-bucketed,
and exchanged with **two ``lax.all_to_all`` collectives inside a single
``shard_map`` program** — not N point-to-point RPCs.  Fault tolerance
inside the collective is capacity-dropping (SURVEY.md §7 "k-of-n inside a
collective"); true peer failure handling stays on the DHT/RPC tier.

Data layout through the program (per device; E=global experts, e=local
experts, ep=expert-axis size, n=local tokens, C=capacity, d=model dim):

    x [n,d] ── gate ──▶ plan [n,E,C] ── dispatch ──▶ [E,C,d]
      reshape [ep,e,C,d] ── all_to_all ──▶ [ep,e,C,d]   (tokens arrive)
      regroup [e,ep*C,d] ── batched expert FFN (MXU) ──▶ [e,ep*C,d]
      regroup [ep,e,C,d] ── all_to_all ──▶ [E,C,d]       (outputs return)
      combine ──▶ y [n,d]

Expert compute is one batched einsum over the local expert stack — large,
dense, bfloat16-friendly: exactly what the MXU wants.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from learning_at_home_tpu.ops.moe_dispatch import (
    choose_dispatch_impl,
    combine_outputs,
    combine_outputs_indexed,
    compute_capacity,
    dispatch_tokens,
    dispatch_tokens_indexed,
    dropless_routing,
    grouped_matmul,
    kept_groups,
    share_buffer_rows,
    share_combine,
    share_routing,
    share_sort_tokens,
    sort_tokens,
    top_k_gating,
    top_k_gating_indices,
    unsort_combine,
)
from learning_at_home_tpu.models.trunk import gate_activation
from learning_at_home_tpu.parallel.mesh import data_axes

Params = dict[str, jax.Array]


def _router_z_loss(
    logits: jax.Array, token_mask: jax.Array | None
) -> jax.Array:
    """Router z-loss (ST-MoE): penalizes logit magnitude so the softmax
    stays in a well-conditioned regime at scale (real tokens only)."""
    lse2 = jax.scipy.special.logsumexp(logits, axis=-1) ** 2
    if token_mask is None:
        return jnp.mean(lse2)
    v = token_mask.astype(lse2.dtype)
    return (lse2 * v).sum() / jnp.maximum(v.sum(), 1.0)


class ShardedMixtureOfExperts:
    """Expert-parallel MoE FFN over a mesh with an ``expert`` axis.

    Parameters (``init_params``):
      gate  [d, E]            — replicated
    ``expert_kind="gelu"`` (``w2(gelu(w1 x + b1)) + b2``):
      w1    [E, d, ffn]       — sharded on axis 0 over ``expert``
      b1    [E, ffn]
      w2    [E, ffn, d]
      b2    [E, d]
    ``expert_kind="gated_silu"`` (``w_down(silu(w_gate x) * (w_up x))``,
    no biases) and ``"gated_relu"`` (ReGLU: the same three matrices and
    code, ``relu`` on the gate branch):
      w_gate, w_up  [E, d, ffn]
      w_down        [E, ffn, d]
    ``expert_kind="relu2"`` (``w_down(relu(w_up x)^2)``: no gate branch,
    no biases; two matrices an expert and two grouped matmuls forward):
      w_up    [E, d, ffn]
      w_down  [E, ffn, d]

    ``router_input=True``: the call takes the router's input beside the
    experts' (``router_x``: a router placed before the attention block
    reads that block's normalized input, the experts the stream after
    it); dropless routing only.

    ``routing="capacity"``: the ``[E, C, d]`` slot program above, tokens
    beyond an expert's capacity dropped.  ``routing="dropless"``: the
    n*k assignments sorted by expert and run through a grouped matmul,
    nothing dropped (``_local_forward_dropless``); experts must be whole
    on every device (``expert`` axis 1).

    ``held_experts=G`` (dropless only): this layer holds ``G`` of the
    ``num_experts`` its router scores, experts ``first_held_expert ..
    first_held_expert + G - 1`` (one chip's share of a layer whose experts
    no chip holds whole).  It routes over all of them, computes the part
    of the result that its own give, with the gates as normalised over all
    k chosen, and leaves out what the absent experts would have added: no
    exchange, nothing standing in for the other chips.  The expert stacks
    are ``[G, ...]``, the gate stays ``[d, num_experts]``
    (``_local_forward_share``).

    ``router_score="sigmoid"``: every expert scored on its own, the k
    largest chosen and renormalised, times ``routed_scale``;
    ``router_bias=True`` adds the parameter ``router_bias`` [E] float32 to
    the scores for the CHOICE alone (``ops.moe_dispatch.router_choice``);
    ``router_groups=(n_group, topk_group)``: the k are chosen inside the
    ``topk_group`` best of ``n_group`` groups of consecutive experts, a
    group scored by the sum of its two best selection scores
    (``ops.moe_dispatch.group_limited``); a share then counts, as
    ``groups_reaching_share``, the share of tokens any of whose kept groups
    holds a held expert.
    """

    def __init__(
        self,
        mesh: Mesh,
        hidden_dim: int,
        num_experts: int,
        k: int = 2,
        capacity_factor: float = 1.25,
        ffn_mult: int = 4,
        dtype: Any = jnp.bfloat16,
        param_dtype: Any = jnp.float32,
        router_jitter: float = 0.0,
        ffn_dim: int | None = None,
        expert_kind: str = "gelu",
        routing: str = "capacity",
        renormalize: bool = True,
        router_input: bool = False,
        held_experts: int | None = None,
        first_held_expert: int = 0,
        router_score: str = "softmax",
        router_bias: bool = False,
        routed_scale: float = 1.0,
        router_groups: tuple[int, int] | None = None,
    ):
        if expert_kind not in ("gelu", "gated_silu", "gated_relu", "relu2"):
            raise ValueError(
                f"expert_kind must be 'gelu', 'gated_silu', 'gated_relu' or "
                f"'relu2', got {expert_kind!r}"
            )
        if routing not in ("capacity", "dropless"):
            raise ValueError(
                f"routing must be 'capacity' or 'dropless', got {routing!r}"
            )
        if routing == "dropless" and router_jitter:
            raise ValueError(
                "routing='dropless' is top-k on clean gates: "
                "router_jitter must be 0"
            )
        if router_input and routing != "dropless":
            raise NotImplementedError(
                "a router input of its own (router_input=True) with "
                "routing='capacity': the slot program's gate, jitter and "
                "dispatch all read the one token tensor it exchanges over "
                "'expert'; only the dropless path takes two"
            )
        if router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_score must be 'softmax' or 'sigmoid', got "
                f"{router_score!r}"
            )
        if (router_score != "softmax" or routed_scale != 1.0) and (
            routing != "dropless"
        ):
            raise NotImplementedError(
                "router_score='sigmoid' / routed_scale with routing="
                "'capacity': the slot program's gates are a softmax over "
                "all experts; only the dropless path scores otherwise"
            )
        if router_bias and router_score != "sigmoid":
            raise ValueError(
                "router_bias=True is the selection bias of a sigmoid router"
            )
        if router_groups is not None and (
            router_score != "sigmoid" or num_experts % router_groups[0]
            or not 0 < router_groups[1] <= router_groups[0]
            or k > router_groups[1] * (num_experts // router_groups[0])
        ):
            raise ValueError(
                f"router_groups={router_groups} is (n_group, topk_group) of "
                f"a sigmoid router: equal groups of the {num_experts} "
                f"experts, the kept ones holding k={k} experts at least"
            )
        held = num_experts if held_experts is None else held_experts
        if not 0 <= first_held_expert <= num_experts - held or held < 1:
            raise ValueError(
                f"experts {first_held_expert}..{first_held_expert + held - 1}"
                f" are not among the router's {num_experts}"
            )
        if held < num_experts and (
            routing != "dropless" or mesh.shape.get("expert", 1) > 1
            or expert_kind == "gelu"
        ):
            raise NotImplementedError(
                f"held_experts={held} of {num_experts}: a share is the "
                "dropless path of the kinds without biases (not 'gelu') on "
                "a mesh whose 'expert' axis is 1; across chips the shares' "
                "rows need the ragged all-to-all"
            )
        if "expert" not in mesh.axis_names:
            raise ValueError("mesh must have an 'expert' axis")
        self.mesh = mesh
        self.ep = mesh.shape["expert"]
        # optional tensor parallelism: a 'model' mesh axis shards each
        # expert's FFN dimension; the second einsum produces partial sums
        # that one psum over 'model' reduces (Megatron-style column+row
        # split, per expert)
        self.tp = mesh.shape.get("model", 1)
        if num_experts % self.ep:
            raise ValueError(
                f"num_experts={num_experts} must divide over expert axis "
                f"size {self.ep}"
            )
        self.hidden_dim = hidden_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        # an explicit width where it is no multiple of hidden_dim (OLMoE's
        # 1024 is half of 2048)
        self.ffn_dim = ffn_mult * hidden_dim if ffn_dim is None else ffn_dim
        if self.ffn_dim % self.tp:
            raise ValueError(
                f"ffn_dim={self.ffn_dim} must divide over model axis size {self.tp}"
            )
        self.dtype = dtype
        self.param_dtype = param_dtype
        # deterministic multiplicative routing noise (see
        # ops.moe_dispatch.router_jitter) — breaks routing collapse when
        # many rows are near-identical (byte-level data near init)
        self.router_jitter = router_jitter
        self.expert_kind = expert_kind
        self.routing = routing
        self.renormalize = renormalize
        self.router_input = router_input
        self.held_experts = held
        self.first_held_expert = first_held_expert
        self.router_score = router_score
        self.router_bias = router_bias
        self.routed_scale = routed_scale
        self.router_groups = router_groups or (1, 1)
        self._gate_act = gate_activation(expert_kind)
        if routing == "dropless" and (self.ep > 1 or self.tp > 1):
            raise NotImplementedError(
                f"routing='dropless' on a mesh with expert={self.ep}, "
                f"model={self.tp}: its sorted rows stay on the device that "
                "routed them, so every device must hold every expert whole. "
                "Experts split over 'expert' need the ragged all-to-all "
                "(group sizes differ per peer), which arrives with the "
                "olmoe-train-pod4 cell"
            )
        self._shard = data_axes(mesh)  # axes the token batch is split over

    # ---- parameters ----

    def init_params(self, rng: jax.Array, device_put: bool = True) -> Params:
        """``device_put=False`` returns the raw tree (for a caller that
        places it as part of a larger tree: the model's ``init_params``
        puts the whole model on the mesh in one ``device_put``)."""
        kg, k1, k2 = jax.random.split(rng, 3)
        # e: the experts whose matrices are here (a share's G; the router
        # keeps its width)
        d, e, f = self.hidden_dim, self.held_experts, self.ffn_dim
        n_scored = self.num_experts
        init = jax.nn.initializers.lecun_normal()
        # near-zero router init: logits start ~flat so top-k routing is
        # near-uniform and the capacity drop starts low (lecun-scale gate
        # measured 0.40-0.48 dropped at init on the 256-expert flagship;
        # small init gives balance a head start and the aux loss keeps it)
        gate_init = jax.nn.initializers.normal(stddev=1e-2)
        if self.expert_kind != "gelu":
            # fan-in of ONE expert's matrix (the gelu stack's lecun_normal
            # counts the expert axis into its fan-in: kept, its
            # checkpoints and baselines were made with it)
            per_expert = jax.nn.initializers.lecun_normal(batch_axis=0)
            k1a, k1b = jax.random.split(k1)
            params = {
                "gate": gate_init(kg, (d, n_scored), self.param_dtype),
                "w_up": per_expert(k1b, (e, d, f), self.param_dtype),
                "w_down": per_expert(k2, (e, f, d), self.param_dtype),
            }
            if self.expert_kind != "relu2":
                params["w_gate"] = per_expert(k1a, (e, d, f), self.param_dtype)
        else:
            params = {
                "gate": gate_init(kg, (d, n_scored), self.param_dtype),
                "w1": init(k1, (e, d, f), self.param_dtype),
                "b1": jnp.zeros((e, f), self.param_dtype),
                "w2": init(k2, (e, f, d), self.param_dtype),
                "b2": jnp.zeros((e, d), self.param_dtype),
            }
        if self.router_bias:
            # float32 whatever the parameters' dtype: it moves by 1e-3 a
            # step, under bf16's resolution near 1
            params["router_bias"] = jnp.zeros((n_scored,), jnp.float32)
        if not device_put:
            return params
        return jax.device_put(params, self.param_shardings())

    def _expert_param_specs(self) -> dict[str, P]:
        if self.expert_kind != "gelu":
            col = P("expert", None, "model") if self.tp > 1 else P("expert")
            row = P("expert", "model", None) if self.tp > 1 else P("expert")
            if self.expert_kind == "relu2":
                return {"w_up": col, "w_down": row}
            return {"w_gate": col, "w_up": col, "w_down": row}
        if self.tp > 1:
            return {
                "w1": P("expert", None, "model"),  # column split
                "b1": P("expert", "model"),
                "w2": P("expert", "model", None),  # row split
                "b2": P("expert"),
            }
        return {"w1": P("expert"), "b1": P("expert"),
                "w2": P("expert"), "b2": P("expert")}

    def param_specs(self) -> dict[str, P]:
        """PartitionSpec per param."""
        specs = dict(self._expert_param_specs())
        specs["gate"] = P()
        if self.router_bias:
            specs["router_bias"] = P()
        return specs

    def param_shardings(self) -> dict[str, NamedSharding]:
        return {
            name: NamedSharding(self.mesh, spec)
            for name, spec in self.param_specs().items()
        }

    # ---- the sharded program ----

    def __call__(
        self, params: Params, x: jax.Array,
        jitter_salt: jax.Array | int = 0,
        token_mask: jax.Array | None = None,
        router_x: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        """x: [n_tokens, d] sharded over the data axes.  Returns (y, aux).

        ``router_x`` [n_tokens, d] (with ``router_input=True``, and only
        then): what the router reads in place of ``x``.

        ``jitter_salt``: static int or traced scalar (e.g. the layer index
        inside a scan-over-layers) folded into the router-jitter key so
        each call site draws a decorrelated noise pattern.

        ``token_mask`` [n_tokens] bool (optional, traced): False =
        padding — routed to no expert, claims no capacity, contributes
        zero output (the batched-decode fix; see ops.moe_dispatch).  The
        None path compiles exactly the unmasked program — no masking ops
        on the training hot path."""
        n_global = x.shape[0]
        n_shards = 1
        for a in self._shard:
            n_shards *= self.mesh.shape[a]
        if n_global % n_shards:
            raise ValueError(
                f"token count {n_global} must divide across {n_shards} shards"
            )
        n_local = n_global // n_shards
        if (router_x is not None) != self.router_input:
            raise ValueError(
                f"router_input={self.router_input} but router_x is "
                f"{'given' if router_x is not None else 'missing'}"
            )
        if self.routing == "dropless":
            aux_names = ("aux_loss", "router_z_loss", "dropped_fraction",
                         "expert_load_max_over_mean")
            share = self.held_experts < self.num_experts
            if share:
                aux_names += ("local_rows_over_level",)
            if self.router_bias:
                aux_names += ("expert_counts", "router_bias_abs_max")
            elif share:  # no counts a step to read the empty experts off
                aux_names += ("held_experts_empty",)
            if share and self.router_groups[0] > 1:
                aux_names += ("groups_reaching_share",)
            return shard_map(
                self._local_forward_share if share
                else self._local_forward_dropless,
                mesh=self.mesh,
                # a None among the per-token arguments has no leaf to place
                in_specs=(self.param_specs(),) + (P(self._shard),) * 3,
                out_specs=(P(self._shard), {name: P() for name in aux_names}),
                check_vma=False,
            )(params, x, token_mask, router_x)
        capacity = compute_capacity(
            n_local, self.num_experts, self.k, self.capacity_factor
        )

        in_specs = [
            self.param_specs(),
            P(self._shard),
            P(),  # jitter salt: replicated scalar
        ]
        out_specs = (
            P(self._shard),
            {"aux_loss": P(), "router_z_loss": P(), "dropped_fraction": P()},
        )
        if token_mask is None:
            fn = shard_map(
                functools.partial(self._local_forward, capacity=capacity),
                mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=out_specs,
                check_vma=False,
            )
            return fn(params, x, jnp.asarray(jitter_salt, jnp.int32))
        fn = shard_map(
            lambda p, xx, s, m: self._local_forward(
                p, xx, s, capacity=capacity, token_mask=m
            ),
            mesh=self.mesh,
            in_specs=tuple(in_specs) + (P(self._shard),),
            out_specs=out_specs,
            check_vma=False,
        )
        return fn(
            params, x, jnp.asarray(jitter_salt, jnp.int32), token_mask
        )

    def _local_forward(
        self, params: Params, x: jax.Array, jitter_salt: jax.Array,
        capacity: int, token_mask: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        e_local = self.num_experts // self.ep
        d = self.hidden_dim
        compute = self.dtype

        # 'gather' moves tokens with index gathers/scatters (O(E*C*d) data
        # movement); 'onehot' uses the GShard-style [n,E,C] einsums
        # (O(n*E*C*d) MXU work): chosen per static shape at the crossover
        # measured on a v5e
        impl = choose_dispatch_impl(x.shape[0], self.num_experts * capacity)

        # 1) gate + routing plan for MY tokens (logits in f32 for stable softmax)
        with jax.named_scope("router"):
            logits = (
                x.astype(compute) @ params["gate"].astype(compute)
            ).astype(jnp.float32)
            if impl == "gather":
                plan = top_k_gating_indices(
                    logits, self.k, capacity, self.renormalize,
                    jitter=self.router_jitter,
                    jitter_salt=jitter_salt, token_mask=token_mask,
                )
            else:
                plan = top_k_gating(
                    logits, self.k, capacity, self.renormalize,
                    jitter=self.router_jitter,
                    jitter_salt=jitter_salt, token_mask=token_mask,
                )
        # 2) my tokens to their experts' devices
        with jax.named_scope("moe_dispatch"):
            if impl == "gather":
                x_send = dispatch_tokens_indexed(x.astype(compute), plan)
            else:
                x_send = dispatch_tokens(x.astype(compute), plan)  # [E, C, d]
            x_send = x_send.reshape(self.ep, e_local, capacity, d)
            x_recv = jax.lax.all_to_all(
                x_send, "expert", split_axis=0, concat_axis=0, tiled=False
            )  # [ep, e_local, C, d] — slice j = tokens from expert-row peer j

        # 3) batched expert FFN on the MXU (one einsum over the local stack).
        # With tensor parallelism the FFN dim f is sharded over 'model':
        # column-split w1 -> local activations, row-split w2 -> partial
        # sums, one psum completes the contraction (Megatron pattern).
        with jax.named_scope("experts"):
            xe = x_recv.transpose(1, 0, 2, 3).reshape(
                e_local, self.ep * capacity, d
            )
            if self.expert_kind != "gelu":
                def product(w):
                    return jnp.einsum(
                        "egd,edf->egf", xe, params[w].astype(compute))

                if self.expert_kind == "relu2":
                    h = self._gate_act(product("w_up"))
                else:
                    h = self._gate_act(product("w_gate")) * product("w_up")
                ye = jnp.einsum("egf,efd->egd", h,
                                params["w_down"].astype(compute))
                if self.tp > 1:
                    ye = jax.lax.psum(ye, "model")
            else:
                w1 = params["w1"].astype(compute)
                b1 = params["b1"].astype(compute)
                w2 = params["w2"].astype(compute)
                b2 = params["b2"].astype(compute)
                h = jax.nn.gelu(
                    jnp.einsum("egd,edf->egf", xe, w1) + b1[:, None, :]
                )
                ye = jnp.einsum("egf,efd->egd", h, w2)
                if self.tp > 1:
                    ye = jax.lax.psum(ye, "model")
                ye = ye + b2[:, None, :]

        # 4) return outputs to their source devices, and
        # 5) gate-weighted combine for MY tokens
        with jax.named_scope("moe_combine"):
            y_send = ye.reshape(
                e_local, self.ep, capacity, d
            ).transpose(1, 0, 2, 3)
            y_recv = jax.lax.all_to_all(
                y_send, "expert", split_axis=0, concat_axis=0, tiled=False
            ).reshape(self.num_experts, capacity, d)
            if impl == "gather":
                y = combine_outputs_indexed(y_recv, plan).astype(x.dtype)
            else:
                y = combine_outputs(y_recv, plan).astype(x.dtype)

        axes = self._shard
        router_z = _router_z_loss(logits, token_mask)
        aux = {
            "aux_loss": jax.lax.pmean(plan.aux_loss, axes),
            "router_z_loss": jax.lax.pmean(router_z, axes),
            "dropped_fraction": jax.lax.pmean(plan.dropped_fraction, axes),
        }
        return y, aux

    def _local_forward_dropless(
        self, params: Params, x: jax.Array,
        token_mask: jax.Array | None = None,
        router_x: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        """Dropless top-k for MY tokens, all experts here: route in
        float32 (on ``router_x`` where the router has an input of its
        own), sort the n*k assignments by expert, grouped matmuls over
        the sorted rows, unsort, gate-weighted sum.  No capacity, so no
        slot tensor and ``dropped_fraction`` exactly 0."""
        compute = self.dtype
        n = x.shape[0]
        if router_x is None:
            router_x = x
        # the router is 2*d*E operations a token (0.2 % of an OLMoE layer):
        # float32 operands at full precision, so that which experts are
        # the k largest does not hang on a bf16 rounding of the logits
        with jax.named_scope("router"):
            logits = self.router_logits(params, router_x)
            plan = dropless_routing(
                logits, self.k, self.renormalize, token_mask,
                self.router_score, params.get("router_bias"),
                self.routed_scale, *self.router_groups,
            )
        with jax.named_scope("moe_sort"):
            xs = sort_tokens(x.astype(compute), plan)  # [n*k, d]
        if self.expert_kind != "gelu":
            ys = self._unbiased_experts(params, xs, plan.group_sizes)
        else:
            expert_of_row = jnp.repeat(
                jnp.arange(self.num_experts), plan.group_sizes,
                total_repeat_length=n * self.k,
            )
            with jax.named_scope("experts/gate_up"):
                h = jax.nn.gelu(
                    grouped_matmul(
                        xs, params["w1"].astype(compute), plan.group_sizes
                    ) + params["b1"].astype(compute)[expert_of_row]
                )
            with jax.named_scope("experts/down"):
                ys = grouped_matmul(
                    h, params["w2"].astype(compute), plan.group_sizes
                ) + params["b2"].astype(compute)[expert_of_row]
        with jax.named_scope("moe_combine"):
            y = unsort_combine(ys, plan, x.dtype)

        router_z = _router_z_loss(logits, token_mask)
        load = jnp.max(plan.group_sizes).astype(jnp.float32) / (
            n * self.k / self.num_experts
        )
        axes = self._shard
        aux = {
            "aux_loss": jax.lax.pmean(plan.aux_loss, axes),
            "router_z_loss": jax.lax.pmean(router_z, axes),
            "dropped_fraction": jnp.float32(0),
            "expert_load_max_over_mean": jax.lax.pmean(load, axes),
        }
        if self.router_bias:
            aux.update(self._bias_aux(params, plan.group_sizes))
        return y, aux

    def _unbiased_experts(
        self, params: Params, xs: jax.Array, group_sizes: jax.Array
    ) -> jax.Array:
        """The experts without biases on rows sorted by expert.  The gated
        kinds: two grouped matmuls, the gate branch's activation, a third
        (scopes ``experts/gate_up``, ``experts/down``); ``relu2``: one
        grouped matmul, its squared ReLU, a second (``experts/up``,
        ``experts/down``)."""
        compute = self.dtype
        if self.expert_kind == "relu2":
            with jax.named_scope("experts/up"):
                h = self._gate_act(grouped_matmul(
                    xs, params["w_up"].astype(compute), group_sizes))
        else:
            with jax.named_scope("experts/gate_up"):
                h = self._gate_act(grouped_matmul(
                    xs, params["w_gate"].astype(compute), group_sizes)
                ) * grouped_matmul(
                    xs, params["w_up"].astype(compute), group_sizes)
        with jax.named_scope("experts/down"):
            return grouped_matmul(
                h, params["w_down"].astype(compute), group_sizes
            )

    def router_logits(self, params: Params, router_x: jax.Array) -> jax.Array:
        """[n, d] → [n, E] float32: the dropless router's logits, float32
        operands at full precision."""
        return jnp.dot(
            router_x.astype(jnp.float32), params["gate"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )

    def _bias_aux(self, params: Params, counts: jax.Array) -> dict:
        """What the balancing rule reads (the step's assignments per
        expert, over every token shard) and the bias's largest size."""
        return {
            "expert_counts": jax.lax.psum(counts, self._shard),
            "router_bias_abs_max": jnp.max(jnp.abs(params["router_bias"])),
        }

    def _local_forward_share(
        self, params: Params, x: jax.Array,
        token_mask: jax.Array | None = None,
        router_x: jax.Array | None = None,
    ) -> tuple[jax.Array, dict]:
        """``_local_forward_dropless`` for a layer that holds a share of
        its experts: route MY tokens over all ``num_experts``, sort the
        assignments that fall on the held ones into a buffer of
        ``share_buffer_rows`` rows, grouped matmuls over it, and add each
        token's gate-weighted outputs (zero for a token with no assignment
        here).  Assignments beyond the buffer are dropped and counted."""
        compute = self.dtype
        n = x.shape[0]
        held, scored = self.held_experts, self.num_experts
        if router_x is None:
            router_x = x
        with jax.named_scope("router"):
            logits = self.router_logits(params, router_x)
            plan = share_routing(
                logits, self.k, self.first_held_expert, held,
                share_buffer_rows(n, self.k, held, scored),
                self.renormalize, token_mask, self.router_score,
                params.get("router_bias"), self.routed_scale,
                *self.router_groups,
            )
        with jax.named_scope("moe_sort"):
            xs = share_sort_tokens(x.astype(compute), plan)  # [R, d]
        ys = self._unbiased_experts(params, xs, plan.group_sizes)
        with jax.named_scope("moe_combine"):
            y = share_combine(ys, plan, n, x.dtype)

        axes = self._shard
        here = plan.routed_here.astype(jnp.float32)
        kept = plan.group_sizes.sum().astype(jnp.float32)
        aux = {
            "aux_loss": jax.lax.pmean(plan.aux_loss, axes),
            "router_z_loss": jax.lax.pmean(
                _router_z_loss(logits, token_mask), axes
            ),
            # of the assignments that fall on a held expert
            "dropped_fraction": jax.lax.pmean(
                (here - kept) / jnp.maximum(here, 1.0), axes
            ),
            # over all the router's experts, held or not
            "expert_load_max_over_mean": jax.lax.pmean(
                jnp.max(plan.counts).astype(jnp.float32)
                / (n * self.k / scored), axes
            ),
            "local_rows_over_level": jax.lax.pmean(
                here / (n * self.k * held / scored), axes
            ),
        }
        if self.router_bias:
            aux.update(self._bias_aux(params, plan.counts))
        else:  # a share with no bias to level: its load is what the data gives
            aux["held_experts_empty"] = jax.lax.pmax(
                jnp.sum(plan.group_sizes == 0).astype(jnp.float32), axes)
        n_group, topk_group = self.router_groups
        if n_group > 1:  # whole groups are in or out a token
            with jax.named_scope("router"), jax.named_scope("groups"):
                size = scored // n_group
                kept = kept_groups(
                    jax.nn.sigmoid(logits) + params.get("router_bias", 0.0),
                    n_group, topk_group)
                first = self.first_held_expert
                reaching = kept[:, first // size:(first + held - 1) // size + 1]
                aux["groups_reaching_share"] = jax.lax.pmean(
                    jnp.mean(jnp.any(reaching, axis=1).astype(jnp.float32)), axes)
        return y, aux
