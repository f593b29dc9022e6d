"""RemoteExpert: a network-remote expert that behaves like a local function.

Contract from the reference's ``hivemind/client/expert.py`` (SURVEY.md §2;
unverifiable refs, mount empty): ``RemoteExpert`` is an ``nn.Module`` whose
forward serializes inputs and RPCs the server; a custom autograd Function
makes ``backward`` issue a second RPC that returns input-gradients (and, as
a side effect, triggers the server's async optimizer step).

TPU-native realization: a ``jax.custom_vjp`` function whose primal and
cotangent rules are **host callbacks** (``jax.experimental.io_callback``)
doing the framed RPC.  This composes with jit: a training step containing
remote experts compiles into one XLA program with host-offload points where
the network call happens; grads flow through ``jax.grad`` transparently.
Faults here RAISE (single-expert semantics, matching the reference);
k-of-n fault *tolerance* lives in RemoteMixtureOfExperts.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from learning_at_home_tpu.client.rpc import client_loop, pool_registry
from learning_at_home_tpu.utils.connection import Endpoint

logger = logging.getLogger(__name__)


class RemoteExpert:
    """Stub for one expert hosted on a remote Server.

    Output specs (io_callback needs static result shapes) resolve in
    priority order:

    1. an explicit ``output_spec_fn(*input_specs) -> spec-or-tuple``;
    2. the server's published ``output_schema`` (per-row leaf shapes +
       dtypes, set once the expert has warmed up or served a forward) —
       fetched lazily with one ``info`` RPC and cached, this also enables
       **multi-output experts** with no client-side configuration;
    3. fallback: output shaped like the first input (the standard blocks).
    """

    def __init__(
        self,
        uid: str,
        endpoint: Endpoint,
        timeout: float = 30.0,
        output_spec_fn: Optional[Callable] = None,
        wire_dtype: Optional[str] = None,
    ):
        from learning_at_home_tpu.client.rpc import ensure_sync_cpu_dispatch

        ensure_sync_cpu_dispatch()  # host-callback path: see rpc.py
        from learning_at_home_tpu.utils.serialization import validate_wire_dtype

        validate_wire_dtype(wire_dtype)
        # transport encoding: floating payloads downcast both ways (server
        # computes in f32 — see server/connection_handler.py).  NB
        # forward_blocking/backward_blocking then RETURN wire-dtype arrays;
        # the jit path upcasts them to the output specs' dtype.
        self.wire_dtype = wire_dtype
        self.uid = uid
        self.endpoint = (endpoint[0], int(endpoint[1]))
        self.timeout = timeout
        self.output_spec_fn = output_spec_fn
        self._server_output_schema = ()  # () = not fetched yet; None = absent
        self._structure_checked = False
        self._call = self._build_custom_vjp()

    # ---- blocking host-side RPCs (also used by the MoE layer) ----

    async def _rpc(self, msg_type, tensors, meta):
        pool = pool_registry().get(self.endpoint)
        return await pool.rpc(msg_type, tensors, meta, timeout=self.timeout)

    async def _rpc_prepared(self, msg_type, wire, meta):
        pool = pool_registry().get(self.endpoint)
        return await pool.rpc_prepared(msg_type, wire, meta, timeout=self.timeout)

    def _wire_cast(self, arrs) -> list:
        from learning_at_home_tpu.utils.serialization import wire_cast

        return wire_cast(arrs, self.wire_dtype)

    def _wire_meta(self, meta: dict) -> dict:
        if self.wire_dtype is not None:
            meta["wire"] = self.wire_dtype
        return meta

    def _call_blocking(self, msg_type: str, tensors, meta: dict):
        """One exchange with serialization on THIS thread: the wire cast
        above and the spec/blob walk both run on the host thread already
        blocked inside io_callback, so the shared ``lah-client`` loop only
        writes ready buffers."""
        from learning_at_home_tpu.utils.serialization import WireTensors

        wire = WireTensors.prepare(tensors)
        out, _ = client_loop().run(self._rpc_prepared(msg_type, wire, meta))
        return out

    def forward_blocking(self, inputs: Sequence[np.ndarray]) -> list[np.ndarray]:
        return self._call_blocking(
            "forward", self._wire_cast(inputs),
            self._wire_meta({"uid": self.uid}),
        )

    def backward_blocking(
        self, inputs: Sequence[np.ndarray], grad_outputs: Sequence[np.ndarray]
    ) -> list[np.ndarray]:
        return self._call_blocking(
            "backward",
            self._wire_cast([*inputs, *grad_outputs]),
            self._wire_meta({"uid": self.uid, "n_inputs": len(inputs)}),
        )

    def info(self) -> dict:
        _, meta = client_loop().run(self._rpc("info", (), {"uid": self.uid}))
        return meta

    # ---- the jax-transformable call path ----

    def _output_specs(self, input_specs: tuple) -> tuple:
        """Static output specs for io_callback (see class docstring for
        the resolution order).  Always returns a tuple of specs."""
        if self.output_spec_fn is not None:
            spec = self.output_spec_fn(*input_specs)
            return tuple(spec) if isinstance(spec, (tuple, list)) else (spec,)
        if self._server_output_schema == ():
            # cache ONLY a published schema; on RPC failure or a not-yet-
            # warmed server (no schema in info) fall back for THIS trace
            # and re-fetch on the next one — the schema appears as soon as
            # the expert serves its first forward
            try:
                schema = self.info().get("output_schema")
            except Exception:
                logger.warning(
                    "info RPC for %s failed; falling back to "
                    "first-input-shaped output spec", self.uid, exc_info=True
                )
                schema = None
            if schema:
                self._server_output_schema = schema
        else:
            schema = self._server_output_schema
        if schema:
            rows = input_specs[0].shape[0]
            return tuple(
                jax.ShapeDtypeStruct(
                    (rows, *s["shape"]), np.dtype(s["dtype"])
                )
                for s in schema
            )
        return (input_specs[0],)

    def _build_custom_vjp(self):
        def host_backward(n_in, args):
            arrs = [np.asarray(a) for a in args]
            grads = self.backward_blocking(arrs[:n_in], arrs[n_in:])
            if len(grads) != n_in:
                raise ValueError(
                    f"expert {self.uid} returned {len(grads)} input-grads "
                    f"for {n_in} inputs"
                )
            return grads

        @jax.custom_vjp
        def remote_call(*inputs):
            specs = self._output_specs(
                tuple(jax.ShapeDtypeStruct(np.shape(x), x.dtype) for x in inputs)
            )

            def cb(*xs):
                outs = self.forward_blocking([np.asarray(x) for x in xs])
                if len(outs) != len(specs):
                    raise ValueError(
                        f"expert {self.uid} returned {len(outs)} outputs, "
                        f"client expected {len(specs)}"
                    )
                return tuple(
                    np.asarray(o, dtype=s.dtype) for o, s in zip(outs, specs)
                )

            out = io_callback(cb, specs, *inputs)
            return out[0] if len(specs) == 1 else tuple(out)

        def fwd(*inputs):
            return remote_call(*inputs), inputs

        def bwd(residual_inputs, grad_out):
            grads_out = (
                list(grad_out)
                if isinstance(grad_out, (tuple, list))
                else [grad_out]
            )
            n_in = len(residual_inputs)
            # integer wire inputs (e.g. det_dropout's per-row seed) take
            # float0 cotangents, which io_callback cannot produce — the
            # callback ships ALL inputs to the server (it needs them to
            # re-forward) but returns grads only for the float primals
            diff_idx = tuple(
                i for i, x in enumerate(residual_inputs)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
            )
            diff_specs = tuple(
                jax.ShapeDtypeStruct(
                    np.shape(residual_inputs[i]), residual_inputs[i].dtype
                )
                for i in diff_idx
            )
            def cb(*args):
                grads = host_backward(n_in, args)
                return tuple(
                    np.asarray(grads[i], dtype=s.dtype)
                    for i, s in zip(diff_idx, diff_specs)
                )

            diff_grads = io_callback(cb, diff_specs, *residual_inputs, *grads_out)
            by_idx = dict(zip(diff_idx, diff_grads))
            return tuple(
                by_idx.get(i, np.zeros(np.shape(x), jax.dtypes.float0))
                for i, x in enumerate(residual_inputs)
            )

        remote_call.defvjp(fwd, bwd)
        return remote_call

    def __call__(self, *inputs):
        """Jit/grad-compatible remote forward; backward RPCs on the vjp.

        Arguments may be arbitrary pytrees of arrays — they are flattened
        to the wire's flat-tensor order (jax flattening), and on the first
        nested call the client checks its structure against the server's
        published ``input_schema`` so a flatten-order mismatch (e.g.
        OrderedDict vs plain dict) fails loudly instead of silently
        binding tensors to the wrong arguments."""
        leaves = jax.tree_util.tree_leaves(inputs)
        if len(leaves) != len(inputs) and not self._structure_checked:
            self._check_structure(inputs)
        return self._call(*leaves)

    def _check_structure(self, inputs: tuple) -> None:
        from learning_at_home_tpu.utils.nested import schema_from_tree

        server_schema = self.info().get("input_schema")
        if server_schema is not None:
            client_tree = inputs[0] if len(inputs) == 1 else tuple(inputs)
            client_schema = schema_from_tree(client_tree)
            if client_schema != server_schema:
                raise ValueError(
                    f"input structure mismatch for expert {self.uid}: "
                    f"client sends {client_schema}, server expects "
                    f"{server_schema} — tensors would bind to the wrong "
                    "arguments"
                )
        self._structure_checked = True

    def __repr__(self) -> str:
        return f"RemoteExpert({self.uid!r} @ {self.endpoint[0]}:{self.endpoint[1]})"
