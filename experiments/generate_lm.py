#!/usr/bin/env python
"""Autoregressive generation from a pod-mode DMoE-Transformer checkpoint.

The serving-side complement of ``train_lm.py --mode pod``: restores a
checkpoint saved with ``--checkpoint-dir``, decodes continuations for a
prompt with the KV-cache decoder (``generate(use_cache=True)``, O(S·d)
per token — see models/transformer.py), and reports decode steps/sec.
Works on fresh random weights too (``--no-checkpoint``) as a pure
throughput probe.

The reference has no generation path at all (it is a training framework);
this exists because a complete LM stack needs one, and the TPU-native
design (static-shape caches, jit-compiled decode loop) is where it pays.

``--swarm`` (ISSUE 12) decodes against live expert servers instead: the
trunk runs locally and every MoE layer goes over the wire through the
same :class:`~learning_at_home_tpu.models.swarm_decoder.SwarmKVDecoder`
the serving gateway batches with — one decode path, two front ends.  The
pod-mode path is untouched by the flag.

Usage:
  python experiments/generate_lm.py --checkpoint-dir /tmp/ckpt \
      --prompt "the meaning of life" --max-new-tokens 64
  python experiments/generate_lm.py --no-checkpoint --bench 128
  python experiments/generate_lm.py --no-checkpoint --swarm \
      --expert-server 127.0.0.1:31337 --prompt "the " --max-new-tokens 16
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _parse_ep(s: str) -> tuple[str, int]:
    host, sep, port = s.rpartition(":")
    if not sep or not port.isdigit():
        raise SystemExit(f"endpoint {s!r} must be host:port")
    return (host, int(port))


def _swarm_main(p, args) -> None:
    """The ``--swarm`` arm: local trunk + remote experts through the
    gateway's own KV decoder (models/swarm_decoder.py) — the shared
    decode helper is the point, not a reimplementation."""
    import jax

    from learning_at_home_tpu.client import reset_client_rpc
    from learning_at_home_tpu.client.routing import StaticExpertSource
    from learning_at_home_tpu.models.data import VOCAB_SIZE, encode_bytes
    from learning_at_home_tpu.models.swarm_decoder import SwarmKVDecoder
    from learning_at_home_tpu.models.transformer_swarm import (
        SwarmDMoETransformerLM,
        SwarmTransformerConfig,
    )

    if args.initial_peers:
        from learning_at_home_tpu.dht import DHT

        source = DHT(
            initial_peers=[_parse_ep(s) for s in args.initial_peers]
        )
    elif args.expert_server:
        eps = [_parse_ep(s) for s in args.expert_server]
        if len(eps) == 1:
            eps = eps * args.n_layers
        if len(eps) != args.n_layers:
            p.error(f"--expert-server: pass 1 endpoint or exactly "
                    f"n_layers ({args.n_layers})")
        source = StaticExpertSource({
            f"{args.uid_prefix}{layer}.{e}": eps[layer]
            for layer in range(args.n_layers)
            for e in range(args.experts_per_layer)
        })
    else:
        p.error("--swarm needs --expert-server or --initial-peers")

    cfg = SwarmTransformerConfig(
        vocab_size=VOCAB_SIZE,
        d_model=args.d_model,
        n_layers=args.n_layers,
        seq_len=args.seq_len,
        grid_size=(args.experts_per_layer,),
        k_best=args.k,
        uid_prefix=args.uid_prefix,
    )
    model = SwarmDMoETransformerLM(cfg, source)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    if args.checkpoint_dir:
        from learning_at_home_tpu.utils.checkpoint import (
            latest_step,
            restore_pytree,
        )

        step = latest_step(args.checkpoint_dir)
        if step is None:
            raise SystemExit(f"no checkpoint under {args.checkpoint_dir}")
        params = restore_pytree(args.checkpoint_dir, step, "params", params)
        print(f"# restored step {step}", file=sys.stderr, flush=True)

    prompt = list(encode_bytes(args.prompt))
    if not prompt:
        raise SystemExit("--prompt must encode to at least one byte")
    if len(prompt) + args.max_new_tokens > cfg.seq_len:
        raise SystemExit(
            f"prompt ({len(prompt)}) + max_new_tokens "
            f"({args.max_new_tokens}) exceeds seq_len {cfg.seq_len}"
        )
    kv_kwargs = {"kv_layout": args.kv_layout}
    if args.kv_layout == "paged":
        kv_kwargs["page_len"] = args.page_len
    try:
        dec = SwarmKVDecoder(model, params, max_slots=args.batch,
                             **kv_kwargs)
        outs = dec.generate([prompt] * args.batch, args.max_new_tokens)
        text = bytes(t for t in outs[0] if t < 256).decode(
            "utf-8", errors="replace"
        )
        print(json.dumps({"completion": text, "mode": "swarm"}), flush=True)
        if args.bench:
            n = args.bench
            if len(prompt) + n > cfg.seq_len:
                raise SystemExit(f"--bench {n} exceeds seq_len headroom")
            bench_dec = SwarmKVDecoder(model, params, max_slots=args.batch,
                                       **kv_kwargs)
            t0 = time.perf_counter()
            bench_dec.generate([prompt] * args.batch, n)
            dt = time.perf_counter() - t0
            print(json.dumps({
                "decode_steps_per_sec": round(n / dt, 1),
                "tokens_per_sec": round(args.batch * n / dt, 1),
                "mode": "swarm",
                "batch": args.batch,
                "seq_len": cfg.seq_len,
                "kv_layout": args.kv_layout,
            }), flush=True)
    finally:
        reset_client_rpc()


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--no-checkpoint", action="store_true",
                   help="random init (throughput probe)")
    p.add_argument("--prompt", default="the ")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--batch", type=int, default=1,
                   help="decode the prompt this many times in parallel")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--num-experts", type=int, default=256)
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--no-cache", action="store_true",
                   help="use the O(S^2) re-forward decoder instead")
    p.add_argument("--bench", type=int, default=0, metavar="N",
                   help="also time N decode steps (steady state)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--swarm", action="store_true",
                   help="decode against live expert servers (the gateway's "
                        "KV decoder) instead of the pod-mode model")
    p.add_argument("--kv-layout", choices=("dense", "paged"),
                   default="dense",
                   help="[swarm] KV cache layout: the static per-slot "
                        "table, or the paged pool the gateway serves "
                        "from (bitwise-identical tokens either way)")
    p.add_argument("--page-len", type=int, default=16,
                   help="[swarm] tokens per KV page for --kv-layout paged")
    p.add_argument("--expert-server", action="append", default=[],
                   metavar="HOST:PORT",
                   help="[swarm] expert server endpoint; one entry maps "
                        "every expert to it, n_layers entries map layer-wise")
    p.add_argument("--initial-peers", nargs="+", default=None,
                   metavar="HOST:PORT",
                   help="[swarm] DHT bootstrap peers (experts DISCOVERED "
                        "instead of typed)")
    p.add_argument("--uid-prefix", default="ffn",
                   help="[swarm] expert uid prefix (layer l expert e is "
                        "<prefix><l>.<e>)")
    p.add_argument("--experts-per-layer", type=int, default=2)
    args = p.parse_args()
    if not args.checkpoint_dir and not args.no_checkpoint:
        p.error("pass --checkpoint-dir or --no-checkpoint")
    from learning_at_home_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()
    if args.swarm:
        if args.temperature > 0 or args.no_cache:
            p.error("--swarm decodes greedily through the KV decoder "
                    "(no --temperature / --no-cache)")
        return _swarm_main(p, args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_at_home_tpu.models.data import VOCAB_SIZE, encode_bytes
    from learning_at_home_tpu.models.transformer import (
        DMoETransformerConfig,
        DMoETransformerLM,
    )
    from learning_at_home_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"expert": 1}, devices=jax.devices()[:1])
    on_tpu = jax.devices()[0].platform != "cpu"
    cfg = DMoETransformerConfig(
        vocab_size=VOCAB_SIZE,
        d_model=args.d_model,
        n_layers=args.n_layers,
        seq_len=args.seq_len,
        num_experts=args.num_experts,
        k=args.k,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    model = DMoETransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    if args.checkpoint_dir:
        from learning_at_home_tpu.utils.checkpoint import (
            latest_step,
            restore_pytree,
        )

        step = latest_step(args.checkpoint_dir)
        if step is None:
            raise SystemExit(f"no checkpoint under {args.checkpoint_dir}")
        params = restore_pytree(args.checkpoint_dir, step, "params", params)
        print(f"# restored step {step}", file=sys.stderr, flush=True)

    prompt = np.asarray(encode_bytes(args.prompt), np.int32)
    if len(prompt) == 0:
        raise SystemExit(
            "--prompt must encode to at least one byte (an empty prompt "
            "would mis-index the decode buffer)"
        )
    if len(prompt) + args.max_new_tokens > cfg.seq_len:
        raise SystemExit(
            f"prompt ({len(prompt)}) + max_new_tokens "
            f"({args.max_new_tokens}) exceeds seq_len {cfg.seq_len}"
        )
    ids = jnp.asarray(np.tile(prompt[None, :], (args.batch, 1)))
    rng = jax.random.PRNGKey(args.seed) if args.temperature > 0 else None

    out = model.generate(
        params, ids, args.max_new_tokens,
        temperature=args.temperature, rng=rng,
        use_cache=not args.no_cache,
    )
    text = bytes(
        int(t) for t in np.asarray(out[0]) if int(t) < 256
    ).decode("utf-8", errors="replace")
    print(json.dumps({"completion": text}), flush=True)

    if args.bench:
        n = args.bench
        if len(prompt) + n > cfg.seq_len:
            raise SystemExit(f"--bench {n} exceeds seq_len headroom")
        gen_kw = dict(
            temperature=args.temperature, rng=rng,
            use_cache=not args.no_cache,
        )
        # warm AND drain the warm run before the timer starts (async
        # dispatch: an unsynchronized warmup still executes inside the
        # timed window and halves the reported rate)
        jax.block_until_ready(model.generate(params, ids, n, **gen_kw))
        t0 = time.perf_counter()
        r = model.generate(params, ids, n, **gen_kw)
        jax.block_until_ready(r)
        dt = time.perf_counter() - t0
        print(json.dumps({
            "decode_steps_per_sec": round(n / dt, 1),
            "tokens_per_sec": round(args.batch * n / dt, 1),
            "use_cache": not args.no_cache,
            "temperature": args.temperature,
            "batch": args.batch,
            "seq_len": cfg.seq_len,
        }), flush=True)


if __name__ == "__main__":
    main()
