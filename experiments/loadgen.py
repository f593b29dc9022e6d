#!/usr/bin/env python
"""Open-loop load generator for the serving gateway (ISSUE 12/13).

Poisson arrivals at a target rate (exponential inter-arrival gaps — the
open-loop discipline: arrivals do NOT wait for earlier requests, so a
saturated gateway sees real queue growth instead of the closed-loop
self-throttling that hides it), per-request prompt/length sampling, and a
JSON report:

- ``tokens_per_sec`` served (completed streams' tokens over the wall),
- ``ttft_p50_ms`` / ``ttft_p99_ms`` — submit-accepted → first token,
- ``itl_p50_ms`` / ``itl_p99_ms`` — gaps between token receipts
  (measured at poll granularity),
- ``shed_fraction`` — sheds / arrivals (a shed is counted, not retried:
  the report is about what THIS rate does to THIS gateway),
- ``errors`` / ``crashes`` — stream-level error replies vs client-side
  exceptions (the acceptance bar wants zero of the latter at any load).

Workload shaping (ISSUE 13 — the paged-KV/chunked-prefill A/B knobs):

- ``prompt_len_dist`` — a weighted mixture of named length buckets
  (``[("short", 4, 12, 0.8), ("long", 40, 80, 0.2)]``); the report
  carries TTFT/ITL percentiles PER BUCKET under ``"buckets"``, which is
  how the bench shows a long prompt's prefill no longer spikes short
  streams' ITL;
- ``prefix_share`` / ``prefix_len`` — with probability ``prefix_share``
  a request's first ``min(prefix_len, len-1)`` tokens are one fixed
  seed-derived shared prefix (total length still comes from the bucket,
  so prefix on/off A/Bs compare equal-length work) — the shared-prefix
  workload the gateway's content-addressed prefix cache accelerates;
- ``temperature`` / ``top_p`` / ``top_k`` / ``sample_seed`` (ISSUE 17)
  — per-request sampling knobs forwarded as optional ``gen_submit``
  fields.  Request *i* samples under seed ``sample_seed + i``, so a
  rerun at the same base seed replays token-identical sampled streams
  (the gateway's counter-based RNG); all-None keeps greedy requests
  with no sampling fields on the wire.

Importable (``run_load``) for collect_gate.py, or a CLI::

    python experiments/loadgen.py --endpoint 127.0.0.1:31400 \
        --rate 20 --duration 10 \
        --prompt-len-dist short:4:12:0.8,long:40:80:0.2 \
        --prefix-share 0.5 --prefix-len 24
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from learning_at_home_tpu.utils import sanitizer  # noqa: E402


def _pct(values, q) -> float:
    # shared percentile engine (ISSUE 19): "linear" reproduces
    # np.percentile's lerp bit-for-bit — pinned by tests/test_sketch.py
    from learning_at_home_tpu.utils.sketch import percentile

    return percentile(values, q, method="linear", default=0.0)


def check_floors(
    report: dict, *, min_completed: int = 1, max_shed: int = 0,
    max_errors: int = 0, ttft_p99_max_ms: Optional[float] = None,
) -> list:
    """Declarative floors over a :func:`run_load` report (ISSUE 19):
    the same ``Threshold`` / ``evaluate_thresholds`` engine as the
    rebalancer's SLO gate and the macro-sim ``--check`` ceilings, so
    collect_gate smokes assert loadgen health through one evaluator.
    Returns failure detail strings (empty = healthy)."""
    from learning_at_home_tpu.utils.slo import Threshold, evaluate_thresholds

    specs = [
        Threshold("completed_floor", "completed", ">=",
                  float(min_completed)),
        Threshold("shed_ceiling", "shed", "<=", float(max_shed)),
        Threshold("errors_ceiling", "errors", "<=", float(max_errors)),
        Threshold("crashes_zero", "crashes", "<=", 0.0),
    ]
    if ttft_p99_max_ms is not None:
        specs.append(
            Threshold("ttft_p99_ceiling", "ttft_p99_ms", "<=",
                      float(ttft_p99_max_ms))
        )
    return [v["detail"] for v in evaluate_thresholds(report, specs)]


def parse_len_dist(spec: str) -> list:
    """``"short:4:12:0.8,long:40:80:0.2"`` → [(name, lo, hi, weight)]."""
    out = []
    for part in spec.split(","):
        fields = part.strip().split(":")
        if len(fields) != 4:
            raise ValueError(
                f"bucket {part!r} must be name:min:max:weight"
            )
        name, lo, hi, w = fields
        out.append((name, int(lo), int(hi), float(w)))
    if not out or sum(w for *_x, w in out) <= 0:
        raise ValueError(f"no usable buckets in {spec!r}")
    return out


def run_load(
    endpoint,
    *,
    rate_hz: float,
    duration_s: float,
    prompt_len: tuple = (4, 12),
    max_new: tuple = (8, 16),
    vocab: int = 258,
    seed: int = 0,
    poll_interval_s: float = 0.005,
    drain_timeout_s: float = 120.0,
    prompt_len_dist: list = None,
    prefix_share: float = 0.0,
    prefix_len: int = 0,
    temperature: float = None,
    top_p: float = None,
    top_k: int = None,
    sample_seed: int = None,
    trace=None,
) -> dict:
    """Drive one gateway open-loop and return the JSON-ready report.

    Every arrival runs on its own thread (submit + poll via
    :class:`GatewayClient`; the RPC pool muxes them over shared
    connections).  After the arrival window closes, in-flight streams are
    drained up to ``drain_timeout_s`` so served-token counts are not
    truncated mid-stream.

    ``trace`` (a :class:`~learning_at_home_tpu.sim.trace.Trace` or a
    segment-spec string — the SAME grammar the macro-sim scenarios use)
    replaces the constant-rate Poisson process with the trace's arrival
    schedule: ``rate_hz`` and ``duration_s`` are then taken from the
    trace, so a shape validated in simulation replays 1:1 against a real
    gateway."""
    from learning_at_home_tpu.gateway import GatewayClient

    if isinstance(trace, str):
        from learning_at_home_tpu.sim.trace import parse_trace
        trace = parse_trace(trace)
    if trace is not None:
        duration_s = trace.duration_s
        rate_hz = (
            sum(s.rate_hz * s.duration_s for s in trace.segments)
            / max(1e-9, duration_s)
        )

    client = GatewayClient(endpoint)
    rng = np.random.RandomState(seed)
    if prompt_len_dist is None:
        prompt_len_dist = [("all", prompt_len[0], prompt_len[1], 1.0)]
    weights = np.asarray([w for *_x, w in prompt_len_dist], float)
    weights = weights / weights.sum()
    # the shared prefix is derived from the seed ONLY — every run_load
    # with the same seed targets the same resident pages, which is what
    # lets a warm gateway show cross-run prefix hits
    prefix_rng = np.random.RandomState(seed + 104729)
    shared_prefix = (
        prefix_rng.randint(0, vocab, size=max(0, int(prefix_len))).tolist()
        if prefix_len > 0 else []
    )
    lock = sanitizer.lock("loadgen.report")
    report = {
        "arrivals": 0, "completed": 0, "shed": 0, "shed_with_retry_after": 0,
        "errors": 0, "crashes": 0, "tokens_served": 0,
        "prefix_share": float(prefix_share), "prefix_len": int(prefix_len),
    }
    if any(v is not None for v in (temperature, top_p, top_k, sample_seed)):
        report["sampling"] = {
            "temperature": temperature, "top_p": top_p, "top_k": top_k,
            "sample_seed": sample_seed,
        }
    ttfts: list[float] = []
    itls: list[float] = []
    buckets = {
        name: {"arrivals": 0, "completed": 0, "shed": 0,
               "ttfts": [], "itls": []}
        for name, *_rest in prompt_len_dist
    }
    threads: list[threading.Thread] = []

    def one_request(prompt, n_new, bucket, req_seed) -> None:
        token_times: list[float] = []
        t_submit = time.monotonic()
        try:
            out = client.generate(
                prompt, n_new,
                poll_interval_s=poll_interval_s,
                deadline_s=drain_timeout_s,
                on_token=token_times.append,
                seed=req_seed, temperature=temperature,
                top_p=top_p, top_k=top_k,
            )
        except Exception:
            with lock:
                report["crashes"] += 1
            return
        with lock:
            if out.get("shed"):
                report["shed"] += 1
                buckets[bucket]["shed"] += 1
                # a well-formed shed carries a positive retry-after —
                # the overload acceptance bar checks this count == shed
                ra = out.get("retry_after_s")
                if isinstance(ra, (int, float)) and ra > 0:
                    report["shed_with_retry_after"] += 1
                return
            if out.get("error"):
                report["errors"] += 1
                return
            report["completed"] += 1
            buckets[bucket]["completed"] += 1
            report["tokens_served"] += len(out["tokens"])
            if token_times:
                ttfts.append(token_times[0] - t_submit)
                buckets[bucket]["ttfts"].append(token_times[0] - t_submit)
                gaps = np.diff(token_times).tolist()
                itls.extend(gaps)
                buckets[bucket]["itls"].extend(gaps)

    t0 = time.monotonic()
    deadline = t0 + duration_s
    if trace is not None:
        import random as pyrandom

        # the same seeded thinning stream the macro-sim injector draws,
        # so sim and real replay the identical arrival schedule
        _offsets = trace.iter_arrivals(pyrandom.Random(f"{seed}|trace"))
        next_arrival = next(_offsets, None)
        next_arrival = None if next_arrival is None else t0 + next_arrival
    else:
        _offsets = None
        next_arrival = t0
    while next_arrival is not None and next_arrival < deadline:
        delay = next_arrival - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        b = int(rng.choice(len(prompt_len_dist), p=weights))
        name, lo, hi, _w = prompt_len_dist[b]
        p_len = int(rng.randint(lo, hi + 1))
        n_new = int(rng.randint(max_new[0], max_new[1] + 1))
        prompt = rng.randint(0, vocab, size=p_len).tolist()
        if shared_prefix and rng.random_sample() < prefix_share:
            # keep the TOTAL length from the bucket so prefix on/off
            # A/Bs compare equal-length work; at least one tail token
            # stays private (the cache never skips the final position)
            k = min(len(shared_prefix), p_len - 1)
            if k > 0:
                prompt = shared_prefix[:k] + prompt[k:]
        # per-arrival sampling seed: decorrelated streams, reproducible
        # per (sample_seed, arrival index) — two runs at the same seed
        # replay token-identical sampled streams (counter-based RNG)
        req_seed = (
            int(sample_seed) + report["arrivals"]
            if sample_seed is not None else None
        )
        th = threading.Thread(
            target=one_request, args=(prompt, n_new, name, req_seed),
            daemon=True,
        )
        th.start()
        threads.append(th)
        report["arrivals"] += 1
        buckets[name]["arrivals"] += 1
        if _offsets is not None:
            t = next(_offsets, None)
            next_arrival = None if t is None else t0 + t
        else:
            next_arrival += float(rng.exponential(1.0 / rate_hz))
    for th in threads:
        th.join(timeout=drain_timeout_s)
    wall = time.monotonic() - t0
    with lock:
        out = dict(report)
        bucket_rows = {
            name: {
                "arrivals": rec["arrivals"],
                "completed": rec["completed"],
                "shed": rec["shed"],
                "ttft_p50_ms": round(_pct(rec["ttfts"], 50) * 1e3, 1),
                "ttft_p99_ms": round(_pct(rec["ttfts"], 99) * 1e3, 1),
                "itl_p50_ms": round(_pct(rec["itls"], 50) * 1e3, 1),
                "itl_p99_ms": round(_pct(rec["itls"], 99) * 1e3, 1),
            }
            for name, rec in buckets.items()
        }
    if trace is not None:
        from learning_at_home_tpu.sim.trace import trace_to_json
        out["trace"] = trace_to_json(trace)
    out.update(
        rate_hz=round(rate_hz, 3),
        duration_s=duration_s,
        wall_s=round(wall, 3),
        tokens_per_sec=round(out["tokens_served"] / wall, 2) if wall else 0.0,
        shed_fraction=round(
            out["shed"] / out["arrivals"], 4
        ) if out["arrivals"] else 0.0,
        ttft_p50_ms=round(_pct(ttfts, 50) * 1e3, 1),
        ttft_p99_ms=round(_pct(ttfts, 99) * 1e3, 1),
        itl_p50_ms=round(_pct(itls, 50) * 1e3, 1),
        itl_p99_ms=round(_pct(itls, 99) * 1e3, 1),
        buckets=bucket_rows,
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--endpoint", required=True,
                    help="gateway host:port (frontdoor RPC port)")
    ap.add_argument("--rate", type=float, default=10.0,
                    help="mean Poisson arrival rate, requests/s")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="arrival window, seconds (drain not included)")
    ap.add_argument("--trace", type=str, default=None,
                    help="arrival-trace segment spec (sim/trace.py "
                         "grammar, e.g. 'poisson:20:10,burst:200:3,"
                         "diurnal:30:60:0.5:20'); overrides "
                         "--rate/--duration with the trace's schedule")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 12),
                    metavar=("MIN", "MAX"))
    ap.add_argument("--prompt-len-dist", type=str, default=None,
                    help="weighted length buckets, e.g. "
                         "'short:4:12:0.8,long:40:80:0.2' "
                         "(overrides --prompt-len; per-bucket TTFT/ITL "
                         "percentiles are reported)")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="fraction of requests whose prompt starts with "
                         "the fixed seed-derived shared prefix")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="length of the shared prefix (tokens)")
    ap.add_argument("--max-new", type=int, nargs=2, default=(8, 16),
                    metavar=("MIN", "MAX"))
    ap.add_argument("--vocab", type=int, default=258)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=None,
                    help="sampling temperature for every request "
                         "(default: greedy — no sampling fields on the "
                         "wire at all)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus-sampling mass (requires temperature)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="top-k truncation (requires temperature)")
    ap.add_argument("--sample-seed", type=int, default=None,
                    help="base sampling seed; request i uses "
                         "sample-seed + i, so reruns replay "
                         "token-identical sampled streams")
    args = ap.parse_args(argv)
    host, _, port = args.endpoint.rpartition(":")
    if not port.isdigit():
        raise SystemExit(f"--endpoint {args.endpoint!r} must be host:port")
    report = run_load(
        (host, int(port)),
        rate_hz=args.rate,
        duration_s=args.duration,
        prompt_len=tuple(args.prompt_len),
        max_new=tuple(args.max_new),
        vocab=args.vocab,
        seed=args.seed,
        prompt_len_dist=(
            parse_len_dist(args.prompt_len_dist)
            if args.prompt_len_dist else None
        ),
        prefix_share=args.prefix_share,
        prefix_len=args.prefix_len,
        temperature=args.temperature,
        top_p=args.top_p,
        top_k=args.top_k,
        sample_seed=args.sample_seed,
        trace=args.trace,
    )
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
