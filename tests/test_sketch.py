"""Shared percentile helper + mergeable quantile sketches (ISSUE 19).

The contracts under test:

- **percentile parity**: ``percentile(..., method="linear")`` is
  bit-for-bit ``np.percentile``'s default interpolation (the loadgen and
  bench numbers must not move when they switch off numpy), and
  ``method="nearest"`` reproduces the macro-sim's historical pure-Python
  nearest-rank formula exactly, banker's rounding included;
- **sketch accuracy**: for positive values, ``quantile(q)`` is within
  ``relative_accuracy`` of the true nearest-rank percentile;
- **merge correctness**: merging sketches equals sketching the
  concatenation (bucketwise sum), and a fixture where the documented
  MAX-of-locals fallback is off by 1000× shows WHY the sketch path
  exists;
- **wire form**: JSON round-trips preserve every query; malformed wire
  dicts degrade to None (lah_top's never-crash contract), never raise;
- **registry backing**: histograms export a sketch in their snapshot.
"""

import json
import math
import random

import numpy as np
import pytest

from learning_at_home_tpu.utils.metrics import MetricsRegistry
from learning_at_home_tpu.utils.sketch import (
    QuantileSketch,
    merge_dicts,
    percentile,
    try_from_dict,
)

QS = (0.0, 1.0, 10.0, 25.0, 50.0, 75.0, 90.0, 95.0, 99.0, 99.9, 100.0)


# ---------------------------------------------------------------------------
# percentile parity (satellite: three private helpers → one definition)
# ---------------------------------------------------------------------------


def _old_sim_pct(values, q):
    """The macro-sim's former private nearest-rank helper, verbatim."""
    if not values:
        return 0.0
    vs = sorted(values)
    idx = int(round((q / 100.0) * (len(vs) - 1)))
    return vs[min(len(vs) - 1, max(0, idx))]


def test_linear_matches_numpy_bit_for_bit():
    rng = np.random.default_rng(7)
    for n in (1, 2, 3, 4, 7, 31, 100, 999):
        vals = (rng.standard_normal(n) * 37.0 + 5.0).tolist()
        for q in QS:
            ours = percentile(vals, q, method="linear")
            theirs = float(np.percentile(np.asarray(vals), q))
            assert ours == theirs, (n, q, ours, theirs)


def test_linear_matches_numpy_on_adversarial_inputs():
    cases = [
        [1.0],
        [2.0, 1.0],
        [0.1, 0.1, 0.1],
        [1e-9, 1e9],
        [-5.0, -1.0, 0.0, 3.0],
        list(range(10)),
    ]
    for vals in cases:
        for q in QS:
            assert percentile(vals, q, method="linear") == float(
                np.percentile(np.asarray(vals, dtype=float), q)
            )


def test_nearest_matches_old_sim_formula_including_bankers_rounding():
    rng = random.Random(3)
    for n in (1, 2, 3, 5, 10, 101):
        vals = [rng.uniform(0, 100) for _ in range(n)]
        for q in QS:
            assert percentile(vals, q, method="nearest") == _old_sim_pct(
                vals, q
            ), (n, q)
    # the banker's-rounding edge the old formula had: n=2, q=50 →
    # rank 0.5 → round() → 0 → the LOWER value
    assert percentile([1.0, 9.0], 50, method="nearest") == 1.0


def test_percentile_empty_and_unknown_method():
    assert percentile([], 99) == 0.0
    assert percentile([], 99, default=-1.0) == -1.0
    with pytest.raises(ValueError):
        percentile([1.0, 2.0], 50, method="midpoint")


# ---------------------------------------------------------------------------
# sketch accuracy + merge
# ---------------------------------------------------------------------------


def test_sketch_quantile_within_relative_accuracy():
    rng = random.Random(11)
    for trial in range(4):
        vals = [rng.lognormvariate(0.0, 2.0) for _ in range(500)]
        sk = QuantileSketch()
        for v in vals:
            sk.add(v)
        for q in (1.0, 10.0, 50.0, 90.0, 95.0, 99.0, 100.0):
            truth = percentile(vals, q, method="nearest")
            est = sk.quantile(q)
            assert abs(est - truth) <= sk.relative_accuracy * truth * (
                1.0 + 1e-9
            ), (trial, q, est, truth)


def test_sketch_merge_equals_sketch_of_concatenation():
    rng = random.Random(5)
    halves = [
        [rng.expovariate(1.0) for _ in range(200)],
        [rng.expovariate(0.1) for _ in range(300)],
    ]
    whole = QuantileSketch()
    for vals in halves:
        for v in vals:
            whole.add(v)
    merged = QuantileSketch()
    for vals in halves:
        part = QuantileSketch()
        for v in vals:
            part.add(v)
        merged.merge(part)
    assert merged.count == whole.count
    assert merged.bins == whole.bins
    for q in (1.0, 50.0, 95.0, 99.0):
        assert merged.quantile(q) == whole.quantile(q)


def test_max_merge_is_provably_wrong_sketch_merge_is_right():
    """5 slow samples on one peer vs 995 fast ones on another: the true
    fleet p99 is the FAST latency (rank 989 of 1000 sits deep in the
    fast mass), but MAX-of-per-peer-p99s reports the slow peer's 1 s —
    three orders of magnitude off.  The merged sketch gets it right."""
    slow, fast = QuantileSketch(), QuantileSketch()
    for _ in range(5):
        slow.add(1.0)
    for _ in range(995):
        fast.add(0.001)
    truth = percentile([1.0] * 5 + [0.001] * 995, 99, method="nearest")
    assert truth == 0.001
    max_rule = max(slow.quantile(99), fast.quantile(99))
    assert max_rule >= 0.99  # the documented fallback: wildly pessimistic
    merged = QuantileSketch().merge(slow).merge(fast)
    assert abs(merged.quantile(99) - truth) <= 0.01 * truth * (1 + 1e-9)


def test_sketch_mismatched_accuracy_refuses_merge():
    with pytest.raises(ValueError):
        QuantileSketch(0.01).merge(QuantileSketch(0.05))


def test_sketch_zero_negative_and_nan_values():
    sk = QuantileSketch()
    for v in (0.0, -3.0, float("nan"), 2.0):
        sk.add(v)
    assert sk.count == 3  # NaN dropped, zero/negative counted
    assert sk.zero_count == 2
    assert sk.quantile(0) == -3.0  # rank 0 is the exact min
    assert sk.quantile(100) <= sk.max


def test_sketch_max_bins_collapses_lowest_first():
    sk = QuantileSketch(max_bins=16)
    for i in range(-40, 40):
        sk.add(math.exp(i))  # one value per decade-ish bucket
    assert len(sk.bins) <= 16
    # the collapse eats the LOW end: the top quantile stays accurate
    assert abs(sk.quantile(100) - math.exp(39)) <= 0.01 * math.exp(39) * (
        1 + 1e-9
    )


# ---------------------------------------------------------------------------
# wire form
# ---------------------------------------------------------------------------


def test_wire_form_json_round_trip_preserves_queries():
    rng = random.Random(9)
    sk = QuantileSketch()
    for _ in range(300):
        sk.add(rng.lognormvariate(0.0, 1.5))
    back = QuantileSketch.from_dict(json.loads(json.dumps(sk.to_dict())))
    assert back.count == sk.count and back.sum == sk.sum
    assert back.min == sk.min and back.max == sk.max
    for q in (1.0, 50.0, 99.0):
        assert back.quantile(q) == sk.quantile(q)


def test_wire_form_empty_sketch_round_trip():
    back = QuantileSketch.from_dict(QuantileSketch().to_dict())
    assert back.count == 0 and back.quantile(99) == 0.0


def test_try_from_dict_tolerates_garbage():
    good = QuantileSketch()
    good.add(1.0)
    wire = good.to_dict()
    assert try_from_dict(wire) is not None
    for junk in (
        None, 7, "sketch", [], {},
        {"kind": "histogram"},  # wrong discriminator
        {**wire, "ra": "fast"},  # non-numeric accuracy
        {**wire, "bins": [[0]]},  # malformed pair
        {**wire, "bins": [[0, -5]]},  # negative bucket count
        {**wire, "count": None},
    ):
        assert try_from_dict(junk) is None, junk


def test_merge_dicts_skips_malformed_and_none_when_nothing_merged():
    a, b = QuantileSketch(), QuantileSketch()
    for _ in range(10):
        a.add(0.5)
        b.add(2.0)
    merged = merge_dicts([a.to_dict(), {"kind": "nope"}, b.to_dict()])
    assert merged is not None and merged.count == 20
    assert merge_dicts([]) is None
    assert merge_dicts([None, {}, "x"]) is None


# ---------------------------------------------------------------------------
# registry histograms carry sketches (the /metrics.json wire path)
# ---------------------------------------------------------------------------


def test_registry_histogram_exports_sketch():
    reg = MetricsRegistry()
    h = reg.histogram("lah_t_lat_seconds")
    for v in (0.001, 0.002, 0.004, 1.0):
        h.observe(v)
    snap = reg.snapshot()
    wire = snap["histograms"]["lah_t_lat_seconds"]["sketch"]
    json.dumps(wire)  # JSON-safe in place
    sk = try_from_dict(wire)
    assert sk is not None and sk.count == 4
    # labeled histograms carry one sketch per label variant
    hl = reg.histogram("lah_t_lbl_seconds")
    hl.observe(0.5, pool="a")
    hl.observe(2.5, pool="b")
    labelled = reg.snapshot()["histograms"]["lah_t_lbl_seconds"]
    variants = [v for v in labelled.values() if isinstance(v, dict)]
    assert len(variants) == 2
    assert all(try_from_dict(v["sketch"]) is not None for v in variants)
