"""What reads ``tests/benchmark_cells.py``'s ``ROWS`` as a whole: the guard
follows a fold of the manifest, and every row has its module (a row's
rehearsal runs from ``tests/test_benchmark_cell_<model>.py``, so that
``--dist loadfile`` spreads the seven over the workers).
"""

import collections
import glob
import json
import os
import re

from benchmark_cells import (
    REPO,
    ROWS,
    harness,
    layer_metric_file,
    reading_of,
    readings_of_cell,
)


def test_every_row_runs_from_a_module_of_its_own():
    """One ``rehearsal_of("<cell>")`` a row, one row a module: a new
    configuration's row cannot stay unrun, and no module outlives its row."""
    called = collections.Counter()
    for path in glob.glob(os.path.join(REPO, "tests", "test_benchmark_cell_*.py")):
        cells = re.findall(r'rehearsal_of\(\s*"([^"]+)"\s*\)', open(path).read())
        assert len(cells) == 1, path
        called[cells[0]] += 1
    assert called == {row.cell: 1 for row in ROWS}


def _folded(manifest: dict) -> tuple:
    """``manifest`` with every group of two or more per-layer entries that
    are one reading moving one metric through one ``reducer`` with one
    ``args`` replaced by ONE entry ``train.<reading>`` over the union of
    their cells, and the file that serves each name: the rule that says
    which entries are copies."""
    files = {e["name"]: layer_metric_file(manifest, e) for e in manifest["per_layer"]}
    groups = collections.defaultdict(list)
    for e in manifest["per_layer"]:
        served = files[e["name"]]
        groups[reading_of(e), e["moves"], served["reducer"],
               json.dumps(served["args"], sort_keys=True)].append(e)
    every_cell = [w["name"] for w in manifest["workloads"]]
    per_layer = []
    for (reading, *_), copies in groups.items():
        if len(copies) == 1:
            per_layer += copies
            continue
        cells = [c for e in copies for c in e.get("workloads", every_cell)]
        name = "train." + reading
        per_layer.append(dict(copies[0], name=name, workloads=list(dict.fromkeys(cells))))
        files[name] = files[copies[0]["name"]]
    names = [e["name"] for e in per_layer]
    assert len(names) == len(set(names)), names
    return dict(manifest, per_layer=per_layer), files


def test_the_guard_reads_a_folded_manifest_as_it_reads_this_one():
    """The copies folded in memory: every row's cell reports the same
    readings, as many, each served by the same ``reducer`` and ``args``."""
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    folded, files = _folded(manifest)
    for row in ROWS:
        before = readings_of_cell(manifest, row.cell)
        after = readings_of_cell(folded, row.cell)
        assert set(after) == set(before) and len(after) == row.count
        for reading, entry in before.items():
            was, now = files[entry["name"]], files[after[reading]["name"]]
            assert (was["reducer"], was["args"]) == (now["reducer"], now["args"]), reading
