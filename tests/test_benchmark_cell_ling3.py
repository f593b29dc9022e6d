"""The rehearsal of ``ling-3.0-flash-vl-train-zipf16k``: its row of
``tests/benchmark_cells.py``'s ``ROWS``, in a module of its own."""

from benchmark_cells import rehearsal_of

test_benchmark_manifests_pass_selfcheck_and_the_runner_rehearses = rehearsal_of(
    "ling-3.0-flash-vl-train-zipf16k")
