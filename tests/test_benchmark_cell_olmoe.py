"""The rehearsal of ``olmoe-1b-7b-train-zipf4k``: its row of
``tests/benchmark_cells.py``'s ``ROWS``, in a module of its own."""

from benchmark_cells import rehearsal_of

test_benchmark_manifests_pass_selfcheck_and_the_runner_rehearses = rehearsal_of(
    "olmoe-1b-7b-train-zipf4k")
