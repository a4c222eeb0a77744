"""perfbench — the repository's benchmark (see BENCHMARK.json, PERF.md).

One run of one cell:
``python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own (``configs/``, ``traffic/``,
``layer_metrics/``) found by the name ``BENCHMARK.json`` gives it; a later
PR adds cells and metrics by adding files and entries, and edits nothing
that is here.  Importing this package imports neither JAX nor the program.
"""
