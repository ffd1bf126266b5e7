"""The benchmark of the AULID serving engine: one cell per run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root names the cells, configurations and metrics;
``harness.py`` runs one cell. See ``PERF.md``.
"""
