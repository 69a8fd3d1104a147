"""The benchmark of paddle_tpu: BENCHMARK.json at the root names every
file here by name; ``run.py`` runs one cell once.  See PERF.md."""
