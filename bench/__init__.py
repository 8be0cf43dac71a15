"""The benchmark: one harness (``bench/run.py``) driven by the data files
beside it.  See ``BENCHMARK.json`` at the root of the repository."""
