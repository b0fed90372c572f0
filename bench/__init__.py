"""On-chip benchmark of the served geodesic morphology path (see
``BENCHMARK.json`` at the repository root and ``bench/run.py``)."""
