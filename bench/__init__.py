"""The benchmark of record (see ``BENCHMARK.json`` and ``bench/run.py``)."""
