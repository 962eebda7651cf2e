"""The reader of ``host_loop_ms.sync``, per aggregation event of the async scheduler."""

from pathlib import Path

from bench.metrics import reader

read = reader("host_loop_ms.sync", Path(__file__).resolve().parents[2])
