"""The reader of ``device_idle_share.sync``, per aggregation event of the async scheduler."""

from pathlib import Path

from bench.metrics import reader

read = reader("device_idle_share.sync", Path(__file__).resolve().parents[2])
