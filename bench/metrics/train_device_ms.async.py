"""The reader of ``train_device_ms``, per aggregation event of the async scheduler."""

from pathlib import Path

from bench.metrics import reader

read = reader("train_device_ms", Path(__file__).resolve().parents[2])
