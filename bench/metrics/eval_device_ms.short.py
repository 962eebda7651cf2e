"""The reader of ``eval_device_ms``, for rounds of a few milliseconds (``round_ms.short``)."""

from pathlib import Path

from bench.metrics import reader

read = reader("eval_device_ms", Path(__file__).resolve().parents[2])
