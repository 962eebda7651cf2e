"""The reader of ``device_idle_share.sync``, for rounds of a few milliseconds (``round_ms.short``)."""

from pathlib import Path

from bench.metrics import reader

read = reader("device_idle_share.sync", Path(__file__).resolve().parents[2])
