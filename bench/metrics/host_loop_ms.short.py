"""The reader of ``host_loop_ms.sync``, for rounds of a few milliseconds (``round_ms.short``)."""

from pathlib import Path

from bench.metrics import reader

read = reader("host_loop_ms.sync", Path(__file__).resolve().parents[2])
