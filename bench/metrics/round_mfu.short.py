"""The reader of ``round_mfu``, for rounds of a few milliseconds (``round_ms.short``)."""

from pathlib import Path

from bench.metrics import reader

read = reader("round_mfu", Path(__file__).resolve().parents[2])
