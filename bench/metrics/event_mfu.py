"""The reader of ``round_mfu``, over the async scheduler's events: the
useful work of an event is the landing clients' training and the
evaluation of every client."""

from pathlib import Path

from bench.metrics import reader

read = reader("round_mfu", Path(__file__).resolve().parents[2])
