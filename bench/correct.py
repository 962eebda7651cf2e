"""What decides ``correct``: the candidate's first rounds against the
plain reference of the configuration's model.

The candidate is whatever produced the per-round outputs that the window's
first hook received: the program in a benchmark run, or the reference put
in its place for the control and the planted faults (``bench.control``).
The model's module (``bench/models/<model>.py``, ``numbers``) compares
them with its reference and returns named numbers; each has a limit in the
workload file, and the run is correct when none exceeds its limit.
"""

from __future__ import annotations

COMPARED_ROUNDS = 4


def judge(found: dict, limits: dict) -> bool:
    return all(found[k] <= limits[k] for k in limits)


def limit_lines(found: dict, limits: dict) -> list[str]:
    return [f"{k} {found[k]!r} limit {limits[k]!r}" for k in limits]
