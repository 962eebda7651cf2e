"""Device time of the expert layers per round, mean over the chips used:
the ops in the ``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.shared`` and ``moe.combine`` scopes (``models/layers.py``
``moe_apply_held``) inside the traced window (``bench.scopes.scope_ms``).
The scopes do not nest in each other, so their times add. A program
without them has nothing to read."""

from bench import scopes

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.shared", "moe.combine")


def read(facts):
    found = [scopes.scope_ms(facts, name) for name in SCOPES]
    found = [v for v in found if v is not None]
    return sum(found) if found else None
