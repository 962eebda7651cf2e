"""Device time of the expert layers outside their matmuls per round, mean
over the chips used: the ``moe.route``, ``moe.dispatch`` and
``moe.combine`` scopes (routing, each token's gate for each held expert,
and the gate-weighted sum of the experts' outputs), not ``moe.experts`` and
``moe.shared`` (``bench.scopes.scope_ms``). A program without them has
nothing to read."""

from bench import scopes

SCOPES = ("moe.route", "moe.dispatch", "moe.combine")


def read(facts):
    found = [scopes.scope_ms(facts, name) for name in SCOPES]
    found = [v for v in found if v is not None]
    return sum(found) if found else None
