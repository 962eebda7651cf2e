"""Device time of the uplink per round, mean over the chips used: the ops
in the round step's ``fl.transmit`` scope (the wire codec with its error
feedback, the update norms and the finite guard's reverts) inside the
traced window (``bench.scopes``), over the rounds completed in it."""

from bench import scopes


def read(facts):
    return scopes.phase_ms(facts, "transmit")
