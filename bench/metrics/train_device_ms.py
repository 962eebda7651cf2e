"""Device time of local training per round, mean over the chips used: the
ops in the round step's ``fl.train`` scope inside the traced window
(``bench.scopes``), over the rounds completed in it."""

from bench import scopes


def read(facts):
    return scopes.phase_ms(facts, "train")
