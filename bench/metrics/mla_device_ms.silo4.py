"""Device time of multi-head latent attention per round, mean over the
chips used: the ops in the ``mla.attn`` scope (``models/layers.py``
``mla_attention``; forward, recomputed forward and backward, in training
and evaluation) inside the traced window (``bench.scopes.scope_ms``). A
program without the scope has nothing to read."""

from bench import scopes


def read(facts):
    return scopes.scope_ms(facts, "mla.attn")
