"""The toy model's training FLOPs over the device time of ``fl.train``
times the chips' bf16 peak."""

from bench import scopes


def read(facts):
    train_ms = scopes.scope_ms(facts, "fl.train")
    if facts.peak is None or train_ms is None:
        return None
    per_round = facts.model.round_flops(facts) / facts.rounds
    return 100.0 * per_round / (1e-3 * train_ms * facts.peak["flops_bf16"] * facts.chips)
