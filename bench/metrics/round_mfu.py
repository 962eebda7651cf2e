"""Useful model FLOPs of the rounds completed in the traced window (the
model's ``round_flops``; har-mlp: ``bench.flops``) over the window's
length times the chips' bf16 peak."""


def read(facts):
    if facts.peak is None or facts.rounds == 0 or facts.window_s <= 0:
        return None
    work = facts.model.round_flops(facts)
    return 100.0 * work / (facts.window_s * facts.peak["flops_bf16"] * facts.chips)
