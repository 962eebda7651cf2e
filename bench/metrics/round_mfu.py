"""Useful model FLOPs of the rounds completed in the traced window (see
``bench.flops``) over the window's length times the chips' bf16 peak."""

from bench import flops


def read(facts):
    if facts.peak is None or facts.rounds == 0 or facts.window_s <= 0:
        return None
    c, r = facts.config, facts.recipe
    sizes = [c["n_features"], *c["hidden"], c["n_classes"]]
    work = flops.round_flops(
        sizes, facts.sel, facts.n_train_valid, facts.n_train_rows,
        facts.n_test_valid, r["batch_size"], r["epochs"],
    )
    return 100.0 * work / (facts.window_s * facts.peak["flops_bf16"] * facts.chips)
