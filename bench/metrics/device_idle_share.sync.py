"""Share of the traced window in which no operation ran on the device,
mean over the chips used (sync rounds)."""


def read(facts):
    if facts.window_s <= 0 or not facts.reduced["busy_ns"]:
        return None
    return 100.0 * (1.0 - facts.busy_s / facts.window_s)
