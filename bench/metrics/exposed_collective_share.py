"""Share of the traced window in which a collective runs on a chip while
no compute op runs there, mean over the chips used: the cross-chip
exchange (the aggregation's psum, the all-gather of the lanes' models
before the scatter) that nothing hides. A trace with no collective on any
chip has nothing to read."""


def read(facts):
    red = facts.reduced
    if facts.window_s <= 0 or not any(red["collective_ns"]):
        return None
    exposed = sum(red["exposed_collective_ns"]) / len(red["exposed_collective_ns"])
    return 100.0 * exposed * 1e-9 / facts.window_s
