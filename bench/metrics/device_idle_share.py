"""Share of the traced window in which no operation ran on a chip, in
percent, averaged over the chips: 100 x (1 - busy / window). Nothing to
read where the trace holds no device operation in the window."""


def read(trace, lo, hi):
    busy = [trace.busy_in(d, lo, hi) for d in trace.devices]
    if not busy or sum(busy) == 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
