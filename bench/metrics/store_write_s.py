"""Seconds per question in the store's staged file writes: the program's
``repro.store.write`` spans in the traced window (``np.save`` of the
optima, ``savez_compressed`` of the tile argmins and hardware columns,
the manifest), summed and divided by the window's ``repro.codesign``
spans. Nothing to read where the trace holds neither."""


def read(trace, lo, hi):
    def inside(name):
        return [(a, b) for a, b in trace.spans.get(name, []) if lo <= a and b <= hi]

    writes, questions = inside("repro.store.write"), inside("repro.codesign")
    if not writes or not questions:
        return None
    return sum(b - a for a, b in writes) / len(questions) / 1e9
