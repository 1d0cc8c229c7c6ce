"""Seconds per question in the sweep driver's host copies of the results:
the program's ``repro.sweep.fetch`` spans in the traced window (each
waits for its dispatch on the device, then copies and casts to
float64/int64), summed and divided by the window's ``repro.codesign``
spans. Nothing to read where the trace holds neither."""


def read(trace, lo, hi):
    def inside(name):
        return [(a, b) for a, b in trace.spans.get(name, []) if lo <= a and b <= hi]

    fetches, questions = inside("repro.sweep.fetch"), inside("repro.codesign")
    if not fetches or not questions:
        return None
    return sum(b - a for a, b in fetches) / len(questions) / 1e9
