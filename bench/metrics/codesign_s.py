"""Seconds per question inside the program's ``codesign()``: the sweep
driver and the engine it dispatches, host and device together (mean of
the ``bench.codesign`` spans in the traced window)."""


def read(trace, lo, hi):
    spans = [(a, b) for a, b in trace.spans.get("bench.codesign", []) if lo <= a and b <= hi]
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e9
