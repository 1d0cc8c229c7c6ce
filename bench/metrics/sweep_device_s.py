"""Device seconds per question of the sweep programs: the union of the
device's operation intervals inside each ``bench.codesign`` span, on the
busiest chip of that question, averaged over the questions of the traced
window. Nothing to read where no operation ran inside those spans."""


def read(trace, lo, hi):
    spans = trace.spans.get("bench.codesign", [])
    per = [max(busy) for (a, b), busy in zip(spans, trace.busy_per_span("bench.codesign"))
           if lo <= a and b <= hi and busy]
    if not per or max(per) == 0:
        return None
    return sum(per) / len(per) / 1e9
