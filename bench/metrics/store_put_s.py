"""Seconds per question inside ``ArtifactStore.put``: the staged write of
the optima matrix, the tile argmins and the manifest, the rename, and the
reload (mean of the ``bench.store_put`` spans in the traced window)."""


def read(trace, lo, hi):
    spans = [(a, b) for a, b in trace.spans.get("bench.store_put", []) if lo <= a and b <= hi]
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e9
