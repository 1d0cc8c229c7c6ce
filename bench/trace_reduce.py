"""Reduce a JAX profiler trace to the intervals the benchmark's metrics need.

A trace (``<dir>/plugins/profile/<time>/<host>.xplane.pb``) is read with
``jax.profiler.ProfileData`` and nothing else. Two kinds of events are
kept, on the profiler's one clock:

* host spans: the ``jax.profiler.TraceAnnotation`` events the benchmark
  opens around its calls into the program, found by their ``bench.``
  prefix on any host thread;
* device operations: the events of each device plane's ``XLA Ops`` line
  (one plane per chip, ``/device:TPU:<n>``), and the programs they belong
  to, from its ``XLA Modules`` line, named by the jitted function
  (``jit_solve``) without the fingerprint XLA appends.

From those, :class:`Trace` gives a device's busy time (the union of its
operations' intervals), the busy time inside each occurrence of a span,
the device's idle time attributed to what the host was doing (the
innermost span that covers it), and the operations that took most time.
Nothing here knows a metric; the readers in ``metrics/`` do.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
FINGERPRINT = re.compile(r"\(\d+\)$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering exactly what the input covers."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _from(merged: Sequence[Interval], lo: int) -> int:
    """Index of the first interval of ``merged`` that may reach past ``lo``."""
    return max(bisect.bisect_left(merged, (lo, lo)) - 1, 0)


def covered(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Length of [lo, hi) that the disjoint, sorted ``merged`` covers."""
    total = 0
    for a, b in merged[_from(merged, lo):]:
        if b <= lo:
            continue
        if a >= hi:
            break
        total += min(b, hi) - max(a, lo)
    return total


def gaps(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi) that ``merged`` leaves uncovered."""
    out, cur = [], lo
    for a, b in merged[_from(merged, lo):]:
        if b <= cur:
            continue
        if a >= hi:
            break
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost(spans: Sequence[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """Split properly nested spans into disjoint segments, each labelled
    with the innermost span covering it."""
    points = sorted({p for s in spans for p in s[:2]})
    out: List[Tuple[int, int, str]] = []
    for lo, hi in zip(points, points[1:]):
        cover = [s for s in spans if s[0] <= lo and s[1] >= hi]
        if cover:
            out.append((lo, hi, max(cover, key=lambda s: (s[0], -s[1]))[2]))
    return out


@dataclasses.dataclass
class Trace:
    """Host spans and device operations of one traced run."""

    spans: Dict[str, List[Interval]]
    ops: Dict[int, List[Tuple[int, int, str]]]
    modules: Dict[int, List[Tuple[int, int, str]]] = dataclasses.field(default_factory=dict)

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy(self, device: int) -> List[Interval]:
        return union((a, b) for a, b, _ in self.ops[device])

    def window(self, name: str = "bench.window") -> Optional[Interval]:
        """The first occurrence of span ``name``: the measured window."""
        found = self.spans.get(name)
        return found[0] if found else None

    def busy_in(self, device: int, lo: int, hi: int) -> int:
        return covered(self.busy(device), lo, hi)

    def busy_per_span(self, name: str) -> List[List[int]]:
        """For each occurrence of span ``name``, the busy ns of every device
        inside it: ``[[dev0, dev1, ...], ...]``."""
        merged = {d: self.busy(d) for d in self.devices}
        return [[covered(merged[d], lo, hi) for d in self.devices]
                for lo, hi in self.spans.get(name, [])]

    def idle_by_span(self, device: int, lo: int, hi: int) -> Dict[str, int]:
        """Idle ns of ``device`` inside [lo, hi), by the innermost host span
        covering it; idle time outside every span is ``(no span)``."""
        segs = innermost([(a, b, n) for n, ivs in self.spans.items() for a, b in ivs])
        ends = [s[1] for s in segs]
        out: Dict[str, int] = collections.Counter()
        for g_lo, g_hi in gaps(self.busy(device), lo, hi):
            inside = 0
            for s_lo, s_hi, name in segs[bisect.bisect_right(ends, g_lo):]:
                if s_lo >= g_hi:
                    break
                part = min(s_hi, g_hi) - max(s_lo, g_lo)
                out[name] += part
                inside += part
            if g_hi - g_lo > inside:
                out["(no span)"] += g_hi - g_lo - inside
        return dict(out)

    def top_ops(self, lo: int, hi: int, n: int = 10, modules: bool = False) -> List[Tuple[str, int]]:
        """The ``n`` operation (or, with ``modules``, program) names with
        the most device ns inside [lo, hi), summed over devices and
        occurrences."""
        total: Dict[str, int] = collections.Counter()
        for events in (self.modules if modules else self.ops).values():
            for a, b, name in events:
                part = min(b, hi) - max(a, lo)
                if part > 0:
                    total[name] += part
        return sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = collections.defaultdict(list)
    ops: Dict[int, List[Tuple[int, int, str]]] = {}
    modules: Dict[int, List[Tuple[int, int, str]]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    events = (ops if line.name == OPS_LINE else modules).setdefault(dev, [])
                    for e in line.events:
                        start = int(e.start_ns)
                        name = e.name if line.name == OPS_LINE else FINGERPRINT.sub("", e.name)
                        events.append((start, start + int(e.duration_ns), name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        start = int(e.start_ns)
                        spans[e.name].append((start, start + int(e.duration_ns)))
    for ivs in spans.values():
        ivs.sort()
    return Trace(dict(spans), ops, modules)
