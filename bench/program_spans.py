"""The program's own spans in a profiler trace, beside the benchmark's.

Every span of the program (``repro.obs.trace``) is a profiler annotation
named ``repro.<name>`` whose arguments are the span's attrs: a sweep
dispatch's ``engine``, ``dims`` and ``compiles``, a staged write's
``kind``. :func:`trace_reduce.load` keeps the benchmark's ``bench.`` host
spans only; :func:`load` here keeps both, so that the device's idle time
can be put down to the innermost span of either, and returns each
``repro.`` event's arguments as well.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Tuple

import trace_reduce

PREFIX = "repro."

#: one occurrence of a span: start and end in ns, and its arguments
Event = Tuple[int, int, Dict[str, object]]


def load(path: str) -> Tuple[trace_reduce.Trace, Dict[str, List[Event]]]:
    """Read one ``.xplane.pb``: the :class:`trace_reduce.Trace` with both
    prefixes' spans, and the ``repro.`` events by name, in start order."""
    from jax.profiler import ProfileData

    base = trace_reduce.load(path)
    events: Dict[str, List[Event]] = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    events[e.name].append((start, start + int(e.duration_ns), dict(e.stats)))
    spans = dict(base.spans)
    for name, found in events.items():
        found.sort(key=lambda ev: ev[:2])
        spans[name] = [(a, b) for a, b, _ in found]
    return dataclasses.replace(base, spans=spans), dict(events)


def within(events: List[Event], lo: int, hi: int) -> List[Event]:
    """The occurrences that lie inside [lo, hi)."""
    return [ev for ev in events if lo <= ev[0] and ev[1] <= hi]

