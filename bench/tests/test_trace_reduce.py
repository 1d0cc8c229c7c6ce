"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5 lite (``data/paper_trace.xplane.pb``: two cold
questions of ``paper-gtx980.cold``)."""

import os

import numpy as np
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "paper_trace.xplane.pb")


def brute_busy(intervals, lo, hi):
    """Busy ns of [lo, hi) by marking every ns: the obvious way."""
    mark = np.zeros(hi - lo, bool)
    for a, b in intervals:
        mark[max(a, lo) - lo:max(min(b, hi) - lo, 0)] = True
    return int(mark.sum())


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_covered_and_gaps_agree_with_brute_force(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 900, 40)
    ivs = [(int(a), int(a + d)) for a, d in zip(starts, rng.integers(1, 60, 40))]
    merged = tr.union(ivs)
    lo, hi = 100, 800
    assert tr.covered(merged, lo, hi) == brute_busy(ivs, lo, hi)
    idle = sum(b - a for a, b in tr.gaps(merged, lo, hi))
    assert idle == (hi - lo) - brute_busy(ivs, lo, hi)


def test_innermost_labels_nested_spans():
    spans = [(0, 100, "q"), (10, 40, "codesign"), (50, 90, "put"), (60, 70, "inner")]
    segs = tr.innermost(spans)
    assert segs == [(0, 10, "q"), (10, 40, "codesign"), (40, 50, "q"), (50, 60, "put"),
                    (60, 70, "inner"), (70, 90, "put"), (90, 100, "q")]


def test_idle_by_span_attributes_every_idle_ns():
    trace = tr.Trace(
        spans={"bench.question": [(0, 100)], "bench.codesign": [(10, 40)], "bench.store_put": [(50, 90)]},
        ops={0: [(12, 20, "fusion"), (25, 38, "fusion"), (60, 61, "copy")]},
    )
    idle = trace.idle_by_span(0, 0, 120)
    assert idle == {"bench.question": 10 + 10 + 10, "bench.codesign": 2 + 5 + 2,
                    "bench.store_put": 39, "(no span)": 20}
    assert sum(idle.values()) == 120 - trace.busy_in(0, 0, 120)
    assert trace.busy_per_span("bench.codesign") == [[21]]
    assert trace.top_ops(0, 120) == [("fusion", 21), ("copy", 1)]


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_trace_has_window_spans_and_device(recorded):
    assert recorded.devices == [0]
    lo, hi = recorded.window()
    questions = recorded.spans["bench.question"]
    assert len(questions) >= 2
    assert all(lo <= a and b <= hi for a, b in questions)
    for name in ("bench.codesign", "bench.store_put", "bench.server", "bench.query"):
        assert len(recorded.spans[name]) >= len(questions)


def test_recorded_busy_matches_brute_force(recorded):
    lo, hi = recorded.window()
    ops = [(a, b) for a, b, _ in recorded.ops[0]]
    # microsecond grid keeps the brute force small; union is exact in ns
    us = [(a // 1000, -(-b // 1000)) for a, b in ops]
    busy_us = brute_busy(us, lo // 1000, -(-hi // 1000))
    assert recorded.busy_in(0, lo, hi) / 1000 == pytest.approx(busy_us, rel=0.02, abs=50)


def test_recorded_device_work_lies_inside_codesign_spans(recorded):
    """The sweep's device operations run while the host waits in
    codesign(): the host and device clocks of the trace agree."""
    lo, hi = recorded.window()
    inside = sum(sum(b) for b in recorded.busy_per_span("bench.codesign"))
    assert inside > 0.9 * recorded.busy_in(0, lo, hi)
