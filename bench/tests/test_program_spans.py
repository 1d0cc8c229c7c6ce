"""The program's own spans on the profiler's clock, read from a small
trace recorded on a TPU v5 lite (``data/paper_trace_spans.xplane.pb``:
two cold questions of ``paper-gtx980.cold`` after the warm-up, inside a
``bench.window`` span), and the readers of ``store_write_s`` and
``sweep_fetch_s`` on it and on the older ``data/paper_trace.xplane.pb``,
recorded before the program had spans of its own."""

import os

import pytest

import program_spans as ps
import trace_reduce as tr
from harness import BENCH, load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "paper_trace_spans.xplane.pb")
OLD = os.path.join(DATA, "paper_trace.xplane.pb")

#: the new readers, the program span each sums, and the existing reader of
#: the enclosing layer, which times that layer from outside the program
NEW = {"store_write_s": ("repro.store.write", "store_put_s"),
       "sweep_fetch_s": ("repro.sweep.fetch", "codesign_s")}


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"), f"bench_metric_{name}")


@pytest.fixture(scope="module")
def recorded():
    trace, events = ps.load(RECORDED)
    lo, hi = trace.window()
    return trace, events, lo, hi


def test_recorded_trace_is_small():
    assert os.path.getsize(RECORDED) < 2 * 1024 * 1024


def test_load_keeps_both_prefixes(recorded):
    trace, events, lo, hi = recorded
    bench_only = tr.load(RECORDED)
    assert {n for n in trace.spans if n.startswith("bench.")} == set(bench_only.spans)
    assert all(trace.spans[n] == bench_only.spans[n] for n in bench_only.spans)
    assert {"repro.codesign", "repro.sweep.dispatch", "repro.sweep.fetch", "repro.store.put",
            "repro.store.write"} <= set(events)
    assert all(trace.spans[n] == [ev[:2] for ev in events[n]] for n in events)


def test_each_question_holds_six_steady_dispatches(recorded):
    trace, events, lo, hi = recorded
    questions = ps.within(events["repro.codesign"], lo, hi)
    assert len(questions) == 2
    for q_lo, q_hi, attrs in questions:
        assert attrs["hw"] == 5121 and attrs["cells"] == 96
        dispatches = ps.within(events["repro.sweep.dispatch"], q_lo, q_hi)
        assert len(dispatches) == 6
        assert all(d["compiles"] == 0 and d["p"] == 16 and d["h"] == 5121
                   for _, _, d in dispatches)
        assert sorted(d["dims"] for _, _, d in dispatches) == [2, 2, 2, 2, 3, 3]
        for d_lo, d_hi, _ in dispatches:
            assert len(ps.within(events["repro.sweep.fetch"], d_lo, d_hi)) == 1


def test_store_spans_nest_inside_the_benchmarks_put_span(recorded):
    trace, events, lo, hi = recorded
    puts = [(a, b) for a, b in trace.spans["bench.store_put"] if lo <= a and b <= hi]
    assert len(puts) == 2
    for p_lo, p_hi in puts:
        (put,) = ps.within(events["repro.store.put"], p_lo, p_hi)
        for name in ("repro.store.key", "repro.store.write", "repro.store.commit",
                     "repro.store.reload"):
            assert len(ps.within(events[name], put[0], put[1])) == 1, name
        (write,) = ps.within(events["repro.store.write"], put[0], put[1])
        assert write[2] == {"kind": "sweep"}
        for part in ("times", "argmins", "manifest"):
            assert len(ps.within(events[f"repro.store.write.{part}"], write[0], write[1])) == 1


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_read_the_program_spans(recorded, name):
    trace, events, lo, hi = recorded
    span, layer = NEW[name]
    value = reader(name).read(trace, lo, hi)
    total = sum(b - a for a, b, _ in ps.within(events[span], lo, hi))
    assert value == pytest.approx(total / 2 / 1e9)  # two questions
    assert 0 < value < reader(layer).read(trace, lo, hi)


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("load", ["old trace", "bench spans only"])
def test_new_readers_find_nothing_without_program_spans(name, load):
    if load == "old trace":
        trace, events = ps.load(OLD)
        assert not events
    else:
        trace = tr.load(RECORDED)
    lo, hi = trace.window()
    assert reader(name).read(trace, lo, hi) is None


@pytest.mark.parametrize("name", ["codesign_s", "store_put_s", "sweep_device_s",
                                  "device_idle_share"])
def test_existing_readers_read_the_same_with_program_spans(recorded, name):
    trace, events, lo, hi = recorded
    bench_only = tr.load(RECORDED)
    assert reader(name).read(trace, lo, hi) == reader(name).read(bench_only, lo, hi)


def test_idle_by_innermost_span_still_sums_to_idle(recorded):
    trace, events, lo, hi = recorded
    (dev,) = trace.devices
    idle = trace.idle_by_span(dev, lo, hi)
    assert sum(idle.values()) == (hi - lo) - trace.busy_in(dev, lo, hi)
    named = {n for n, ns in idle.items() if ns > 0}
    assert any(n.startswith("repro.store.") for n in named)
    assert any(n.startswith("repro.sweep.") for n in named)
    # the idle time put down to bench.store_put before now splits between
    # the program's store spans and what is left of the wrapper's call
    before = tr.load(RECORDED).idle_by_span(dev, lo, hi)["bench.store_put"]
    parts = sum(ns for n, ns in idle.items() if n.startswith("repro.store."))
    assert parts + idle.get("bench.store_put", 0) == before
    assert parts > 0.9 * before


def test_device_programs_have_stable_names(recorded):
    trace, events, lo, hi = recorded
    modules = {n for n, _ in trace.top_ops(lo, hi, 100, modules=True)}
    assert {"jit_sweep_2d", "jit_sweep_3d"} <= modules
    assert "jit_solve" not in modules
