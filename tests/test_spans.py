"""Program spans on the profiler's clock, the sweep's compile counter and
stable program names, and the store's spans.

Every ``repro.obs.trace`` span is also a ``repro.<name>`` profiler
annotation; these tests read it back from a CPU profiler session's
``.xplane.pb`` and from request span trees, where the same attrs land."""

import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import MAXWELL, MAXWELL_GPU, STENCILS, codesign, enumerate_hw_space
from repro.core import sweep
from repro.core.solver import LATTICE_2D, LATTICE_3D
from repro.core.workload import paper_workload
from repro.obs.metrics import get_registry
from repro.obs.trace import set_attrs, span, trace
from repro.service.store import ArtifactStore

SIZES = np.array([[256.0, 256.0, 1.0, 64.0], [512.0, 512.0, 1.0, 64.0]])
SIZES_3D = np.array([[64.0, 64.0, 64.0, 16.0]])


def small_hw(step=128):
    return enumerate_hw_space(MAXWELL, max_area=650.0).downsample(step)


def host_events(trace_dir):
    """``{name: [stats dict, ...]}`` of the ``repro.`` events on host planes."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    found.setdefault(e.name, []).append(dict(e.stats))
    return found


def counter(name, **labels):
    for s in get_registry().snapshot().get(name, {}).get("samples", []):
        if s["labels"] == labels:
            return s.get("value", s.get("count"))
    return 0


def names(node):
    """A span tree as nested ``(name, [children])`` pairs."""
    return (node["name"], [names(c) for c in node.get("children", [])])


# ---------------------------------------------------------------------------
# one span, two outputs
# ---------------------------------------------------------------------------
def test_span_lands_on_the_profiler_trace_with_its_attrs(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe.outer", engine="jax", p=16) as s:
            assert s is None  # no request trace: no tree node
            with span("probe.inner"):
                set_attrs(compiles=3)
        with trace("probe.root", trace_id="t1", route="/x"):
            with span("probe.child", key="abc"):
                pass
    finally:
        jax.profiler.stop_trace()
    found = host_events(tmp_path)
    assert found["repro.probe.outer"] == [{"engine": "jax", "p": 16}]
    assert found["repro.probe.inner"] == [{"compiles": 3}]
    assert found["repro.probe.root"] == [{"route": "/x"}]
    assert found["repro.probe.child"] == [{"key": "abc"}]


def test_set_attrs_reaches_the_request_tree():
    with trace("root") as root:
        with span("work", a=1):
            set_attrs(b=2)
    (child,) = root.tree()["children"]
    assert child["attrs"] == {"a": 1, "b": 2}
    set_attrs(ignored=1)  # no open span: nothing to add to, no error


def test_obs_spans_work_without_jax(subprocess_env):
    code = textwrap.dedent("""
        import sys
        sys.modules["jax"] = None  # any import of jax now fails
        from repro.obs.trace import set_attrs, span, trace
        with span("a", x=1) as s:
            assert s is None
            set_attrs(y=2)
        with trace("root") as root:
            with span("b"):
                pass
        assert [c["name"] for c in root.tree()["children"]] == ["b"]
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=subprocess_env, capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# the sweep: compiles where they happen, stable names, optima counted
# ---------------------------------------------------------------------------
ENGINES = {
    "jax": lambda st, sizes, hw, chunk: sweep.sweep_cells(
        st, MAXWELL_GPU, sizes, hw.n_sm, hw.n_v, hw.m_sm, chunk=chunk),
    "sharded": lambda st, sizes, hw, chunk: sweep.sweep_cells_sharded(
        st, MAXWELL_GPU, sizes, hw.n_sm, hw.n_v, hw.m_sm, chunk=chunk, devices=1),
}


@pytest.mark.parametrize("engine,chunk", [("jax", 13), ("sharded", 11)])
def test_fresh_shape_compiles_and_its_repeat_does_not(engine, chunk):
    """A chunk no other test uses keys a fresh solver, so its first
    dispatch compiles (or loads from the persistent cache) and the repeat
    runs the cached program; the span attr and the phase label agree."""
    hw, st = small_hw(), STENCILS["heat2d"]
    before = {p: counter("repro_sweep_dispatch_seconds", engine=engine, phase=p)
              for p in ("compile", "steady")}
    compiles_before = counter("repro_sweep_compiles_total", engine=engine)
    attrs = []
    for _ in range(2):
        with trace("q") as root:
            ENGINES[engine](st, SIZES, hw, chunk)
        (dispatch,) = root.tree()["children"]
        assert names(dispatch) == ("sweep.dispatch", [("sweep.fetch", [])])
        attrs.append(dispatch["attrs"])
    assert attrs[0]["compiles"] >= 1
    assert attrs[1]["compiles"] == 0
    assert {k: attrs[1][k] for k in ("engine", "dims", "p", "h")} == {
        "engine": engine, "dims": 2, "p": 2, "h": len(hw)}
    assert counter("repro_sweep_dispatch_seconds", engine=engine,
                   phase="compile") == before["compile"] + 1
    assert counter("repro_sweep_dispatch_seconds", engine=engine,
                   phase="steady") == before["steady"] + 1
    assert (counter("repro_sweep_compiles_total", engine=engine)
            == compiles_before + attrs[0]["compiles"])


@pytest.mark.parametrize("engine,dims", [("jax", 2), ("jax", 3), ("sharded", 2), ("sharded", 3)])
def test_sweep_programs_have_stable_names(engine, dims):
    lattice = LATTICE_3D if dims == 3 else LATTICE_2D
    hw = small_hw()
    h = len(hw)
    cols = [np.zeros(h, np.float32)] * 3
    consts = sweep._pack_consts(STENCILS["heat3d" if dims == 3 else "heat2d"], SIZES)
    if engine == "jax":
        solve = sweep._cells_solver(dims, MAXWELL_GPU, lattice, 64)
        lowered = solve.lower(*cols, consts)
        want = f"jit_sweep_{dims}d"
    else:
        _, solve = sweep._sharded_cells_solver(
            dims, MAXWELL_GPU, lattice, 64, tuple(jax.devices()[:1]))
        cols = [np.zeros(64, np.float32)] * 3
        lowered = solve.lower(*cols, consts)
        want = f"jit_sweep_{dims}d_sharded"
    assert f"module @{want} " in lowered.as_text()


@pytest.mark.parametrize("engine", ["jax", "sharded", "numpy"])
def test_optima_counter_grows_by_p_times_h(engine):
    hw = small_hw()
    before = counter("repro_sweep_optima_total", engine=engine)
    if engine == "numpy":
        wl = paper_workload(["heat2d"])
        codesign(wl, gpu=MAXWELL_GPU, hw=hw, engine="numpy")
        added = len(wl.cells) * len(hw)
    else:
        for st, sizes in ((STENCILS["heat2d"], SIZES), (STENCILS["heat3d"], SIZES_3D)):
            ENGINES[engine](st, sizes, hw, None)
        added = (len(SIZES) + len(SIZES_3D)) * len(hw)
    assert counter("repro_sweep_optima_total", engine=engine) == before + added


def test_codesign_stages_hw_columns_once_a_question():
    """A warm question copies the hardware columns once and each group's
    constants once, all explicitly: no implicit host-to-device copy, 3 + G
    transfers on the ``codesign`` span and the counter, 1 a dispatch."""
    wl = paper_workload(["heat2d", "heat3d"])
    hw = small_hw()
    codesign(wl, gpu=MAXWELL_GPU, hw=hw, engine="jax")  # warm
    before = counter("repro_sweep_transfers_total", engine="jax")
    with jax.transfer_guard_host_to_device("disallow"), trace("q") as root:
        codesign(wl, gpu=MAXWELL_GPU, hw=hw, engine="jax")
    (cd,) = root.tree()["children"]
    assert cd["attrs"]["transfers"] == 3 + 2
    assert [d["attrs"]["transfers"] for d in cd["children"]] == [1, 1]
    assert counter("repro_sweep_transfers_total", engine="jax") == before + 5


def test_sweep_cells_with_host_columns_counts_four_transfers():
    hw = small_hw()
    before = counter("repro_sweep_transfers_total", engine="jax")
    with trace("q") as root:
        ENGINES["jax"](STENCILS["heat2d"], SIZES, hw, None)
    (dispatch,) = root.tree()["children"]
    assert dispatch["attrs"]["transfers"] == 4
    assert counter("repro_sweep_transfers_total", engine="jax") == before + 4


def test_codesign_holds_one_dispatch_per_stencil():
    wl = paper_workload(["heat2d", "heat3d"])
    hw = small_hw()
    codesign(wl, gpu=MAXWELL_GPU, hw=hw, engine="jax")  # warm
    with trace("q") as root:
        codesign(wl, gpu=MAXWELL_GPU, hw=hw, engine="jax")
    (cd,) = root.tree()["children"]
    assert cd["name"] == "codesign"
    dispatches = cd["children"]
    assert [d["name"] for d in dispatches] == ["sweep.dispatch"] * 2
    assert sorted(d["attrs"]["dims"] for d in dispatches) == [2, 3]
    assert all(d["attrs"]["compiles"] == 0 for d in dispatches)


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------
STAGED = [("store.lock", []), ("store.write", "WRITE"), ("store.commit", []),
          ("store.reload", [])]


@pytest.mark.parametrize("kind", ["sweep", "measurement"])
def test_store_spans_nest_as_documented(tmp_path, kind):
    store = ArtifactStore(str(tmp_path))
    if kind == "sweep":
        result = codesign(paper_workload(["heat2d"]), gpu=MAXWELL_GPU,
                          hw=small_hw(), engine="numpy")
        with trace("q") as root:
            art = store.put(result, engine="numpy")
        (top,) = root.tree()["children"]
        assert top["name"] == "store.put"
        spans = top["children"]
        assert names(spans[0]) == ("store.key", [])
        spans = spans[1:]
        writes = [("store.write.times", []), ("store.write.argmins", []),
                  ("store.write.manifest", [])]
    else:
        with trace("q") as root:
            store.put_json("measurement", {"points": [1, 2, 3]})
        spans = root.tree()["children"]
        writes = []
    want = [(n, writes if c == "WRITE" else c) for n, c in STAGED]
    assert [names(s) for s in spans] == want
    (write,) = [s for s in spans if s["name"] == "store.write"]
    assert write["attrs"] == {"kind": kind}
    if kind == "sweep":  # the argmins' write says what it put on disk
        (argmins,) = [s for s in write["children"]
                      if s["name"] == "store.write.argmins"]
        assert argmins["attrs"] == {
            "bytes": os.path.getsize(os.path.join(art.path, "arrays.npz")),
            "idx_dtype": "int16",
        }


# ---------------------------------------------------------------------------
# the LM grid: one dispatch per cell, compiles where they happen, names
# ---------------------------------------------------------------------------
def _lm_question():
    from repro.configs import get_arch
    from repro.core.lmcells import enumerate_lm_hw_space, lm_workload

    wl = lm_workload(archs=[get_arch("deepseek-v3-671b").reduced()], name="lm-spans")
    return wl, enumerate_lm_hw_space(max_chips=16)


def test_lm_codesign_spans_nest_and_count_compiles():
    """``lm.codesign`` holds one ``lm.dispatch`` per cell, then one
    ``lm.fetch`` of every grid; it made 1 + C host-to-device copies, and
    ``repro_lm_transfers_total`` grows by them. A repeat of the same
    question compiles nothing, and the compile counter grows by what the
    spans counted."""
    from repro.core.lmcells import _JIT_CACHE, lm_codesign, lm_sw_lattice

    wl, hw = _lm_question()
    C = len(wl.cells)
    _JIT_CACHE.clear()  # the first call traces each op's grid afresh
    counted = {op: counter("repro_lm_compiles_total", op=op) for op in
               ("prefill", "decode", "train", "moe_dispatch")}
    trees = []
    for _ in range(2):
        transfers = counter("repro_lm_transfers_total")
        with trace("q") as root:
            lm_codesign(wl, hw=hw, engine="jax")
        assert counter("repro_lm_transfers_total") == transfers + 1 + C
        (top,) = root.tree()["children"]
        trees.append(top)
    for top in trees:
        assert top["name"] == "lm.codesign"
        assert top["attrs"] == {"engine": "jax", "cells": C, "h": len(hw), "transfers": 1 + C}
        assert [names(c) for c in top["children"]] == [("lm.dispatch", [])] * C + [
            ("lm.fetch", [])]
        dispatches, fetch = top["children"][:C], top["children"][C]
        assert [(d["attrs"]["op"], d["attrs"]["h"], d["attrs"]["l"]) for d in dispatches] == [
            (c.op, len(hw), len(lm_sw_lattice(c.op))) for c in wl.cells]
        assert fetch["attrs"] == {"grids": C}
    first = [d["attrs"]["compiles"] for d in trees[0]["children"][:C]]
    assert all(n >= 1 for n in first)
    assert all(d["attrs"]["compiles"] == 0 for d in trees[1]["children"][:C])
    for op, before in counted.items():
        (n,) = [n for n, c in zip(first, wl.cells) if c.op == op]
        assert counter("repro_lm_compiles_total", op=op) == before + n


@pytest.mark.parametrize("op", ["prefill", "decode", "train", "moe_dispatch"])
def test_lm_grid_programs_have_stable_names(op):
    from repro.core.lmcells import _cell_consts, _jax_grid_fn

    wl, hw = _lm_question()
    (cell,) = [c for c in wl.cells if c.op == op]
    consts = np.asarray(_cell_consts(cell), np.float32)
    lowered = _jax_grid_fn(op).lower(consts, np.zeros((3, len(hw)), np.float32))
    assert f"module @jit_lm_grid_{op} " in lowered.as_text()
