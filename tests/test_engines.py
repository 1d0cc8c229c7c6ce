"""The one engine rule (repro.core.engines): the family a content key
records is the family of the engine ``codesign()`` dispatches, on every
engine x hardware-space size x device count, and the content keys of a
small stencil spec and a small LM spec stay where they are."""

import importlib

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import MAXWELL, MAXWELL_GPU, enumerate_hw_space, sweep
from repro.core.engines import ENGINES, engine_family
from repro.core.lmcells import enumerate_lm_hw_space, lm_workload
from repro.core.workload import paper_workload
from repro.service import ArtifactStore, CodesignServer
from repro.service.store import artifact_spec, lm_artifact_spec, spec_key

# repro.core re-exports the codesign *function* under the submodule's name
codesign_mod = importlib.import_module("repro.core.codesign")


@pytest.mark.parametrize("n_dev", [1, 8])
@pytest.mark.parametrize("n_hw", [3, 1000])
@pytest.mark.parametrize("engine", ENGINES)
def test_stored_family_is_the_dispatched_family(engine, n_hw, n_dev, tmp_path, monkeypatch):
    """A server's key is computed before any sweep and the build dispatches
    later; both ask the one rule, so the family in the stored spec is the
    family of the engine that ran (the sharded engine is jax's)."""
    monkeypatch.setattr(sweep, "device_count", lambda: n_dev)
    ran = []

    def fake(name):
        def solve(st, gpu, sizes, n_sm, *args, **kwargs):
            ran.append(name)
            # the sweeps answer (P, H) for P sizes, the oracle (H,) for one
            shape = (len(n_sm),) if name == "numpy" else (len(sizes), len(n_sm))
            return np.ones(shape), np.zeros(shape, np.int64)

        return solve

    monkeypatch.setattr(sweep, "sweep_cells", fake("jax"))
    monkeypatch.setattr(sweep, "sweep_cells_sharded", fake("sharded"))
    monkeypatch.setattr(codesign_mod, "solve_cell", fake("numpy"))
    full = enumerate_hw_space(MAXWELL, max_area=650.0)
    hw = type(full)(full.n_sm[:n_hw], full.n_v[:n_hw], full.m_sm[:n_hw], full.area[:n_hw])
    store = ArtifactStore(str(tmp_path))
    srv = CodesignServer(store, workload=paper_workload(["jacobi2d"]), hw=hw,
                         engine=engine, batch_window=0.0)
    srv.ensure_artifact()
    (dispatched,) = set(ran)
    stored = store.get(srv.key).manifest["spec"]["engine"]
    assert stored == {"sharded": "jax"}.get(dispatched, dispatched)
    assert stored == engine_family(engine, n_hw)
    if dispatched == "sharded":
        assert engine == "auto" and n_dev > 1 and n_hw >= 64


#: content keys computed before the engine rule moved into one module; a
#: change here moves every stored artifact's address.
PINNED_KEYS = [
    ("stencil", "auto", "9d90902545c16d548a35"),
    ("stencil", "jax", "9d90902545c16d548a35"),
    ("stencil", "numpy", "7676bb688c8a959de1ef"),
    ("lm", "auto", "9cfd4fd8aebaeae7756f"),
    ("lm", "numpy", "87b3a824894a61e8101d"),
]


@pytest.mark.parametrize("family,engine,key", PINNED_KEYS)
def test_content_key_is_pinned(family, engine, key):
    if family == "stencil":
        wl = paper_workload(["jacobi2d", "heat3d"])
        hw = enumerate_hw_space(MAXWELL, max_area=650.0).downsample(64)
        spec = artifact_spec(wl, MAXWELL_GPU, hw, engine)
    else:
        wl = lm_workload(archs=[get_arch("llama3-8b").reduced()], name="lm-key")
        spec = lm_artifact_spec(wl, enumerate_lm_hw_space(max_chips=32), engine, "tpu_v5e")
    assert spec_key(spec) == key
