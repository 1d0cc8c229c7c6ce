"""Timing harness over the tile-parameterized Pallas stencils.

One measurement = one (stencil, problem size, tile config) triple executed
for ``steps`` time steps by :func:`repro.kernels.pallas_stencils
.stencil_run_tiled`, timed with the standard discipline:

* **warmup** calls first (compilation + caches), never timed;
* ``repeats`` timed calls, each fenced by ``block_until_ready`` (wall time
  without device sync measures dispatch, not execution);
* the **median** is recorded (robust against scheduler noise, the usual
  choice for microbenchmarks).

Records carry everything the calibration fit needs to reproduce the model
prediction for the same configuration: the size row, the tile row (in
``sweep.SW_NAMES`` order), and the nominal hardware point the measured
machine is described as. Runs serialize to plain JSON
(:meth:`MeasurementRun.to_payload`) so they can live in the artifact store
as ``kind: "measurement"`` manifests.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timemodel import (
    MAXWELL_GPU,
    STENCILS,
    GPUSpec,
    ProblemSize,
    feasible,
)
from repro.kernels.pallas_stencils import TILE_NAMES, normalize_tiles, run_tiled
from repro.obs.metrics import get_registry as _obs_registry

# ---- observability (repro.obs) -------------------------------------------
_REG = _obs_registry()
_M_POINTS = _REG.counter(
    "repro_measure_points_total",
    "measured (stencil, size, tiles) points, by stencil",
    labels=("stencil",),
)
_M_POINT_SECONDS = _REG.histogram(
    "repro_measure_point_seconds",
    "median wall seconds of one measured point (the recorded time_s)",
)

__all__ = [
    "MeasurementRecord",
    "MeasurementRun",
    "STOCK_HW",
    "STOCK_HW_BY_GPU",
    "stock_hw",
    "default_grid",
    "frame_tiles",
    "feasible_tiles",
    "measure_one",
    "measure_grid",
]

#: nominal description of the measured machine as a paper hardware point
#: (n_SM, n_V, M_SM kB). The calibration fit holds this fixed and moves
#: only the machine parameters (C_iter, bandwidth, launch overhead); the
#: stock points keep the numbers comparable with the paper's §IV.B /
#: Table I (GTX-980: 16 SMs, Titan X: 24 SMs, both 128 lanes / 96 kB).
STOCK_HW: Dict[str, float] = {"n_sm": 16.0, "n_v": 128.0, "m_sm": 96.0}
STOCK_HW_BY_GPU: Dict[str, Dict[str, float]] = {
    "gtx980": STOCK_HW,
    "titanx": {"n_sm": 24.0, "n_v": 128.0, "m_sm": 96.0},
}


def stock_hw(gpu: GPUSpec) -> Dict[str, float]:
    """The nominal hardware point a measurement on ``gpu``'s family is
    described as -- a titanx-framed run must be predicted at the Titan X's
    SM count, not the GTX-980's."""
    return dict(STOCK_HW_BY_GPU.get(gpu.name, STOCK_HW))


@dataclasses.dataclass(frozen=True)
class MeasurementRecord:
    """One timed (stencil, size, tiles) point plus its context."""

    stencil: str
    size: Tuple[int, int, int, int]  # (s1, s2, s3, t) -- t = measured steps
    tiles: Tuple[int, ...]  # TILE_NAMES order
    time_s: float  # median wall seconds for the whole t-step run
    hw: Tuple[float, float, float]  # (n_sm, n_v, m_sm) nominal description
    repeats: int = 1
    warmup: int = 1
    #: every timed repeat, in call order (time_s is their median). Optional
    #: telemetry: serialized only when present, tolerated absent so old
    #: manifests (and hand-written fixtures) still load.
    times_s: Optional[Tuple[float, ...]] = None

    def problem_size(self) -> ProblemSize:
        s1, s2, s3, t = self.size
        return ProblemSize(s1=s1, s2=s2, t=t, s3=s3)

    def tile_dict(self) -> Dict[str, int]:
        return dict(zip(TILE_NAMES, self.tiles))

    def to_json(self) -> dict:
        out = {
            "stencil": self.stencil,
            "size": list(self.size),
            "tiles": list(self.tiles),
            "time_s": float(self.time_s),
            "hw": list(self.hw),
            "repeats": int(self.repeats),
            "warmup": int(self.warmup),
        }
        if self.times_s is not None:
            out["times_s"] = [float(t) for t in self.times_s]
        return out

    @classmethod
    def from_json(cls, obj: Mapping) -> "MeasurementRecord":
        raw_times = obj.get("times_s")
        return cls(
            stencil=str(obj["stencil"]),
            size=tuple(int(v) for v in obj["size"]),
            tiles=tuple(int(v) for v in obj["tiles"]),
            time_s=float(obj["time_s"]),
            hw=tuple(float(v) for v in obj["hw"]),
            repeats=int(obj.get("repeats", 1)),
            warmup=int(obj.get("warmup", 1)),
            times_s=None if raw_times is None
            else tuple(float(t) for t in raw_times),
        )


@dataclasses.dataclass
class MeasurementRun:
    """A list of records plus run-level context (the persistable unit)."""

    records: List[MeasurementRecord]
    gpu_name: str  # GPU family whose constants frame the fit
    backend: str  # jax backend that executed the kernels
    interpret: bool  # True = Pallas interpret mode (CPU CI lane)
    note: str = ""
    #: the executing device as JAX reports it (``device_kind``, e.g. "TPU
    #: v5 lite") and the attached device count; None in runs recorded
    #: before these fields existed.
    device_kind: Optional[str] = None
    device_count: Optional[int] = None

    def to_payload(self) -> dict:
        """Plain-JSON payload (the artifact-store manifest body)."""
        return {
            "records": [r.to_json() for r in self.records],
            "gpu_name": self.gpu_name,
            "backend": self.backend,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
            "interpret": bool(self.interpret),
            "note": self.note,
        }

    @classmethod
    def from_payload(cls, obj: Mapping) -> "MeasurementRun":
        count = obj.get("device_count")
        return cls(
            records=[MeasurementRecord.from_json(r) for r in obj["records"]],
            gpu_name=str(obj["gpu_name"]),
            backend=str(obj["backend"]),
            interpret=bool(obj["interpret"]),
            note=str(obj.get("note", "")),
            device_kind=obj.get("device_kind"),
            device_count=None if count is None else int(count),
        )

    def stencil_names(self) -> List[str]:
        seen: Dict[str, None] = {}
        for r in self.records:
            seen.setdefault(r.stencil)
        return list(seen)


def frame_tiles(name: str, tiles) -> Tuple[int, ...]:
    """Normalized tile tuple in the frame the MODEL evaluates it: 2D
    stencils get ``t_s3`` pinned to 1. The 2D kernel never reads ``t_s3``
    but the time model's compute term multiplies by it for every
    dimensionality, so a 2D record stamped ``t_s3=8`` would make the fit
    absorb an 8x compute factor the kernel never executed -- and the
    eq.-18 sweep's ``LATTICE_2D`` evaluates 2D tiles at ``t_s3=1``, the
    frame calibrated parameters must transfer to."""
    t = list(normalize_tiles(tiles))
    if STENCILS[name].dims == 2:
        t[TILE_NAMES.index("t_s3")] = 1
    return tuple(t)


def feasible_tiles(
    name: str,
    tile_candidates: Iterable[Mapping[str, int]],
    gpu: GPUSpec = MAXWELL_GPU,
    hw: Mapping[str, float] = None,
) -> List[Dict[str, int]]:
    """Keep only candidates the analytical model itself deems feasible at
    the nominal hardware point (eqs. 9-15). An infeasible tile predicts
    ``+inf``, which no fit can use -- filtering here keeps the measurement
    grid and the model's domain aligned. Candidates are put in the
    :func:`frame_tiles` frame first, and deduped (distinct ``t_s3``
    values collapse for 2D stencils)."""
    hw = dict(STOCK_HW if hw is None else hw)
    st = STENCILS[name]
    out: List[Dict[str, int]] = []
    seen = set()
    for cand in tile_candidates:
        framed = frame_tiles(name, cand)
        if framed in seen:
            continue
        seen.add(framed)
        t = dict(zip(TILE_NAMES, framed))
        ok = feasible(
            st, gpu, hw["n_sm"], hw["n_v"], hw["m_sm"],
            t["t_s1"], t["t_s2"], t["t_t"], t["k"], t["t_s3"],
        )
        if bool(np.asarray(ok)):
            out.append(t)
    return out


def measure_one(
    name: str,
    shape: Sequence[int],
    steps: int,
    tiles: Mapping[str, int],
    warmup: int = 1,
    repeats: int = 3,
    *,
    interpret: bool,
    hw: Mapping[str, float] = None,
    seed: int = 0,
) -> MeasurementRecord:
    """Time one configuration (median of ``repeats`` fenced runs).
    ``interpret`` as in :func:`repro.kernels.pallas_stencils.run_tiled`."""
    hw = dict(STOCK_HW if hw is None else hw)
    tile_tuple = frame_tiles(name, tiles)  # 2D: t_s3 pinned to 1
    x = jax.random.normal(jax.random.PRNGKey(seed), tuple(shape), jnp.float32)
    x = jax.block_until_ready(x)

    def run() -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(
            run_tiled(name, x, steps=steps, tiles=tiles, interpret=interpret)
        )
        return time.perf_counter() - t0

    for _ in range(max(0, warmup)):
        run()
    times = [run() for _ in range(max(1, repeats))]
    dims = STENCILS[name].dims
    size = (
        int(shape[0]),
        int(shape[1]),
        int(shape[2]) if dims == 3 else 1,
        int(steps),
    )
    median = float(statistics.median(times))
    _M_POINTS.labels(stencil=name).inc()
    _M_POINT_SECONDS.observe(median)
    return MeasurementRecord(
        stencil=name,
        size=size,
        tiles=tile_tuple,
        time_s=median,
        hw=(hw["n_sm"], hw["n_v"], hw["m_sm"]),
        repeats=int(repeats),
        warmup=int(warmup),
        times_s=tuple(float(t) for t in times),
    )


def default_grid(
    smoke: bool = True, gpu: GPUSpec = MAXWELL_GPU
) -> Dict[str, List[dict]]:
    """stencil -> list of {"shape", "steps", "tiles"} configs.

    The smoke grid is sized for the CI interpret-mode lane (seconds, not
    minutes) while still varying every axis the fit needs signal on: tile
    shape (footprint / bandwidth term), time-tile depth (launch-overhead
    term via the pass count), and problem size (compute term). Tile
    candidates are feasibility-filtered against ``gpu``'s family at its
    :func:`stock_hw` point, so the grid and the fit share one frame, and
    every candidate is a legal TPU block (``t_s1`` a multiple of 8,
    ``t_s2`` of 128; see :func:`repro.kernels.pallas_stencils
    .chip_tile_error`), so either grid compiles for the chip.
    """
    if smoke:
        shapes_2d = [(48, 64), (96, 128)]
        shapes_3d = [(16, 16, 32)]
        steps = 4
        tile_cands = [
            {"t_s1": 8, "t_s2": 128, "t_t": 2, "k": 1, "t_s3": 2},
            {"t_s1": 16, "t_s2": 128, "t_t": 4, "k": 2, "t_s3": 1},
            {"t_s1": 8, "t_s2": 128, "t_t": 4, "k": 1, "t_s3": 1},
        ]
    else:
        shapes_2d = [(256, 256), (512, 512), (1024, 1024)]
        shapes_3d = [(48, 48, 64), (96, 96, 96)]
        steps = 8
        # 2-D keeps the first five (t_s3 collapses); the model's footprint
        # bound leaves 3-D the first, fourth and fifth
        tile_cands = [
            {"t_s1": 8, "t_s2": 128, "t_t": 2, "k": 1, "t_s3": 2},
            {"t_s1": 16, "t_s2": 128, "t_t": 4, "k": 2, "t_s3": 1},
            {"t_s1": 32, "t_s2": 256, "t_t": 4, "k": 1, "t_s3": 1},
            {"t_s1": 8, "t_s2": 128, "t_t": 4, "k": 1, "t_s3": 1},
            {"t_s1": 8, "t_s2": 128, "t_t": 2, "k": 1, "t_s3": 4},
            {"t_s1": 8, "t_s2": 256, "t_t": 8, "k": 1, "t_s3": 1},
        ]
    grid: Dict[str, List[dict]] = {}
    for name, st in STENCILS.items():
        shapes = shapes_3d if st.dims == 3 else shapes_2d
        cands = feasible_tiles(name, tile_cands, gpu, stock_hw(gpu))
        grid[name] = [
            {"shape": shape, "steps": steps, "tiles": t}
            for shape in shapes
            for t in cands
        ]
    return grid


def measure_grid(
    grid: Optional[Dict[str, List[dict]]] = None,
    warmup: int = 1,
    repeats: int = 3,
    *,
    interpret: bool,
    gpu: GPUSpec = MAXWELL_GPU,
    note: str = "",
) -> MeasurementRun:
    """Run every configuration of a :func:`default_grid`-shaped grid.
    Records are stamped with ``gpu``'s family stock hardware point (a
    config may override with its own ``"hw"``), the run with the device
    that executed it."""
    if grid is None:
        grid = default_grid(gpu=gpu)
    records: List[MeasurementRecord] = []
    for name, configs in grid.items():
        for cfg in configs:
            records.append(
                measure_one(
                    name,
                    cfg["shape"],
                    cfg["steps"],
                    cfg["tiles"],
                    warmup=warmup,
                    repeats=repeats,
                    interpret=interpret,
                    hw=cfg.get("hw", stock_hw(gpu)),
                )
            )
    return MeasurementRun(
        records=records,
        gpu_name=gpu.name,
        backend=jax.default_backend(),
        interpret=bool(interpret),
        note=note,
        device_kind=jax.devices()[0].device_kind,
        device_count=jax.device_count(),
    )
