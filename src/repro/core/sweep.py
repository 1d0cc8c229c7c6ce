"""JAX-native codesign sweep engine (the eq.-18 inner solves, compiled).

The seed solved each per-(stencil, size) cell with chunked NumPy broadcasts
(:func:`repro.core.solver.solve_cell`): serial, CPU-bound, float64, and a
fresh pile of temporaries per chunk. This module re-expresses the same
lattice sweep as a **jitted vmap over hardware points x tile-lattice
candidates**, so XLA fuses the whole time-model expression into one kernel
and runs it on whatever backend is attached (CPU, GPU, TPU):

* the time model itself is untouched -- :func:`repro.core.timemodel
  .stencil_time` is called with ``xp=jax.numpy``, so the NumPy path stays
  the bit-exact reference oracle (see ``tests/test_sweep.py``);
* problem sizes are *dynamic* jit arguments AND a batch (vmap) axis: all 16
  paper sizes of a stencil solve in one compiled dispatch
  (:func:`sweep_cells`), instead of recompiling -- or even re-dispatching --
  per cell;
* an optional ``lax.map`` chunking knob bounds peak memory at
  ``chunk x |lattice|`` floats, for hardware spaces far larger than the
  paper's ~13k points;
* :func:`sweep_cells_sharded` shards the hardware axis over a 1-D device
  ``Mesh`` with ``shard_map`` + ``NamedSharding`` -- each device streams
  its shard through the *same* fused body, so multi-device results are
  bit-identical to the single-device engine while wall time scales with
  the mesh (the fleet path; see README "Scaling the sweep");
* coordinate-descent refinement (:func:`refine_points`) is batched across
  all reported design points at once -- each descent round evaluates every
  (point, +/-step neighbor) pair in a single compiled call instead of the
  seed's one-at-a-time Python loops.
"""

from __future__ import annotations

import contextlib
import functools
import time
import warnings
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.obs.compiles import compiles_so_far, listen_for_compiles
from repro.obs.metrics import get_registry as _obs_registry
from repro.obs.trace import set_attrs, span

from .solver import TileLattice
from .solver import _STEPS as _SOLVER_STEPS
from .timemodel import GPUSpec, ProblemSize, StencilSpec, stencil_time

__all__ = [
    "DEFAULT_CHUNK",
    "device_count",
    "sweep_cell",
    "sweep_cells",
    "sweep_cells_sharded",
    "stage_columns",
    "refine_points",
    "clear_caches",
]

#: lax.map chunk: 2048 hw points x ~2.9k lattice candidates x 4 B ~ 24 MB
#: peak per intermediate -- measured fastest on small CPU hosts (fits L3
#: alongside the fused expression's live values) and tiny for devices.
DEFAULT_CHUNK = 2048

#: software-parameter column order used by the packed (P, 5) refine arrays.
SW_NAMES = ("t_s1", "t_s2", "t_t", "k", "t_s3")

#: aligned unit steps per parameter (eq. 13: warps; eq. 15: even t_T) and
#: the lower bounds the descent must not cross -- derived from the NumPy
#: oracle's table so the two refine paths can never drift apart.
SW_STEPS = tuple(float(_SOLVER_STEPS[k]) for k in SW_NAMES)
SW_MINS = tuple(1.0 if k == "t_s1" else float(_SOLVER_STEPS[k]) for k in SW_NAMES)

# ---- observability (repro.obs) -------------------------------------------
_REG = _obs_registry()
_M_DISPATCH_SECONDS = _REG.histogram(
    "repro_sweep_dispatch_seconds",
    "wall time of one compiled sweep dispatch (solver lookup, input "
    "conversion, solve call through host materialization), split by "
    "engine and phase: 'compile' when JAX compiled a program or loaded "
    "one from its persistent cache during the dispatch, 'steady' "
    "otherwise",
    labels=("engine", "phase"),
)
_M_OPTIMA = _REG.counter(
    "repro_sweep_optima_total",
    "optima-matrix entries produced: P problem sizes x H hardware points "
    "per dispatch (each the argmin over a whole tile lattice)",
    labels=("engine",),
)
_M_COMPILES = _REG.counter(
    "repro_sweep_compiles_total",
    "programs JAX compiled or loaded from its persistent cache during "
    "sweep dispatches, counted on the dispatching thread",
    labels=("engine",),
)
_M_TRANSFERS = _REG.counter(
    "repro_sweep_transfers_total",
    "host-to-device copies of sweep inputs: a question's staged hardware "
    "columns once, then each dispatch's constants and any host columns",
    labels=("engine",),
)

listen_for_compiles()


@contextlib.contextmanager
def _dispatch(engine: str, dims: int, p: int, h: int, transfers: int) -> Iterator[None]:
    """One compiled dispatch: the ``sweep.dispatch`` span, its compile
    count (the span's ``compiles`` attr), its host-to-device copies (the
    ``transfers`` attr), the phase-split wall time and the optima
    counter."""
    _M_TRANSFERS.labels(engine=engine).inc(transfers)
    with span("sweep.dispatch", engine=engine, dims=dims, p=p, h=h, transfers=transfers):
        n0 = compiles_so_far()
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        compiles = compiles_so_far() - n0
        set_attrs(compiles=compiles)
    _M_DISPATCH_SECONDS.labels(
        engine=engine, phase="compile" if compiles else "steady"
    ).observe(dt)
    _M_COMPILES.labels(engine=engine).inc(compiles)
    _M_OPTIMA.labels(engine=engine).inc(p * h)


def device_count() -> int:
    """Attached devices. The engine="auto" promotion test monkeypatches
    this, so route all auto decisions through here."""
    return jax.device_count()


def _resolve_devices(devices):
    """Normalize the ``devices=`` knob to a concrete device list.

    ``None`` -> every attached device; an int n -> the first n devices (a
    mesh smaller than the host); an explicit sequence of jax devices is
    used as-is.
    """
    if devices is None:
        return tuple(jax.devices())
    if isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"devices={devices} out of range (1..{len(avail)} attached)"
            )
        return tuple(avail[:devices])
    return tuple(devices)


def _lattice_arrays(lattice: TileLattice, gpu: GPUSpec):
    """Pruned (candidates, original-index) lattice columns.

    Candidates violating the *hardware-independent* feasibility constraints
    (eqs. 10/12-15 restricted to GPU-family constants) are +inf for every
    hardware point, so dropping them up front cannot change any argmin --
    it only shrinks the compiled (H x L) sweep (~28% of the seed's 2D
    lattice is dead weight). Original lattice indices are kept so callers
    still receive seed-compatible indices for ``decode_index``.
    """
    g = lattice.grid()
    keep = (
        (g["k"] * g["t_s2"] <= gpu.max_threads_per_sm)
        & (g["t_s2"] <= gpu.max_threads_per_block)
        & (g["k"] <= gpu.max_threadblocks_per_sm)
        & (g["t_t"] % 2 == 0)
        & (g["t_s2"] % 32 == 0)
    )
    keep_idx = np.nonzero(keep)[0]
    cols = tuple(jnp.asarray(g[k][keep_idx], jnp.float32) for k in SW_NAMES)
    return cols, jnp.asarray(keep_idx, jnp.int32)


def _pack_consts(st: StencilSpec, sizes: np.ndarray) -> np.ndarray:
    """A stencil group's constants as one float32 ``(P + 1, 4)`` host
    array: the P ``(s1, s2, s3, t)`` size rows, then ``(radius, c_iter,
    n_arrays, 0)``. Rounded to float32 here, on the host, as a
    ``jnp.asarray(..., float32)`` of each value rounds it: the program
    sees the same bits."""
    scalars = np.array([[st.radius, st.c_iter, st.n_arrays, 0.0]], np.float64)
    return np.concatenate([sizes, scalars]).astype(np.float32)


def _unpack_consts(dims: int, consts):
    """Inside a sweep program: ``(sizes (P, 4), traced spec)`` sliced from
    the constants :func:`_pack_consts` packed."""
    return consts[:-1], _traced_spec(dims, consts[-1, 0], consts[-1, 1], consts[-1, 2])


def _traced_spec(dims: int, radius, c_iter, n_arrays) -> StencilSpec:
    """A StencilSpec carrying tracers for its numeric fields.

    Only ``dims`` shapes the traced program (a static Python branch in the
    time model); radius / C_iter / n_arrays are plain multiplicands, so
    passing them as jit arguments lets ALL stencils of a dimensionality
    share one compiled executable instead of recompiling per stencil.
    """
    return StencilSpec(
        name="<traced>", dims=dims, radius=radius, flops_per_point=0.0,
        n_arrays=n_arrays, c_iter=c_iter,
    )


def _best_of_factory(gpu: GPUSpec, lat, keep_idx):
    """The fused eq.-18 inner body shared by every compiled engine.

    Returns ``best_of(hw_chunk (n, 3), sizes (P, 4), st) -> (best_t (P, n),
    best_i (P, n))``. Both the single-device and the shard_map engines call
    exactly this function on their slabs, which is what makes the sharded
    results bit-identical: the per-point expression, reduction order, and
    dtype are byte-for-byte the same program.
    """

    def tile_times(hw_point, size_scalars, st):
        """(L,) candidate times for one hardware point -- the vmap body."""
        n_sm, n_v, m_sm = hw_point
        s1, s2, s3, t = size_scalars
        size = ProblemSize(s1=s1, s2=s2, t=t, s3=s3)
        return stencil_time(
            st, gpu, size, n_sm, n_v, m_sm, *lat, xp=jnp, dtype=jnp.float32
        )

    def best_of(hw_chunk, sizes, st):
        """(P, chunk) optima: vmap over sizes x vmap over hardware points."""
        times = jax.vmap(
            lambda sz: jax.vmap(
                lambda p: tile_times(p, (sz[0], sz[1], sz[2], sz[3]), st)
            )(hw_chunk)
        )(sizes)  # (P, chunk, L)
        best_i = jnp.argmin(times, axis=2)
        best_t = jnp.take_along_axis(times, best_i[..., None], axis=2)[..., 0]
        # map back to seed lattice indices; -1 where nothing was feasible
        best_i = jnp.where(jnp.isfinite(best_t), keep_idx[best_i], -1)
        return best_t, best_i

    return best_of


def _solve_empty(n_sm, n_v, m_sm, consts):
    """Every-candidate-infeasible fast path (no lattice point survives the
    static constraints): +inf / -1 without touching the mesh or compiler."""
    p, h = consts.shape[0] - 1, n_sm.shape[0]
    return jnp.full((p, h), jnp.inf), jnp.full((p, h), -1, jnp.int32)


@functools.lru_cache(maxsize=None)
def _cells_solver(dims: int, gpu: GPUSpec, lattice: TileLattice, chunk: int):
    """Compiled (sizes x hardware x lattice) argmin solver, shared per
    (dims, GPU, lattice, chunk).

    Returned callable:
    ``(n_sm, n_v, m_sm, consts (P + 1, 4)) -> (best_t (P, H), best_i (P,
    H))`` over (H,) hardware arrays, ``consts`` the sizes and stencil
    scalars :func:`_pack_consts` packs. They are one dynamic jit argument,
    and the size axis is an extra vmap dimension: all P problem sizes of a
    stencil family sweep in ONE dispatch (the seed looped Python-side,
    paying per-cell dispatch).
    The whole six-stencil paper sweep still compiles exactly twice
    (2D + 3D); only a new (P, H) shape pair retraces.
    """
    lat, keep_idx = _lattice_arrays(lattice, gpu)
    if keep_idx.shape[0] == 0:  # no candidate survives the static constraints
        return _solve_empty
    best_of = _best_of_factory(gpu, lat, keep_idx)

    def solve(n_sm, n_v, m_sm, consts):
        sizes, st = _unpack_consts(dims, consts)
        hw = jnp.stack([n_sm, n_v, m_sm], axis=1)  # (H, 3)
        h = hw.shape[0]
        if chunk <= 0 or h <= chunk:
            return best_of(hw, sizes, st)
        # pad to a chunk multiple, lax.map over (B, chunk, 3) slabs so peak
        # memory is P x chunk x |lattice| regardless of |hardware space|.
        b = -(-h // chunk)
        pad = b * chunk - h
        hw = jnp.concatenate([hw, jnp.broadcast_to(hw[:1], (pad, 3))], axis=0)
        best_t, best_i = lax.map(
            lambda slab: best_of(slab, sizes, st),
            hw.reshape(b, chunk, 3),
        )  # (B, P, chunk)
        best_t = jnp.moveaxis(best_t, 0, 1).reshape(sizes.shape[0], -1)[:, :h]
        best_i = jnp.moveaxis(best_i, 0, 1).reshape(sizes.shape[0], -1)[:, :h]
        return best_t, best_i

    # the program's name on a device trace: jit_sweep_2d / jit_sweep_3d
    solve.__name__ = solve.__qualname__ = f"sweep_{dims}d"
    return jax.jit(solve)


@functools.lru_cache(maxsize=None)
def _sharded_cells_solver(
    dims: int,
    gpu: GPUSpec,
    lattice: TileLattice,
    chunk: int,
    devices: tuple,
):
    """Multi-device solver: the (H,) hardware axis sharded over a 1-D mesh.

    Same contract as :func:`_cells_solver`, but the caller must pass the
    hardware columns already padded to ``len(devices) x max(chunk, 1)``
    (see :func:`sweep_cells_sharded`): each device receives whole chunks,
    so the per-shard program is shape-static and identical on every device.
    ``devices`` is a tuple of jax Device objects (hashable singletons, so
    they key the lru_cache directly -- never remapped through per-backend
    integer ids, which collide across backends).

    Inside each shard a ``lax.fori_loop`` streams chunk-sized slabs through
    the fused time-model body and writes the per-chunk argmins into a
    preallocated ``(P, H/D)`` output -- peak per-device memory is the
    ``P x chunk x |lattice|`` times tensor of ONE slab plus the output,
    regardless of how large the hardware space grows. The hw slab buffers
    are donated: at fleet scale they are dead weight after the stack.
    """
    mesh = Mesh(np.array(devices), ("hw",))
    lat, keep_idx = _lattice_arrays(lattice, gpu)
    if keep_idx.shape[0] == 0:
        return mesh, _solve_empty
    best_of = _best_of_factory(gpu, lat, keep_idx)

    def shard_body(n_sm, n_v, m_sm, consts):
        """One device's shard: hw columns are the local (H/D,) slice."""
        sizes, st = _unpack_consts(dims, consts)
        hw = jnp.stack([n_sm, n_v, m_sm], axis=1)  # (H/D, 3)
        h, p = hw.shape[0], sizes.shape[0]
        if chunk <= 0 or h <= chunk:
            return best_of(hw, sizes, st)
        # the carry varies over "hw" (each device writes its own shard), so
        # its initial value must be typed varying too or fori_loop rejects it
        out_t = lax.pcast(jnp.full((p, h), jnp.inf, jnp.float32), "hw", to="varying")
        out_i = lax.pcast(jnp.full((p, h), -1, jnp.int32), "hw", to="varying")

        def one_chunk(c, carry):
            out_t, out_i = carry
            slab = lax.dynamic_slice_in_dim(hw, c * chunk, chunk, axis=0)
            t, i = best_of(slab, sizes, st)
            out_t = lax.dynamic_update_slice_in_dim(out_t, t, c * chunk, axis=1)
            out_i = lax.dynamic_update_slice_in_dim(out_i, i, c * chunk, axis=1)
            return out_t, out_i

        return lax.fori_loop(0, h // chunk, one_chunk, (out_t, out_i))

    shard_body.__name__ = shard_body.__qualname__ = f"sweep_{dims}d_sharded"
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P("hw"), P("hw"), P("hw"), P()),
        out_specs=(P(None, "hw"), P(None, "hw")),
    )
    return mesh, jax.jit(sharded, donate_argnums=(0, 1, 2))


def _prep_cells(st, sizes, lattice, chunk):
    """Shared argument normalization for the compiled engines: default
    lattice by dimensionality, (P, 4) size validation, P-scaled chunk."""
    if lattice is None:
        from .solver import LATTICE_2D, LATTICE_3D

        lattice = LATTICE_3D if st.dims == 3 else LATTICE_2D
    sizes = np.atleast_2d(np.asarray(sizes, np.float64))
    if sizes.shape[1] != 4:
        raise ValueError(f"sizes must be (P, 4) (s1, s2, s3, t); got {sizes.shape}")
    if chunk is None:
        chunk = max(1, DEFAULT_CHUNK // sizes.shape[0])
    return lattice, sizes, int(chunk)


def sweep_cells(
    st: StencilSpec,
    gpu: GPUSpec,
    sizes: np.ndarray,
    n_sm: np.ndarray,
    n_v: np.ndarray,
    m_sm: np.ndarray,
    lattice: TileLattice | None = None,
    chunk: int | None = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """All P problem sizes of one stencil in a single compiled dispatch.

    ``sizes`` is a ``(P, 4)`` float array of ``(s1, s2, s3, t)`` rows (the
    :data:`repro.core.workload.paper_sizes` grid packs 16 of them). Returns
    ``(best_time (P, H), best_lattice_index (P, H))`` as float64/int64;
    infeasible points get ``+inf`` / ``-1``. ``chunk=None`` scales the
    hardware slab down by P so peak memory matches the single-size sweep.

    Hardware columns that are already device arrays, as
    :func:`stage_columns` makes them, are used where they lie; host ones
    are copied as float32. The host columns and the packed sizes and
    stencil scalars go in one explicit ``jax.device_put`` (the span's
    ``transfers``: 1 with staged columns, 4 with host ones), and the
    optima come back in one ``jax.device_get``.
    """
    lattice, sizes, chunk = _prep_cells(st, sizes, lattice, chunk)
    cols = (n_sm, n_v, m_sm)
    staged = all(isinstance(a, jax.Array) for a in cols)
    host = [] if staged else [np.asarray(a, np.float32).ravel() for a in cols]
    h = np.size(n_sm)  # a device array's size is read from its shape
    with _dispatch("jax", st.dims, sizes.shape[0], h, len(host) + 1):
        solve = _cells_solver(st.dims, gpu, lattice, chunk)
        *put, consts = jax.device_put(host + [_pack_consts(st, sizes)])
        best_t, best_i = solve(*(cols if staged else put), consts)
        with span("sweep.fetch"):
            # blocks until the dispatch is done, then copies and casts
            best_t, best_i = jax.device_get((best_t, best_i))
            return best_t.astype(np.float64), best_i.astype(np.int64)


def stage_columns(n_sm, n_v, m_sm) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The three hardware columns as float32 device arrays, copied in one
    explicit ``jax.device_put``: what every :func:`sweep_cells` call of a
    question can share instead of copying them again. Counts 3 transfers
    on ``repro_sweep_transfers_total{engine="jax"}``."""
    cols = jax.device_put(
        tuple(np.asarray(a, np.float32).ravel() for a in (n_sm, n_v, m_sm))
    )
    _M_TRANSFERS.labels(engine="jax").inc(len(cols))
    return cols


def sweep_cells_sharded(
    st: StencilSpec,
    gpu: GPUSpec,
    sizes: np.ndarray,
    n_sm: np.ndarray,
    n_v: np.ndarray,
    m_sm: np.ndarray,
    lattice: TileLattice | None = None,
    chunk: int | None = None,
    devices=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`sweep_cells` with the hardware axis sharded across a device
    mesh -- the fleet-scale eq.-18 path.

    The (H,) hardware arrays are padded to a multiple of
    ``len(devices) x chunk`` (repeating the first point, whose padded
    results are discarded), partitioned over a 1-D ``Mesh(("hw",))`` with
    ``NamedSharding``, and each device streams its shard through the same
    fused time-model body as the single-device engine -- the gathered
    ``(best_t, best_i)`` are **bit-identical** to :func:`sweep_cells`
    (tested in ``tests/test_sweep_sharded.py``).

    ``devices`` is ``None`` (all attached), an int (first n devices), or an
    explicit device sequence. On CPU hosts, force a multi-device view with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* jax
    initializes to exercise the real sharded path.
    """
    lattice, sizes, chunk = _prep_cells(st, sizes, lattice, chunk)
    devs = _resolve_devices(devices)
    n_dev = len(devs)
    cols = [
        np.asarray(np.asarray(a).ravel(), np.float32) for a in (n_sm, n_v, m_sm)
    ]
    h = cols[0].shape[0]
    if h == 0:
        p = sizes.shape[0]
        return np.full((p, 0), np.inf), np.full((p, 0), -1, np.int64)
    # cap the per-device chunk at the actual shard size: the default 2048
    # against a small H would otherwise pad every device to a full chunk
    # of discarded time-model evaluations (8 dev x 2048 for H=64).
    if chunk > 0:
        chunk = min(chunk, -(-h // n_dev))
    # pad H so every device gets the same whole number of chunks: the shard
    # program is shape-static, and a ragged tail cannot skew one device.
    quantum = n_dev * max(chunk, 1)
    h_pad = -(-h // quantum) * quantum
    if h_pad != h:
        cols = [np.concatenate([a, np.full(h_pad - h, a[0], a.dtype)]) for a in cols]
    with _dispatch("sharded", st.dims, sizes.shape[0], h, len(cols) + 1):
        mesh, solve = _sharded_cells_solver(st.dims, gpu, lattice, chunk, devs)
        shard = NamedSharding(mesh, P("hw"))
        repl = NamedSharding(mesh, P())
        with warnings.catch_warnings():
            # the hw slabs are donated for accelerator meshes (dead after
            # the stack); on hosts where no output can alias them XLA drops
            # the donation and warns -- expected, not actionable.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            best_t, best_i = solve(
                *jax.device_put(cols, shard),
                jax.device_put(_pack_consts(st, sizes), repl),
            )
        with span("sweep.fetch"):
            # blocks on the dispatch, then copies, casts and drops the padding
            best_t, best_i = jax.device_get((best_t, best_i))
            return best_t[:, :h].astype(np.float64), best_i[:, :h].astype(np.int64)


def sweep_cell(
    st: StencilSpec,
    gpu: GPUSpec,
    size: ProblemSize,
    n_sm: np.ndarray,
    n_v: np.ndarray,
    m_sm: np.ndarray,
    lattice: TileLattice | None = None,
    chunk: int = DEFAULT_CHUNK,
) -> Tuple[np.ndarray, np.ndarray]:
    """Drop-in replacement for :func:`repro.core.solver.solve_cell` -- the
    P=1 case of :func:`sweep_cells`.

    Returns ``(best_time (H,), best_lattice_index (H,))`` as float64/int64
    NumPy arrays; infeasible hardware points get ``+inf`` / ``-1``.
    """
    sizes = np.array([[size.s1, size.s2, size.s3, size.t]], np.float64)
    best_t, best_i = sweep_cells(
        st, gpu, sizes, n_sm, n_v, m_sm, lattice, int(chunk)
    )
    return best_t[0], best_i[0]


# ---------------------------------------------------------------------------
# Batched coordinate-descent refinement
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _refine_descent(dims: int, gpu: GPUSpec):
    """Compiled whole-descent best-neighbor refinement over (P,) points.

    Candidates per point per round: current + (+step, -step) for each of
    the 5 software parameters, clamped to the aligned lower bounds; every
    point moves to its best single-parameter neighbor simultaneously
    (Jacobi-style). The rounds live in a ``lax.while_loop`` that stops on
    convergence (a no-movement round) or after ``max_rounds`` -- the whole
    descent is ONE dispatch and ONE device->host sync, where the previous
    engine forced a blocking ``bool(jnp.all(...))`` transfer every round.
    ``max_rounds`` is a dynamic operand, so changing the budget never
    retraces.
    """
    steps = jnp.asarray(SW_STEPS, jnp.float32)
    mins = jnp.asarray(SW_MINS, jnp.float32)
    n_par = len(SW_NAMES)

    def candidates(sw):
        """(2*n_par + 1, 5): current point first, then +/- steps."""
        deltas = jnp.concatenate(
            [jnp.zeros((1, n_par)), jnp.diag(steps), -jnp.diag(steps)], axis=0
        )
        return jnp.maximum(sw[None, :] + deltas, mins[None, :])

    def eval_point(st, hw, size_scalars, sw_cands):
        n_sm, n_v, m_sm = hw
        s1, s2, s3, t = size_scalars
        size = ProblemSize(s1=s1, s2=s2, t=t, s3=s3)
        return stencil_time(
            st, gpu, size, n_sm, n_v, m_sm,
            sw_cands[:, 0], sw_cands[:, 1], sw_cands[:, 2], sw_cands[:, 3],
            sw_cands[:, 4], xp=jnp, dtype=jnp.float32,
        )

    @jax.jit
    def descend(hw, sizes, sw0, radius, c_iter, n_arrays, max_rounds):
        """hw (P,3), sizes (P,4), sw0 (P,5) ->
        (times (P,), sw (P,5), rounds executed)."""
        st = _traced_spec(dims, radius, c_iter, n_arrays)

        def one_round(sw):
            cands = jax.vmap(candidates)(sw)  # (P, 2n+1, 5)
            times = jax.vmap(
                lambda h, s, c: eval_point(st, h, (s[0], s[1], s[2], s[3]), c)
            )(hw, sizes, cands)  # (P, 2n+1)
            best = jnp.argmin(times, axis=1)
            best_t = jnp.take_along_axis(times, best[:, None], axis=1)[:, 0]
            best_sw = jnp.take_along_axis(cands, best[:, None, None], axis=1)[:, 0]
            return best_t, best_sw

        def cond(carry):
            _, _, rounds, moved = carry
            return moved & (rounds < max_rounds)

        def body(carry):
            sw, _, rounds, _ = carry
            best_t, best_sw = one_round(sw)
            # a no-movement round means every point sat still (argmin ties
            # break to the current point), so best_t is exact: stop.
            moved = jnp.any(best_sw != sw)
            return best_sw, best_t, rounds + 1, moved

        t0 = jnp.full((sw0.shape[0],), jnp.inf, jnp.float32)
        sw, t, rounds, _ = lax.while_loop(
            cond, body, (sw0, t0, jnp.int32(0), jnp.bool_(True))
        )
        return t, sw, rounds

    return descend


def refine_points(
    st: StencilSpec,
    gpu: GPUSpec,
    sizes: np.ndarray,
    hw: np.ndarray,
    sw0: np.ndarray,
    max_rounds: int = 64,
) -> Tuple[np.ndarray, np.ndarray]:
    """Coordinate descent over aligned integer steps, batched over P points.

    Parameters
    ----------
    sizes: (P, 4) float array of (s1, s2, s3, t) per design point.
    hw:    (P, 3) float array of (n_sm, n_v, m_sm).
    sw0:   (P, 5) float array of starting tile sizes in :data:`SW_NAMES`
           order (e.g. lattice optima from :func:`sweep_cell`).

    Returns ``(times (P,), sw (P, 5))`` where no point's single aligned-step
    neighbor improves on its returned tile sizes (the same local-exactness
    guarantee as the seed's :func:`repro.core.solver.refine_point`, reached
    by best-neighbor rounds instead of first-improvement scans). As with
    the seed, the guarantee holds only when the descent converges within
    ``max_rounds``; lattice-optimum starts (the intended use) converge in a
    handful of rounds, but arbitrary far-from-optimal ``sw0`` may exhaust
    the budget and return the best point reached so far. The whole descent
    -- every round, every ``P x 11`` candidate -- is one compiled
    ``lax.while_loop`` dispatch with a single device->host sync at the end
    (the previous per-round ``bool(jnp.all(...))`` convergence check forced
    a blocking transfer every round).
    """
    hw64 = np.asarray(hw, np.float64)
    sizes64 = np.asarray(sizes, np.float64)
    sw = np.asarray(sw0, np.float64)
    if max_rounds <= 0:  # return the start points untouched, like the oracle
        size = ProblemSize(
            s1=sizes64[:, 0], s2=sizes64[:, 1], t=sizes64[:, 3], s3=sizes64[:, 2]
        )
        cur = stencil_time(
            st, gpu, size, hw64[:, 0], hw64[:, 1], hw64[:, 2],
            sw[:, 0], sw[:, 1], sw[:, 2], sw[:, 3], sw[:, 4],
        )
        return np.asarray(cur, np.float64), sw
    descend = _refine_descent(st.dims, gpu)
    t, sw_out, _ = descend(
        jnp.asarray(hw64, jnp.float32),
        jnp.asarray(sizes64, jnp.float32),
        jnp.asarray(sw, jnp.float32),
        jnp.asarray(st.radius, jnp.float32),
        jnp.asarray(st.c_iter, jnp.float32),
        jnp.asarray(st.n_arrays, jnp.float32),
        jnp.asarray(max_rounds, jnp.int32),
    )
    return np.asarray(t, np.float64), np.asarray(sw_out, np.float64)


def decode_sw(sw_row: np.ndarray) -> Dict[str, int]:
    """(5,) packed software-parameter row -> tile-size dict."""
    return {name: int(v) for name, v in zip(SW_NAMES, sw_row)}


def clear_caches() -> None:
    """Drop compiled solvers (mainly for tests/benchmarks timing cold starts)."""
    _cells_solver.cache_clear()
    _sharded_cells_solver.cache_clear()
    _refine_descent.cache_clear()
