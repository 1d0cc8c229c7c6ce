"""Analytic TPU execution-time model for the LM cells -- the `T(p, h, s)`
of the paper's codesign problem, re-grounded on the v5e fleet (DESIGN.md,
"The TPU bridge").

Problem parameters  p: ArchConfig + ShapeSpec (the 40 assigned cells)
Hardware parameters h: mesh factorization (pod, data, model) of the chip
                       budget -- the paper's (n_SM, n_V, M_SM) analogue
Software parameters s: microbatches, remat policy, fsdp on/off,
                       gradient compression -- the paper's tile sizes

The model returns the three roofline terms (seconds/step, per chip) plus
an HBM-fit feasibility flag (the eq. 9/11 analogue: the working set must
fit the per-chip memory budget). The constants are v5e datasheet values;
no prediction has yet been checked against a timed step on the chip.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from ..configs.base import ArchConfig, ShapeSpec

__all__ = ["MeshPlan", "lm_roofline", "HW"]

#: TPU v5e per-chip constants. Units: ``peak_flops_bf16`` FLOP/s,
#: ``hbm_bw``/``ici_link_bw``/``dci_link_bw`` bytes/s, ``ici_links`` count
#: (the torus gives each chip 4 usable links), ``hbm_bytes`` bytes.
HW = {
    "peak_flops_bf16": 197e12,
    "hbm_bw": 819e9,
    "ici_link_bw": 50e9,
    "ici_links": 4,
    "dci_link_bw": 12.5e9,  # cross-pod (data-center network) per chip
    "hbm_bytes": 16e9,
}


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """One point in the hardware x software design space.

    Hardware axes (chip-count factorization, ``chips = pod*data*model``):
    ``pod`` pods bridged by DCN, ``data``-way data parallelism within a
    pod, ``model``-way tensor parallelism. Software knobs (the paper's
    tile-size analogue): ``microbatches`` splits the global batch into
    sequential pipeline passes; ``remat`` trades +50% forward FLOPs for a
    4x smaller activation working set when "full"; ``fsdp`` additionally
    shards weights over the data axis (all-gathering them per pass);
    ``compress_grads`` sends int8 (1-byte) instead of f32 gradients in the
    data-parallel all-reduce.
    """

    pod: int
    data: int
    model: int
    microbatches: int = 1
    remat: str = "full"  # none | full
    fsdp: bool = False
    compress_grads: bool = False

    @property
    def chips(self) -> int:
        return self.pod * self.data * self.model

    @property
    def data_shards(self) -> int:
        return self.pod * self.data


def lm_roofline(
    cfg: ArchConfig,
    shape: ShapeSpec,
    plan: MeshPlan,
    n_params: int,
    n_active: int,
) -> Dict:
    """Three analytic roofline terms + feasibility for one design point.

    Args:
        cfg: architecture (its attention layers price attention over
            context; its routed experts, counted by ``jax.eval_shape``,
            spread over the expert-parallel group).
        shape: workload shape; ``kind`` picks the cost model. For decode,
            "one step" means one token generated per sequence, so the
            compute term scales with ``global_batch`` tokens while the
            memory term streams the full ``seq_len``-deep KV cache.
        plan: mesh factorization + software knobs (see :class:`MeshPlan`).
        n_params: total parameter count (elements, bf16-stored).
        n_active: parameters touched per token (``< n_params`` for MoE).

    Returns a dict of per-step wall-clock seconds — ``compute_s``,
    ``memory_s``, ``collective_s``, their max ``bound_s`` with the
    ``dominant`` term's name — plus the per-chip working set ``hbm_bytes``,
    ``fits`` (True iff it is under 90% of HBM, the eq. 9/11 analogue) and
    the shardability flags ``div_ok``/``feasible``. The terms, and their
    sources, are :func:`repro.core.lmcells.lm_cell_roofline`'s: this builds
    the one cell and evaluates it there, so the scalar model, its
    vectorized twin and this entry point cannot drift apart.
    """
    from ..models.model import routed_expert_params
    from .lmcells import lm_cell, lm_cell_roofline

    cell = lm_cell(cfg, shape.kind, shape, n_params, n_active, routed_expert_params(cfg))
    return lm_cell_roofline(cell, plan)
