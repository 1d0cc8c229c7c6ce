"""LM op-graph workload cells -- the second :class:`~repro.core.workload.Cell`
family (``family="lm"``), routing the repo's real model configs through the
same eq.-18 machinery as the stencils.

The mapping onto the paper's decomposition:

* **cell**: one ``(model, op, shape)`` triple -- ``prefill``, ``decode``
  (KV-cache streaming via :func:`repro.serve.kvcache.cache_bytes`),
  ``train`` step, or ``moe_dispatch`` (the all-to-all routing op of MoE
  models) -- with an occurrence frequency;
* **hardware axis** (the paper's ``(n_SM, n_V, M_SM)`` analogue): the
  chip-budget factorizations ``(pod, data, model)`` of
  :class:`LMHardwareSpace`, with **area := chips** so every existing area
  budget / Pareto / what-if reduction applies unchanged;
* **software axis** (the tile-size analogue): the
  ``(microbatches, remat, fsdp, compress_grads)`` lattice of
  :class:`MeshPlan` knobs, minimized out independently per (cell, hw).

Two engines, mirroring :mod:`repro.core.codesign`: ``"numpy"`` evaluates the
scalar oracle's exact float64 expressions vectorized over the whole
``(hw, sw)`` grid, and ``"jax"`` jits the identical traceable body in
float32 (one compile per op kind, named ``jit_lm_grid_<op>`` -- cell
constants enter as one traced array, the op's software lattice as
constants of the program). :func:`lm_cell_roofline` is the
plain-scalar oracle both are parity-tested against, and what
:func:`repro.core.lmtime.lm_roofline` evaluates; its docstring names the
source of each term beyond the weights (attention over context, expert
parallelism, sequence parallelism).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..configs.base import SHAPES, ArchConfig, ShapeSpec
from ..obs.compiles import compiles_so_far, listen_for_compiles
from ..obs.metrics import get_registry as _obs_registry
from ..obs.trace import set_attrs, span
from .engines import engine_family
from .lmtime import HW, MeshPlan
from .pareto import pareto_mask
from .workload import Workload

__all__ = [
    "LMCell",
    "LMHardwareSpace",
    "LMSwLattice",
    "LMCodesignResult",
    "LM_GPU_NAME",
    "enumerate_lm_hw_space",
    "lm_sw_lattice",
    "attention_flops",
    "lm_cell",
    "lm_cells_for",
    "lm_workload",
    "lm_cell_roofline",
    "lm_codesign",
]

#: default "gpu" routing attribute of LM artifacts: the chip the roofline
#: constants describe. Overridable per sweep (routing is not the model).
LM_GPU_NAME = "tpu_v5e"

#: the acceptance-criteria serving shape: decode at global batch 64 over an
#: 8k context (ISSUE: "what chip config serves Llama-3-8B at batch 64").
DECODE_B64 = ShapeSpec("decode_b64", 8192, 64, "decode")

LM_OPS = ("prefill", "decode", "train", "moe_dispatch")


@dataclasses.dataclass(frozen=True)
class LMCell:
    """One LM workload cell: an op of one model at one shape.

    All numeric fields are plain Python scalars precomputed at build time
    (parameter counts, the routed experts' among them, via
    ``jax.eval_shape``, KV bytes via
    :func:`repro.serve.kvcache.cache_bytes`, attention FLOPs via
    :func:`attention_flops`), so a cell round-trips through a JSON manifest
    and the sweep never re-touches model code. The MoE fields are set on
    every cell of an MoE model: the expert-parallel group of every op is
    sized by ``moe_n_experts``.
    """

    model: str  # arch name, e.g. "llama3-8b"
    op: str  # prefill | decode | train | moe_dispatch
    shape: ShapeSpec
    freq: float
    n_params: int  # total parameters (elements)
    n_active: int  # parameters touched per token (< n_params for MoE)
    kv_bytes: int  # full KV-cache bytes at this shape (0 unless decode)
    d_model: int
    n_layers: int
    flops: float  # useful FLOPs per step -- the GFLOP/s numerator
    moe_top_k: int = 0
    moe_capacity: float = 0.0
    moe_n_experts: int = 0
    attn_flops: float = 0.0  # the part of ``flops`` that is attention over context
    n_routed: int = 0  # routed-expert parameters, spread by expert parallelism

    def __post_init__(self):
        if self.op not in LM_OPS:
            raise ValueError(f"unknown LM op {self.op!r} (want one of {LM_OPS})")

    @property
    def family(self) -> str:
        return "lm"

    @property
    def label(self) -> str:
        return f"{self.model}:{self.op}"

    @property
    def tokens(self) -> int:
        """Tokens processed per step (decode emits one per sequence)."""
        return (
            self.shape.tokens
            if self.shape.kind != "decode"
            else self.shape.global_batch
        )

    def consts(self) -> Dict[str, float]:
        """The serializable numeric identity of this cell."""
        return {
            "n_params": int(self.n_params),
            "n_active": int(self.n_active),
            "kv_bytes": int(self.kv_bytes),
            "d_model": int(self.d_model),
            "n_layers": int(self.n_layers),
            "flops": float(self.flops),
            "moe_top_k": int(self.moe_top_k),
            "moe_capacity": float(self.moe_capacity),
            "moe_n_experts": int(self.moe_n_experts),
            "attn_flops": float(self.attn_flops),
            "n_routed": int(self.n_routed),
        }


# ---------------------------------------------------------------------------
# Design-space enumeration
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class LMHardwareSpace:
    """Flattened chip-budget factorizations; ``area`` IS the chip count, so
    the store/query/gateway area-budget machinery applies verbatim."""

    pod: np.ndarray
    data: np.ndarray
    model: np.ndarray
    area: np.ndarray  # = pod * data * model (chips)

    def __len__(self) -> int:
        return self.pod.shape[0]

    def point(self, i: int) -> Dict[str, float]:
        return {
            "pod": int(self.pod[i]),
            "data": int(self.data[i]),
            "model": int(self.model[i]),
            "chips": int(self.area[i]),
        }

    def downsample(self, step: int) -> "LMHardwareSpace":
        keep = np.arange(len(self)) % step == 0
        return LMHardwareSpace(
            self.pod[keep], self.data[keep], self.model[keep], self.area[keep]
        )


def enumerate_lm_hw_space(
    max_chips: int = 512, multi_pod: bool = True
) -> LMHardwareSpace:
    """All mesh factorizations ``pod * data * model <= max_chips`` with
    power-of-two data/model axes (the shapes XLA meshes actually take),
    sorted by (chips, pod, model) for a deterministic content address.

    At the 512 default every default cell fits HBM somewhere --
    Mixtral-8x22B's train step first fits at 256 v5e chips -- so the
    default pair artifact has a non-empty answer for its own uniform mix
    (a mix is infeasible at a mesh where *any* workload cell is
    infeasible, zero-weighted or not; see docs/lm_codesign.md)."""
    rows: List[Tuple[int, int, int]] = []
    pows = [1 << j for j in range(max_chips.bit_length()) if (1 << j) <= max_chips]
    for pod in (1, 2) if multi_pod else (1,):
        for data in pows:
            for model in pows:
                if pod * data * model <= max_chips:
                    rows.append((pod, data, model))
    rows.sort(key=lambda r: (r[0] * r[1] * r[2], r[0], r[2], r[1]))
    arr = np.array(rows, np.float64)
    return LMHardwareSpace(
        pod=arr[:, 0],
        data=arr[:, 1],
        model=arr[:, 2],
        area=arr[:, 0] * arr[:, 1] * arr[:, 2],
    )


@dataclasses.dataclass(frozen=True)
class LMSwLattice:
    """Software-knob candidate rows (aligned columns, not a cross product
    object -- row ``j`` is one :class:`MeshPlan` knob setting)."""

    microbatches: Tuple[int, ...]
    remat_full: Tuple[int, ...]  # 0 | 1
    fsdp: Tuple[int, ...]  # 0 | 1
    compress: Tuple[int, ...]  # 0 | 1

    def __len__(self) -> int:
        return len(self.microbatches)

    def plan(self, pod: int, data: int, model: int, j: int) -> MeshPlan:
        """Materialize row ``j`` at one hardware point."""
        return MeshPlan(
            pod=pod,
            data=data,
            model=model,
            microbatches=int(self.microbatches[j]),
            remat="full" if self.remat_full[j] else "none",
            fsdp=bool(self.fsdp[j]),
            compress_grads=bool(self.compress[j]),
        )

    def as_dict(self) -> Dict[str, List[int]]:
        return {
            k: [int(x) for x in getattr(self, k)]
            for k in ("microbatches", "remat_full", "fsdp", "compress")
        }


MICROBATCHES = (1, 2, 4, 8, 16, 32)


def lm_sw_lattice(op: str) -> LMSwLattice:
    """The software lattice an op minimizes over (the tile-size analogue).

    Train steps search the full ``microbatches x remat x fsdp x compress``
    product (48 rows: microbatches 1..32, remat none/full, fsdp off/on,
    compression off/on); inference ops and MoE dispatch have no backward
    pass, so only the weight-sharding knob remains (2 rows).
    """
    if op == "train":
        rows = list(
            itertools.product(MICROBATCHES, (0, 1), (0, 1), (0, 1))
        )
    else:
        rows = [(1, 0, 0, 0), (1, 0, 1, 0)]
    cols = list(zip(*rows))
    return LMSwLattice(
        microbatches=tuple(cols[0]),
        remat_full=tuple(cols[1]),
        fsdp=tuple(cols[2]),
        compress=tuple(cols[3]),
    )


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------
def attention_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """FLOPs per step of attention over context: the score (Q.K) and value
    (P.V) matmuls of every attention layer of the stack, which the weight
    term ``2 * n_active`` per token leaves out. Per layer kind:

    * ``full`` (Vaswani et al., arXiv:1706.03762): each of ``n_heads``
      query heads scores and mixes at ``head_dim`` (GQA shares K/V, not
      this work);
    * ``swa`` (Mistral 7B, arXiv:2310.06825 section 2): the same, each
      query seeing at most ``window`` keys;
    * ``mla`` (DeepSeek-V3, arXiv:2412.19437 section 2.1.1): prefill and
      train in the naive form, scores at ``head_dim + rope_head_dim`` (the
      nope and rope parts, 192) and values at ``v_head_dim`` (128); decode
      in the absorbed form (DeepSeek-V2, arXiv:2405.04434 section 2.1.2),
      where each cached position is the ``kv_lora_rank + rope_head_dim``
      latent: scores at that width, values at ``kv_lora_rank``;
    * ``ssm`` layers add nothing here (their scan is not priced yet), and
      neither do an encoder's layers, cross-attention or the MTP block.

    Prefill counts the causal pairs of each sequence (query ``i`` sees
    keys ``1..i``, or the last ``window`` of them) at 2 FLOPs a
    multiply-add; a train step is 3x its forward (the backward pass twice
    the forward); decode is one query per sequence over ``seq_len`` cached
    positions.
    """
    a = cfg.attn
    s, b = shape.seq_len, shape.global_batch
    w = a.window if a.kind == "swa" and a.window else s
    if a.kind == "mla" and shape.kind == "decode":
        qk, v = a.kv_lora_rank + a.rope_head_dim, a.kv_lora_rank
    elif a.kind == "mla":
        qk, v = cfg.head_dim_ + a.rope_head_dim, a.v_head_dim
    else:
        qk = v = cfg.head_dim_
    m = min(s, w)
    pairs = m if shape.kind == "decode" else m * (m + 1) // 2 + (s - m) * m
    n_attn = sum(1 for mixer, _ in cfg.layer_kinds() if mixer == "attn")
    fwd = 2.0 * cfg.n_heads * (qk + v) * pairs * b * n_attn
    return 3.0 * fwd if shape.kind == "train" else fwd


def lm_cell(
    cfg: ArchConfig,
    op: str,
    shape: ShapeSpec,
    n_params: int,
    n_active: int,
    n_routed: int,
    freq: float = 1.0,
) -> LMCell:
    """One cell of ``cfg``. The parameter counts are passed in, since each
    costs an ``eval_shape`` of the model; everything else is computed here.
    ``flops`` is the weight matmuls (``2 * n_active`` a token, ``6 *`` in
    training) plus :func:`attention_flops`; ``moe_dispatch`` counts its
    router matmul."""
    from ..serve.kvcache import cache_bytes

    tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
    moe = cfg.moe
    moe_consts = (
        {"moe_top_k": moe.top_k, "moe_capacity": moe.capacity_factor,
         "moe_n_experts": moe.n_experts}
        if moe is not None
        else {}
    )
    if op == "moe_dispatch":
        attn, kv = 0.0, 0
        flops = 2.0 * cfg.d_model * moe.n_experts * tokens
    else:
        if shape.kind != op:
            raise ValueError(f"shape {shape.name!r} is kind {shape.kind!r}, not {op!r}")
        attn = attention_flops(cfg, shape)
        flops = (6.0 if op == "train" else 2.0) * n_active * tokens + attn
        kv = int(cache_bytes(cfg, shape.global_batch, shape.seq_len)) if op == "decode" else 0
    return LMCell(
        model=cfg.name,
        op=op,
        shape=shape,
        freq=freq,
        n_params=n_params,
        n_active=n_active,
        kv_bytes=kv,
        d_model=cfg.d_model,
        n_layers=cfg.n_layers,
        flops=flops,
        attn_flops=attn,
        n_routed=n_routed,
        **moe_consts,
    )


def lm_cells_for(
    cfg: ArchConfig,
    shapes: Optional[Dict[str, ShapeSpec]] = None,
    freq: float = 1.0,
) -> List[LMCell]:
    """Unnormalized cells for one architecture: prefill + decode@batch-64 +
    train step, plus the MoE dispatch op when the config routes experts.

    ``shapes`` overrides the per-op shape table (keys: op names; the
    dispatch takes the decode shape unless given its own); parameter
    counts come from ``jax.eval_shape`` over the real model init, so they
    are exact without allocating anything.
    """
    from ..models.model import active_params, count_params, routed_expert_params

    shapes = {
        "prefill": SHAPES["prefill_32k"],
        "decode": DECODE_B64,
        "train": SHAPES["train_4k"],
        **(shapes or {}),
    }
    counts = (int(count_params(cfg)), int(active_params(cfg)), int(routed_expert_params(cfg)))
    ops = ["prefill", "decode", "train"]
    if cfg.moe is not None:
        ops.append("moe_dispatch")
        shapes.setdefault("moe_dispatch", shapes["decode"])
    return [lm_cell(cfg, op, shapes[op], *counts, freq=freq) for op in ops]


def lm_workload(
    archs: Sequence = ("llama3-8b", "mixtral-8x22b"),
    name: str = "lm",
    shapes: Optional[Dict[str, ShapeSpec]] = None,
) -> Workload:
    """Uniform-frequency LM workload over the given architectures (names
    resolved through the config registry, or :class:`ArchConfig` objects
    passed directly -- tests use ``cfg.reduced()``). The default pair is
    the docs walkthrough's: a dense 8B and a large MoE."""
    from ..configs import get_arch

    cfgs = [a if isinstance(a, ArchConfig) else get_arch(a) for a in archs]
    raw: List[LMCell] = []
    for cfg in cfgs:
        raw.extend(lm_cells_for(cfg, shapes=shapes))
    cells = tuple(dataclasses.replace(c, freq=1.0 / len(raw)) for c in raw)
    return Workload(name=name, cells=cells)


# ---------------------------------------------------------------------------
# Scalar oracle
# ---------------------------------------------------------------------------
def _div_ok(op: str, gb: int, data_shards: int, microbatches: int) -> bool:
    """Shardability: the global batch splits evenly over the data shards
    (or is smaller than them), and a train step's over its microbatches."""
    if gb % data_shards and gb >= data_shards:
        return False
    if op == "train" and gb % (data_shards * microbatches):
        return False
    return True


def lm_cell_roofline(cell: LMCell, plan: MeshPlan) -> Dict:
    """Plain-scalar reference model for one (cell, plan) point.

    :func:`repro.core.lmtime.lm_roofline` builds a cell and calls this, so
    the two cannot drift; :func:`_grid_times` is its vectorized twin, term
    for term in the same expression order. ``moe_dispatch`` is defined
    here only: the dispatch+combine all-to-all of ``capacity * top_k``
    routed tokens over the expert-parallel group, plus the router matmul,
    with weight-fit feasibility. Adds the mesh shardability constraint
    (``div_ok``) on top of the HBM fit; ``feasible`` is their conjunction
    and is what the sweep masks on.

    Sources of the terms beyond the three rooflines:

    * attention over context is in ``cell.flops`` (:func:`attention_flops`);
    * expert parallelism (DeepSeek-V3, arXiv:2412.19437 section 3.4:
      attention data-parallel, experts expert-parallel over the same
      chips): the routed experts (``cell.n_routed``) spread over the
      ``data x model`` chips of a pod, at most one group per expert
      (``ep``); where the model axis is wider than that group, each expert
      is also split over it (tensor parallelism within experts, as
      :mod:`repro.sharding.partition` does), so an expert shard lives on
      ``max(ep, model)`` chips. FSDP spreads it over every chip, as it does
      the other weights. The other weights divide over ``model`` (``x
      data`` under FSDP) as before. Expert gradients reduce over the
      replicas of their shard, the other gradients over the data axes;
    * sequence parallelism (Korthikanti et al., arXiv:2205.05198): ops over
      whole sequences keep the residual-stream activations split over the
      model axis, in the HBM fit and the activation traffic; each tensor
      all-reduce becomes a reduce-scatter plus an all-gather of the same
      bytes, so the TP term stays.
    """
    chips = plan.chips
    ds = plan.data_shards
    tokens = cell.tokens
    peak, hbm_bw = HW["peak_flops_bf16"], HW["hbm_bw"]
    ici_bw = HW["ici_links"] * HW["ici_link_bw"]
    ep = min(plan.data * plan.model, cell.moe_n_experts)
    e_shards = max(ep, plan.model)
    n_other = cell.n_params - cell.n_routed
    w_shards = plan.model * (ds if plan.fsdp else 1)
    e_div = chips if plan.fsdp else e_shards
    w_bytes = 2.0 * n_other / w_shards + 2.0 * cell.n_routed / e_div
    if cell.op == "moe_dispatch":
        toks_chip = cell.moe_capacity * cell.moe_top_k * tokens / chips
        t_compute = 2.0 * cell.d_model * cell.moe_n_experts * tokens / chips / peak
        t_memory = 2.0 * toks_chip * cell.d_model * 2.0 / hbm_bw
        ep_factor = (ep - 1) / ep
        t_coll = 2.0 * toks_chip * cell.d_model * 2.0 * ep_factor / ici_bw
        hbm = w_bytes
    else:
        train = cell.op == "train"
        n_layers_eff = max(cell.n_layers, 1)
        recompute = 1.0 + (0.5 if (train and plan.remat == "full") else 0.0)
        t_compute = cell.flops * recompute / (chips * peak)
        passes = (2.0 if train else 1.0) * plan.microbatches
        weight_traffic = w_bytes * passes
        tokens_local = tokens / ds
        act_traffic = 12.0 * tokens_local * cell.d_model * 2.0 * n_layers_eff
        if cell.op != "decode":
            act_traffic = act_traffic / plan.model
        opt_traffic = (12.0 * cell.n_params / chips) if train else 0.0
        kv_traffic = cell.kv_bytes / chips if cell.op == "decode" else 0.0
        t_memory = (weight_traffic + act_traffic + opt_traffic + kv_traffic) / hbm_bw
        tp_factor = 0.0 if plan.model == 1 else 2.0 * (plan.model - 1) / plan.model
        ar_per_layer = (4.0 if train and plan.remat == "full" else 2.0) * (
            2.0 if train else 1.0
        ) / 2.0
        tp_bytes = (
            ar_per_layer * n_layers_eff * tokens_local * cell.d_model * 2.0 * tp_factor
        ) * plan.microbatches
        dp_factor = 0.0 if ds == 1 or not train else 2.0 * (ds - 1) / ds
        e_replicas = chips / e_shards
        e_dp_factor = 0.0 if e_replicas == 1 or not train else 2.0 * (e_replicas - 1) / e_replicas
        grad_bytes_unit = 1.0 if plan.compress_grads else 4.0
        dp_bytes = (
            grad_bytes_unit * n_other / plan.model * dp_factor
            + grad_bytes_unit * cell.n_routed / e_shards * e_dp_factor
        )
        fsdp_bytes = (
            (2.0 * n_other / plan.model + 2.0 * cell.n_routed / e_shards) * passes
            if plan.fsdp
            else 0.0
        )
        pod_fraction = 0.0 if plan.pod == 1 else (plan.pod - 1) / plan.pod
        dci_bytes = dp_bytes * pod_fraction
        ici_bytes = tp_bytes + fsdp_bytes + dp_bytes * (1 - pod_fraction)
        t_coll = ici_bytes / ici_bw + dci_bytes / HW["dci_link_bw"]
        hbm = w_bytes
        if train:
            hbm += 12.0 * cell.n_params / chips
            hbm += 3.0 * (tokens_local / plan.microbatches) * cell.d_model * 2.0 * (
                n_layers_eff
            ) * (1.0 if plan.remat == "full" else 4.0) / plan.model
        if cell.op == "decode":
            hbm += cell.kv_bytes / chips
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    fits = hbm <= HW["hbm_bytes"] * 0.9
    div_ok = _div_ok(cell.op, cell.shape.global_batch, ds, plan.microbatches)
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "bound_s": terms[dominant],
        "hbm_bytes": hbm,
        "fits": fits,
        "div_ok": div_ok,
        "feasible": fits and div_ok,
    }


# ---------------------------------------------------------------------------
# Vectorized twin (traceable)
# ---------------------------------------------------------------------------
def _grid_times(op, consts, pod, data, model, mb, remat, fsdp, compress, xp):
    """(H, L) bound-seconds grid; +inf where infeasible.

    ``op`` is the only static branch (cell *structure*); every numeric
    input is an ``xp`` array or scalar, so the body traces under
    ``jax.vmap``/``jit`` and evaluates bit-exactly against the scalar
    oracle under ``xp=numpy`` float64 (identical expression order; the
    sources of the terms are in :func:`lm_cell_roofline`'s docstring).
    Hardware columns arrive shaped (H, 1), software columns (L,); all
    terms broadcast to (H, L).
    """
    (tokens, gb, n_params, kv_bytes, d_model, n_layers_eff, flops,
     top_k, capacity, n_experts, n_routed) = consts
    chips = pod * data * model
    ds = pod * data
    peak, hbm_bw = HW["peak_flops_bf16"], HW["hbm_bw"]
    ici_bw = HW["ici_links"] * HW["ici_link_bw"]
    one = xp.ones_like(mb)  # broadcast helper: (L,)
    ep = xp.minimum(data * model, n_experts)
    e_shards = xp.maximum(ep, model)
    n_other = n_params - n_routed
    w_shards = model * (1.0 + fsdp * (ds - 1.0))
    e_div = e_shards + fsdp * (chips - e_shards)
    w_bytes = 2.0 * n_other / w_shards + 2.0 * n_routed / e_div
    if op == "moe_dispatch":
        toks_chip = capacity * top_k * tokens / chips
        t_compute = (2.0 * d_model * n_experts * tokens / chips / peak) * one
        t_memory = (2.0 * toks_chip * d_model * 2.0 / hbm_bw) * one
        ep_factor = (ep - 1) / ep
        t_coll = (2.0 * toks_chip * d_model * 2.0 * ep_factor / ici_bw) * one
        hbm = w_bytes
    else:
        train = op == "train"
        recompute = 1.0 + 0.5 * remat if train else one
        t_compute = flops * recompute / (chips * peak)
        passes = (2.0 if train else 1.0) * mb
        weight_traffic = w_bytes * passes
        tokens_local = tokens / ds
        act_traffic = 12.0 * tokens_local * d_model * 2.0 * n_layers_eff
        if op != "decode":
            act_traffic = act_traffic / model
        opt_traffic = 12.0 * n_params / chips if train else 0.0
        kv_traffic = kv_bytes / chips if op == "decode" else 0.0
        t_memory = (weight_traffic + act_traffic + opt_traffic + kv_traffic) / hbm_bw
        tp_factor = 2.0 * (model - 1.0) / model
        ar_per_layer = (2.0 + 2.0 * remat) * 2.0 / 2.0 if train else one
        tp_bytes = (
            ar_per_layer * n_layers_eff * tokens_local * d_model * 2.0 * tp_factor
        ) * mb
        dp_factor = 2.0 * (ds - 1.0) / ds if train else 0.0
        e_replicas = chips / e_shards
        e_dp_factor = 2.0 * (e_replicas - 1.0) / e_replicas if train else 0.0
        grad_bytes_unit = 4.0 - 3.0 * compress
        dp_bytes = (
            grad_bytes_unit * n_other / model * dp_factor
            + grad_bytes_unit * n_routed / e_shards * e_dp_factor
        )
        fsdp_bytes = fsdp * ((2.0 * n_other / model + 2.0 * n_routed / e_shards) * passes)
        pod_fraction = (pod - 1.0) / pod
        dci_bytes = dp_bytes * pod_fraction
        ici_bytes = tp_bytes + fsdp_bytes + dp_bytes * (1 - pod_fraction)
        t_coll = ici_bytes / ici_bw + dci_bytes / HW["dci_link_bw"]
        hbm = w_bytes
        if train:
            hbm = hbm + 12.0 * n_params / chips + 3.0 * (
                tokens_local / mb
            ) * d_model * 2.0 * n_layers_eff * (4.0 - 3.0 * remat) / model
        if op == "decode":
            hbm = hbm + kv_bytes / chips
    bound = xp.maximum(t_compute, xp.maximum(t_memory, t_coll))
    fits = hbm <= HW["hbm_bytes"] * 0.9
    div = (xp.mod(gb, ds) == 0) | (gb < ds)
    if op == "train":
        div = div & (xp.mod(gb, ds * mb) == 0)
    feasible = fits & div
    return xp.where(feasible, bound, xp.inf)


def _cell_consts(cell: LMCell) -> Tuple[float, ...]:
    """The numeric tuple :func:`_grid_times` consumes (order matters)."""
    return (
        float(cell.tokens),
        float(cell.shape.global_batch),
        float(cell.n_params),
        float(cell.kv_bytes),
        float(cell.d_model),
        float(max(cell.n_layers, 1)),
        float(cell.flops),
        float(cell.moe_top_k),
        float(cell.moe_capacity),
        float(cell.moe_n_experts),
        float(cell.n_routed),
    )


_REG = _obs_registry()
_M_COMPILES = _REG.counter(
    "repro_lm_compiles_total",
    "programs JAX compiled or loaded from its persistent cache during LM "
    "grid dispatches, counted on the dispatching thread",
    labels=("op",),
)
_M_TRANSFERS = _REG.counter(
    "repro_lm_transfers_total",
    "host-to-device copies of LM grid inputs: the question's meshes once, "
    "then each cell's constants",
)


@contextlib.contextmanager
def _dispatch(op: str, h: int, l: int) -> Iterator[None]:
    """One cell's grid dispatch: the ``lm.dispatch`` span and its compile
    count (the span's ``compiles`` attr and the counter), read from the
    per-thread count of JAX's compile events."""
    listen_for_compiles()
    with span("lm.dispatch", op=op, h=h, l=l):
        n0 = compiles_so_far()
        yield
        compiles = compiles_so_far() - n0
        set_attrs(compiles=compiles)
    _M_COMPILES.labels(op=op).inc(compiles)


_JIT_CACHE: Dict[Tuple[str, LMSwLattice], object] = {}


def _jax_grid_fn(op: str):
    """One compiled grid evaluator per op kind and its software lattice
    (:func:`lm_sw_lattice`, cached under both, so a changed lattice never
    meets a stale program), named ``jit_lm_grid_<op>`` in HLO and on the
    device trace. It takes a cell's constants as a float32 ``(11,)`` array
    in :func:`_cell_consts`' order and the meshes as a float32 ``(3, H)``
    array of pod, data and model rows, sliced to ``(H, 1)`` columns inside
    the program; the lattice's columns are constants of the program. Every
    cell of an op reuses the same executable."""
    lat = lm_sw_lattice(op)
    if (op, lat) not in _JIT_CACHE:
        import jax
        import jax.numpy as jnp

        sw = [np.asarray(c, np.float32)
              for c in (lat.microbatches, lat.remat_full, lat.fsdp, lat.compress)]

        def grid(consts, meshes):
            pod, data, model = (meshes[i][:, None] for i in range(3))
            return _grid_times(op, consts, pod, data, model, *map(jnp.asarray, sw), jnp)

        grid.__name__ = grid.__qualname__ = f"lm_grid_{op}"
        _JIT_CACHE[op, lat] = jax.jit(grid)
    return _JIT_CACHE[op, lat]


# ---------------------------------------------------------------------------
# Result + driver
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class LMCodesignResult:
    """Per-cell optimal step times for every mesh factorization -- the LM
    twin of :class:`repro.core.codesign.CodesignResult`, exposing the same
    reduction surface so the artifact store, query engine, and gateway
    treat both families uniformly. ``gflops`` here reads "model GFLOP/s":
    useful model FLOPs per step over the optimized step time."""

    workload: Workload
    hw: LMHardwareSpace
    cell_time: np.ndarray  # (C, H) optimal bound_s; +inf infeasible
    cell_plan_idx: np.ndarray  # (C, H) winning sw-lattice row (-1 infeasible)
    sw_lattices: List[LMSwLattice]  # per cell
    gpu_name: str = LM_GPU_NAME

    family = "lm"

    # ---- reductions (same contracts as CodesignResult) --------------------
    def cell_freqs(self) -> np.ndarray:
        return np.array([c.freq for c in self.workload.cells], np.float64)

    def cell_flops(self) -> np.ndarray:
        return np.array([c.flops for c in self.workload.cells], np.float64)

    def weighted_time(self, freqs: Optional[np.ndarray] = None) -> np.ndarray:
        if freqs is None:
            freqs = self.cell_freqs()
        freqs = np.asarray(freqs, np.float64)
        return freqs @ self.cell_time

    def gflops(self, freqs: Optional[np.ndarray] = None) -> np.ndarray:
        if freqs is None:
            freqs = self.cell_freqs()
        freqs = np.asarray(freqs, np.float64)
        return (freqs @ self.cell_flops()) / self.weighted_time(freqs) / 1.0e9

    def pareto(self, freqs: Optional[np.ndarray] = None) -> np.ndarray:
        return pareto_mask(self.hw.area, self.gflops(freqs))

    def best(self, max_area: float = np.inf, freqs=None) -> Tuple[int, float]:
        g = self.gflops(freqs)
        g = np.where(self.hw.area <= max_area, g, -np.inf)
        i = int(np.argmax(g))
        return i, float(g[i])

    def plan_for(self, cell_index: int, hw_index: int) -> MeshPlan:
        """The winning :class:`MeshPlan` of one (cell, hw) solve."""
        j = int(self.cell_plan_idx[cell_index, hw_index])
        if j < 0:
            raise ValueError("infeasible cell/hw combination")
        p = self.hw.point(hw_index)
        return self.sw_lattices[cell_index].plan(p["pod"], p["data"], p["model"], j)

    def routing_metadata(self) -> Dict[str, object]:
        """Manifest routing block: same keys a stencil sweep publishes
        (gpu, workload) plus the LM discriminators (family, models, ops) --
        ``workload: "lm"`` is what ``query --workload lm`` selects on."""
        return {
            "gpu": self.gpu_name,
            "workload": self.workload.name,
            "family": "lm",
            "models": sorted({c.model for c in self.workload.cells}),
            "ops": sorted({c.op for c in self.workload.cells}),
        }

    # ---- artifact serialization ------------------------------------------
    def artifact_payload(self) -> Tuple[dict, Dict[str, np.ndarray]]:
        """(manifest, arrays) split; exact inverse of
        :meth:`from_artifact_payload` (JSON round-trips float64 losslessly)."""
        unique: List[LMSwLattice] = []
        lat_idx: List[int] = []
        for lat in self.sw_lattices:
            if lat not in unique:
                unique.append(lat)
            lat_idx.append(unique.index(lat))
        manifest = {
            "workload": {
                "name": self.workload.name,
                "family": "lm",
                "cells": [
                    {
                        "model": c.model,
                        "op": c.op,
                        "shape": {
                            "name": c.shape.name,
                            "seq_len": int(c.shape.seq_len),
                            "global_batch": int(c.shape.global_batch),
                            "kind": c.shape.kind,
                        },
                        "freq": float(c.freq),
                        "consts": c.consts(),
                        "lattice": lat_idx[i],
                    }
                    for i, c in enumerate(self.workload.cells)
                ],
            },
            "gpu": {"name": self.gpu_name, "hw": dict(HW)},
            "sw_lattices": [lat.as_dict() for lat in unique],
            "routing": self.routing_metadata(),
        }
        arrays = {
            "cell_time": np.asarray(self.cell_time, np.float64),
            "cell_plan_idx": np.asarray(self.cell_plan_idx, np.int64),
            "hw_pod": np.asarray(self.hw.pod, np.float64),
            "hw_data": np.asarray(self.hw.data, np.float64),
            "hw_model": np.asarray(self.hw.model, np.float64),
            "hw_area": np.asarray(self.hw.area, np.float64),
        }
        return manifest, arrays

    @staticmethod
    def parse_manifest(
        manifest: dict,
    ) -> Tuple[Workload, str, List[LMSwLattice]]:
        """JSON-only half of :meth:`from_artifact_payload`: ``(workload,
        gpu_name, per-cell sw lattices)``, touching no arrays."""
        lattices_tbl = [
            LMSwLattice(**{k: tuple(int(x) for x in v) for k, v in d.items()})
            for d in manifest["sw_lattices"]
        ]
        cells: List[LMCell] = []
        lattices: List[LMSwLattice] = []
        for c in manifest["workload"]["cells"]:
            s = c["shape"]
            shape = ShapeSpec(s["name"], s["seq_len"], s["global_batch"], s["kind"])
            cells.append(
                LMCell(
                    model=c["model"], op=c["op"], shape=shape, freq=c["freq"],
                    **c["consts"],
                )
            )
            lattices.append(lattices_tbl[c["lattice"]])
        workload = Workload(manifest["workload"]["name"], tuple(cells))
        return workload, manifest["gpu"]["name"], lattices

    @classmethod
    def from_artifact_payload(
        cls, manifest: dict, arrays: Dict[str, np.ndarray]
    ) -> "LMCodesignResult":
        workload, gpu_name, lattices = cls.parse_manifest(manifest)
        hw = LMHardwareSpace(
            pod=np.asarray(arrays["hw_pod"], np.float64),
            data=np.asarray(arrays["hw_data"], np.float64),
            model=np.asarray(arrays["hw_model"], np.float64),
            area=np.asarray(arrays["hw_area"], np.float64),
        )
        return cls(
            workload=workload,
            hw=hw,
            cell_time=np.asarray(arrays["cell_time"]),
            cell_plan_idx=np.asarray(arrays["cell_plan_idx"]),
            sw_lattices=lattices,
            gpu_name=gpu_name,
        )


def lm_codesign(
    workload: Workload,
    hw: Optional[LMHardwareSpace] = None,
    max_chips: int = 512,
    engine: str = "auto",
    gpu_name: str = LM_GPU_NAME,
) -> LMCodesignResult:
    """Eq. (18) for the LM family: for every mesh factorization, the
    optimal software knobs (and step time) of every cell.

    ``engine="numpy"`` evaluates the oracle's float64 expressions
    vectorized (bit-exact vs :func:`lm_cell_roofline`); ``"jax"`` jits the
    same body in float32; ``"auto"`` is jax (:func:`repro.core.engines
    .engine_family`, with no hardware floor). Infeasible
    (cell, hw) combinations -- HBM overflow or unshardable batch at every
    software setting -- carry ``+inf`` time and plan index ``-1``, exactly
    the stencil sweep's convention.

    The jax engine copies the meshes to the device once, as one float32
    ``(3, H)`` array, and each cell's constants once, as one float32
    ``(11,)`` array; it enqueues every cell's grid before it fetches them
    all together. One ``lm.codesign`` span (its ``transfers`` attr counts
    those copies, 1 + C, and ``repro_lm_transfers_total`` grows by it)
    holds an ``lm.dispatch`` span per cell (its ``compiles`` attr counted
    as the stencil sweep counts them), then the jax engine's one
    ``lm.fetch`` (attr ``grids``, the grids it fetched together).
    """
    if getattr(workload, "family", "stencil") != "lm":
        raise ValueError(f"lm_codesign wants an LM workload, got {workload.family!r}")
    if hw is None:
        hw = enumerate_lm_hw_space(max_chips=max_chips)
    eng = engine_family(engine)
    C, H = len(workload.cells), len(hw)
    cell_time = np.empty((C, H))
    cell_idx = np.empty((C, H), dtype=np.int64)
    lattices = [lm_sw_lattice(c.op) for c in workload.cells]
    with span("lm.codesign", engine=eng, cells=C, h=H):
        grids = []
        if eng == "jax":
            import jax

            meshes = jax.device_put(np.stack([hw.pod, hw.data, hw.model]).astype(np.float32))
            for cell, lat in zip(workload.cells, lattices):
                with _dispatch(cell.op, H, len(lat)):
                    consts = jax.device_put(np.asarray(_cell_consts(cell), np.float32))
                    grids.append(_jax_grid_fn(cell.op)(consts, meshes))
            transfers = 1 + C
            with span("lm.fetch", grids=C):
                # blocks until every grid is done, then copies them together
                grids = [np.asarray(g, np.float64) for g in jax.device_get(grids)]
        else:
            c64 = lambda a: np.asarray(a, np.float64)
            for cell, lat in zip(workload.cells, lattices):
                with _dispatch(cell.op, H, len(lat)):
                    grids.append(_grid_times(
                        cell.op, _cell_consts(cell),
                        c64(hw.pod)[:, None], c64(hw.data)[:, None], c64(hw.model)[:, None],
                        c64(lat.microbatches), c64(lat.remat_full),
                        c64(lat.fsdp), c64(lat.compress),
                        np,
                    ))
            transfers = 0
        set_attrs(transfers=transfers)
        _M_TRANSFERS.inc(transfers)
        for ci, grid in enumerate(grids):
            idx = np.argmin(grid, axis=1)
            t = grid[np.arange(H), idx]
            cell_time[ci] = t
            cell_idx[ci] = np.where(np.isfinite(t), idx, -1)
    return LMCodesignResult(workload, hw, cell_time, cell_idx, lattices, gpu_name)
