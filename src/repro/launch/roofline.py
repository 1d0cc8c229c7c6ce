"""Roofline analysis over the dry-run artifacts (deliverable g).

This container is CPU-only; TPU v5e is the *target*. The three roofline
terms are derived per (arch x shape x mesh) from the compiled artifact:

    compute term    = HLO_FLOPs / peak_FLOP/s            [per-chip]
    memory term     = HLO_bytes / HBM_bw                 [per-chip]
    collective term = collective_bytes / (links*link_bw) [per-chip]

where HLO_FLOPs is the *scan-expanded* dot-FLOP count (see hloanalysis.py --
cost_analysis visits while bodies once and would undercount by the layer
count), HLO_bytes is the loop-expanded *materialized* bytes (write+read of
every fusion-boundary tensor -- cost_analysis 'bytes accessed' has no
fusion awareness and overstates HBM traffic by orders of magnitude), and
collective_bytes is the loop-expanded sum of collective operand bytes
parsed from the optimized HLO.

The SPMD module after partitioning is per-chip, so every quantity here is
per-chip per-step; dividing by per-chip peaks gives seconds directly (the
"/ chips" in the assignment formulas is absorbed because cost_analysis is
already per-chip).

Also reported per cell: dominant term, MODEL_FLOPS = 6*N(_active)*D (2*N*D
for inference shapes), useful-compute ratio MODEL_FLOPS/HLO_FLOPs, and a
one-line lever for the dominant term.

This module reads *compiled* HLO counters; its analytic twin is
``repro.core.lmtime.lm_roofline``, which predicts the same three terms
from closed-form traffic formulas (and whose ``HW`` table extends the one
below with DCI constants for cross-pod meshes). The LM codesign sweep
(``repro.core.lmcells``) vectorizes those formulas over whole mesh-plan
lattices.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional

__all__ = ["HW", "roofline_terms", "load_cells", "render_table", "main"]

#: TPU v5e per-chip hardware constants (assignment-provided).
HW = {
    "peak_flops_bf16": 197e12,  # FLOP/s
    "hbm_bw": 819e9,  # B/s
    "ici_link_bw": 50e9,  # B/s per link
    "ici_links": 4,  # torus links usable per chip (2D torus, 4 neighbours)
    "hbm_bytes": 16e9,
}


def model_flops_for(rec: Dict, seq_len: int, global_batch: int) -> float:
    """6*N_active*D for training, 2*N_active*D forward-only (prefill),
    2*N_active*B for one decoded token."""
    n = rec.get("active_params") or rec.get("params") or 0
    kind = rec.get("kind", "train")
    if kind == "train":
        return 6.0 * n * seq_len * global_batch
    if kind == "prefill":
        return 2.0 * n * seq_len * global_batch
    return 2.0 * n * global_batch  # decode: one token per sequence


def roofline_terms(rec: Dict, chips: Optional[int] = None) -> Dict:
    """Three terms in seconds (per chip = per step wall-clock bound)."""
    chips = chips or rec.get("chips", 256)
    raw_flops = rec.get("flops", 0.0) or 0.0
    exp_flops = rec.get("dot_flops_expanded", 0.0) or 0.0
    ratio = exp_flops / raw_flops if raw_flops > 0 and exp_flops > 0 else 1.0
    ratio = max(ratio, 1.0)
    bytes_accessed = rec.get("materialized_bytes", 0.0) or (
        (rec.get("bytes_accessed", 0.0) or 0.0) * ratio
    )
    coll = rec.get("collective_bytes", 0.0) or 0.0

    t_compute = exp_flops / HW["peak_flops_bf16"]
    t_memory = bytes_accessed / HW["hbm_bw"]
    t_coll = coll / (HW["ici_links"] * HW["ici_link_bw"])
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    bound = terms[dominant]
    out = dict(terms)
    out["dominant"] = dominant.replace("_s", "")
    out["bound_s"] = bound
    out["bytes_expansion_ratio"] = ratio
    return out


_LEVERS = {
    "compute": (
        "cut recompute (remat policy) or raise MXU utilization "
        "(pad matmul dims to 128, fuse small einsums)"
    ),
    "memory": (
        "raise arithmetic intensity: larger microbatch per chip, bf16 "
        "accumulators where safe, fuse normalization chains"
    ),
    "collective": (
        "re-shard to cut all-reduce bytes: sequence-parallel reduce-scatter, "
        "microbatch-amortized grad reduction, int8 cross-pod compression, "
        "or a different mesh factorization"
    ),
}


def load_cells(outdir: str, mesh_kind: str = "single") -> List[Dict]:
    d = os.path.join(outdir, mesh_kind)
    cells = []
    if not os.path.isdir(d):
        return cells
    for name in sorted(os.listdir(d)):
        if name.endswith(".json"):
            with open(os.path.join(d, name)) as f:
                cells.append(json.load(f))
    return cells


def analyze_cell(rec: Dict, shapes: Dict) -> Optional[Dict]:
    if rec.get("skipped") or "error" in rec:
        return None
    shape = shapes[rec["shape"]]
    terms = roofline_terms(rec)
    mf_total = model_flops_for(rec, shape.seq_len, shape.global_batch)
    mf_chip = mf_total / rec.get("chips", 256)
    hlo = rec.get("dot_flops_expanded", 0.0) or 1.0
    useful = mf_chip / hlo if hlo else 0.0
    step_s = terms["bound_s"]
    mfu = (mf_chip / HW["peak_flops_bf16"]) / step_s if step_s > 0 else 0.0
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "plan": rec.get("plan", {}),
        **{k: terms[k] for k in ("compute_s", "memory_s", "collective_s")},
        "dominant": terms["dominant"],
        "model_flops_per_chip": mf_chip,
        "useful_ratio": useful,
        "roofline_fraction": mfu,
        "lever": _LEVERS[terms["dominant"]],
        "hbm_gb": (rec.get("memory", {}).get("temp_size_in_bytes", 0)
                   + rec.get("memory", {}).get("argument_size_in_bytes", 0)) / 1e9,
    }


def render_table(rows: List[Dict]) -> str:
    hdr = (
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "useful | roofline frac | HBM GB |\n"
        "|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} | {r['hbm_gb']:.1f} |"
        )
    return hdr + "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--json", default="")
    args = ap.parse_args()
    from repro.configs.base import SHAPES

    rows = []
    for rec in load_cells(args.out, args.mesh):
        row = analyze_cell(rec, SHAPES)
        if row:
            rows.append(row)
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    print(render_table(rows))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
