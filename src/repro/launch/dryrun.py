import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Test hook only: a smaller fake-device count, set BEFORE jax locks devices.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input shape) cell and both production meshes
(single-pod 16x16 = 256 chips, multi-pod 2x16x16 = 512 chips):

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...) \
                       .lower(**input_specs(arch))
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

plus a parse of the optimized HLO for collective operand bytes (the
collective roofline term is not in cost_analysis). Results land as one JSON
per cell under --out; the run is resumable (existing JSONs are skipped)
and `repro.launch.roofline` consumes the artifacts.

train_4k lowers the *train step* (fwd+bwd+AdamW); prefill_32k lowers the
prefill; decode_32k / long_500k lower serve_step (one token against a
seq_len-deep cache). long_500k runs only for sub-quadratic archs (ssm /
hybrid / SWA) -- skips are recorded, not silently dropped.
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES
from repro.configs.base import ArchConfig, ShapeSpec, get_arch
from repro.models.model import (
    _head,
    active_params,
    count_params,
    forward,
    forward_hidden,
    init_model,
)
from repro.optim.adamw import AdamWConfig
from repro.serve.kvcache import init_caches
from repro.sharding.partition import batch_specs, cache_specs, param_specs
from repro.train.train_step import TrainConfig, make_train_step
from repro.launch.mesh import make_mesh, make_production_mesh

#: archs whose attention cost is sub-quadratic in context (may run long_500k)
SUBQUADRATIC = {"mamba2-780m", "jamba-v0.1-52b", "mixtral-8x22b"}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8, "s32": 4,
    "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
    "c128": 16,
}
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_TYPE_RE = re.compile(r"\b(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")


def applicable(arch: str, shape_name: str) -> Tuple[bool, str]:
    if shape_name == "long_500k" and arch not in SUBQUADRATIC:
        return False, (
            "full-attention arch: 500k decode is quadratic-cost; skipped per "
            "assignment note (DESIGN.md §Arch-applicability)"
        )
    return True, ""


# ---------------------------------------------------------------------------
# Per-cell plan: the pre-hillclimb defaults
# ---------------------------------------------------------------------------
def plan_cell(cfg: ArchConfig, shape: ShapeSpec, mesh) -> Dict:
    """Pre-hillclimb defaults.

    * fsdp: on when TP-only parameter shards exceed ~4 GB/chip;
    * remat 'full': 'dots' saves attention probability matrices
      (B*H*S^2 -- 34 GB/chip at train_4k) -- recompute-everything keeps only
      the per-layer residual carry;
    * microbatches sized so the saved residual stash (~3x tokens_local *
      d_model * 2 B per layer) stays under ~4 GB/chip. Tokens shard over the
      data axes only, so the estimate uses data shards, not total chips.
    """
    axis = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_shards = axis.get("data", 1) * axis.get("pod", 1)
    model_size = axis.get("model", 1)
    p_bytes = 2 * count_params(cfg)
    fsdp = p_bytes / model_size > 4e9
    microbatches = 1
    if shape.kind == "train":
        tokens_local = shape.tokens / data_shards
        saved = cfg.n_layers * tokens_local * cfg.d_model * 2 * 3
        # cap: each microbatch must still shard over the data axes, or
        # GSPMD pads/replicates the whole attention path
        mb_cap = max(1, shape.global_batch // data_shards)
        while saved / microbatches > 4e9 and microbatches < mb_cap:
            microbatches *= 2
    return {
        "fsdp": bool(fsdp),
        "microbatches": int(microbatches),
        "remat": "full",
        "attn_impl": "auto",
    }


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """Stand-ins for the *batch* inputs of the lowered step."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"tokens": _sds((b, 1), jnp.int32)}
    specs = {"tokens": _sds((b, s), jnp.int32)}
    if shape.kind == "train":
        s_lab = s + (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)
        specs["labels"] = _sds((b, s_lab), jnp.int32)
    if cfg.frontend or cfg.enc_dec:
        specs["frontend"] = _sds((b, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    return specs


def _abstract_params(cfg: ArchConfig):
    return jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))


def _abstract_state(cfg: ArchConfig, tcfg: TrainConfig):
    params = _abstract_params(cfg)
    mdt = jnp.dtype(tcfg.opt.moment_dtype)
    f32 = lambda t: jax.tree.map(lambda x: _sds(x.shape, jnp.float32), t)
    mom = lambda t: jax.tree.map(lambda x: _sds(x.shape, mdt), t)
    state = {
        "params": params,
        "opt": {"m": mom(params), "v": mom(params), "step": _sds((), jnp.int32)},
    }
    if tcfg.compress_grads:
        state["comp"] = f32(params)
    return state


# ---------------------------------------------------------------------------
# Lowering per shape kind
# ---------------------------------------------------------------------------
def lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, plan: Dict):
    to_sh = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )
    p_abs = _abstract_params(cfg)
    p_sh = to_sh(param_specs(cfg, p_abs, mesh, fsdp=plan["fsdp"]))
    b_specs_all = batch_specs(cfg, mesh, batch_size=shape.global_batch)
    batch_sds = input_specs(cfg, shape)
    b_sh = {k: NamedSharding(mesh, b_specs_all.get(k, b_specs_all["tokens"])) for k in batch_sds}

    if shape.kind == "train":
        tcfg = TrainConfig(
            microbatches=plan["microbatches"],
            remat=plan["remat"],
            attn_impl=plan["attn_impl"],
            fsdp=plan["fsdp"],
            opt=AdamWConfig(moment_dtype=plan.get("moments", "float32")),
        )
        step = make_train_step(cfg, tcfg, mesh)
        state = _abstract_state(cfg, tcfg)
        return step.lower(state, batch_sds)

    if shape.kind == "prefill":
        # vlm: vision embeddings prepend n_frontend_tokens to the sequence
        cache_len = shape.seq_len + (
            cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
        )

        def prefill(params, batch):
            b = batch["tokens"].shape[0]
            caches = init_caches(cfg, b, cache_len, dtype=jnp.dtype(cfg.dtype))
            hidden, caches, _ = forward_hidden(
                params, cfg, batch, caches=caches, impl=plan["attn_impl"]
            )
            return _head(cfg, params, hidden[:, -1:])[:, 0], caches

        return jax.jit(prefill, in_shardings=(p_sh, b_sh)).lower(p_abs, batch_sds)

    # decode: one token against a seq_len-deep cache
    caches_abs = jax.eval_shape(
        lambda: init_caches(
            cfg, shape.global_batch, shape.seq_len, dtype=jnp.dtype(cfg.dtype),
            include_enc=cfg.enc_dec,
        )
    )
    c_sh = to_sh(cache_specs(cfg, caches_abs, mesh, batch_size=shape.global_batch))

    def decode(params, tokens, caches, cache_index):
        batch = {"tokens": tokens, "cache_index": cache_index}
        logits, caches, _ = forward(params, cfg, batch, caches=caches, impl=plan["attn_impl"])
        return logits[:, -1], caches

    return jax.jit(
        decode,
        in_shardings=(p_sh, b_sh["tokens"], c_sh, None),
        donate_argnums=(2,),
    ).lower(
        p_abs,
        input_specs(cfg, shape)["tokens"],
        caches_abs,
        _sds((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Analysis of the compiled artifact
# ---------------------------------------------------------------------------
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum *operand* bytes of every collective op in the optimized HLO.

    XLA's optimized dump types the result (lhs of '='), not the operands,
    so operand bytes are derived from result bytes per op semantics:
    all-reduce/all-to-all/collective-permute have operand == result;
    all-gather's operand is result / group_size; reduce-scatter's operand
    is result * group_size.
    """
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        eq = line.find("=")
        if eq < 0 or eq > m.start():
            continue
        result_part = line[eq + 1 : m.start()]
        nbytes = 0.0
        for t, dims in _TYPE_RE.findall(result_part):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[t]
        g = _group_size(line)
        if op == "all-gather":
            nbytes /= max(g, 1)
        elif op == "reduce-scatter":
            nbytes *= max(g, 1)
        rec = out.setdefault(op, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


def analyze(lowered) -> Dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    rec: Dict = {"compile_s": round(compile_s, 2)}

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec["flops"] = float(cost.get("flops", -1.0))
        rec["bytes_accessed"] = float(cost.get("bytes accessed", -1.0))
        rec["transcendentals"] = float(cost.get("transcendentals", 0.0))
    except Exception as e:  # noqa: BLE001
        rec["cost_error"] = repr(e)

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
    except Exception as e:  # noqa: BLE001
        rec["memory_error"] = repr(e)

    try:
        text = compiled.as_text()
        # scan-aware accounting: while bodies (layer scans, microbatch
        # accumulation, chunked attention) multiplied by their trip counts
        from repro.launch.hloanalysis import analyze_hlo

        totals = analyze_hlo(text)
        rec["dot_flops_expanded"] = totals.dot_flops
        rec["collectives"] = totals.per_collective
        rec["collective_bytes"] = totals.collective_bytes
        rec["materialized_bytes"] = totals.materialized_bytes
        rec["while_trips"] = totals.while_trips[:32]
        # raw single-visit parse kept for reference/debugging
        colls_raw = parse_collectives(text)
        rec["collective_bytes_raw"] = sum(v["bytes"] for v in colls_raw.values())
    except Exception as e:  # noqa: BLE001
        rec["collective_error"] = repr(e)
    return rec


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def run_cell(
    arch: str, shape_name: str, mesh_kind: str, outdir: str, tiny: bool = False,
    plan_overrides: Optional[Dict] = None,
) -> Dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if tiny:
        cfg = cfg.reduced()
        shape = dataclasses.replace(
            shape, seq_len=min(shape.seq_len, 128), global_batch=min(shape.global_batch, 8)
        )
        mesh = make_mesh(
            (2, 2, 2) if mesh_kind == "multi" else (2, 2),
            ("pod", "data", "model") if mesh_kind == "multi" else ("data", "model"),
        )
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    rec: Dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "chips": int(mesh.devices.size),
        "kind": shape.kind,
        "tiny": tiny,
    }
    ok, reason = applicable(arch, shape_name)
    if not ok:
        rec.update(skipped=True, reason=reason)
        return rec

    rec["params"] = count_params(cfg)
    rec["active_params"] = active_params(cfg)
    plan = plan_cell(cfg, shape, mesh)
    if plan_overrides:
        plan.update(plan_overrides)
    rec["plan"] = plan
    t0 = time.time()
    with mesh:
        lowered = lower_cell(cfg, shape, mesh, plan)
    rec["lower_s"] = round(time.time() - t0, 2)
    rec.update(analyze(lowered))
    rec["skipped"] = False
    return rec


def _out_path(outdir, mesh_kind, arch, shape_name):
    d = os.path.join(outdir, mesh_kind)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape id or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--tiny", action="store_true", help="reduced configs (CI)")
    ap.add_argument("--force", action="store_true", help="recompute existing")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--moments", default=None, help="optimizer moment dtype")
    args = ap.parse_args()

    import repro.configs._register_all  # noqa: F401

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    overrides = {}
    if args.fsdp:
        overrides["fsdp"] = args.fsdp == "on"
    if args.microbatches:
        overrides["microbatches"] = args.microbatches
    if args.remat:
        overrides["remat"] = args.remat
    if args.moments:
        overrides["moments"] = args.moments

    n_ok = n_skip = n_fail = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                path = _out_path(args.out, mesh_kind, arch, shape_name)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {mesh_kind}/{arch}/{shape_name}")
                    continue
                t0 = time.time()
                try:
                    rec = run_cell(
                        arch, shape_name, mesh_kind, args.out, tiny=args.tiny,
                        plan_overrides=overrides or None,
                    )
                    status = "SKIP" if rec.get("skipped") else "ok"
                    n_skip += rec.get("skipped", False)
                    n_ok += not rec.get("skipped", False)
                except Exception as e:  # noqa: BLE001
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
                        "error": repr(e), "traceback": traceback.format_exc(),
                        "skipped": False,
                    }
                    status = "FAIL"
                    n_fail += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                dt = time.time() - t0
                extra = ""
                if "flops" in rec:
                    extra = (
                        f" flops={rec['flops']:.3e}"
                        f" coll={rec.get('collective_bytes', 0):.3e}B"
                    )
                print(
                    f"[{status}] {mesh_kind}/{arch}/{shape_name} ({dt:.0f}s)"
                    f"{extra}",
                    flush=True,
                )
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
