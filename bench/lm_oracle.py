"""Plain reference for the LM cold codesign question, and the comparison
that decides ``correct``.

Everything here is computed from a configuration file (``configs/*.json``)
alone and imports nothing of the program under test. The model's widths
are the published ``config.json`` keys the file restates (DeepSeek-V3:
https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json);
from them come, in closed form, the parameter counts (total, active per
token, routed experts), the latent cache's bytes and the attention work
over context. Then, for every cell and every (mesh, software) pair, the
step-time roofline in float64 NumPy: compute, HBM traffic and collectives,
their maximum, and the HBM fit and batch divisibility that make a pair
feasible. Each term is written out once below with its source; there is
no static pruning, chunking or batching.

The comparison reads what the timed path persisted (``cell_time`` and
``cell_plan_idx`` read back from the store, and the hardware columns
stored beside them) at every hardware column, and the answered best
design, and reduces them to two numbers:

* ``cell_err`` -- over every (cell, mesh) entry, the largest of the
  relative error of the stored optimum time and the relative amount by
  which the stored software choice is slower than the reference's
  optimum. An entry whose feasibility differs, whose plan index is out of
  range, or whose stored mesh is not the one the question asked about
  reads ``inf``.
* ``best_err`` -- for the answered best design: the largest of the
  relative error of the claimed model GFLOP/s and the relative amount by
  which the reference finds a better mesh within the chip budget. A
  missing answer, or a named mesh that is not the asked mesh at that
  index, reads ``inf``.
"""

from __future__ import annotations

import itertools
import math
import os
from typing import Dict, List, Tuple

import numpy as np

from oracle import rng_for

HW_NAMES = ("pod", "data", "model", "area")
SW_NAMES = ("microbatches", "remat_full", "fsdp", "compress")
BF16_BYTES = 2.0


# ---------------------------------------------------------------------------
# the model, in closed form from the published widths
# ---------------------------------------------------------------------------
def _moe_layers(cfg: dict) -> int:
    """MoE layers of the main model: every layer from
    ``first_k_dense_replace`` on, at ``moe_layer_freq``."""
    first, every, n = cfg["first_k_dense_replace"], cfg["moe_layer_freq"], cfg["num_hidden_layers"]
    return sum(1 for i in range(first, n) if i % every == 0)


def param_counts(cfg: dict) -> Dict[str, int]:
    """Total, active-per-token and routed-expert parameters of the main
    model plus ``num_nextn_predict_layers`` MTP modules (arXiv:2412.19437
    sections 2.1-2.2), counted from the published widths.

    Per layer: two RMSNorms; MLA's query down- and up-projections with
    the query latent's norm, the joint KV down-projection (latent plus the
    shared rope key) with the KV latent's norm, the KV up-projection to
    per-head nope keys and values, and the output projection; then either
    the dense SwiGLU MLP or the MoE layer: router, its per-expert
    correction bias (``noaux_tc``), the shared experts and the routed
    experts, three d x width matrices each. Embedding and output head
    (untied) and the final norm once. Each MTP module is a block of the
    MoE kind plus its two input norms, the 2d x d projection and its
    output norm; it shares the embedding and the output head.
    """
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_r, kv_r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = (d * q_r + q_r + q_r * h * (nope + rope)
            + d * (kv_r + rope) + kv_r + kv_r * h * (nope + v)
            + h * v * d)
    norms = 2 * d
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    routed_layer = e * 3 * d * f
    moe = d * e + e + 3 * d * f * cfg["n_shared_experts"] + routed_layer
    dense_mlp = 3 * d * cfg["intermediate_size"]
    n_moe = _moe_layers(cfg)
    n_dense = cfg["num_hidden_layers"] - n_moe
    vocab = cfg["vocab_size"] * d * (1 if cfg["tie_word_embeddings"] else 2)
    main = vocab + d + cfg["num_hidden_layers"] * (attn + norms) + n_dense * dense_mlp + n_moe * moe
    n_mtp = cfg["num_nextn_predict_layers"]
    mtp = n_mtp * (2 * d + 2 * d * d + attn + norms + moe + d)
    routed = (n_moe + n_mtp) * routed_layer
    total = main + mtp
    active = total - routed // e * (e - cfg["num_experts_per_tok"])
    return {"total": total, "active": active, "routed": routed}


def kv_cache_bytes(cfg: dict, batch: int, seq_len: int) -> float:
    """MLA caches the compressed KV latent and the shared rope key per
    position and layer (section 2.1.1): ``kv_lora_rank + qk_rope_head_dim``
    values, in bf16."""
    return BF16_BYTES * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * (
        cfg["num_hidden_layers"] * seq_len * batch)


def attention_flops(cfg: dict, op: str, seq_len: int, batch: int) -> float:
    """Score and value matmuls over context, all layers (every layer is
    MLA), 2 FLOPs a multiply-add. Prefill: each query i of a sequence sees
    keys 1..i, at a score width of nope + rope and a value width of
    ``v_head_dim`` per head. Train: 3x the forward. Decode: one query per
    sequence over ``seq_len`` cached latents in the absorbed form
    (DeepSeek-V2, arXiv:2405.04434 section 2.1.2): scores over the
    ``kv_lora_rank + qk_rope_head_dim`` latent, values over
    ``kv_lora_rank``."""
    h, n = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    if op == "decode":
        width = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) + cfg["kv_lora_rank"]
        return 2.0 * h * width * seq_len * batch * n
    width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) + cfg["v_head_dim"]
    causal_pairs = seq_len * (seq_len + 1) / 2.0
    fwd = 2.0 * h * width * causal_pairs * batch * n
    return 3.0 * fwd if op == "train" else fwd


def cells(cfg: dict) -> List[dict]:
    """The workload's cells in order, each with its constants; uniform mix."""
    counts = param_counts(cfg)
    out = []
    for c in cfg["cells"]:
        op, s, b = c["op"], c["seq_len"], c["global_batch"]
        tokens = b if op in ("decode", "moe_dispatch") else s * b
        cell = {"op": op, "seq_len": s, "batch": b, "tokens": tokens, "n_params": counts["total"],
                "n_active": counts["active"], "n_routed": counts["routed"], "attn_flops": 0.0,
                "kv_bytes": 0.0}
        if op == "moe_dispatch":
            # the router: d x n_experts per token
            cell["flops"] = 2.0 * cfg["hidden_size"] * cfg["n_routed_experts"] * tokens
        else:
            cell["attn_flops"] = attention_flops(cfg, op, s, b)
            weights = (6.0 if op == "train" else 2.0) * counts["active"] * tokens
            cell["flops"] = weights + cell["attn_flops"]
            if op == "decode":
                cell["kv_bytes"] = kv_cache_bytes(cfg, b, s)
        out.append(cell)
    for c in out:
        c["freq"] = 1.0 / len(out)
    return out


# ---------------------------------------------------------------------------
# the design space
# ---------------------------------------------------------------------------
def hardware_space(cfg: dict) -> Dict[str, np.ndarray]:
    """Every mesh ``pod x data x model`` within the chip budget, data and
    model powers of two, pods as listed, in the order (chips, pod, model,
    data)."""
    hs = cfg["hardware_space"]
    top = hs["max_chips"]
    pows = [1 << j for j in range(top.bit_length()) if (1 << j) <= top]
    rows = sorted(((p, d, m) for p in hs["pods"] for d in pows for m in pows if p * d * m <= top),
                  key=lambda r: (r[0] * r[1] * r[2], r[0], r[2], r[1]))
    arr = np.array(rows, np.float64)
    out = {"pod": arr[:, 0], "data": arr[:, 1], "model": arr[:, 2],
           "area": arr[:, 0] * arr[:, 1] * arr[:, 2]}
    if len(rows) != hs["points"]:
        raise ValueError(f"{cfg['name']}: {len(rows)} meshes, the configuration states {hs['points']}")
    return out


def permuted(space: Dict[str, np.ndarray], seed: int, question: int) -> Dict[str, np.ndarray]:
    """The meshes of one question: the whole space in an order drawn from
    (seed, question). Same work and shapes for every question, a new
    content address each time."""
    perm = rng_for(seed, 1, question).permutation(len(space["pod"]))
    return {k: v[perm] for k, v in space.items()}


def sw_lattice(cfg: dict, op: str) -> Dict[str, np.ndarray]:
    """The software settings one op minimizes over, in the artifact's plan
    index order: the product of the value lists in ``SW_NAMES`` order."""
    lat = cfg["software"]["train" if op == "train" else "other"]
    rows = np.array(list(itertools.product(*(lat[k] for k in SW_NAMES))), np.float64)
    return {k: rows[:, j] for j, k in enumerate(SW_NAMES)}


# ---------------------------------------------------------------------------
# the roofline (float64 on NumPy; any array namespace and dtype)
# ---------------------------------------------------------------------------
def step_times(cfg: dict, cell: dict, hw: Dict, xp=np, dtype=np.float64):
    """Bound seconds of one step for every (mesh, software) pair that
    ``hw`` (columns shaped (S, 1)) and the op's lattice (L,) broadcast to;
    +inf where the pair does not fit HBM or cannot shard the batch.

    * compute: the cell's FLOPs (weights, attention over context) over the
      chips' bf16 peak, x1.5 under full rematerialisation in training;
    * memory: each chip's weights once per pass (two passes per
      microbatch in training), residual-stream activations (12 reads and
      writes of tokens x d per layer, split over the model axis by
      sequence parallelism, arXiv:2205.05198, except in decode), Adam's 12
      bytes a parameter in training, and the KV cache in decode;
    * collectives: tensor-parallel all-reduces of the activations (one
      per layer in a forward pass; 2, or 4 under remat, per layer and
      microbatch in training), gradient all-reduces
      (the other weights over the data replicas, each routed-expert shard
      over its own replicas; the pod part over the cross-pod network),
      FSDP's weight gathers; for ``moe_dispatch``, the dispatch and
      combine all-to-all of ``capacity x top_k`` tokens over the expert
      group;
    * weights: routed experts spread over the expert-parallel group, the
      ``data x model`` chips of a pod up to one per expert, split further
      over the model axis where it is wider (arXiv:2412.19437 section
      3.4), or over every chip under FSDP; the other weights over the
      model axis, times data under FSDP;
    * fit: weights, Adam state over all chips and activations (x4 without
      remat, per microbatch, split over the model axis) in training, and
      the cache in decode, within 90% of HBM.
    """
    tpu = cfg["tpu_v5e"]
    f = lambda v: xp.asarray(v, dtype)
    lat = sw_lattice(cfg, cell["op"])
    mb, remat, fsdp, comp = (f(lat[k]) for k in SW_NAMES)
    pod, data, model = f(hw["pod"]), f(hw["data"]), f(hw["model"])
    chips, ds = pod * data * model, pod * data
    d, n_layers = f(cfg["hidden_size"]), f(cfg["num_hidden_layers"])
    n_params, n_routed = f(cell["n_params"]), f(cell["n_routed"])
    n_other = n_params - n_routed
    tokens = f(cell["tokens"])
    peak, hbm_bw = f(tpu["peak_flops_bf16"]), f(tpu["hbm_bw"])
    ici_bw = f(tpu["ici_links"] * tpu["ici_link_bw"])
    op, train = cell["op"], cell["op"] == "train"

    group = xp.minimum(data * model, f(cfg["n_routed_experts"]))
    expert_home = xp.maximum(group, model)
    per_chip_weights = (BF16_BYTES * n_other / (model * xp.where(fsdp > 0, ds, 1.0))
                        + BF16_BYTES * n_routed / xp.where(fsdp > 0, chips, expert_home))

    if op == "moe_dispatch":
        slots = f(cfg["capacity_factor"] * cfg["num_experts_per_tok"]) * tokens / chips
        t_compute = f(cell["flops"]) / chips / peak + 0.0 * mb
        t_memory = 2.0 * slots * d * BF16_BYTES / hbm_bw + 0.0 * mb
        t_coll = 2.0 * slots * d * BF16_BYTES * (group - 1.0) / group / ici_bw + 0.0 * mb
        hbm = per_chip_weights
    else:
        remat_cost = 1.0 + 0.5 * remat if train else 1.0 + 0.0 * remat
        t_compute = f(cell["flops"]) * remat_cost / (chips * peak)
        passes = (2.0 if train else 1.0) * mb
        tokens_local = tokens / ds
        acts = 12.0 * tokens_local * d * BF16_BYTES * n_layers
        if op != "decode":
            acts = acts / model
        adam = 12.0 * n_params / chips if train else 0.0
        kv = f(cell["kv_bytes"]) / chips
        t_memory = (per_chip_weights * passes + acts + adam + kv) / hbm_bw
        all_reduces = (2.0 + 2.0 * remat) if train else 1.0 + 0.0 * remat
        tp = all_reduces * n_layers * tokens_local * d * BF16_BYTES * (2.0 * (model - 1.0) / model) * mb
        grad_unit = 4.0 - 3.0 * comp
        replicas = chips / expert_home
        grads = (grad_unit * n_other / model * (2.0 * (ds - 1.0) / ds)
                 + grad_unit * n_routed / expert_home * (2.0 * (replicas - 1.0) / replicas))
        grads = grads if train else 0.0 * grads
        gathers = fsdp * (BF16_BYTES * n_other / model + BF16_BYTES * n_routed / expert_home) * passes
        cross_pod = (pod - 1.0) / pod
        t_coll = (tp + gathers + grads * (1.0 - cross_pod)) / ici_bw + grads * cross_pod / f(tpu["dci_link_bw"])
        hbm = per_chip_weights + kv
        if train:
            act_set = 3.0 * (tokens_local / mb) * d * BF16_BYTES * n_layers * (4.0 - 3.0 * remat) / model
            hbm = hbm + 12.0 * n_params / chips + act_set
    bound = xp.maximum(t_compute, xp.maximum(t_memory, t_coll))
    gb = f(cell["batch"])
    shards = (xp.mod(gb, ds) == 0) | (gb < ds)
    if train:
        shards = shards & (xp.mod(gb, ds * mb) == 0)
    ok = (hbm <= f(tpu["hbm_bytes"] * tpu["hbm_usable"])) & shards
    return xp.where(ok, bound, xp.inf)


def cell_tables(cfg: dict, hw_cols: Dict[str, np.ndarray], xp=np, dtype=np.float64):
    """Per cell, the (S, L) table of candidate times at the S meshes
    given (as float64 NumPy)."""
    hw = {name: np.asarray(hw_cols[name], np.float64)[:, None] for name in ("pod", "data", "model")}
    for ci, cell in enumerate(cells(cfg)):
        yield ci, np.asarray(step_times(cfg, cell, hw, xp, dtype), np.float64)


def optimum(table: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(time, plan index) of the first fastest candidate per mesh; +inf /
    -1 where none is feasible."""
    idx = np.argmin(table, axis=1)
    t = table[np.arange(table.shape[0]), idx]
    return t, np.where(np.isfinite(t), idx, -1)


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------
def weighted_gflops(cfg: dict, times: np.ndarray) -> np.ndarray:
    """(S,) workload model GFLOP/s from a (C, S) matrix of per-cell times."""
    cs = cells(cfg)
    freqs = np.array([c["freq"] for c in cs])
    flops = np.array([c["flops"] for c in cs])
    return (freqs @ flops) / (freqs @ times) / 1.0e9


def entry_err(table: np.ndarray, t_ref: np.ndarray, t_got: np.ndarray, i_got: np.ndarray) -> np.ndarray:
    """(S,) per-mesh errors of one cell's stored optima against the
    reference's (S, L) table and its optimum times ``t_ref``."""
    feas = np.isfinite(t_ref)
    err = np.zeros(len(t_ref))
    bad = (np.isfinite(t_got) != feas) | ((i_got >= 0) != feas)
    bad |= feas & ((i_got < 0) | (i_got >= table.shape[1]))
    ok = feas & ~bad
    t_at = table[np.nonzero(ok)[0], i_got[ok]]
    err[ok] = np.maximum(np.abs(t_got[ok] - t_ref[ok]), t_at - t_ref[ok]) / t_ref[ok]
    err[bad] = np.inf
    return err


def best_err(asked: Dict[str, np.ndarray], g_ref: np.ndarray, answer: dict, budget: float) -> float:
    """The ``best_err`` of one answer against the reference's GFLOP/s."""
    pos, claimed = answer["pos"], answer["gflops"]
    if pos is None or not math.isfinite(claimed):
        return math.inf
    named = {"pod": "pod", "data": "data", "model": "model", "area": "chips"}
    for name, key in named.items():
        if float(answer["point"].get(key, math.nan)) != float(asked[name][pos]):
            return math.inf
    g = np.where((asked["area"] <= budget) & np.isfinite(g_ref), g_ref, -np.inf)
    if not np.isfinite(g[pos]):
        return math.inf
    top = float(g.max())
    return float(max(abs(claimed - g[pos]), top - g[pos]) / top)


def compare(cfg: dict, asked: Dict[str, np.ndarray], budget: float,
            answers: Dict[str, dict]) -> Dict[str, Dict[str, float]]:
    """``cell_err`` and ``best_err`` of each answer to one question.

    ``asked`` holds the meshes the question asked about, in its order.
    Each answer gives ``hw`` (the meshes it stored), ``time``/``idx`` (its
    (C, S) optima and plan indices), ``pos`` (the index of its best design,
    None for no answer), ``gflops`` (its claim for that design) and
    ``point`` (the design it names). ``cell_err_finite`` is ``cell_err``
    over the entries that read finite."""
    n = len(asked["pod"])
    worst = {key: 0.0 for key in answers}
    finite = {key: 0.0 for key in answers}
    bad_hw = {}
    for key, a in answers.items():
        bad_hw[key] = np.zeros(n, bool)
        for name in HW_NAMES:
            bad_hw[key] |= np.asarray(a["hw"][name], np.float64) != asked[name]
    ref_time = np.empty((len(cfg["cells"]), n))
    for ci, table in cell_tables(cfg, asked):
        t_ref, _ = optimum(table)
        ref_time[ci] = t_ref
        for key, a in answers.items():
            err = entry_err(table, t_ref, a["time"][ci], a["idx"][ci])
            err[bad_hw[key]] = np.inf
            worst[key] = max(worst[key], float(err.max(initial=0.0)))
            finite[key] = max(finite[key], float(err[np.isfinite(err)].max(initial=0.0)))
    with np.errstate(invalid="ignore", divide="ignore"):
        g_ref = weighted_gflops(cfg, ref_time)
    return {key: {"cell_err": worst[key], "best_err": best_err(asked, g_ref, a, budget),
                  "cell_err_finite": finite[key]}
            for key, a in answers.items()}


def control_answer(cfg: dict, asked: Dict[str, np.ndarray], xp, dtype, budget: float) -> dict:
    """The reference computed in a lower precision, put in the program's
    place: its optima, and the design it would answer with its own
    GFLOP/s claim (an answer in the form :func:`compare` takes)."""
    tables = list(cell_tables(cfg, asked, xp=xp, dtype=dtype))
    times = np.empty((len(tables), len(asked["pod"])))
    idx = np.empty(times.shape, np.int64)
    for ci, table in tables:
        times[ci], idx[ci] = optimum(table)
    with np.errstate(invalid="ignore", divide="ignore"):
        g = weighted_gflops(cfg, times)
    g = np.where((asked["area"] <= budget) & np.isfinite(g), g, -np.inf)
    pos = int(np.argmax(g))
    point = {"pod": asked["pod"][pos], "data": asked["data"][pos], "model": asked["model"][pos],
             "chips": asked["area"][pos]}
    return {"hw": asked, "time": times, "idx": idx, "pos": pos, "gflops": float(g[pos]),
            "point": {k: float(v) for k, v in point.items()}}


def readback(art_dir: str) -> Tuple[np.ndarray, np.ndarray, Dict[str, np.ndarray]]:
    """The persisted artifact's optima, plan indices and mesh columns, read
    from its files (``cell_time.npy`` and ``arrays.npz``)."""
    cell_time = np.load(os.path.join(art_dir, "cell_time.npy"))
    with np.load(os.path.join(art_dir, "arrays.npz")) as z:
        idx = np.asarray(z["cell_plan_idx"], np.int64)
        hw = {name: np.asarray(z[f"hw_{name}"], np.float64) for name in HW_NAMES}
    return np.asarray(cell_time, np.float64), idx, hw
