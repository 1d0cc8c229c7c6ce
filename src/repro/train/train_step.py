"""The jitted train step: microbatched grad accumulation, remat policy,
MTP auxiliary loss, optional gradient compression, AdamW -- compiled with
explicit in/out shardings from ``repro.sharding``.

Distributed-optimization posture:
* grad accumulation over ``microbatches`` happens *inside* the jit via
  ``lax.scan``, so the data-parallel gradient all-reduce is emitted once
  per step, not once per microbatch (collective bytes / step drop by M);
* the remat policy is a named knob ('none'|'dots'|'full') -- it is one of
  the software parameters the LM codesign sweep (repro.core.lmcells)
  searches;
* parameter/optimizer shardings are donated, so the step is in-place at
  the XLA level.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..models.model import chunked_ce, forward_hidden, init_model, lm_loss
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from ..optim.compression import CompressionState, compress_grads, compression_init
from ..sharding.partition import batch_specs, opt_state_specs, param_specs

__all__ = ["TrainConfig", "init_train_state", "make_train_step"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: str = "dots"
    attn_impl: str = "auto"
    mtp_weight: float = 0.3
    compress_grads: bool = False
    fsdp: bool = False  # weight-sharding over the data axes (ZeRO-3 style)
    loss_chunks: int = 0  # 0 = auto: bound live logits to ~256 MB/chip
    opt: AdamWConfig = AdamWConfig()


def _batch_specs_for(cfg: ArchConfig, mesh: Mesh) -> Dict[str, P]:
    """Specs restricted to exactly the keys the data pipeline produces."""
    specs = batch_specs(cfg, mesh)
    keys = ["tokens", "labels"]
    if cfg.frontend or cfg.enc_dec:
        keys.append("frontend")
    return {k: specs.get(k, specs["tokens"]) for k in keys}


def init_train_state(
    cfg: ArchConfig, tcfg: TrainConfig, mesh: Mesh, seed: int = 0
) -> Dict[str, Any]:
    """Initialize params + optimizer state, sharded onto the mesh."""
    abstract = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(seed)))
    p_specs = param_specs(cfg, abstract, mesh, fsdp=tcfg.fsdp)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    params = jax.jit(
        lambda: init_model(cfg, jax.random.PRNGKey(seed)), out_shardings=p_shard
    )()
    o_specs = opt_state_specs(cfg, abstract, mesh, fsdp=tcfg.fsdp)
    mdt = jnp.dtype(tcfg.opt.moment_dtype)
    state = {
        "params": params,
        "opt": {
            "m": jax.jit(
                lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, mdt), abstract),
                out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs),
            )(),
            "v": jax.jit(
                lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, mdt), abstract),
                out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs),
            )(),
            "step": jnp.zeros((), jnp.int32),
        },
    }
    if tcfg.compress_grads:
        state["comp"] = jax.jit(
            lambda: compression_init(abstract).error,
            out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs),
        )()
    return state


def _loss_fn(params, cfg: ArchConfig, tcfg: TrainConfig, batch, n_chunks: int):
    hidden, _, ex = forward_hidden(
        params, cfg, batch, impl=tcfg.attn_impl, remat=tcfg.remat, want_mtp=cfg.mtp
    )
    loss = chunked_ce(cfg, params, hidden, batch["labels"], n_chunks)
    total = loss + ex["aux"]
    metrics = {"lm_loss": loss, "aux_loss": ex["aux"]}
    if "mtp_hidden" in ex:
        # position t predicts token t+2 == labels shifted one further
        mtp = chunked_ce(cfg, params, ex["mtp_hidden"], batch["labels"][:, 1:], n_chunks)
        total = total + tcfg.mtp_weight * mtp
        metrics["mtp_loss"] = mtp
    metrics["loss"] = total
    return total, metrics


def _auto_loss_chunks(cfg: ArchConfig, tcfg: TrainConfig, mesh: Mesh, batch_shape) -> int:
    """Bound live f32 chunk logits to ~256 MB per chip."""
    if tcfg.loss_chunks:
        return tcfg.loss_chunks
    b, s = batch_shape
    chips = mesh.devices.size
    budget = 256e6
    n = int(np.ceil(b // max(1, tcfg.microbatches) * s * cfg.vocab * 4 / (chips * budget)))
    return max(1, min(n, s))


def make_train_step(cfg: ArchConfig, tcfg: TrainConfig, mesh: Mesh):
    """Returns a jitted (state, batch) -> (state, metrics) step."""
    abstract = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    p_specs = param_specs(cfg, abstract, mesh, fsdp=tcfg.fsdp)
    o_specs = opt_state_specs(cfg, abstract, mesh, fsdp=tcfg.fsdp)
    b_specs = _batch_specs_for(cfg, mesh)

    def step_fn(state, batch):
        params = state["params"]
        m = tcfg.microbatches
        n_chunks = _auto_loss_chunks(cfg, tcfg, mesh, batch["tokens"].shape)

        if m == 1:
            grads, metrics = jax.grad(
                lambda p: _loss_fn(p, cfg, tcfg, batch, n_chunks), has_aux=True
            )(params)
        else:
            def slice_mb(x):
                b = x.shape[0]
                return x.reshape(m, b // m, *x.shape[1:])

            mbs = jax.tree.map(slice_mb, batch)

            def accum(carry, mb):
                g_acc, _ = carry
                # re-pin the batch sharding: GSPMD loses the data-axis
                # sharding when slicing scan xs, silently replicating the
                # whole microbatch's compute on every data shard (measured
                # 2.7x FLOP inflation at mb=16 -- see EXPERIMENTS.md §Perf)
                mb = {
                    k: jax.lax.with_sharding_constraint(
                        v, NamedSharding(mesh, b_specs[k])
                    )
                    for k, v in mb.items()
                }
                g, mets = jax.grad(
                    lambda p: _loss_fn(p, cfg, tcfg, mb, n_chunks), has_aux=True
                )(params)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32) / m, g_acc, g
                )
                return (g_acc, mets), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            dummy = {
                "lm_loss": jnp.zeros((), jnp.float32),
                "aux_loss": jnp.zeros((), jnp.float32),
                "loss": jnp.zeros((), jnp.float32),
            }
            if cfg.mtp:
                dummy["mtp_loss"] = jnp.zeros((), jnp.float32)
            (grads, metrics), _ = jax.lax.scan(accum, (g0, dummy), mbs)

        new_state = dict(state)
        if tcfg.compress_grads:
            grads, comp = compress_grads(grads, CompressionState(state["comp"]))
            new_state["comp"] = comp.error

        params, opt, opt_metrics = adamw_update(params, grads, state["opt"], tcfg.opt)
        new_state["params"] = params
        new_state["opt"] = opt
        metrics = dict(metrics, **opt_metrics)
        return new_state, metrics

    state_specs = {
        "params": p_specs,
        "opt": {"m": o_specs, "v": o_specs, "step": P()},
    }
    if tcfg.compress_grads:
        state_specs["comp"] = o_specs
    to_sh = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    metric_names = ["lm_loss", "aux_loss", "loss", "grad_norm", "lr"] + (
        ["mtp_loss"] if cfg.mtp else []
    )
    return jax.jit(
        step_fn,
        in_shardings=(to_sh(state_specs), to_sh(b_specs)),
        out_shardings=(
            to_sh(state_specs),
            {k: NamedSharding(mesh, P()) for k in metric_names},
        ),
        donate_argnums=(0,),
    )
