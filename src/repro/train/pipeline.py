"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Complements the DP/TP/EP rules in ``repro.sharding``: when a model's layers
do not fit even with TP+FSDP, stages of layers are placed on a ``stage``
mesh axis and microbatches stream through with the classic GPipe schedule
(M + S - 1 ticks, bubble fraction (S-1)/(M+S-1)).

TPU-native mapping (DESIGN.md "hardware adaptation"): stage-to-stage
transfers are ``jax.lax.ppermute`` over the stage axis inside a
``shard_map`` -- the ICI-neighbour communication pattern a real pod
pipeline uses -- rather than host-mediated sends.

The schedule is deliberately the simple fill-drain GPipe (not 1F1B):
activations for in-flight microbatches are the caller's remat problem, and
the dry-run measures it like everything else.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """Idle fraction of the fill-drain schedule."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    mesh: Mesh,
    n_microbatches: int,
    axis: str = "stage",
):
    """Run ``stage_fn`` as an S-stage pipeline over microbatches.

    stage_fn(params_one_stage, h) -> h  applied by every stage in order;
    stage_params: pytree with leading dim S (sharded over ``axis``);
    x: (B, ...) global input; B must divide by n_microbatches.

    Returns stage_{S-1}(... stage_0(x)) with identical semantics to the
    sequential loop (asserted in tests/test_pipeline.py).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches
    xs = x.reshape(n_microbatches, mb, *x.shape[1:])

    p_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(p_spec, P()),  # params split by stage; data replicated
        out_specs=P(),
        check_vma=False,
    )
    def run(params_local, xs_rep):
        # params_local leaves: (1, ...) -- this device's stage
        params_here = jax.tree.map(lambda a: a[0], params_local)
        sidx = jax.lax.axis_index(axis)
        ticks = n_microbatches + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]  # stage i -> i+1

        h0 = jnp.zeros_like(xs_rep[0])
        outs0 = jnp.zeros_like(xs_rep)

        def tick(carry, t):
            h_in, outs = carry
            # stage 0 injects microbatch t (when one is due)
            feed_idx = jnp.clip(t, 0, n_microbatches - 1)
            h_feed = jnp.where(
                (sidx == 0) & (t < n_microbatches),
                xs_rep[feed_idx],
                h_in,
            )
            active = (t >= sidx) & (t < sidx + n_microbatches)
            h_out = jnp.where(active, stage_fn(params_here, h_feed), h_feed)
            # last stage banks microbatch (t - (S-1)) when it completes
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            bank = (sidx == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(
                bank[None] if bank.ndim else bank,
                outs.at[done_idx].set(h_out),
                outs,
            )
            # shift activations one stage to the right
            h_next = jax.lax.ppermute(h_out, axis, perm)
            return (h_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (h0, outs0), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        outs = jnp.where(sidx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    out = run(stage_params, xs)
    return out.reshape(b, *x.shape[1:])
