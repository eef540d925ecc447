"""Pipeline parallelism: GPipe-style microbatched schedule over a 'stage'
mesh axis, expressed with shard_map + ppermute.

Completes the parallelism matrix (DP/TP/EP/SP/FSDP elsewhere in
parallel/): at 1000+-node scale the model axis saturates one pod's ICI,
and depth must shard across pods — each stage holds a contiguous slice of
the layer stack, activations flow stage-to-stage over collective-permute
(the only inter-pod traffic: one [mb, S, D] tensor per microbatch per
boundary, vs TP's per-layer collectives).

Schedule: the classic GPipe fill-drain loop — T = n_micro + n_stages - 1
ticks; at tick t, stage s computes microbatch (t - s) when
0 <= t - s < n_micro, else it computes on garbage and the result is
masked (the bubble). Efficiency = n_micro / T, reported by
:func:`bubble_fraction`.

The layer slice per stage is the SAME stacked-params layout the model
uses (params sharded over the stage axis on the layer dim), so a dense
model's ``groups`` pytree drops in unchanged.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(
    stacked_params: Any,          # pytree, leaves [L, ...] — L % n_stages == 0
    x: jax.Array,                 # [n_micro, mb, S, D] microbatched input
    block_fn: Callable,           # (layer_params, x) -> x  (one layer)
    mesh,
    *,
    stage_axis: str = "stage",
    extra_specs: P = P(),         # sharding of non-stage dims of x (e.g. data)
) -> jax.Array:
    """Run the layer stack as a pipeline; returns [n_micro, mb, S, D].

    ``stacked_params`` leaves are sharded over ``stage_axis`` on dim 0 by
    the in_specs below — each stage sees its [L/n_stages, ...] slice and
    scans it locally per tick.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    n_ticks = n_micro + n_stages - 1
    fwd_ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    p_specs = jax.tree.map(lambda _: P(stage_axis), stacked_params)
    x_spec = P(None, *extra_specs)   # microbatch dim replicated per stage

    def staged(params_blk, x_all):
        stage = jax.lax.axis_index(stage_axis)

        def local_stack(h):
            def body(carry, lp):
                return block_fn(lp, carry), None
            out, _ = jax.lax.scan(body, h, params_blk)
            return out

        mb_shape = x_all.shape[1:]
        outputs = jnp.zeros_like(x_all)

        def tick(t, carry):
            cur, outputs = carry
            # stage 0 injects microbatch t; others take the permuted input
            inject = x_all[jnp.clip(t, 0, n_micro - 1)]
            h_in = jnp.where(stage == 0, inject, cur)
            h_out = local_stack(h_in)
            # emit: the LAST stage finished microbatch (t - n_stages + 1)
            mb_idx = t - (n_stages - 1)
            is_valid = jnp.logical_and(stage == n_stages - 1, mb_idx >= 0)
            outputs = jax.lax.cond(
                is_valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(mb_idx, 0), 0),
                lambda o: o,
                outputs)
            # pass activations down the ring for the next tick
            nxt = jax.lax.ppermute(h_out, stage_axis, fwd_ring)
            return nxt, outputs

        cur = jnp.zeros(mb_shape, x_all.dtype)
        _, outputs = jax.lax.fori_loop(0, n_ticks, tick, (cur, outputs))
        # only the last stage holds non-zero outputs; psum replicates them
        # so the out_spec (no stage axis) is well-defined on every shard
        return jax.lax.psum(outputs, stage_axis)

    out = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stacked_params, x)
    return out
