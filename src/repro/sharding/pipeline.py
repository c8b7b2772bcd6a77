"""Pipeline parallelism: GPipe schedule over a mesh axis via shard_map.

Alternative layout for the multi-pod mesh: map the 'pod' (or a dedicated
'pipe') axis to pipeline stages — each device group holds one stage's layer
slice, activations flow stage-to-stage with jax.lax.ppermute, microbatches
fill the pipeline (bubble fraction = (S-1)/(M+S-1)).

This complements the GSPMD DP/TP path (sharding/specs.py): PP is the
explicit-collective style (shard_map), exercised on virtual devices by
tests/pipeline_runner.py, and composes with inner-TP by nesting meshes.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jnp.ndarray


def gpipe_forward(stage_fn: Callable, stage_params: Any, x_mb: Array,
                  mesh: Mesh, axis: str = "pipe") -> Array:
    """Run M microbatches through S pipeline stages.

    stage_fn:      (params_slice, activations (mb, ...)) -> activations
    stage_params:  pytree with leading stage dim (S, ...) on every leaf
    x_mb:          (M, mb, ...) microbatched input
    Returns (M, mb, ...) outputs (replicated across the axis).
    """
    nstage = mesh.shape[axis]
    nmb = x_mb.shape[0]

    def per_device(params_local, x_local):
        # params_local leaves: (1, ...) stage slice; x_local: (M, mb, ...)
        p = jax.tree.map(lambda v: v[0], params_local)
        idx = jax.lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == nstage - 1
        buf = jnp.zeros_like(x_local[0])
        outs = jnp.zeros_like(x_local)
        fwd_perm = [(i, i + 1) for i in range(nstage - 1)]

        for t in range(nmb + nstage - 1):
            mb = t - idx                                # this stage's µb id
            active = jnp.logical_and(mb >= 0, mb < nmb)
            feed = jnp.where(is_first,
                             x_local[jnp.clip(jnp.asarray(t), 0, nmb - 1)],
                             buf)
            y = stage_fn(p, feed)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # drain: last stage records its finished microbatch
            slot = jnp.clip(jnp.asarray(mb), 0, nmb - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
            val = jnp.where(jnp.logical_and(is_last, active), y, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, val, slot, 0)
            # advance: send activations to the next stage
            buf = jax.lax.ppermute(y, axis, fwd_perm)

        # broadcast the last stage's outputs to every stage
        return jax.lax.psum(jnp.where(is_last, outs, jnp.zeros_like(outs)),
                            axis)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(pspec, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_mb)


def pipeline_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1) / (M + S-1)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
