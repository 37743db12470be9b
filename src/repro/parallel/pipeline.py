"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Stage s (device s on the ``pipe`` axis) owns layer slice s of the stacked
params. Microbatches flow left-to-right: on tick t, stage s processes
microbatch (t - s) if it is in range, then ppermutes its activation to
stage s+1. Total ticks = n_micro + P - 1; bubble fraction (P-1)/(T).

This is the optional PP dimension (off by default — the production mesh
uses DP x TP; PP becomes attractive at >2 pods when cross-DCI FSDP gathers
dominate). Correctness is asserted against sequential layer application in
tests/test_pipeline.py on forced host devices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, axis: str, stage_fn, stage_params, x_mb):
    """Run a P-stage pipeline.

    Args:
      mesh: mesh containing ``axis`` with P devices.
      axis: pipeline axis name.
      stage_fn: (params_for_one_stage, x) -> y, applied by every stage.
      stage_params: pytree whose leaves have leading dim P (one slice per
        stage) — sharded over ``axis``.
      x_mb: (n_micro, mb, ...) microbatched input (replicated).

    Returns:
      (n_micro, mb, ...) outputs (gathered from the last stage).
    """
    p_size = mesh.shape[axis]
    n_micro = x_mb.shape[0]
    ticks = n_micro + p_size - 1

    def per_stage(params, x_mb):
        # params: leaves (1, ...) — this stage's slice
        params = jax.tree.map(lambda v: v[0], params)
        s = jax.lax.axis_index(axis)
        mb_shape = x_mb.shape[1:]

        def tick(carry, t):
            left_in, ys = carry
            # stage 0 ingests microbatch t; others take the permuted input
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x0 = jax.lax.dynamic_index_in_dim(x_mb, mb_idx, 0, False)
            x_in = jnp.where(s == 0, x0, left_in)
            active = jnp.logical_and(t - s >= 0, t - s < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # emit on the last stage at position t - (P-1)
            out_idx = jnp.clip(t - (p_size - 1), 0, n_micro - 1)
            emit = jnp.logical_and(s == p_size - 1, active)
            cur = jax.lax.dynamic_index_in_dim(ys, out_idx, 0, False)
            ys = jax.lax.dynamic_update_index_in_dim(
                ys, jnp.where(emit, y, cur), out_idx, 0)
            # shift activations one stage right
            right = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(p_size - 1)])
            return (right, ys), None

        ys0 = jnp.zeros((n_micro,) + mb_shape, x_mb.dtype)
        left0 = jnp.zeros(mb_shape, x_mb.dtype)
        (_, ys), _ = jax.lax.scan(tick, (left0, ys0), jnp.arange(ticks))
        # only the last stage holds real outputs; psum broadcasts them
        # (all other stages contribute zeros)
        return jax.lax.psum(ys, axis)

    n_axes = x_mb.ndim
    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params,
                               is_leaf=lambda x: hasattr(x, "shape")),
                  P(*([None] * n_axes))),
        out_specs=P(*([None] * n_axes)),
        check_vma=False)
    return fn(stage_params, x_mb)
