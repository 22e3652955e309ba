"""Pipeline parallelism over a dedicated mesh axis.

For 1000+-node deployments the cross-pod ("pod") axis has the weakest links
(DCN/optical vs intra-pod ICI); pipelining over it replaces per-layer
collectives with one boundary `ppermute` per microbatch per stage
(DESIGN.md §5). This module implements the schedule as a differentiable
lax.scan inside shard_map:

  tick t ∈ [0, n_micro + n_stages - 1):
      stage s computes microbatch (t - s) when 0 <= t-s < n_micro,
      then ppermutes its boundary activation to stage s+1.

Uniform compute per tick (masked when idle) keeps SPMD happy; autodiff
through ppermute/scan gives the full-stash backward — the runtime's 1F1B
schedule (`repro.runtime.schedule`) wraps `stage_fn` with jax.checkpoint so
only the boundary carries stay resident, matching the planner's in-flight
transient model. Bubble fraction is the usual (S-1)/(T+S-1); the runtime
requires n_micro >= S so the pipeline fills.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel import axes as pax


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, *,
                   mesh: Mesh, axis: str = "pipe",
                   x_spec: Optional[P] = None):
    """Run `stage_fn` as a pipeline over mesh axis `axis`.

    stage_fn(params_slice, x: [mb, ...]) -> [mb, ...]   (uniform stages)
    stage_params: pytree stacked on a leading n_stages dim (sharded on axis)
    x_micro: [n_micro, mb, ...]; `x_spec` is its shard_map spec (default
    fully replicated — pass e.g. P(None, "data") to keep the microbatch
    batch dim data-sharded through the pipeline).
    Returns [n_micro, mb, ...] outputs of the final stage (same spec).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    x_spec = P() if x_spec is None else x_spec

    def per_device(params_local, xs_local):
        # params_local: [1, ...] — this device's stage; xs_local is this
        # device's batch shard of every microbatch
        params_one = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs_local.shape[1:]
        # Carry dtype comes from the stage OUTPUT, not the input: a stage_fn
        # whose output dtype differs (bf16 activations -> fp32 head) must not
        # poison the scan carry with the input dtype.
        y_abs = jax.eval_shape(
            stage_fn, params_one,
            jax.ShapeDtypeStruct(mb_shape, xs_local.dtype))
        if y_abs.shape != mb_shape:
            raise ValueError(
                f"pipeline stage_fn must preserve the microbatch shape "
                f"(stage input feeds the next stage): {mb_shape} -> "
                f"{y_abs.shape}")
        carry_dtype = y_abs.dtype

        def tick(carry, t):
            inbound, outputs = carry
            # stage 0 reads microbatch t (clamped); others read inbound
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            first_in = jax.lax.dynamic_index_in_dim(
                xs_local, mb_idx, 0, keepdims=False).astype(carry_dtype)
            x = jnp.where(stage == 0, first_in, inbound)
            active = (t - stage >= 0) & (t - stage < n_micro)
            y = stage_fn(params_one, x).astype(carry_dtype)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # stash final-stage output at slot (t - (n_stages - 1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_last = stage == n_stages - 1
            take = active & is_last
            upd = jnp.where(take, y,
                            jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                                         keepdims=False))
            outputs = jax.lax.dynamic_update_index_in_dim(outputs, upd,
                                                          out_idx, 0)
            # hand off to the next stage
            inbound = jax.lax.ppermute(y, axis, perm)
            return (inbound, outputs), None

        inbound0 = jnp.zeros(y_abs.shape, carry_dtype)
        outputs0 = jnp.zeros((n_micro,) + y_abs.shape, carry_dtype)
        (_, outputs), _ = jax.lax.scan(tick, (inbound0, outputs0),
                                       jnp.arange(n_ticks))
        # replicate final outputs to all stages: only the last stage's
        # buffer is nonzero, so a psum is a broadcast
        outputs = jax.lax.psum(outputs, axis)
        return outputs

    stacked_spec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(stacked_spec, x_spec), out_specs=x_spec,
                       check_vma=False)
    return fn(stage_params, x_micro)


def split_stages(stacked_params, n_stages: int):
    """Reshape unit-stacked params [R, ...] -> [n_stages, R/n_stages, ...]
    so each pipeline stage owns a contiguous depth range."""
    if n_stages < 1:
        raise ValueError(f"split_stages: n_stages must be >= 1, got "
                         f"{n_stages}")

    def resh(a):
        r = a.shape[0]
        if r % n_stages:
            raise ValueError(
                f"split_stages: stacked depth {r} does not divide into "
                f"{n_stages} pipeline stages")
        return a.reshape((n_stages, r // n_stages) + a.shape[1:])

    return jax.tree.map(resh, stacked_params)
