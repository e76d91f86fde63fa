"""GPipe pipeline parallelism over the ``pod`` axis (optional feature).

The required production mesh is (pod, data, model) with DP on pod — but at
multi-pod scale the inter-pod links are the slow ones, and pipeline
parallelism moves the least bytes across them (one activation tensor per
microbatch per stage boundary, vs full gradient reduction for DP).  This
module provides a shard_map GPipe: layers are partitioned into S stages
along the pipeline axis; microbatches stream through with
``jax.lax.ppermute`` moving activations stage→stage each tick.

Schedule (classic GPipe fill-drain): T = n_micro + S - 1 ticks; stage s
processes microbatch (t - s) at tick t.  Bubble fraction = (S-1)/T.

``pipeline_forward`` is the building block (forward only — enough for the
serving path and for validating the collective pattern; the backward
schedule composes with jax.grad through ppermute, at GPipe's usual
activation cost).  Correctness vs the sequential stack is tested on a real
multi-device mesh in tests/test_pipeline.py.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, mesh: Mesh, axis: str,
                     stage_params, x_micro):
    """Run microbatches through a pipeline over mesh axis ``axis``.

    stage_fn(params_for_stage, x) -> y   (same shape as x)
    stage_params: pytree whose leaves have a leading stage dim (S, ...)
    x_micro: (n_micro, mb, ...) microbatched inputs (replicated)
    Returns (n_micro, mb, ...) outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def per_stage(stage_params, x_all):
        # inside shard_map: this instance holds ONE stage's params
        sp = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (when in range); others use the
            # activation permuted in from the previous stage
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage_id == 0, x_all[inject], inflight)
            y = stage_fn(sp, x_in)
            # last stage writes its result for microbatch (t - S + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = jnp.logical_and(stage_id == n_stages - 1,
                                   t >= n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(take, y, outputs[out_idx]),
                out_idx, 0)
            # move activations one stage down the ring
            nxt = jax.lax.ppermute(
                y, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outputs), None

        inflight0 = jnp.zeros(mb_shape, x_all.dtype)
        outputs0 = jnp.zeros_like(x_all)
        (_, outputs), _ = jax.lax.scan(
            tick, (inflight0, outputs0), jnp.arange(ticks))
        # broadcast results from the last stage to everyone (so out_specs
        # can be replicated) — one small collective at the end
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs, 0.0), axis)
        return outputs

    n_axes = len(mesh.axis_names)
    stage_spec = jax.tree_util.tree_map(
        lambda p: P(*((axis,) + (None,) * (p.ndim - 1))), stage_params)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(stage_spec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)


def split_layers_into_stages(stacked_layers, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major layout."""
    def one(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return jax.tree_util.tree_map(one, stacked_layers)
