"""Pipeline parallelism over the ``pod`` (or any) mesh axis.

GPipe-style schedule via ``shard_map`` + ``collective_permute``: layer
groups are sharded over the stage axis; microbatches stream through the
stages, activations hop stage→stage on the inter-pod links.  Differentiable
(grad of collective_permute is the reverse permute), so the same function
serves training.

This is the alternative use of the multi-pod axis (DESIGN.md §5): DP
across pods costs one cross-pod all-reduce of the full gradient per step,
PP costs microbatch activations per hop — for large models with modest
global batch, PP wins on the slow cross-pod links.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    block_fn: Callable,          # (params_one_layer, x) -> x
    stacked_params,              # leaves [n_layers, ...]
    x: jnp.ndarray,              # [n_micro * micro_bs, ...]
    mesh: Mesh,
    stage_axis: str = "pod",
    n_micro: int = 4,
) -> jnp.ndarray:
    """Run ``n_layers`` blocks as a pipeline over the stage axis.

    n_layers must divide by the number of stages; the global batch splits
    into ``n_micro`` microbatches.  Schedule: S + M - 1 ticks (GPipe fill +
    drain); stage s processes microbatch m at tick s + m.
    """
    n_stages = mesh.shape[stage_axis]
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert n_layers % n_stages == 0
    per_stage = n_layers // n_stages

    # shard layers over the stage axis; batch over nothing (replicated here —
    # compose with DP by vmapping this whole function over a data axis)
    pspec = jax.tree_util.tree_map(lambda _: P(stage_axis), stacked_params)
    xspec = P()

    def stage_fn(params_slice, xs):
        stage = jax.lax.axis_index(stage_axis)
        micro = jnp.split(xs, n_micro, axis=0)
        n_ticks = n_stages + n_micro - 1
        buf = jnp.zeros_like(micro[0])
        outs = [jnp.zeros_like(m) for m in micro]

        def run_stage(x):
            def body(x, p_l):
                return block_fn(p_l, x), None
            y, _ = jax.lax.scan(lambda c, p: (block_fn(p, c), None),
                                x, params_slice)
            return y

        for tick in range(n_ticks):
            m_idx = tick - 0  # microbatch entering stage 0 at this tick
            # stage 0 injects microbatch `tick` (if any)
            inject = micro[m_idx] if 0 <= m_idx < n_micro else jnp.zeros_like(
                buf)
            x_in = jnp.where(stage == 0, inject, buf)
            y = run_stage(x_in)
            # pass activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            # last stage emits microbatch tick - (n_stages - 1)
            out_idx = tick - (n_stages - 1)
            if 0 <= out_idx < n_micro:
                outs[out_idx] = jnp.where(stage == n_stages - 1, y,
                                          outs[out_idx])

        out = jnp.concatenate(outs, axis=0)
        # broadcast the last stage's result to every stage
        return jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            stage_axis)

    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec, check_vma=False)
    return fn(stacked_params, x)
