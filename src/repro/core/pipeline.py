"""Pipeline runner: executes a compiled relational pipeline on the JAX
columnar engine (bind steps) with KV-cache INSERT semantics (append steps).

``run_pipeline`` is functional in ``env``: cache tables are returned updated.
The batched decode tick runs it under ``jax.jit``
(``serving/engine.batched_decode_tick``, one program per batch bucket): the
weight tables enter as arguments, never as constants, and the batch's cache
views and the slot pool's tables are donated, so the tick updates the cache
without a second copy.  A cold prefill runs it under ``jax.jit`` too
(``serving/engine.cold_prefill``, one program per prompt-length bucket).
The suffix prefill of a prefix hit and single-session decode run it
eagerly.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.executor import DenseTable, execute, plan_provenance
from repro.core.opmap import RelPipeline
from repro.obs.trace import traced


def run_pipeline(
    pipeline: RelPipeline,
    env: Dict[str, DenseTable],
    scalars: Optional[Dict[str, jnp.ndarray]] = None,
    layout_plan=None,
    tracer=None,
    shard_runner=None,
) -> Tuple[Dict[str, DenseTable], Dict[str, DenseTable]]:
    """Execute all steps. Returns (outputs, updated_env).

    ``scalars`` supplies append offsets (e.g. ``cache_position``) as traced
    int32 values so the same compiled pipeline serves every decode step.

    If the pipeline was layout-planned (``repro.planner.plan_layouts``),
    the plan's COL_CHUNK tables are materialised into ``env`` on first use
    (transposed from the resident row-layout tables, at the planner's
    per-table chunk size), and ROW_CHUNK tables the planner re-chunked
    (``chunk_mode="auto"``) are replaced by their re-chunked twins so the
    Scans see the declared physical schema; pass ``layout_plan`` to
    override the plan recorded on the pipeline.

    Every step runs inside a ``step.<name>`` profiler annotation and a
    ``jax.named_scope(<name>)``: neither waits on the device, the
    annotation is free when no profile is being captured, and under
    ``jit`` the scope names the step's device operations.

    ``tracer`` (an ``Optional[repro.obs.trace.TraceRecorder]``) records one
    ``cat="step"`` span per pipeline step, blocking on the step's result so
    the span measures real compute (JAX dispatch is asynchronous), plus the
    executor's per-node ``cat="op"`` sub-spans.  With ``tracer=None`` (the
    default) nothing blocks — a recorder must not be attached under
    ``jit`` (the block would fail on traced values).

    ``shard_runner`` (e.g. ``repro.serving.shards.ShardWorkerPool.
    run_step``) takes over bind steps the pipeline's shard plan split
    across workers: it fans the per-shard plan copies out, combines the
    partials, seeds this pipeline's memo at the sharded aggregates and
    executes the step's unsharded tail — returning the step's output
    table.  Steps without shard decisions (and all append steps) run on
    the normal path regardless.
    """
    scalars = scalars or {}
    # .copy() (not dict(...)) so lazy paging environments keep their
    # __missing__ weight-fetch behaviour (serving/engine.LazyEnv)
    env = env.copy()
    layout_plan = layout_plan or getattr(pipeline, "layout_plan", None)
    if layout_plan is not None:
        env = layout_plan.ensure_env(env)
    shard_plan = getattr(pipeline, "shard_plan", None)
    if shard_runner is None:
        shard_plan = None
    memo: Dict[int, DenseTable] = {}

    def _run_step(step) -> None:
        if step.kind == "bind":
            if shard_plan is not None and step.name in shard_plan.by_step:
                env[step.name] = shard_runner(shard_plan, step, env, memo,
                                              scalars, tracer)
                return
            env[step.name] = execute(step.rel.plan, env, memo, scalars,
                                     tracer)
        elif step.kind == "append":
            new = execute(step.rel.plan, env, memo, scalars, tracer)
            cache = env[step.name]
            offset = scalars.get(step.offset_name, 0)
            ax = cache.key_names.index(step.append_key)
            if step.seq_key is not None:
                # batched append: the new relation has one row per sequence
                # and no position key; each sequence's row is scattered at
                # (seq, offset[seq]) — a per-sequence INSERT position.  The
                # cache's physical key order is planner-chosen (the seq key
                # stays leading); align by name, then do ONE indexed
                # scatter over (seq, append) brought to the front — no
                # per-sequence op unroll on the decode hot path.
                sax = cache.key_names.index(step.seq_key)
                nseq = cache.keys[sax][1]
                offsets = jnp.asarray(offset, jnp.int32)
                order = [k for k in cache.key_names if k != step.append_key]
                perm = [new.key_names.index(k) for k in order]
                sax_new = order.index(step.seq_key)
                cols = {}
                for cname, arr in cache.cols.items():
                    new_arr = new.cols[cname]
                    vec = new_arr.ndim > len(perm)
                    new_arr = jnp.transpose(
                        new_arr, perm + ([len(perm)] if vec else []))
                    a2 = jnp.moveaxis(arr, (sax, ax), (0, 1))
                    n2 = jnp.moveaxis(new_arr, sax_new, 0).astype(arr.dtype)
                    a2 = a2.at[jnp.arange(nseq), offsets].set(n2)
                    cols[cname] = jnp.moveaxis(a2, (0, 1), (sax, ax))
                env[step.name] = DenseTable(keys=cache.keys, cols=cols,
                                            col_types=cache.col_types)
                return
            # the cache table's physical key order is planner-chosen
            # (row_chunk / head_major / pos_major); align the new rows'
            # axes by key name and insert at the append key's axis
            perm = [new.key_names.index(k) for k in cache.key_names]
            cols = {}
            for cname, arr in cache.cols.items():
                new_arr = new.cols[cname]
                vec = new_arr.ndim > len(perm)
                new_arr = jnp.transpose(
                    new_arr, perm + ([len(perm)] if vec else []))
                start = tuple(offset if i == ax else 0
                              for i in range(arr.ndim))
                cols[cname] = jax.lax.dynamic_update_slice(
                    arr, new_arr.astype(arr.dtype), start)
            env[step.name] = DenseTable(keys=cache.keys, cols=cols,
                                        col_types=cache.col_types)
        else:
            raise ValueError(step.kind)

    for step in pipeline.steps:
        if tracer is None:
            with traced(f"step.{step.name}"), jax.named_scope(step.name):
                _run_step(step)
        else:
            ops, tables = plan_provenance(step.rel.plan)
            with traced(f"step.{step.name}",
                        tracer.span(step.name, cat="step", kind=step.kind,
                                    ops=list(ops), tables=list(tables))), \
                    jax.named_scope(step.name):
                _run_step(step)
                # block so the span measures compute, not dispatch
                jax.block_until_ready(list(env[step.name].cols.values()))

    outputs = {o: env[o] for o in pipeline.outputs}
    return outputs, env
