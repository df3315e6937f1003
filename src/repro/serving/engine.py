"""Serving engine: the paper's four execution modes under one API.

  mode="relational"  — the paper's path: compiled SQL-equivalent relational
                       pipelines executed on the JAX columnar engine.
  mode="direct"      — conventional dense execution (the PyTorch/llama.cpp
                       role in the paper's comparisons).
  residency="in_memory" — all weights resident (paper's In-memory mode).
  residency="paged"     — weights stream through a bounded WeightPager
                          working set (paper's Disk+mem mode). The
                          relational pager prefetches the next layer's
                          tables during compute (buffer-manager behaviour);
                          the direct pager is synchronous whole-layer
                          loading (llama.cpp-style dynamic loading).

Metrics: TTFT (prompt → first token) and TPOT (mean per subsequent token),
matching §4's definitions.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import llama_graph as lg
from repro.core.graph import infer_shapes
from repro.core.opmap import op_map
from repro.core.passes import postoptimize, preoptimize
from repro.core.pipeline import run_pipeline
from repro.obs.trace import traced
from repro.serving.kvcache import appended_rows
from repro.serving.pager import WeightPager

# host-clock histograms of one served phase, labelled phase="prefill" or
# phase="decode": the dispatch of the plan (one compiled program's launch,
# or run_pipeline op by op), and the copy of its logits to the host, where
# the host waits for the device
PHASE_SERIES = {
    "engine_dispatch_seconds": "host time dispatching one plan",
    "engine_sync_seconds": "host wait for a plan's logits",
}


# series of the sparse-expert layers, labelled phase="prefill" or
# phase="decode", read from the held routes that leave a plan with its
# logits (one observation per expert layer and plan for the histograms)
MOE_SERIES = {
    "moe_routed_pairs_total": "(token, expert) pairs computed here",
    "moe_experts_touched": "distinct held experts of an expert layer",
    "moe_expert_load_max": "most tokens on one held expert of a layer",
}
MOE_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def route_outputs(pipe) -> List[str]:
    """The outputs of ``pipe`` that hold an expert layer's held routes."""
    return [o for o in pipe.outputs if o.startswith(lg.ROUTES)]


def batched_decode_tick(pipe, weights, views, tables, inputs, seq_ids,
                        positions):
    """One batched decode tick as a pure function, for ``jax.jit``: the
    bucket's plan over the weight tables, the batch's cache views and the
    tick's inputs (``token_ids``, ``freq_each_token``), then the row
    writeback of :func:`~repro.serving.kvcache.appended_rows` into the
    pool ``tables``.  Returns the logits column, the held routes of the
    expert layers (none for a dense model), the updated views and the
    updated pool tables."""
    env = {**weights, **views, **inputs}
    outs, env = run_pipeline(pipe, env, scalars={"seq_positions": positions})
    views = {name: env[name] for name in views}
    routes = tuple(outs[o].cols["e"] for o in route_outputs(pipe))
    return (outs["logits"].cols["v"], routes, views,
            appended_rows(tables, views, seq_ids, positions))


def cold_prefill(pipe, weights, caches, inputs, last):
    """One cold prefill as a pure function, for ``jax.jit``: the bucket's
    prefill plan over the weight tables, empty ``caches`` and the prompt's
    ``inputs`` (``token_ids``, ``freq_each_token``) padded at the end to
    the bucket.  Returns the logits row of token ``last``, the last real
    one, the held routes of the expert layers over every row of the
    bucket, and the filled cache tables."""
    env = {**weights, **caches, **inputs}
    outs, env = run_pipeline(pipe, env, scalars={"cache_position": 0})
    v = outs["logits"].cols["v"]
    row = jax.lax.dynamic_index_in_dim(v.reshape(v.shape[0], -1), last,
                                       keepdims=False)
    routes = tuple(outs[o].cols["e"] for o in route_outputs(pipe))
    return row, routes, {name: env[name] for name in caches}


@dataclasses.dataclass
class GenerationResult:
    tokens: List[int]
    ttft_s: float
    tpot_s: float
    peak_working_set: int = 0
    pager_stats: Optional[Dict] = None


class LazyEnv(dict):
    """Environment that pages weight tables in on first Scan.

    COL_CHUNK tables introduced by the layout planner are converted
    *offline* into the pager's cold store (``RelationalEngine.
    _register_layouts``), so they page through the same working-set budget
    as every other weight — ``resolves_layouts`` tells
    ``LayoutPlan.ensure_env`` not to materialise resident copies here.

    ``table_sizes`` maps table names to planner-chosen physical chunk
    sizes (``chunk_size="auto"``); tables absent there wrap at the
    engine's base chunking.  ``quant_specs`` maps quantised table names to
    ``(precision, chunk_size, schema)``: their packed integer codes and
    per-group scales page as two cold entries (``name::q`` /
    ``name::scale``) whose *quantised* byte sizes are what the pager
    accounts — the working set holds ~4× more tables at int8 (~8× at
    packed nf4) under the same budget.  Both dicts are shared by
    reference with the engine so later-planned pipelines (prefill,
    batched decode) extend them in place.
    """

    resolves_layouts = True

    def __init__(self, pager: WeightPager, chunk_size: int, make_table,
                 table_sizes=None, quant_specs=None):
        super().__init__()
        self.pager = pager
        self.cs = chunk_size
        self.make_table = make_table
        self.table_sizes = table_sizes if table_sizes is not None else {}
        self.quant_specs = quant_specs if quant_specs is not None else {}

    def __missing__(self, key):
        spec = self.quant_specs.get(key)
        if spec is not None:
            return self._quant_table(key, *spec)
        arr = self.pager.get(key)
        cs = self.table_sizes.get(key, self.cs)
        tbl = self.make_table(key, np.asarray(arr), cs)
        # don't retain: the pager owns residency, we re-wrap per access
        return tbl

    def _quant_table(self, key, precision, chunk_size, schema):
        """Wrap a quantised table's paged code/scale arrays (zero f32
        inflation: codes stay integer; the dequant happens inside the
        projection the planner emitted)."""
        from repro.core import relational as ra
        from repro.core.executor import DenseTable
        from repro.quant.codecs import CODECS
        codec = CODECS[precision]
        codes = codec.unpack(self.pager.get(key + "::q"), chunk_size)
        scales = self.pager.get(key + "::scale")
        (q_col, q_type), (s_col, _) = schema.cols
        want = tuple(s for _, s in schema.keys)
        if codes.shape != want + (chunk_size,):
            raise ValueError(
                f"quantised table {key!r}: stored code shape {codes.shape} "
                f"!= schema {want + (chunk_size,)}")
        return DenseTable(keys=schema.keys,
                          cols={q_col: codes, s_col: scales},
                          col_types={q_col: q_type, s_col: ra.SCALAR})

    def __contains__(self, key):
        return (dict.__contains__(self, key) or key in self.pager._cold
                or key in self.quant_specs)

    def copy(self):
        new = LazyEnv(self.pager, self.cs, self.make_table,
                      self.table_sizes, self.quant_specs)
        new.update(self)
        return new


def _chunked_table(name, arr, cs):
    from repro.core.chunked import ChunkedTensor
    from repro.core.executor import table_from_chunked
    return table_from_chunked(
        ChunkedTensor.from_dense(name, arr, chunk_size=min(cs, arr.shape[-1])))


class RelationalEngine:
    """The paper's engine: two-stage-compiled pipelines over chunked tables.

    ``chunk_size`` accepts ``"auto"``: the base chunk size is chosen by the
    (optionally calibrated) planner cost model over the candidate grid
    (``repro.planner.calibrate.choose_base_chunk_size`` — the paper's
    Tab. 1 sweep as an optimizer decision), and per-table physical chunk
    sizes are then planned jointly with layouts
    (``plan_layouts(chunk_mode="auto")``).  Pass ``cost_params`` (e.g.
    from ``calibrate.fit_cost_params()``) to plan under
    measurement-calibrated weights instead of the analytic defaults.

    ``precision`` makes the stored payload format of the weight tables a
    planner decision alongside layout and chunk size: ``"int8"`` /
    ``"nf4"`` force a codec on every eligible table, ``"auto"`` prices
    byte traffic against dequant compute and — under a paged residency
    budget — quantises the biggest tables until the working set fits.
    Per-table overrides ride in ``table_precisions`` (e.g.
    ``{"lm_head": "f32"}``); ``accuracy_budget`` runs the quant gate
    (max |Δlogit| vs the f32 engine) at construction.
    """

    PRECISION_KNOBS = ("f32", "auto", "int8", "nf4")

    def __init__(self, spec: lg.LlamaSpec, params: Dict[str, np.ndarray],
                 chunk_size=64, residency: str = "in_memory",
                 budget_bytes: Optional[int] = None,
                 disk_dir: Optional[str] = None, max_len: int = 1024,
                 pager_policy: str = "pin", row2col: str = "auto",
                 cache_layout: str = "auto",
                 chunk_candidates=None, cost_params=None,
                 precision: str = "f32",
                 table_precisions: Optional[Dict[str, str]] = None,
                 accuracy_budget: Optional[float] = None,
                 metrics=None, tracer=None, shards=None):
        # cache_layout defaults to "auto": the locality model is
        # prefill-aware and calibrated against BENCH_attn_layout (ISSUE 5
        # satellite — pass "off" to keep the seed (tp, hk, c) order).
        #
        # precision selects the stored payload format of the weight
        # tables: "f32" (seed), "int8"/"nf4" (force a codec on every
        # eligible table), or "auto" (cost/budget-based — under a paged
        # residency budget the planner quantises the biggest tables until
        # the working set fits).  table_precisions forces per-table
        # choices; accuracy_budget (max |Δlogit| vs the f32 engine on a
        # probe prompt) runs the quant accuracy gate at construction.
        from repro.planner import CACHE_MODES, MODES, ResidencyPool
        assert row2col in MODES, f"row2col must be one of {MODES}"
        assert cache_layout in CACHE_MODES, \
            f"cache_layout must be one of {CACHE_MODES}"
        assert precision in self.PRECISION_KNOBS, \
            f"precision must be one of {self.PRECISION_KNOBS}"
        self._chunk_mode = "off"
        if chunk_size == "auto":
            from repro.planner.calibrate import choose_base_chunk_size
            if row2col == "off":
                raise ValueError("chunk_size='auto' needs the layout "
                                 "planner (row2col 'auto' or 'col')")
            chunk_size = choose_base_chunk_size(
                spec, cache_len=max_len, candidates=chunk_candidates,
                params=cost_params)
            self._chunk_mode = "auto"
        self.spec = spec
        self.cs = int(chunk_size)
        self.max_len = max_len
        # observability (repro.obs): both optional and zero-cost when None —
        # every site guards with `is not None`.  The tracer records one
        # cat="step" span per pipeline step of each prefill/decode tick
        # (it blocks per step, so leave it None when timing end-to-end).
        self.metrics = metrics
        self.tracer = tracer
        # shards: the tensor-parallel planner axis (repro.planner.shard).
        # None/1 keeps plans, SQL and execution bit-identical to an
        # unsharded engine; "auto" sizes the worker pool to the host's
        # cores; N>1 splits eligible matmul sites into N contiguous
        # key-range shards run concurrently by serving.shards.
        if shards in (None, 0, 1):
            self.shards = 1
        elif shards == "auto":
            import os
            self.shards = max(1, os.cpu_count() or 1)
        else:
            self.shards = int(shards)
            if self.shards < 1:
                raise ValueError(f"shards must be >= 1, got {shards}")
        self.shard_pool = None
        self._shard_runner = None
        self.residency = residency
        self.row2col = row2col
        self.precision = precision
        self._precision_mode = "off" if precision == "f32" else precision
        self._table_precisions = dict(table_precisions or {})
        # quantised-table wrap specs shared by reference with the LazyEnv
        # (paged residency): q_table -> (precision, chunk_size, schema)
        self._quant_specs: Dict[str, tuple] = {}
        self._params = params  # kept for the accuracy gate's f32 reference
        self._chunk_candidates = chunk_candidates
        self._cost_params = cost_params
        self._prefill_pipes: Dict[tuple, object] = {}
        # batched decode plans, keyed by (batch-size bucket, shards) —
        # buckets are powers of two: sessions join/leave the batch without
        # replanning — only a tick whose bucket was never seen compiles a
        # new plan
        self._batched_pipes: Dict[tuple, object] = {}
        # the compiled batched decode tick of each of those plans, under
        # the same keys, and the compiled cold prefill of each prompt-length
        # bucket, keyed by (bucket, shards) (see runs_compiled)
        self._decode_ticks: Dict[tuple, object] = {}
        self._prefill_ticks: Dict[tuple, object] = {}
        # paged residency: duplicate column copies compete with the working
        # set, so the global residency pass runs under the pager budget;
        # in-memory residency is unbounded.  One ResidencyPool is shared by
        # the decode and every prefill plan — prefill does not get a second
        # copy of the budget, and column tables a previous plan committed
        # are free for later ones (ROADMAP "residency budget across
        # pipelines").
        self._residency_budget = (budget_bytes if residency != "in_memory"
                                  else None)
        self._residency_pool = ResidencyPool(self._residency_budget)
        # planner-chosen per-table chunk sizes; shared by reference with
        # the LazyEnv so prefill planning extends it in place
        self._table_chunks: Dict[str, int] = {}
        # quantised-payload byte accounting for the metrics gauge (dedup
        # across the decode/prefill/batched plans sharing q-tables)
        self._quant_bytes = 0
        self._quant_counted: set = set()
        # mid-flight re-planning events (the drift watchdog's replan())
        self.replans = 0

        self.decode_pipe = self._compile_pipe(
            lg.build_decode_graph(spec, cache_len=max_len),
            cache_mode=cache_layout)
        self._table_chunks.update(self.decode_pipe.table_chunks)
        # resolved decode-time cache layout; prefill pipelines are forced to
        # it (they share the session environment with decode steps).  When
        # the knob is "off" the planner stays off for prefill too and the
        # session caches keep the seed order.
        plan = self.decode_pipe.layout_plan
        self.cache_layout = (plan.cache_decisions[0].layout
                             if plan is not None and plan.cache_decisions
                             else "row_chunk")
        self._prefill_cache_mode = ("off" if cache_layout == "off"
                                    else self.cache_layout)

        if residency == "in_memory":
            self.env_base = lg.convert_weights(params, chunk_size=self.cs)
            self.pager = None
        else:
            self.pager = WeightPager(budget_bytes or 1 << 62,
                                     disk_dir=disk_dir, policy=pager_policy,
                                     metrics=metrics, tracer=tracer)
            for k, v in params.items():
                self.pager.add(k, v)
            self.env_base = LazyEnv(self.pager, self.cs, _chunked_table,
                                    table_sizes=self._table_chunks,
                                    quant_specs=self._quant_specs)
        self._register_layouts(self.decode_pipe)
        if self.shards > 1:
            from repro.serving.shards import ShardWorkerPool
            self.shard_pool = ShardWorkerPool(
                self.shards, residency=residency, cs=self.cs,
                budget_bytes=self._residency_budget,
                pager_policy=pager_policy, trace=tracer is not None)
            self._shard_runner = self.shard_pool.run_step
        self._register_shards(self.decode_pipe)
        # the gate builds a full in-memory f32 reference engine (a second
        # chunked weight copy + compile) — an opt-in construction cost,
        # skipped when the plan quantised nothing (logits are trivially
        # identical, and constrained-budget callers shouldn't pay for a
        # resident f32 twin they provably don't need)
        if accuracy_budget is not None and self._precision_mode != "off" \
                and self.table_precision_choices:
            from repro.quant.gate import check_accuracy
            ref = RelationalEngine(
                spec, params, chunk_size=self.cs, residency="in_memory",
                max_len=max_len, row2col=row2col, cache_layout=cache_layout,
                cost_params=cost_params, precision="f32")
            check_accuracy(self, ref, tolerance=accuracy_budget)

    def _compile_pipe(self, g, cache_mode: str):
        """Shared graph → planned-pipeline compile path.  Every pipeline
        the engine builds (decode, prefill, batched decode) MUST come
        through here so they plan under identical knobs: one drift — e.g.
        a plan missing the shared residency pool or the pinned per-table
        chunk sizes — and two pipelines would disagree about the physical
        tables they share.  Only the graph and the cache mode (the seed
        decode plan resolves the knob; later plans are forced to its
        choice) differ per call site.

        Per-table chunk pinning reads ``self._table_chunks`` at call time:
        empty for the seed decode plan (which *makes* the choices), the
        decode plan's choices for every later plan.

        When a tracer is attached the whole compile is one named
        ``cat="plan"`` span: first-touch plan compiles happen INSIDE
        serving ticks (a new prefill length, a new batch bucket, a
        watchdog re-plan), and without the span that time would show up
        as unattributed tick wall time in the flight recorder.
        """
        if self.tracer is not None:
            with self.tracer.span(f"compile:{g.name}", cat="plan"):
                return self._compile_pipe_inner(g, cache_mode)
        return self._compile_pipe_inner(g, cache_mode)

    def _compile_pipe_inner(self, g, cache_mode: str):
        infer_shapes(g)
        preoptimize(g)
        pipe = op_map(g, chunk_size=self.cs)
        postoptimize(pipe, layout_mode=self.row2col,
                     cache_mode=cache_mode,
                     cost_params=self._cost_params,
                     chunk_mode=self._chunk_mode,
                     chunk_candidates=self._chunk_candidates,
                     table_chunks=(dict(self._table_chunks)
                                   if self._chunk_mode != "off" and
                                   self._table_chunks else None),
                     pool=self._residency_pool,
                     precision_mode=self._precision_mode,
                     table_precisions=self._table_precisions or None,
                     shards=self.shards if self.shards > 1 else None)
        return pipe

    def _register_layouts(self, pipe) -> None:
        """Make a pipeline's column-layout tables resolvable: materialised
        into the resident env (in-memory), or converted once into the
        pager's cold store (paged) — the offline ROW2COL data conversion,
        so paged accesses stay zero-copy wraps under the same working-set
        budget.  Head-blocked tables transpose per head block.  Planner
        per-table chunk sizes are recorded in ``self._table_chunks`` (the
        LazyEnv wraps cold arrays at those widths); cold copies register
        padded to their chunk so pager byte accounting matches the
        physical working set."""
        self._table_chunks.update(getattr(pipe, "table_chunks", {}) or {})
        plan = getattr(pipe, "layout_plan", None)
        if plan is None:
            return
        if self.metrics is not None and plan.precision_decisions:
            for pd in plan.precision_decisions:
                if pd.q_table not in self._quant_counted:
                    self._quant_counted.add(pd.q_table)
                    self._quant_bytes += pd.q_bytes
            self.metrics.gauge(
                "engine_quantised_resident_bytes",
                "stored bytes of quantised weight tables").set(
                    self._quant_bytes)
        if self.residency == "in_memory":
            plan.ensure_env(self.env_base)
            return
        for d in plan.col_decisions:
            if d.col_table in self.pager._cold:
                continue
            dense = np.asarray(self.pager._cold[d.table])
            if d.is_head_site:  # [H, dh, n] -> [H, n, dh]
                dense = np.ascontiguousarray(dense.transpose(0, 2, 1))
            else:
                dense = np.ascontiguousarray(dense.T)
            self.pager.add(d.col_table, dense, pad_to=d.physical_chunk)
        # quantised payloads: convert each f32 source (row table, or the
        # column copy registered just above) into packed codes + scales in
        # the cold store — the offline quantisation conversion.  The paged
        # working set then holds *quantised* bytes for these tables.
        for pd in plan.precision_decisions:
            if pd.q_table in self._quant_specs:
                continue
            from repro.quant.codecs import CODECS, quantise_dense
            codec = CODECS[pd.precision]
            dense = np.asarray(self.pager._cold[pd.table])
            packed, scales = quantise_dense(dense, pd.chunk_size, codec)
            self.pager.add(pd.q_table + "::q", packed)
            self.pager.add(pd.q_table + "::scale", scales)
            self._quant_specs[pd.q_table] = (pd.precision, pd.chunk_size,
                                             pd.q_schema)

    def _register_shards(self, pipe) -> None:
        """Install a pipeline's shard-plan slices into the worker pool
        (no-op unsharded).  Ranges depend only on the key-domain size
        and N — identical across the decode/prefill/batched plans — so
        the pool dedupes by shard table name."""
        if self.shard_pool is None:
            return
        self.shard_pool.register_plan(
            getattr(pipe, "shard_plan", None), env_base=self.env_base,
            pager=self.pager, quant_specs=self._quant_specs,
            table_chunks=self._table_chunks, cs=self.cs)

    def merge_shard_metrics(self) -> None:
        """Fold each worker's private metrics registry into the engine
        registry under a ``shard`` label (call once at report time)."""
        if self.shard_pool is not None and self.metrics is not None:
            self.shard_pool.merge_metrics(self.metrics)

    def merged_shard_trace(self):
        """Chrome trace combining the coordinator's spans with every
        worker's, one pid per track (None when tracing is off or the
        engine is unsharded)."""
        if self.shard_pool is None:
            return None
        return self.shard_pool.merged_chrome_trace(self.tracer)

    def _plan_cache_event(self, cache: str, hit: bool) -> None:
        if self.metrics is not None:
            self.metrics.counter(
                "engine_plan_cache_total",
                "compiled-plan cache lookups", cache=cache,
                outcome="hit" if hit else "miss").inc()

    def _prefill_pipe(self, T: int, suffix: bool = False):
        # plans are cached per (length, shard count, suffix?): a sharded
        # engine's plans carry per-shard plan copies and a combine
        # decision, so they are not interchangeable with unsharded ones;
        # suffix plans ride the runtime :cache_position for both the
        # append offset AND the causal mask, so ONE suffix plan per
        # suffix length serves every prefix boundary — the boundary is a
        # bound parameter, not part of the plan-cache key
        key = (T, self.shards, suffix)
        self._plan_cache_event("prefill", key in self._prefill_pipes)
        if key not in self._prefill_pipes:
            # prefill shares the session environment with decode: it draws
            # on the same residency pool and is pinned to the decode plan's
            # per-table chunk sizes (both pipelines scan the same physical
            # tables) — all enforced by the shared compile path
            pipe = self._compile_pipe(
                lg.build_prefill_graph(self.spec, T, cache_len=self.max_len,
                                       suffix=suffix),
                cache_mode=self._prefill_cache_mode)
            self._register_layouts(pipe)
            self._register_shards(pipe)
            self._prefill_pipes[key] = pipe
        return self._prefill_pipes[key]

    def _batched_decode_pipe(self, batch: int):
        """Compile (once per batch-size bucket) the seq-keyed decode plan
        that advances ``batch`` sequences in ONE ``run_pipeline`` call.

        The plan is priced at batch size B (the matmul sites' seq_len *is*
        the batch), draws on the same residency pool as the decode/prefill
        plans, is pinned to their per-table chunk sizes, and is forced to
        the session cache layout (the batched cache pool's key order).
        """
        key = (batch, self.shards)
        self._plan_cache_event("batched_decode", key in self._batched_pipes)
        if key not in self._batched_pipes:
            pipe = self._compile_pipe(
                lg.build_decode_graph(self.spec, cache_len=self.max_len,
                                      batch=batch),
                cache_mode=self._prefill_cache_mode)
            self._register_layouts(pipe)
            self._register_shards(pipe)
            self._batched_pipes[key] = pipe
        return self._batched_pipes[key]

    @property
    def runs_compiled(self) -> bool:
        """Whether the batched decode tick and the cold prefill run as
        compiled XLA programs (:meth:`_decode_tick`, :meth:`_prefill_tick`):
        when the weights are held in memory, no recorder is attached (it
        blocks on every step, which a traced program cannot) and the plans
        are unsharded.  Otherwise their plans are dispatched op by op, as
        the suffix prefill of a prefix hit and single-session decode
        always are."""
        return self.pager is None and self.tracer is None and self.shards == 1

    @property
    def decode_path(self) -> str:
        """How a batched decode tick runs: ``"compiled"``, one XLA program
        per batch bucket, where :attr:`runs_compiled`; otherwise
        ``"eager"``."""
        return "compiled" if self.runs_compiled else "eager"

    def _decode_tick(self, batch: int, pipe):
        """``(tick, weight names)``: the compiled tick over ``pipe``, the
        bucket's registered batched plan, and the weight tables to pass it.
        The weights enter as arguments (as closure constants they would be
        embedded in the program; ``jit`` drops those the plan never reads).
        Their names are those ``env_base`` held once the plan was
        registered, so a table a later plan adds does not change the
        tick's signature.  The views and pool tables are donated, so the
        tick updates the cache without a second copy of it."""
        key = (batch, self.shards)
        if key not in self._decode_ticks:
            def decode_tick(*args):  # names the program jit_decode_tick
                return batched_decode_tick(pipe, *args)
            self._decode_ticks[key] = (
                jax.jit(decode_tick, donate_argnums=(1, 2)),
                tuple(self.env_base))
        return self._decode_ticks[key]

    def _prefill_tick(self, bucket: int, pipe):
        """``(prefill, weight names)``: the compiled cold prefill over
        ``pipe``, the bucket's registered prefill plan, and the weight
        tables to pass it, as :meth:`_decode_tick` gives them.  The program
        makes its own empty cache tables and donates nothing: the prefix
        cache holds the tables it returns by reference.  The last real
        token's row is a traced index, so one program serves every prompt
        length of the bucket."""
        key = (bucket, self.shards)
        if key not in self._prefill_ticks:
            def prefill(weights, inputs, last):  # names it jit_prefill
                return cold_prefill(pipe, weights, self._empty_caches(),
                                    inputs, last)
            self._prefill_ticks[key] = (jax.jit(prefill),
                                        tuple(self.env_base))
        return self._prefill_ticks[key]

    def _prefill_bucket(self, T: int) -> int:
        """Length bucket a compiled prefill of ``T`` tokens runs at: ``T``
        rounded up to a multiple of the chunk size, at most ``max_len``."""
        return min(-(-T // self.cs) * self.cs, self.max_len)

    @staticmethod
    def _decode_bucket(batch: int) -> int:
        """Batch-size bucket (next power of two) a tick's plan is keyed by."""
        b = 1
        while b < batch:
            b *= 2
        return b

    def _weights_env(self):
        if self.residency == "in_memory":
            return dict(self.env_base)
        # .copy() keeps the shared table_sizes reference so sessions
        # wrap cold arrays at the planner's per-table chunk sizes
        return self.env_base.copy()

    def _empty_caches(self):
        return lg.empty_cache_tables(self.spec, cache_len=self.max_len,
                                     chunk_size=self.cs,
                                     layout=self.cache_layout)

    def _fresh_env(self):
        env = self._weights_env()
        env.update(self._empty_caches())
        return env

    def _argmax_token(self, out_table) -> int:
        return int(np.argmax(self._final_logits(out_table)))

    def _final_logits(self, out_table) -> np.ndarray:
        """Final-position logits row (un-padded vocab)."""
        return np.asarray(out_table.cols["v"]).reshape(
            out_table.cols["v"].shape[0], -1)[-1, : self.spec.vocab]

    def _phase_hist(self, series: str, phase: str):
        """The ``phase`` histogram of one of ``PHASE_SERIES``; None
        without a registry."""
        if self.metrics is None:
            return None
        return self.metrics.histogram(series, PHASE_SERIES[series],
                                      phase=phase)

    def _fetch(self, logits, routes, phase: str, rows: int) -> np.ndarray:
        """Host copy of a plan's ``logits``.  With a registry, its held
        ``routes`` come in the same transfer and the ``MOE_SERIES`` count
        them over the first ``rows`` tokens."""
        if self.metrics is None or not routes:
            return np.asarray(logits)
        logits, routes = jax.device_get((logits, list(routes)))
        self.observe_routes(routes, phase, rows)
        return logits

    def observe_routes(self, routes, phase: str, rows: int) -> None:
        """Record one plan's routes, ``[tokens, top_k]`` local expert ids
        (-1 not held) per expert layer, of which the first ``rows`` tokens
        are real (a batch bucket's padding repeats a sequence)."""
        m = self.metrics
        pairs = m.counter("moe_routed_pairs_total",
                          MOE_SERIES["moe_routed_pairs_total"], phase=phase)
        touched = m.histogram("moe_experts_touched",
                              MOE_SERIES["moe_experts_touched"],
                              buckets=MOE_BUCKETS, phase=phase)
        load = m.histogram("moe_expert_load_max",
                           MOE_SERIES["moe_expert_load_max"],
                           buckets=MOE_BUCKETS, phase=phase)
        for ids in routes:
            ids = np.asarray(ids).reshape(np.shape(ids)[0], -1)[:rows]
            held = ids[ids >= 0].astype(np.int64)
            per = np.bincount(held, minlength=1)
            pairs.inc(int(held.size))
            touched.observe(int(np.count_nonzero(per)))
            load.observe(int(per.max()))

    def _count_prefill(self, path: str, padded: int) -> None:
        if self.metrics is not None:
            self.metrics.counter("engine_prefill_total",
                                 "prefills, by how they ran", path=path).inc()
            self.metrics.counter(
                "engine_prefill_padded_tokens_total",
                "rows a compiled prefill computed past the prompt").inc(
                    padded)

    def _run_compiled_prefill(self, prompt: List[int]):
        """The cold prefill of ``prompt`` as one compiled program of its
        length bucket (:meth:`_prefill_tick`), the prompt padded at the end
        with token 0 at the positions that follow it; returns the filled
        cache tables and the last real token's logits on the host.  Under
        the causal mask no real row attends to a padded one; the padded
        rows fill cache positions past the prompt, which decode masks
        until it overwrites them."""
        T = len(prompt)
        bucket = self._prefill_bucket(T)
        ids = np.zeros(bucket, np.int32)
        ids[:T] = prompt
        inputs = {"token_ids": lg.token_table(ids),
                  "freq_each_token": lg.rope_freq_table(
                      np.arange(bucket), self.spec.head_dim,
                      self.spec.rope_theta)}
        self._count_prefill("compiled", bucket - T)
        prefill, names = self._prefill_tick(bucket,
                                            self._prefill_pipe(bucket))
        with traced("prefill.dispatch",
                    hist=self._phase_hist("engine_dispatch_seconds",
                                          "prefill")):
            logits, routes, caches = prefill(
                {n: self.env_base[n] for n in names}, inputs,
                np.int32(T - 1))
        with traced("prefill.sync",
                    hist=self._phase_hist("engine_sync_seconds", "prefill")):
            logits = self._fetch(logits, routes, "prefill", T)
        return caches, logits.reshape(-1)[: self.spec.vocab]

    def _run_prefill(self, T: int, suffix: bool, env, position: int):
        """The prefill plan over ``env``, dispatched op by op, and its
        final-position logits on the host; returns ``(env, logits)``."""
        self._count_prefill("eager", 0)
        if self.pager is not None:
            self.pager.prefetch(["vocabulary"])
        pipe = self._prefill_pipe(T, suffix=suffix)
        with traced("prefill.dispatch",
                    hist=self._phase_hist("engine_dispatch_seconds",
                                          "prefill")):
            outs, env = run_pipeline(pipe, env,
                                     scalars={"cache_position": position},
                                     tracer=self.tracer,
                                     shard_runner=self._shard_runner)
        with traced("prefill.sync",
                    hist=self._phase_hist("engine_sync_seconds", "prefill")):
            logits = self._fetch(
                outs["logits"].cols["v"],
                [outs[o].cols["e"] for o in route_outputs(pipe)],
                "prefill", T)
        logits = logits.reshape(logits.shape[0], -1)[-1, : self.spec.vocab]
        return env, logits

    @property
    def table_precision_choices(self) -> Dict[str, str]:
        """Planner-chosen payload precision per stored weight table (the
        decode plan's decisions; tables absent here store f32)."""
        plan = getattr(self.decode_pipe, "layout_plan", None)
        if plan is None:
            return {}
        return {d.table: d.precision for d in plan.precision_decisions}

    def replan(self, cost_params) -> None:
        """Re-run physical planning under recalibrated cost weights and
        swap the compiled plan caches — the drift watchdog's observe→act
        hook (ROADMAP "adaptive re-planning").  Call between scheduler
        ticks only: live sessions hold references to the *old* pipelines
        for at most the tick in flight, and the next tick's plan lookups
        recompile against the new weights.

        Token-exactness mid-flight is guaranteed by what stays pinned:

        * **cache layout** — live session/batched cache tables already
          materialised their key order; the recompile is forced to the
          resolved ``self._prefill_cache_mode``, exactly like every
          prefill/batched plan after the seed decode plan.
        * **chunk sizes** — ``self._table_chunks`` (and the shared
          ``ResidencyPool.chunks``) pin every previously-chunked table,
          so no plan can re-declare a physical width.
        * **precision** — the shared pool records a precision decision
          for *every* candidate table (f32 included), so recalibrated
          weights can re-rank layouts but never flip a stored payload
          format under a running session.

        What the new weights CAN change — row-vs-col access paths, and
        chunk/precision choices for tables planned for the first time —
        is value-exact by construction.
        """
        from repro.obs.log import log_event
        self._cost_params = cost_params
        # make the current plan's quantisation choices explicit pins
        # (the pool already enforces them; this keeps them visible on
        # the engine and survives a future pool swap)
        for t, p in self.table_precision_choices.items():
            self._table_precisions.setdefault(t, p)
        pipe = self._compile_pipe(
            lg.build_decode_graph(self.spec, cache_len=self.max_len),
            cache_mode=self._prefill_cache_mode)
        self._register_layouts(pipe)
        self._register_shards(pipe)
        self.decode_pipe = pipe
        # drop the derived plan caches: next tick recompiles its bucket
        # under the new weights (sessions join/leave freely meanwhile)
        self._prefill_pipes.clear()
        self._batched_pipes.clear()
        self._decode_ticks.clear()
        self._prefill_ticks.clear()
        self.replans += 1
        if self.metrics is not None:
            self.metrics.counter(
                "engine_replans_total",
                "mid-flight re-planning events (drift watchdog)").inc()
        log_event("engine_replan", replans=self.replans,
                  group_weight=getattr(cost_params, "group_weight", None))

    # -- incremental session API (used by the continuous-batching scheduler) --

    def start_session(self, prompt: List[int]):
        """Prefill; returns a session dict holding env + cursor + first tok.
        The prefill runs as one compiled program of the prompt's length
        bucket where :attr:`runs_compiled`, else op by op."""
        T = len(prompt)
        if self.runs_compiled:
            caches, logits = self._run_compiled_prefill(prompt)
            env = self._weights_env()
            env.update(caches)
        else:
            env_span = (self.tracer.span("session_env", cat="decoder")
                        if self.tracer is not None
                        else contextlib.nullcontext())
            with env_span:
                env = self._fresh_env()
                env["token_ids"] = lg.token_table(
                    np.asarray(prompt, np.int32))
                env["freq_each_token"] = lg.rope_freq_table(
                    np.arange(T), self.spec.head_dim, self.spec.rope_theta)
            env, logits = self._run_prefill(T, False, env, 0)
        return {"env": env, "pos": T, "tok": int(np.argmax(logits)),
                "logits": logits}

    def start_suffix_session(self, prompt: List[int], boundary: int,
                             cache_tables: Dict[str, object]):
        """Prefill only ``prompt[boundary:]`` over caches already holding
        the prefix ``prompt[:boundary]`` (a shared prefix segment).

        ``cache_tables`` supplies the segment's ``k_cache_L*``/
        ``v_cache_L*`` relations; they are shared by reference — the
        pipeline's appends functionally update them into fresh arrays, so
        the segment is never mutated (copy-on-write past the boundary).
        RoPE frequencies and the causal mask both place the suffix at
        absolute positions ``boundary .. len(prompt)-1``; the boundary is
        bound at runtime (``:cache_position``), so every boundary shares
        one compiled plan per suffix length.
        """
        prompt = list(prompt)
        if boundary <= 0:
            return self.start_session(prompt)
        T = len(prompt) - boundary
        if T <= 0:
            raise ValueError(
                f"suffix prefill needs >= 1 new token: prompt length "
                f"{len(prompt)} <= boundary {boundary}")
        env_span = (self.tracer.span("session_env", cat="decoder")
                    if self.tracer is not None else contextlib.nullcontext())
        with env_span:
            env = self._weights_env()
            env.update(cache_tables)
            env["token_ids"] = lg.token_table(
                np.asarray(prompt[boundary:], np.int32))
            env["freq_each_token"] = lg.rope_freq_table(
                np.arange(boundary, len(prompt)), self.spec.head_dim,
                self.spec.rope_theta)
        env, logits = self._run_prefill(T, True, env, boundary)
        return {"env": env, "pos": len(prompt),
                "tok": int(np.argmax(logits)), "logits": logits}

    def prefill_logits(self, prompt: List[int]) -> np.ndarray:
        """Final-position prefill logits (the accuracy gate's probe)."""
        return self.start_session(list(prompt))["logits"]

    def session_step(self, sess) -> int:
        """One KV-cached decode step (the §3.4 compact queries)."""
        env, pos, tok = sess["env"], sess["pos"], sess["tok"]
        env["token_ids"] = lg.token_table(np.asarray([tok], np.int32))
        env["freq_each_token"] = lg.rope_freq_table(
            np.asarray([pos]), self.spec.head_dim, self.spec.rope_theta)
        t0 = time.perf_counter() if self.metrics is not None else 0.0
        outs, env = run_pipeline(self.decode_pipe, env,
                                 scalars={"cache_position": pos},
                                 tracer=self.tracer,
                                 shard_runner=self._shard_runner)
        tok = self._argmax_token(outs["logits"])
        if self.metrics is not None:
            self.metrics.histogram(
                "engine_decode_step_seconds",
                "single-sequence decode step latency").observe(
                    time.perf_counter() - t0)
        sess.update(env=env, pos=pos + 1, tok=tok)
        return tok

    def generate(self, prompt: List[int], max_new_tokens: int
                 ) -> GenerationResult:
        t0 = time.perf_counter()
        sess = self.start_session(prompt)
        tokens = [sess["tok"]]
        ttft = time.perf_counter() - t0

        t1 = time.perf_counter()
        for _ in range(max_new_tokens - 1):
            tokens.append(self.session_step(sess))
        n_rest = max(1, max_new_tokens - 1)
        tpot = (time.perf_counter() - t1) / n_rest
        stats = dataclasses.asdict(self.pager.stats) if self.pager else None
        peak = self.pager.stats.peak_bytes if self.pager else \
            sum(int(np.prod(t.cols[c].shape)) * 4
                for t in self.env_base.values() for c in t.cols)
        return GenerationResult(tokens, ttft, tpot, peak, stats)

    # -- batched serving API (one relational plan per scheduler tick) ---------

    def batched_decoder(self, max_seqs: int, prefix_block: int = 16,
                        prefix_bind: str = "auto",
                        prefix_cache_bytes: Optional[int] = None
                        ) -> "BatchedDecoder":
        """Seq-slotted decode front-end: ``prefill``/``decode`` callbacks
        for :class:`~repro.serving.scheduler.ContinuousBatcher`, with
        ``decode`` advancing ALL active sequences in ONE ``run_pipeline``
        call on the batched plan.

        ``prefix_block`` sizes the prefix cache's content-hash blocks
        (0 disables prefix caching); ``prefix_bind`` picks the segment
        bind mode (``"copy"`` / ``"share"`` / ``"auto"``);
        ``prefix_cache_bytes`` bounds the segment store (defaults to the
        engine's paged residency budget when one is set)."""
        return BatchedDecoder(self, max_seqs, prefix_block=prefix_block,
                              prefix_bind=prefix_bind,
                              prefix_cache_bytes=prefix_cache_bytes)


class BatchedDecoder:
    """Batched relational decode over seq-slotted cache tables.

    Wraps a :class:`RelationalEngine` with the scheduler's callback shape:

      ``prefill(prompt, seq_id)``       — single-sequence prefill, cache
                                          rows copied into slot ``seq_id``
      ``decode(seq_ids, last_tokens)``  — ONE ``run_pipeline`` call on the
                                          batch-bucketed seq-keyed plan;
                                          per-sequence positions ride in as
                                          the ``seq_positions`` vector.  It
                                          runs as one compiled program with
                                          the cache writeback inside where
                                          ``engine.decode_path`` says so

    Ticks whose batch size is below the bucket pad by repeating the last
    sequence: the padded rows recompute that sequence's step and scatter
    back identical values, so padding is semantically free.
    """

    BIND_MODES = ("auto", "copy", "share")

    def __init__(self, engine: RelationalEngine, max_seqs: int,
                 prefix_block: int = 16, prefix_bind: str = "auto",
                 prefix_cache_bytes: Optional[int] = None):
        from repro.serving.kvcache import BatchedCacheTables, PrefixCache
        assert prefix_bind in self.BIND_MODES, \
            f"prefix_bind must be one of {self.BIND_MODES}"
        self.engine = engine
        self.pool = BatchedCacheTables(engine.spec, max_seqs, engine.max_len,
                                       engine.cs,
                                       layout=engine.cache_layout)
        # content-hash prefix cache over completed prefills; prefill_ex
        # consults it, plain prefill() stays the cold path (bit-identical
        # to the pre-prefix-cache decoder)
        if prefix_cache_bytes is None:
            prefix_cache_bytes = engine._residency_budget
        self.prefix_cache = (None if not prefix_block else PrefixCache(
            block=prefix_block, budget_bytes=prefix_cache_bytes,
            metrics=engine.metrics))
        self.prefix_bind = prefix_bind
        self.decode_calls = 0  # == run_pipeline calls for decode ticks
        # gathered batch views cached across ticks: re-gathering the full
        # cache_len-deep tables every tick is O(B·cache_len) read traffic
        # when only one row per sequence changed — reuse last tick's
        # updated views while batch membership and slot contents are
        # unchanged.  The cache key is (slot ids, slot *generations*): the
        # pool bumps a slot's generation on every mutation outside decode
        # (prefill fill, free, bulk scatter), so invalidation also fires
        # when a freed slot is reused by a NEW sequence — same ids tuple,
        # different contents — even through pool-level writes this decoder
        # never sees.
        self._view_key: Optional[tuple] = None
        self._views: Optional[dict] = None
        # the last tick's logits [B, vocab] as brought to the host for the
        # argmax: what a reference comparison reads
        self.last_logits: Optional[np.ndarray] = None

    def _span(self, name: str, **args):
        """Named decoder-phase span (no-op without a tracer) — slot
        writes and prefix-cache work happen outside ``run_pipeline``, and
        unnamed they would show up as unattributed tick wall time in the
        flight recorder."""
        if self.engine.tracer is None:
            return contextlib.nullcontext()
        return self.engine.tracer.span(name, cat="decoder", **args)

    def prefill(self, prompt: List[int], seq_id: int) -> int:
        # write_prefill overwrites the WHOLE slot (full cache_len), so a
        # reused slot cannot leak a previous sequence's rows even if the
        # scheduler never called free() for it; it also bumps the slot
        # generation, invalidating any cached batch view over it
        self._unbind(seq_id)
        sess = self.engine.start_session(list(prompt))
        with traced("prefill.cache_fill", self._span("cache_fill")):
            self.pool.write_prefill(seq_id, sess["env"], len(prompt))
        return sess["tok"]

    def prefill_ex(self, prompt: List[int], seq_id: int
                   ) -> "tuple[int, int]":
        """Prefix-cached prefill: ``(first_token, cached_tokens)``.

        Looks up the longest cached prefix, binds the slot to the shared
        segment (copy or share mode, see :meth:`_resolve_bind`) and runs
        the suffix-only prefill plan over ``prompt[cached:]``; on a miss
        it falls back to the cold path and interns the result as a new
        segment.  Token-exact either way: the suffix plan's causal mask
        and RoPE positions place the suffix at its absolute offsets, and
        the segment rows it attends to are the very arrays the donor
        prefill produced.
        """
        prompt = list(prompt)
        self._unbind(seq_id)  # slot reuse: drop any stale binding first
        pc = self.prefix_cache
        if pc is None:
            return self.prefill(prompt, seq_id), 0
        with self._span("prefix_lookup"):
            hit = pc.lookup(prompt)
        if hit is None:
            sess = self.engine.start_session(prompt)
            with traced("prefill.cache_fill", self._span("cache_fill")):
                self.pool.write_prefill(seq_id, sess["env"], len(prompt))
                pc.insert(prompt, sess["env"])
            return sess["tok"], 0
        seg, boundary = hit
        sess = self.engine.start_suffix_session(prompt, boundary,
                                                seg.tables)
        with traced("prefill.cache_fill",
                    self._span("cache_fill", prefix_hit=boundary)):
            if self._resolve_bind(boundary) == "share":
                # slot holds only the divergent suffix; gathers splice the
                # segment's rows in (UNION-remap); the segment stays pinned
                pc.acquire(seg)
                self.pool.write_suffix(seq_id, sess["env"], len(prompt),
                                       boundary)
                self.pool.bind_segment(seq_id, seg, boundary)
            else:
                # bulk copy (INSERT ... SELECT): the slot owns a private
                # full copy, no pin, no gather-time splice
                self.pool.write_prefill(seq_id, sess["env"], len(prompt))
            # intern the extended prefix too (no-op if coverage unchanged)
            pc.insert(prompt, sess["env"])
        return sess["tok"], boundary

    def _resolve_bind(self, boundary: int) -> str:
        """Bind-mode pricing.  Copy costs one full-slot device write at
        bind; share saves that write but pins the segment and pays a
        boundary-row splice whenever batch membership changes.  Under a
        bounded residency budget the pin is what matters (shared rows are
        stored once), so ``auto`` shares; unconstrained, the cheaper
        steady-state decode path (no splice) wins and ``auto`` copies."""
        if self.prefix_bind != "auto":
            return self.prefix_bind
        return ("share" if self.engine._residency_budget is not None
                else "copy")

    def _unbind(self, seq_id: int) -> None:
        seg = self.pool.release_binding(seq_id)
        if seg is not None and self.prefix_cache is not None:
            self.prefix_cache.release(seg)

    def free(self, seq_id: int) -> None:
        self._unbind(seq_id)
        self.pool.free(seq_id)

    def decode(self, seq_ids: List[int], last_tokens: List[int]
               ) -> List[int]:
        eng = self.engine
        metrics = eng.metrics
        # decoder-phase spans (cat="decoder") name the tick's work outside
        # run_pipeline — view gathers, cache writeback, logits extraction —
        # so a flight-recorded tick attributes its wall time end to end;
        # the decode.* profiler annotations split the same tick into
        # views, dispatch, writeback and the wait for the logits
        span = (eng.tracer.span if eng.tracer is not None
                else (lambda *a, **k: contextlib.nullcontext()))
        t0 = time.perf_counter() if metrics is not None else 0.0
        B = len(seq_ids)
        bucket = eng._decode_bucket(B)
        ids = list(seq_ids) + [seq_ids[-1]] * (bucket - B)
        toks = list(last_tokens) + [last_tokens[-1]] * (bucket - B)
        pipe = eng._batched_decode_pipe(bucket)
        path = eng.decode_path
        positions = self.pool.positions[np.asarray(ids)]
        view_key = (tuple(ids), self.pool.slot_generations(ids))
        view_hit = self._view_key == view_key
        with traced("decode.views",
                    span("cache_views", cat="decoder",
                         outcome="hit" if view_hit else "miss")):
            # an unchanged batch reuses last tick's updated views
            views = self._views if view_hit else self.pool.gather_views(ids)
            inputs = {
                "token_ids": lg.token_table(np.asarray(toks, np.int32),
                                            key="seq"),
                "freq_each_token": lg.rope_freq_table(
                    positions, eng.spec.head_dim, eng.spec.rope_theta,
                    key="seq")}
        if metrics is not None:
            metrics.counter("decoder_view_cache_total",
                            "batched cache-view gathers",
                            outcome="hit" if view_hit else "miss").inc()
            metrics.counter("decoder_ticks_total",
                            "batched decode ticks, by how they ran",
                            path=path).inc()
        with traced("decode.dispatch",
                    hist=eng._phase_hist("engine_dispatch_seconds",
                                         "decode")):
            if path == "compiled":
                # the row writeback runs inside the compiled tick
                tick, names = eng._decode_tick(bucket, pipe)
                logits, routes, views, tables = tick(
                    {n: eng.env_base[n] for n in names}, views,
                    self.pool.tables, inputs, np.asarray(ids, np.int32),
                    positions)
            else:
                env = eng._weights_env()
                env.update(views)
                env.update(inputs)
                outs, env = run_pipeline(
                    pipe, env,
                    scalars={"seq_positions": jnp.asarray(positions,
                                                          jnp.int32)},
                    tracer=eng.tracer, shard_runner=eng._shard_runner)
                logits = outs["logits"].cols["v"]
                routes = [outs[o].cols["e"] for o in route_outputs(pipe)]
                views = {name: env[name] for name in self.pool.tables}
        self.decode_calls += 1
        # the tick's only cache mutation is one appended row per sequence
        # at positions[b] — write back just those rows; the updated views
        # (which already contain them) serve the next tick's gather
        with traced("decode.writeback", span("cache_writeback",
                                             cat="decoder")):
            if path == "compiled":
                for name, t in tables.items():
                    self.pool.tables[name].cols.update(t.cols)
            else:
                self.pool.scatter_rows(ids, views, positions)
            self._views = views
            self._view_key = view_key
            for s in seq_ids:
                self.pool.positions[s] += 1
        with span("logits_argmax", cat="decoder"):
            with traced("decode.sync",
                        hist=eng._phase_hist("engine_sync_seconds",
                                             "decode")):
                logits = eng._fetch(logits, routes, "decode", B)
            logits = logits.reshape(bucket, -1)[:B, : eng.spec.vocab]
            next_toks = [int(t) for t in np.argmax(logits, axis=1)]
        self.last_logits = logits
        if metrics is not None:
            metrics.histogram(
                "decoder_tick_seconds",
                "batched decode tick latency").observe(
                    time.perf_counter() - t0)
        return next_toks


class DirectEngine:
    """Dense-JAX engine (baseline role). residency="paged" emulates
    llama.cpp-style synchronous dynamic weight loading (no prefetch)."""

    def __init__(self, cfg, params, residency: str = "in_memory",
                 budget_bytes: Optional[int] = None,
                 disk_dir: Optional[str] = None, max_len: int = 1024):
        from repro.models import transformer as tf
        self.cfg = cfg
        self.tf = tf
        self.max_len = max_len
        self.residency = residency
        if residency == "in_memory":
            self.params = params
            self.pager = None
        else:
            self.pager = WeightPager(budget_bytes or 1 << 62,
                                     disk_dir=disk_dir)
            self.pager.add_tree(params)
            self._abstract = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params)
        self._prefill_jit = jax.jit(
            lambda p, t, c: tf.prefill(p, t, cfg, c), donate_argnums=(2,))
        self._decode_jit = jax.jit(
            lambda p, t, c, pos: tf.decode_step(p, t, c, pos, cfg),
            donate_argnums=(2,))

    def _materialise(self):
        """Paged mode: pull the whole tree through the bounded working set —
        synchronous, per-leaf, evicting as the budget demands."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self._abstract)
        leaves = []
        for path, _ in flat:
            key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                           for k in path)
            leaves.append(self.pager.get(key))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._abstract), leaves)

    def generate(self, prompt: List[int], max_new_tokens: int
                 ) -> GenerationResult:
        t0 = time.perf_counter()
        params = self.params if self.pager is None else self._materialise()
        toks = jnp.asarray([prompt], jnp.int32)
        caches = self.tf.init_caches(self.cfg, 1, self.max_len,
                                     dtype=jnp.float32)
        logits, caches, _ = self._prefill_jit(params, toks, caches)
        tok = int(jnp.argmax(logits[0, -1]))
        tokens = [tok]
        ttft = time.perf_counter() - t0

        t1 = time.perf_counter()
        T = len(prompt)
        for i in range(max_new_tokens - 1):
            if self.pager is not None:
                params = self._materialise()  # synchronous reload pressure
            logits, caches = self._decode_jit(
                params, jnp.asarray([[tok]], jnp.int32), caches,
                jnp.asarray(T + i))
            tok = int(jnp.argmax(logits[0, -1]))
            tokens.append(tok)
        tpot = (time.perf_counter() - t1) / max(1, max_new_tokens - 1)
        stats = dataclasses.asdict(self.pager.stats) if self.pager else None
        peak = (self.pager.stats.peak_bytes if self.pager else
                sum(int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in jax.tree_util.tree_leaves(self.params)))
        return GenerationResult(tokens, ttft, tpot, peak, stats)
