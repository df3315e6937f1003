"""Paged KV cache — the paper's KV-cache tables (§3.4) as fixed-size pages.

The paper stores cached keys/values as relational rows keyed by token
index; decode INSERTs the new row and joins against the table.  Physically
that is a *paged* layout: fixed-size pages (= chunk tables) indexed through
a per-sequence page table.  The join key (seq, token) → (page, slot) is the
address split ``token // page ↦ page_id, token % page ↦ slot`` — exactly
the paper's chunk-index projection.

Pages are pooled across sequences (no per-sequence max-length allocation);
``kernels/paged_attention`` consumes the slot-major pool order via
:meth:`PagedKVCache.kernel_views` (which transposes when the pool is
stored ``head_major``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# token-count histogram buckets for the cached-prefix-length distribution
# (the registry's DEFAULT_BUCKETS are latency seconds — useless for tokens)
CACHED_TOKEN_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                        256.0, 512.0, 1024.0, 2048.0, 4096.0)


@dataclasses.dataclass
class PagedKVConfig:
    n_layers: int
    n_kv: int
    head_dim: int
    page_size: int = 64          # tokens per page (the chunk size)
    n_pages: int = 256           # pool size (all sequences, per layer)
    max_pages_per_seq: int = 64
    dtype: str = "float32"
    # physical in-page layout (planner cache layouts): "row_chunk" clusters
    # a page by slot (position-major, the seed); "head_major" clusters by
    # KV head, so one head's history within a page is contiguous (the
    # planner's decode-attention locality choice).  Kernels consume the
    # slot-major order via PagedKVCache.kernel_views.
    layout: str = "row_chunk"


class PagedKVCache:
    """Host-managed page tables + device-resident page pool.

    pool[layer]: k/v arrays [n_pages, page_size, n_kv, head_dim]
    (``layout="row_chunk"``) or [n_pages, n_kv, page_size, head_dim]
    (``layout="head_major"``).
    page_table: [max_seqs, max_pages_per_seq] int32 (-1 = unmapped).
    """

    def __init__(self, cfg: PagedKVConfig, max_seqs: int):
        if cfg.layout not in ("row_chunk", "head_major"):
            raise ValueError(f"unsupported KV page layout {cfg.layout!r}")
        self.cfg = cfg
        self.max_seqs = max_seqs
        dt = jnp.dtype(cfg.dtype)
        if cfg.layout == "head_major":
            shape = (cfg.n_layers, cfg.n_pages, cfg.n_kv, cfg.page_size,
                     cfg.head_dim)
        else:
            shape = (cfg.n_layers, cfg.n_pages, cfg.page_size, cfg.n_kv,
                     cfg.head_dim)
        self.k_pool = jnp.zeros(shape, dt)
        self.v_pool = jnp.zeros(shape, dt)
        self.page_table = np.full((max_seqs, cfg.max_pages_per_seq), -1,
                                  np.int32)
        self.seq_lens = np.zeros((max_seqs,), np.int32)
        self._free: List[int] = list(range(cfg.n_pages))[::-1]
        self._active: Dict[int, bool] = {}

    # -- page-table management (host side, per scheduler tick) -----------------

    def allocate_seq(self, seq_id: int) -> None:
        assert not self._active.get(seq_id, False)
        self._active[seq_id] = True
        self.page_table[seq_id, :] = -1
        self.seq_lens[seq_id] = 0

    def free_seq(self, seq_id: int) -> None:
        for p in self.page_table[seq_id]:
            if p >= 0:
                self._free.append(int(p))
        self.page_table[seq_id, :] = -1
        self.seq_lens[seq_id] = 0
        self._active[seq_id] = False

    def ensure_capacity(self, seq_id: int, new_len: int) -> None:
        """Map enough pages for ``new_len`` tokens (INSERT pre-allocation)."""
        need = -(-new_len // self.cfg.page_size)
        have = int((self.page_table[seq_id] >= 0).sum())
        if need > self.cfg.max_pages_per_seq:
            raise RuntimeError("sequence exceeds max_pages_per_seq")
        for i in range(have, need):
            if not self._free:
                raise RuntimeError("KV page pool exhausted (preemption "
                                   "required — scheduler handles this)")
            self.page_table[seq_id, i] = self._free.pop()

    def free_page_count(self) -> int:
        return len(self._free)

    # -- device-side append / gather -------------------------------------------

    def append(self, seq_id: int, layer_k: jnp.ndarray, layer_v: jnp.ndarray,
               pos: int) -> None:
        """Write one token's K/V (all layers) at absolute position ``pos``.

        layer_k/v: [n_layers, n_kv, head_dim].  The (page, slot) address is
        the chunk-key projection of ``pos``.
        """
        self.ensure_capacity(seq_id, pos + 1)
        page = int(self.page_table[seq_id, pos // self.cfg.page_size])
        slot = pos % self.cfg.page_size
        if self.cfg.layout == "head_major":
            self.k_pool = self.k_pool.at[:, page, :, slot].set(
                layer_k.astype(self.k_pool.dtype))
            self.v_pool = self.v_pool.at[:, page, :, slot].set(
                layer_v.astype(self.v_pool.dtype))
        else:
            self.k_pool = self.k_pool.at[:, page, slot].set(
                layer_k.astype(self.k_pool.dtype))
            self.v_pool = self.v_pool.at[:, page, slot].set(
                layer_v.astype(self.v_pool.dtype))
        self.seq_lens[seq_id] = max(int(self.seq_lens[seq_id]), pos + 1)

    def gather(self, seq_id: int, layer: int) -> Tuple[jnp.ndarray,
                                                       jnp.ndarray, int]:
        """Materialise a sequence's K/V [T, n_kv, dh] (reference path)."""
        T = int(self.seq_lens[seq_id])
        pages = self.page_table[seq_id][: -(-T // self.cfg.page_size)]
        k, v = self.k_pool[layer, pages], self.v_pool[layer, pages]
        if self.cfg.layout == "head_major":  # [P, hk, slot, dh] -> slot-major
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
        k = k.reshape(-1, self.cfg.n_kv, self.cfg.head_dim)[:T]
        v = v.reshape(-1, self.cfg.n_kv, self.cfg.head_dim)[:T]
        return k, v, T

    def batch_views(self, seq_ids: List[int]):
        """Page tables + lengths for a decode batch (kernel inputs)."""
        pt = jnp.asarray(self.page_table[seq_ids])
        lens = jnp.asarray(self.seq_lens[seq_ids])
        return pt, lens

    def kernel_views(self, layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """This layer's K/V pools in the slot-major order
        ``[n_pages, page_size, n_kv, head_dim]`` that
        ``kernels/paged_attention`` unpacks positionally — the transpose is
        applied when the pool is stored ``head_major``.  Kernel consumers
        must go through this accessor rather than indexing ``k_pool``
        directly, since the pool's physical layout is config-chosen."""
        k, v = self.k_pool[layer], self.v_pool[layer]
        if self.cfg.layout == "head_major":  # [P, hk, slot, d] -> slot-major
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
        return k, v


@dataclasses.dataclass
class CacheSegment:
    """An immutable, refcounted KV segment: the cache tables of a completed
    (single-sequence) prefill, valid for the token prefix ``tokens``.

    The tables are the session environment's ``k_cache_L*``/``v_cache_L*``
    relations, ``cache_len`` rows deep — rows ``[0, len(tokens))`` hold the
    prefix's K/V, rows beyond are stale and never read (causal masking).
    JAX functional updates make these genuinely immutable: a sequence that
    extends past the shared boundary appends through ``.at[].set`` /
    ``dynamic_update_slice``, which builds NEW arrays — copy-on-write on
    the first divergent append, with zero copies at bind time.
    """

    tokens: Tuple[int, ...]
    tables: Dict[str, object]  # table name -> DenseTable (immutable)
    nbytes: int
    refcount: int = 0
    last_use: int = 0

    def __hash__(self):  # identity: segments are interned by the cache
        return id(self)

    def __eq__(self, other):
        return self is other


class PrefixCacheStats:
    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0
        self.cached_tokens = 0  # total tokens served from cache


class PrefixCache:
    """Content-hash prefix index over KV segments (the relational analogue
    of vLLM-style automatic prefix caching).

    Prompts are hashed in ``block``-token chunks with a *chained* block
    hash (``h_i = hash((h_{i-1}, block_i))``), so a digest at boundary
    ``b`` commits to the entire prefix ``tokens[:b]``, not just the last
    block.  ``lookup`` walks the query's block boundaries deepest-first
    and returns the longest indexed prefix — verified token-exact against
    the segment's stored tokens, so a (vanishingly unlikely) digest
    collision can never splice wrong K/V rows into a sequence.

    Segments are refcounted (share-mode bindings pin them) and evicted
    LRU among ``refcount == 0`` segments when the store exceeds
    ``budget_bytes`` (or ``max_segments``) — dead segments only, mirroring
    the pager's rule that pinned working-set entries never evict.
    """

    def __init__(self, block: int = 16, budget_bytes: Optional[int] = None,
                 max_segments: Optional[int] = 32, metrics=None):
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        self.block = int(block)
        self.budget_bytes = budget_bytes
        self.max_segments = max_segments
        self.metrics = metrics
        self.stats = PrefixCacheStats()
        # chained digest -> list of (segment, boundary); a segment of
        # length L is indexed at every block boundary b <= L
        self._index: Dict[int, List[Tuple[CacheSegment, int]]] = {}
        self._segments: List[CacheSegment] = []
        self._tick = 0  # LRU clock (monotonic use counter)

    # -- hashing ----------------------------------------------------------

    def _boundaries(self, tokens) -> List[Tuple[int, int]]:
        """(boundary, chained digest) at every full block of ``tokens``."""
        out = []
        h = 0
        for i in range(0, len(tokens) - len(tokens) % self.block,
                       self.block):
            h = hash((h,) + tuple(tokens[i:i + self.block]))
            out.append((i + self.block, h))
        return out

    # -- metrics ----------------------------------------------------------

    def _count(self, outcome: str) -> None:
        if self.metrics is not None:
            self.metrics.counter(
                f"serving_prefix_cache_{outcome}_total",
                f"prefix cache {outcome}").inc()

    def _export_gauges(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge(
                "serving_prefix_cache_segments",
                "live KV segments in the prefix cache").set(
                    len(self._segments))
            self.metrics.gauge(
                "serving_prefix_cache_resident_bytes",
                "nominal bytes of live KV segments").set(
                    self.resident_bytes)

    @property
    def resident_bytes(self) -> int:
        return sum(s.nbytes for s in self._segments)

    # -- lookup / insert / refcounts --------------------------------------

    def lookup(self, tokens) -> Optional[Tuple[CacheSegment, int]]:
        """Longest cached prefix of ``tokens``, or None.

        The boundary is capped at ``len(tokens) - 1``: at least one prompt
        token must remain for the suffix prefill, whose final-position
        logits produce the sequence's first generated token.
        """
        tokens = list(tokens)
        for boundary, digest in reversed(self._boundaries(tokens)):
            if boundary >= len(tokens):
                continue
            for seg, b in self._index.get(digest, ()):
                if b == boundary and tuple(seg.tokens[:b]) == \
                        tuple(tokens[:b]):
                    self._tick += 1
                    seg.last_use = self._tick
                    self.stats.hits += 1
                    self.stats.cached_tokens += boundary
                    self._count("hits")
                    if self.metrics is not None:
                        self.metrics.histogram(
                            "serving_prefix_cached_tokens",
                            "tokens served from the prefix cache per hit",
                            buckets=CACHED_TOKEN_BUCKETS).observe(boundary)
                    return seg, boundary
        self.stats.misses += 1
        self._count("misses")
        return None

    def insert(self, tokens, env, nbytes: Optional[int] = None
               ) -> Optional[CacheSegment]:
        """Intern ``env``'s cache tables as a segment covering ``tokens``.

        Skipped when an already-indexed segment covers the deepest block
        boundary of ``tokens`` (inserting would add index weight without
        extending coverage).  The tables are shared by reference — O(1),
        no device copies (see :class:`CacheSegment` on why that is safe).
        """
        bounds = self._boundaries(tokens)
        if not bounds:
            return None
        deepest, digest = bounds[-1]
        for seg, b in self._index.get(digest, ()):
            if b == deepest and tuple(seg.tokens[:b]) == \
                    tuple(tokens[:b]):
                return None  # coverage already indexed
        tables = {nm: t for nm, t in env.items()
                  if nm.startswith(("k_cache_L", "v_cache_L"))}
        if nbytes is None:
            nbytes = sum(int(np.prod(t.cols[c].shape))
                         * jnp.dtype(t.cols[c].dtype).itemsize
                         for t in tables.values() for c in t.cols)
        self._tick += 1
        seg = CacheSegment(tokens=tuple(int(t) for t in tokens),
                           tables=tables, nbytes=int(nbytes),
                           last_use=self._tick)
        self._segments.append(seg)
        for boundary, digest in bounds:
            self._index.setdefault(digest, []).append((seg, boundary))
        self.stats.insertions += 1
        self._evict()
        self._export_gauges()
        return seg

    def acquire(self, seg: CacheSegment) -> None:
        seg.refcount += 1

    def release(self, seg: CacheSegment) -> None:
        assert seg.refcount > 0, "refcount underflow"
        seg.refcount -= 1
        # a just-released segment may unblock a pending eviction
        self._evict()
        self._export_gauges()

    # -- eviction ---------------------------------------------------------

    def _over_budget(self) -> bool:
        if self.max_segments is not None and \
                len(self._segments) > self.max_segments:
            return True
        return (self.budget_bytes is not None and
                self.resident_bytes > self.budget_bytes)

    def _evict(self) -> None:
        """Drop LRU dead (refcount-0) segments until within budget.  Live
        segments are pinned by their bindings and never evicted — the
        store may transiently exceed budget while every segment is live,
        exactly like pinned pages in the weight pager."""
        while self._over_budget():
            dead = [s for s in self._segments if s.refcount == 0]
            if not dead:
                return
            victim = min(dead, key=lambda s: s.last_use)
            self._segments.remove(victim)
            for entries in self._index.values():
                entries[:] = [(s, b) for s, b in entries if s is not victim]
            self.stats.evictions += 1
            self._count("evictions")
        self._export_gauges()


class BatchedCacheTables:
    """Seq-indexed views over the relational KV-cache *tables* for batched
    decode (the paper's §3.4 cache relations with a leading ``seq`` key).

    One device-resident pool per cache table holds ``max_seqs`` slots; the
    batched decode pipeline sees gathered ``(seq ∈ [B), …)`` table views,
    runs ONE plan for the whole batch, and the functionally-updated tables
    are scattered back into their slots.  Sequences join (``write_prefill``)
    and leave (``free``) without touching the other slots — and without any
    replanning, since the plan is keyed only by the batch size.

    The trailing key order is the planner-chosen cache layout (``layout``),
    matching the single-sequence prefill environments that fill the slots.
    """

    def __init__(self, spec, max_seqs: int, cache_len: int, chunk_size: int,
                 layout: str = "row_chunk"):
        from repro.core.llama_graph import empty_cache_tables
        self.max_seqs = max_seqs
        self.cache_len = cache_len
        self.tables = empty_cache_tables(spec, cache_len,
                                         chunk_size=chunk_size,
                                         layout=layout, batch=max_seqs)
        self.positions = np.zeros(max_seqs, np.int32)
        # per-slot generation counters: bumped on every slot mutation that
        # does NOT go through a decode tick (prefill fill, free).  Cached
        # batch views (BatchedDecoder) key on these, so view invalidation
        # fires even when a freed slot is reused by a NEW sequence through
        # pool-level writes the decoder never sees — same slot id, same
        # batch tuple, different contents.
        self.generations = np.zeros(max_seqs, np.int64)
        # share-mode prefix bindings: seq_id -> (CacheSegment, boundary).
        # A bound slot's pool rows are authoritative only for positions
        # >= boundary; gather_views splices the segment's rows below it.
        # The slot never writes below the boundary (decode appends land at
        # the sequence's position, >= its full prompt length > boundary),
        # so the shared segment arrays are never touched — copy-on-write
        # falls out of JAX's functional updates.
        self.bindings: Dict[int, Tuple[CacheSegment, int]] = {}

    def slot_generations(self, seq_ids) -> tuple:
        """Generation stamp of a batch of slots (view-cache key)."""
        return tuple(int(g) for g in
                     self.generations[np.asarray(seq_ids, np.int32)])

    def write_prefill(self, seq_id: int, env, length: int) -> None:
        """Copy a single-sequence session's cache tables into a slot —
        the WHOLE slot is overwritten, so slot reuse never depends on
        :meth:`free` having run.  Key orders are aligned by name (the
        session caches may carry a different planner layout)."""
        from repro.core.llama_graph import copy_cache_slot
        self.release_binding(seq_id)
        copy_cache_slot(self.tables, seq_id, env)
        self.positions[seq_id] = length
        self.generations[seq_id] += 1

    def write_suffix(self, seq_id: int, env, length: int, boundary: int,
                     pos_key: str = "tp") -> None:
        """Share-mode slot fill: copy only rows ``[boundary, cache_len)``
        of a (suffix-prefilled) session's cache tables into the slot —
        the relational ``INSERT ... SELECT ... WHERE tp >= boundary``.
        Rows below the boundary stay whatever the slot last held; they are
        shadowed by the bound segment at gather time
        (:meth:`gather_views`), never read directly."""
        from repro.core.executor import permute_table_keys
        for nm, dst in self.tables.items():
            src = permute_table_keys(env[nm], dst.key_names[1:])
            cn = next(iter(dst.cols))
            pax = dst.key_names[1:].index(pos_key)
            slot = jnp.moveaxis(dst.cols[cn][seq_id], pax, 0)
            rows = jnp.moveaxis(src.cols[cn], pax, 0)
            slot = slot.at[boundary:].set(
                rows[boundary:].astype(slot.dtype))
            dst.cols[cn] = dst.cols[cn].at[seq_id].set(
                jnp.moveaxis(slot, 0, pax))
        self.positions[seq_id] = length
        self.generations[seq_id] += 1

    def bind_segment(self, seq_id: int, segment: CacheSegment,
                     boundary: int) -> None:
        """Record a share-mode binding (caller holds the segment's ref)."""
        self.bindings[seq_id] = (segment, boundary)
        self.generations[seq_id] += 1

    def release_binding(self, seq_id: int) -> Optional[CacheSegment]:
        """Drop a slot's binding, returning the segment (for the caller to
        unref) or None.  Idempotent; called on free AND on slot refill so
        reuse never inherits a stale splice."""
        bound = self.bindings.pop(seq_id, None)
        if bound is None:
            return None
        self.generations[seq_id] += 1
        return bound[0]

    def free(self, seq_id: int) -> None:
        """Release a slot: reset its position.  This is state hygiene and
        observability, not a correctness requirement — stale rows are
        never read (gathers cover active slots only, and reads beyond a
        sequence's position are causally masked) and ``write_prefill``
        overwrites the whole slot on reuse; zeroing the device arrays
        here would cost 2·n_layers scatters per completion for nothing.

        NOTE: callers owning prefix-cache refs (``BatchedDecoder.free``)
        must release the slot's binding through their own path first;
        any binding still present here is dropped without unref."""
        self.release_binding(seq_id)
        self.positions[seq_id] = 0
        self.generations[seq_id] += 1

    def gather_views(self, seq_ids, pos_key: str = "tp"):
        """Batch views: {table: DenseTable keyed (seq ∈ [B), …)}.

        Duplicate ids are allowed (batch-size-bucket padding): the padded
        rows compute redundantly and scatter back identical values.

        Slots bound to a shared prefix segment (:meth:`bind_segment`) are
        *composed* here: the segment's rows ``[0, boundary)`` are spliced
        over the gathered slot at the position axis — the relational
        ``seq-view UNION segment rows re-keyed to this seq`` — so the
        batched plan sees one seamless seq-keyed table.  The splice writes
        into the freshly gathered batch copy, never into the pool or the
        segment; the decoder's generation-keyed view cache makes it a
        once-per-batch-change cost, not a per-tick one.
        """
        from repro.core.executor import DenseTable, permute_table_keys
        ids = np.asarray(seq_ids, np.int32)
        bound = [(b, int(s)) for b, s in enumerate(ids)
                 if int(s) in self.bindings]
        gathered = _slot_rows({name: next(iter(pool.cols.values()))
                               for name, pool in self.tables.items()}, ids)
        out = {}
        for name, pool in self.tables.items():
            cn = next(iter(pool.cols))
            arr = gathered[name]
            pax = pool.key_names.index(pos_key) - 1  # axis within a slot
            for b, sid in bound:
                seg, boundary = self.bindings[sid]
                src = permute_table_keys(seg.tables[name],
                                         pool.key_names[1:])
                row = jnp.moveaxis(arr[b], pax, 0)
                seg_rows = jnp.moveaxis(src.cols[cn], pax, 0)
                row = row.at[:boundary].set(
                    seg_rows[:boundary].astype(row.dtype))
                arr = arr.at[b].set(jnp.moveaxis(row, 0, pax))
            out[name] = DenseTable(
                keys=(("seq", len(ids)),) + pool.keys[1:],
                cols={cn: arr},
                col_types=dict(pool.col_types))
        return out

    def scatter(self, seq_ids, env) -> None:
        """Write updated batch views back into their slots (full tables).

        Reference/bulk path (tests, checkpoint-style state import) — the
        decode hot path uses :meth:`scatter_rows`, which writes back only
        the one row per sequence a tick appends."""
        ids = np.asarray(seq_ids, np.int32)
        for name, pool in self.tables.items():
            cn = next(iter(pool.cols))
            pool.cols[cn] = pool.cols[cn].at[ids].set(
                env[name].cols[cn].astype(pool.cols[cn].dtype))
        self.generations[ids] += 1  # external slot mutation: views go stale

    def scatter_rows(self, seq_ids, env, positions,
                     pos_key: str = "tp") -> None:
        """Write back only the rows a decode tick appended.

        A decode tick's sole cache mutation is one new row per sequence at
        ``(seq, positions[seq])``, so copying the full ``cache_len``-deep
        views back (:meth:`scatter`) is O(cache_len) wasted write traffic
        per tick — this extracts each sequence's appended row from the
        updated view and scatters just that, at the pool's (planner-chosen)
        position axis.  Duplicate ids (bucket padding) write identical
        values.
        """
        new = appended_rows(self.tables, env, seq_ids, positions, pos_key)
        for name, pool in self.tables.items():
            pool.cols.update(new[name].cols)


@jax.jit
def _slot_rows(cols, ids):
    """Each pool column's slots ``ids``: one program per batch size, where
    indexing the tables eagerly launches several programs per table."""
    return {name: arr[ids] for name, arr in cols.items()}


def appended_rows(tables, views, seq_ids, positions, pos_key: str = "tp"):
    """The pool ``tables`` with each sequence's row at ``(seq_ids[b],
    positions[b])`` taken from batch row ``b`` of the updated ``views``:
    a pure function, so it runs eagerly (:meth:`BatchedCacheTables.
    scatter_rows`) and inside the compiled decode tick alike."""
    from repro.core.executor import DenseTable
    ids = jnp.asarray(seq_ids, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    b_idx = jnp.arange(ids.shape[0])
    out = {}
    for name, pool in tables.items():
        cn = next(iter(pool.cols))
        pax = pool.key_names.index(pos_key)  # seq is axis 0
        upd = views[name].cols[cn].astype(pool.cols[cn].dtype)
        rows = jnp.moveaxis(upd, pax, 1)[b_idx, pos]
        p2 = jnp.moveaxis(pool.cols[cn], pax, 1)
        p2 = p2.at[ids, pos].set(rows)
        out[name] = DenseTable(keys=pool.keys,
                               cols={cn: jnp.moveaxis(p2, 1, pax)},
                               col_types=pool.col_types)
    return out
