"""Serving-layer units: pager behaviour, paged KV cache, continuous
batching scheduler with preemption, and the batched relational decode
path (one seq-keyed plan per scheduler tick)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.serving.kvcache import (BatchedCacheTables, PagedKVCache,
                                   PagedKVConfig)
from repro.serving.pager import WeightPager
from repro.serving.scheduler import ContinuousBatcher, Request


class TestPager:
    def test_clock_eviction_and_reuse(self):
        pager = WeightPager(budget_bytes=3 * 400)  # 3 × 100 f32
        for i in range(6):
            pager.add(f"w{i}", np.full(100, i, np.float32))
        for i in range(6):
            pager.get(f"w{i}")
        assert pager.stats.evictions >= 3
        assert pager.held_bytes <= 3 * 400
        # re-access: values still correct after paging back in
        arr = np.asarray(pager.get("w0"))
        np.testing.assert_array_equal(arr, np.full(100, 0, np.float32))

    def test_prefetch_counts_as_hit(self):
        pager = WeightPager(budget_bytes=1 << 20)
        pager.add("a", np.zeros(64, np.float32))
        t = pager.prefetch(["a"])
        t.join()
        pager.get("a")
        assert pager.stats.prefetch_hits == 1
        assert pager.stats.misses == 0

    def test_disk_tier_memmap(self, tmp_path):
        pager = WeightPager(budget_bytes=1 << 20,
                            disk_dir=str(tmp_path / "cold"))
        x = np.arange(32, dtype=np.float32)
        pager.add("w", x)
        assert isinstance(pager._cold["w"], np.memmap)
        np.testing.assert_array_equal(np.asarray(pager.get("w")), x)

    def test_prefetch_accounts_against_budget(self):
        """Regression (ISSUE 8): prefetched arrays live on device, so they
        must count toward the budget — aggressive prefetch used to hold
        budget + prefetched bytes silently."""
        pager = WeightPager(budget_bytes=2 * 400)  # room for 2 × 100 f32
        for i in range(3):
            pager.add(f"w{i}", np.full(100, i, np.float32))
        pager.prefetch(["w0", "w1", "w2"]).join()
        # the third entry is dropped rather than blowing the budget
        assert pager.held_bytes <= 2 * 400
        assert len(pager._prefetched) == 2
        # consuming a prefetched entry transfers ownership, not bytes
        pager.get("w0")
        assert pager.held_bytes <= 2 * 400
        assert pager.stats.prefetch_hits == 1
        # the dropped entry pages in through the ordinary miss path
        np.testing.assert_array_equal(np.asarray(pager.get("w2")),
                                      np.full(100, 2, np.float32))
        assert pager.stats.misses == 1
        assert pager.held_bytes <= 2 * 400

    def test_prefetch_evicts_hot_entries_to_fit(self):
        pager = WeightPager(budget_bytes=2 * 400)
        for k in ("a", "b", "c"):
            pager.add(k, np.full(100, ord(k), np.float32))
        pager.get("a")
        pager.get("b")
        assert pager.held_bytes == 2 * 400
        pager.prefetch(["c"]).join()
        assert "c" in pager._prefetched
        assert pager.held_bytes <= 2 * 400
        assert pager.stats.evictions >= 1

    def test_clock_hand_keeps_scan_position_after_eviction(self):
        """Regression (ISSUE 8): ``_clock.remove`` + reset-to-0 used to
        lose the CLOCK hand's scan position whenever the un-normalised
        hand pointed past the removed index, spuriously burning reference
        bits — a referenced entry could be evicted ahead of stale ones."""
        pager = WeightPager(budget_bytes=4 * 400, policy="clock")
        for k in "abcdefg":
            pager.add(k, np.full(100, ord(k), np.float32))
        for k in "abcd":
            pager.get(k)
        # refs as a scan pass might leave them; hand un-normalised from
        # second-chance skips (it only ever grew before the fix)
        pager._ref.update({"a": True, "b": False, "c": True, "d": False})
        pager._hand = 5
        for k in "efg":
            pager.get(k)
        # the unreferenced entries must go first; the referenced "a"
        # survives the three evictions
        assert "a" in pager._hot
        assert not {"b", "c", "d"} & set(pager._hot)


class TestPagedKV:
    def _cache(self):
        cfg = PagedKVConfig(n_layers=2, n_kv=2, head_dim=4, page_size=4,
                            n_pages=8, max_pages_per_seq=4)
        return PagedKVCache(cfg, max_seqs=3), cfg

    def test_append_gather_roundtrip(self):
        kv, cfg = self._cache()
        kv.allocate_seq(0)
        rng = np.random.default_rng(0)
        ks = rng.standard_normal((6, cfg.n_layers, cfg.n_kv, cfg.head_dim)
                                 ).astype(np.float32)
        for pos in range(6):
            kv.append(0, jnp.asarray(ks[pos]), jnp.asarray(ks[pos] * 2), pos)
        k, v, T = kv.gather(0, layer=1)
        assert T == 6
        np.testing.assert_allclose(np.asarray(k), ks[:, 1], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v), ks[:, 1] * 2, rtol=1e-6)

    def test_head_major_layout_roundtrip(self):
        """The planner's head_major cache layout: pages cluster by KV head;
        append/gather stay exact."""
        cfg = PagedKVConfig(n_layers=2, n_kv=2, head_dim=4, page_size=4,
                            n_pages=8, max_pages_per_seq=4,
                            layout="head_major")
        kv = PagedKVCache(cfg, max_seqs=3)
        assert kv.k_pool.shape == (2, 8, cfg.n_kv, cfg.page_size,
                                   cfg.head_dim)
        kv.allocate_seq(0)
        rng = np.random.default_rng(1)
        ks = rng.standard_normal((6, cfg.n_layers, cfg.n_kv, cfg.head_dim)
                                 ).astype(np.float32)
        for pos in range(6):
            kv.append(0, jnp.asarray(ks[pos]), jnp.asarray(ks[pos] * 2), pos)
        k, v, T = kv.gather(0, layer=1)
        assert T == 6
        np.testing.assert_allclose(np.asarray(k), ks[:, 1], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v), ks[:, 1] * 2, rtol=1e-6)
        # kernel consumers get the slot-major order regardless of layout
        kk, vk = kv.kernel_views(layer=1)
        assert kk.shape == (8, cfg.page_size, cfg.n_kv, cfg.head_dim)
        page0 = int(kv.page_table[0, 0])
        np.testing.assert_allclose(np.asarray(kk[page0]),
                                   ks[:4, 1].reshape(4, cfg.n_kv,
                                                     cfg.head_dim),
                                   rtol=1e-6)

    def test_unknown_layout_rejected(self):
        cfg = PagedKVConfig(n_layers=1, n_kv=1, head_dim=4, layout="bogus")
        with pytest.raises(ValueError):
            PagedKVCache(cfg, max_seqs=1)

    def test_page_reuse_after_free(self):
        kv, cfg = self._cache()
        kv.allocate_seq(0)
        kv.ensure_capacity(0, 16)  # all 4 pages
        free_before = kv.free_page_count()
        kv.free_seq(0)
        assert kv.free_page_count() == free_before + 4

    def test_pool_exhaustion_raises(self):
        kv, cfg = self._cache()
        for s in range(3):
            kv.allocate_seq(s)
        kv.ensure_capacity(0, 16)
        kv.ensure_capacity(1, 16)
        with pytest.raises(RuntimeError):
            kv.ensure_capacity(2, 16)  # only 8 pages in the pool


class TestScheduler:
    def _mk(self, n_pages=16, max_batch=3, **kwargs):
        cfg = PagedKVConfig(n_layers=1, n_kv=1, head_dim=4, page_size=4,
                            n_pages=n_pages, max_pages_per_seq=8)
        kv = PagedKVCache(cfg, max_seqs=8)

        def prefill(req, seq_id):
            # prefill over the full context (prompt + preserved generated
            # prefix) — the resume-not-replay protocol
            ctx = req.context
            kv.ensure_capacity(seq_id, len(ctx))
            return ctx[-1] + 1

        def decode(seq_ids, last):
            return [t + 1 for t in last]

        return (ContinuousBatcher(kv, prefill, decode, max_batch=max_batch,
                                  **kwargs), kv)

    def test_all_requests_complete(self):
        sched, kv = self._mk()
        for r in range(5):
            sched.submit(Request(rid=r, prompt=[1, 2, 3], max_new_tokens=4))
        done = sched.run()
        assert len(done) == 5
        for req in done:
            assert len(req.generated) == 4
            assert req.generated == [4, 5, 6, 7]
            assert req.first_token_s is not None
        # all pages returned
        assert kv.free_page_count() == kv.cfg.n_pages

    def test_continuous_admission(self):
        """New requests join while others are mid-generation."""
        sched, kv = self._mk(max_batch=2)
        for r in range(4):
            sched.submit(Request(rid=r, prompt=[1], max_new_tokens=6))
        ticks = 0
        while sched.tick():
            ticks += 1
            assert len(sched.active) <= 2
        assert sched.stats.completed == 4
        # iteration-level batching: far fewer ticks than sequential serving
        assert sched.stats.decode_steps < 4 * 6

    def test_preemption_on_pool_exhaustion(self):
        sched, kv = self._mk(n_pages=6, max_batch=3)
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1, 2, 3, 4], max_new_tokens=8))
        done = sched.run()
        assert len(done) == 3
        assert sched.stats.preemptions > 0
        for req in done:  # preempted requests still finish correctly
            assert len(req.generated) == 8

    def test_preemption_does_not_double_count_ttft(self):
        """Regression: a preempted request's re-prefill must keep the TTFT
        measured at its FIRST prefill — re-admission used to overwrite
        ``first_token_s`` with the (strictly later) re-prefill time."""
        sched, kv = self._mk(n_pages=6, max_batch=3)
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1, 2, 3, 4],
                                 max_new_tokens=8))
        first_seen = {}
        while sched.tick():
            for req in list(sched.active.values()) + sched.finished:
                if req.first_token_s is not None:
                    first_seen.setdefault(req.rid, req.first_token_s)
        done = sched.run()
        assert sched.stats.preemptions > 0
        preempted = [r for r in done if r.preemptions > 0]
        assert preempted  # the scenario really exercised a re-prefill
        for req in done:
            assert req.first_token_s == first_seen[req.rid]

    def test_max_new_tokens_one_completes_at_prefill(self):
        """Regression (ISSUE 8): the prefill token already satisfies
        ``max_new_tokens=1`` — waiting for a decode tick used to generate
        a second token."""
        sched, kv = self._mk()
        sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=1))
        done = sched.run()
        assert len(done) == 1
        assert done[0].generated == [4]          # exactly ONE token
        assert sched.stats.decode_steps == 0     # no decode tick needed
        assert kv.free_page_count() == kv.cfg.n_pages  # released at admit

    def test_one_token_request_rides_along_with_longer_ones(self):
        sched, kv = self._mk()
        sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=1))
        sched.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=4))
        done = {r.rid: r for r in sched.run()}
        assert done[0].generated == [3]
        assert done[1].generated == [3, 4, 5, 6]
        assert kv.free_page_count() == kv.cfg.n_pages

    def test_preemption_resumes_without_replaying_tokens(self):
        """Regression (ISSUE 8): preemption used to clear ``generated``
        and re-sample from the prompt — a streaming consumer saw the
        prefix re-generated.  The scheduler now preserves the delivered
        prefix and resumes decode after it: the on_token stream must be
        exactly the final generation, no token index emitted twice."""
        streamed = {}
        sched, kv = self._mk(
            n_pages=6, max_batch=3,
            on_token=lambda req, tok: streamed.setdefault(req.rid,
                                                          []).append(tok))
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1, 2, 3, 4],
                                 max_new_tokens=8))
        done = sched.run()
        assert sched.stats.preemptions > 0
        assert any(r.preemptions > 0 for r in done)
        for req in done:
            # exact resume: consecutive tokens, exactly max_new of them
            assert req.generated == list(range(5, 13))
            # the stream matches the final generation 1:1 — nothing was
            # re-emitted after a preemption round-trip
            assert streamed[req.rid] == req.generated

    def test_on_done_fires_once_per_request(self):
        finished = []
        sched, _ = self._mk(on_done=lambda req: finished.append(req.rid))
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1], max_new_tokens=2))
        sched.run()
        assert sorted(finished) == [0, 1, 2]

    def test_max_batch_above_kv_slots_rejected_at_construction(self):
        """Regression (ISSUE 8): this used to surface later as a bare
        StopIteration from the free-slot search in _admit."""
        cfg = PagedKVConfig(n_layers=1, n_kv=1, head_dim=4, page_size=4,
                            n_pages=16, max_pages_per_seq=8)
        kv = PagedKVCache(cfg, max_seqs=2)
        with pytest.raises(ValueError, match="max_seqs"):
            ContinuousBatcher(kv, lambda r, s: 0, lambda i, t: t,
                              max_batch=3)

    def test_admit_falls_back_when_slots_held_externally(self):
        """Even with max_batch == max_seqs, a KV slot held outside the
        scheduler must stall admission, not crash it."""
        cfg = PagedKVConfig(n_layers=1, n_kv=1, head_dim=4, page_size=4,
                            n_pages=16, max_pages_per_seq=8)
        kv = PagedKVCache(cfg, max_seqs=2)
        kv.allocate_seq(1)  # held by someone else (e.g. a pinned session)

        def prefill(req, seq_id):
            ctx = req.context
            kv.ensure_capacity(seq_id, len(ctx))
            return ctx[-1] + 1

        sched = ContinuousBatcher(kv, prefill,
                                  lambda ids, last: [t + 1 for t in last],
                                  max_batch=2)
        for r in range(2):
            sched.submit(Request(rid=r, prompt=[1, 2], max_new_tokens=3))
        done = sched.run()  # serialises through the single free slot
        assert len(done) == 2
        assert all(r.generated == [3, 4, 5] for r in done)

    def test_deadline_expired_victim_preempted_first(self):
        """SLO-aware preemption: the page-pressure victim is the request
        already past its deadline, not the youngest arrival."""
        sched, kv = self._mk(n_pages=6, max_batch=3)
        # three requests; rid 1 carries an SLO it has already blown by
        # the time pressure hits (deadline in the past)
        reqs = [Request(rid=r, prompt=[1, 2, 3, 4], max_new_tokens=8)
                for r in range(3)]
        reqs[1].ttft_slo_s = 1e-9      # expired ~immediately
        reqs[1].tpot_slo_s = 1e-9
        for r in reqs:
            sched.submit(r)
        done = {r.rid: r for r in sched.run()}
        assert sched.stats.preemptions > 0
        # the expired request absorbed the (first) preemptions
        assert done[1].preemptions > 0
        # and still completed correctly (resume semantics)
        assert done[1].generated == list(range(5, 13))


class TestPrefillKindStats:
    """ISSUE 9 satellite: ``stats.prefills`` split into cold / resume /
    prefix_hit, with the legacy aggregate preserved as a property."""

    def _mk(self, prefill, n_pages=16, max_batch=3, **kwargs):
        cfg = PagedKVConfig(n_layers=1, n_kv=1, head_dim=4, page_size=4,
                            n_pages=n_pages, max_pages_per_seq=8)
        kv = PagedKVCache(cfg, max_seqs=8)
        sched = ContinuousBatcher(kv, prefill,
                                  lambda ids, last: [t + 1 for t in last],
                                  max_batch=max_batch, **kwargs)
        return sched, kv

    def test_tuple_contract_splits_cold_vs_prefix_hit(self):
        def prefill(req, seq_id):
            ctx = req.context
            # rids 1 and 2 simulate a prefix-cache hit at admission
            return ctx[-1] + 1, (2 if req.rid in (1, 2) else 0)

        sched, kv = self._mk(prefill)
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1, 2, 3], max_new_tokens=4))
        done = {r.rid: r for r in sched.run()}
        assert sched.stats.prefills_cold == 1
        assert sched.stats.prefills_prefix_hit == 2
        assert sched.stats.prefills_resume == 0
        # back-compat aggregate is the sum of the split counters
        assert sched.stats.prefills == 3
        # the usage-reporting field lands on the request
        assert done[0].cached_tokens == 0
        assert done[1].cached_tokens == 2 and done[2].cached_tokens == 2
        # generation is unchanged by the tuple contract
        assert all(done[r].generated == [4, 5, 6, 7] for r in range(3))

    def test_resume_prefills_counted_as_resume_not_hit(self):
        """A preempted request's re-prefill is a *resume* even when the
        prefix cache covers its context; ``cached_tokens`` keeps the
        value recorded at FIRST admission."""
        def prefill(req, seq_id):
            ctx = req.context
            return ctx[-1] + 1, 1   # every prefill reports a cache hit

        sched, kv = self._mk(prefill, n_pages=6)
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1, 2, 3, 4],
                                 max_new_tokens=8))
        done = sched.run()
        assert sched.stats.preemptions > 0
        assert sched.stats.prefills_resume > 0
        assert sched.stats.prefills_prefix_hit == 3   # first admissions
        assert sched.stats.prefills_cold == 0
        assert sched.stats.prefills == \
            sched.stats.prefills_prefix_hit + sched.stats.prefills_resume
        for req in done:
            assert req.cached_tokens == 1
            assert req.generated == list(range(5, 13))

    def test_legacy_int_contract_still_counts_cold(self):
        def prefill(req, seq_id):
            return req.context[-1] + 1   # pre-ISSUE-9 int return

        sched, kv = self._mk(prefill)
        sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
        done = sched.run()
        assert sched.stats.prefills_cold == 1
        assert sched.stats.prefills == 1
        assert done[0].cached_tokens == 0


class TestBatchedRelationalDecode:
    """The tentpole: ONE seq-keyed relational plan advances the whole batch
    per scheduler tick — no per-sequence decode loop anywhere."""

    @pytest.fixture(scope="class")
    def engine(self):
        from repro.core.llama_graph import LlamaSpec, init_llama_params
        from repro.serving.engine import RelationalEngine
        spec = LlamaSpec(vocab=64, d_model=32, n_layers=1, n_heads=4,
                         n_kv=2, d_ff=64, rope_theta=10000.0)
        return RelationalEngine(spec, init_llama_params(spec, seed=3),
                                chunk_size=8, residency="in_memory",
                                max_len=24)

    def _serve(self, engine, prompts, max_new, max_batch=3):
        dec = engine.batched_decoder(max_seqs=4)
        cfg = PagedKVConfig(n_layers=1, n_kv=2,
                            head_dim=engine.spec.head_dim, page_size=8,
                            n_pages=32, max_pages_per_seq=4)
        kv = PagedKVCache(cfg, max_seqs=4)

        def prefill(req, seq_id):
            ctx = req.context
            kv.ensure_capacity(seq_id, len(ctx))
            return dec.prefill(ctx, seq_id)

        sched = ContinuousBatcher(kv, prefill, dec.decode,
                                  max_batch=max_batch, release_fn=dec.free)
        for r, p in enumerate(prompts):
            sched.submit(Request(rid=r, prompt=p, max_new_tokens=max_new))
        done = sched.run()
        return sched, dec, {r.rid: r.generated for r in done}

    def test_batched_serving_matches_sequential(self, engine):
        """Ragged prompts served through the batched plan generate exactly
        what B independent sequential runs generate."""
        prompts = [[5, 9, 2, 7], [1, 2, 3], [11, 4, 6, 8, 10]]
        refs = [engine.generate(p, max_new_tokens=4).tokens
                for p in prompts]
        sched, dec, got = self._serve(engine, prompts, max_new=4)
        for rid, ref in enumerate(refs):
            assert got[rid] == ref

    def test_one_plan_call_per_tick(self, engine):
        """decode_fn is ONE run_pipeline call regardless of batch size."""
        prompts = [[5, 9], [1, 2, 3], [7, 7]]
        sched, dec, _ = self._serve(engine, prompts, max_new=3)
        assert dec.decode_calls == sched.stats.decode_steps
        # iteration-level batching really shared ticks across sequences
        assert sched.stats.decode_steps < len(prompts) * 3

    def test_sessions_join_and_leave_without_replanning(self, engine):
        """Plans are cached per batch-size bucket: a serving run whose
        active batch fluctuates compiles at most one plan per bucket."""
        engine._batched_pipes.clear()
        prompts = [[5, 9], [1, 2, 3], [7, 7], [3, 4, 5]]
        sched, dec, _ = self._serve(engine, prompts, max_new=3)
        # cache keys are (batch_bucket, shards); this engine is unsharded
        buckets = set(engine._batched_pipes)
        assert buckets <= {(1, 1), (2, 1), (4, 1)}
        # rerunning the same shapes compiles nothing new
        n = len(engine._batched_pipes)
        self._serve(engine, prompts, max_new=3)
        assert len(engine._batched_pipes) == n

    def test_preemption_with_batched_decoder(self, engine):
        """Preempt-and-readmit through the real batched decoder: slot
        reuse (prefill over a freed slot) must invalidate the cached
        batch views, and every request must still generate exactly the
        sequential-reference tokens."""
        prompts = [[5, 9, 2, 7], [1, 2, 3, 4], [11, 4, 6, 8]]
        refs = [engine.generate(p, max_new_tokens=6).tokens
                for p in prompts]
        dec = engine.batched_decoder(max_seqs=4)
        cfg = PagedKVConfig(n_layers=1, n_kv=2,
                            head_dim=engine.spec.head_dim, page_size=4,
                            n_pages=6, max_pages_per_seq=6)
        kv = PagedKVCache(cfg, max_seqs=4)

        def prefill(req, seq_id):
            ctx = req.context
            kv.ensure_capacity(seq_id, len(ctx))
            return dec.prefill(ctx, seq_id)

        sched = ContinuousBatcher(kv, prefill, dec.decode, max_batch=3,
                                  release_fn=dec.free)
        for r, p in enumerate(prompts):
            sched.submit(Request(rid=r, prompt=p, max_new_tokens=6))
        done = sched.run()
        assert sched.stats.preemptions > 0
        got = {r.rid: r.generated for r in done}
        for rid, ref in enumerate(refs):
            assert got[rid] == ref

    def test_view_cache_invalidated_on_pool_level_slot_reuse(self, engine):
        """Regression (ISSUE 5 satellite): when a freed slot is reused by
        a NEW sequence through *pool-level* writes in the same tick —
        ``pool.free`` + ``pool.write_prefill``, never touching the
        decoder — the decoder's cached batch views must still be
        invalidated.  The old id-tuple cache key matched (same slots,
        same batch) and served the previous sequence's stale rows."""
        ref0 = engine.generate([5, 9, 2], max_new_tokens=3).tokens
        ref1 = engine.generate([7, 1, 4, 2], max_new_tokens=2).tokens

        dec = engine.batched_decoder(max_seqs=4)
        t0 = dec.prefill([5, 9, 2], 0)
        t1 = dec.prefill([1, 2, 3], 1)
        # one tick populates the decoder's cached views for slots (0, 1)
        step1 = dec.decode([0, 1], [t0, t1])
        assert step1[0] == ref0[1]
        # slot 1 leaves and is refilled by a NEW sequence via the pool
        # directly (a scheduler or state-import path the decoder can't
        # observe) — the ids tuple for the next tick is unchanged
        dec.pool.free(1)
        sess = engine.start_session([7, 1, 4, 2])
        dec.pool.write_prefill(1, sess["env"], 4)
        step2 = dec.decode([0, 1], [step1[0], sess["tok"]])
        # both sequences must decode against their OWN cache contents
        assert step2[0] == ref0[2]
        assert sess["tok"] == ref1[0] and step2[1] == ref1[1]

    def test_batched_cache_pool_roundtrip(self, engine):
        """Slot gather/scatter is exact and leaves other slots untouched."""
        pool = BatchedCacheTables(engine.spec, max_seqs=3,
                                  cache_len=engine.max_len, chunk_size=8,
                                  layout=engine.cache_layout)
        name = next(iter(pool.tables))
        cn = next(iter(pool.tables[name].cols))
        rng = np.random.default_rng(0)
        sess = engine.start_session([5, 9, 2])
        pool.write_prefill(1, sess["env"], 3)
        assert pool.positions[1] == 3
        views = pool.gather_views([1])
        np.testing.assert_array_equal(
            np.asarray(views[name].cols[cn][0]),
            np.asarray(sess["env"][name].cols[cn]))
        # scatter back modified rows; slot 0 stays zero
        views[name].cols[cn] = views[name].cols[cn] + 1.0
        pool.scatter([1], views)
        np.testing.assert_array_equal(
            np.asarray(pool.tables[name].cols[cn][0]), 0.0)
        # free releases the slot cheaply (position reset only; stale rows
        # are never read and write_prefill overwrites the slot on reuse)
        pool.free(1)
        assert pool.positions[1] == 0
        sess2 = engine.start_session([7, 1])
        pool.write_prefill(1, sess2["env"], 2)
        np.testing.assert_array_equal(
            np.asarray(pool.gather_views([1])[name].cols[cn][0]),
            np.asarray(sess2["env"][name].cols[cn]))


class TestPrefixCachedDecode:
    """ISSUE 9 tentpole: content-hash prefix cache over the batched cache
    pool — hits bind refcounted segments (copy or share mode) and prefill
    only the divergent suffix, token-exactly."""

    PREFIX = [5, 9, 2, 7, 11, 4, 6, 8]   # two full hash blocks (block=4)

    @pytest.fixture(scope="class")
    def engine(self):
        from repro.core.llama_graph import LlamaSpec, init_llama_params
        from repro.serving.engine import RelationalEngine
        spec = LlamaSpec(vocab=64, d_model=32, n_layers=1, n_heads=4,
                         n_kv=2, d_ff=64, rope_theta=10000.0)
        return RelationalEngine(spec, init_llama_params(spec, seed=3),
                                chunk_size=8, residency="in_memory",
                                max_len=24)

    def _decode_n(self, dec, sid, tok, n):
        toks = [tok]
        for _ in range(n - 1):
            tok = dec.decode([sid], [tok])[0]
            toks.append(tok)
        return toks

    @pytest.mark.parametrize("mode", ["copy", "share"])
    def test_hit_decode_token_exact(self, engine, mode):
        """Suffix-only prefill after a hit generates exactly the cold
        reference tokens, in both bind modes."""
        p1 = self.PREFIX + [1, 2]
        p2 = self.PREFIX + [3]
        ref = engine.generate(p2, max_new_tokens=4).tokens
        dec = engine.batched_decoder(max_seqs=2, prefix_block=4,
                                     prefix_bind=mode)
        t0, c0 = dec.prefill_ex(p1, 0)      # cold: interns the segment
        assert c0 == 0
        t1, c1 = dec.prefill_ex(p2, 1)      # hit on the shared prefix
        assert c1 == len(self.PREFIX)
        assert self._decode_n(dec, 1, t1, 4) == ref
        pc = dec.prefix_cache
        assert pc.stats.hits == 1 and pc.stats.misses == 1

    def test_shared_and_cold_slots_decode_together(self, engine):
        """A share-bound slot (spliced segment rows) and a cold slot decode
        in the same batched tick, each against its own cache contents."""
        p_cold = [3, 4, 5, 6]
        p_hit = self.PREFIX + [1]
        ref_cold = engine.generate(p_cold, max_new_tokens=4).tokens
        ref_hit = engine.generate(p_hit, max_new_tokens=4).tokens
        dec = engine.batched_decoder(max_seqs=3, prefix_block=4,
                                     prefix_bind="share")
        dec.prefill_ex(self.PREFIX + [2], 2)        # intern the segment
        ta, ca = dec.prefill_ex(p_cold, 0)
        tb, cb = dec.prefill_ex(p_hit, 1)
        assert ca == 0 and cb == len(self.PREFIX)
        got_a, got_b = [ta], [tb]
        for _ in range(3):
            ta, tb = dec.decode([0, 1], [ta, tb])
            got_a.append(ta)
            got_b.append(tb)
        assert got_a == ref_cold
        assert got_b == ref_hit

    def test_share_mode_refcounts_and_free(self, engine):
        dec = engine.batched_decoder(max_seqs=2, prefix_block=4,
                                     prefix_bind="share")
        dec.prefill_ex(self.PREFIX + [1], 0)
        _, cached = dec.prefill_ex(self.PREFIX + [2], 1)
        assert cached == len(self.PREFIX)
        seg, boundary = dec.pool.bindings[1]
        assert boundary == len(self.PREFIX)
        assert seg.refcount == 1            # pinned by the binding
        dec.free(1)
        assert 1 not in dec.pool.bindings   # binding dropped with the slot
        assert seg.refcount == 0            # unpinned -> evictable

    def test_eviction_skips_pinned_segments(self, engine):
        from repro.serving.kvcache import PrefixCache
        pc = PrefixCache(block=4, max_segments=1)
        p1, p2 = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
        seg1 = pc.insert(p1, engine.start_session(p1)["env"])
        pc.acquire(seg1)                 # pinned by a share-mode binding
        pc.insert(p2, engine.start_session(p2)["env"])
        # over budget: the dead newcomer is reclaimed at insert time; the
        # pinned segment never is (the pager's pinned-pages rule)
        assert pc.stats.evictions == 1 and len(pc._segments) == 1
        assert pc.lookup(p1) is not None
        assert pc.lookup(p2) is None

    def test_release_unblocks_pending_eviction(self, engine):
        from repro.serving.kvcache import PrefixCache
        pc = PrefixCache(block=4, max_segments=2)
        p1, p2 = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
        seg1 = pc.insert(p1, engine.start_session(p1)["env"])
        pc.acquire(seg1)
        seg2 = pc.insert(p2, engine.start_session(p2)["env"])
        pc.acquire(seg2)
        pc.max_segments = 1              # budget shrinks under live load
        pc._evict()
        assert pc.stats.evictions == 0   # all pinned: transient overflow
        pc.release(seg1)
        # the release unblocks eviction of the now-dead LRU segment
        assert pc.stats.evictions == 1 and len(pc._segments) == 1
        assert pc.lookup(p2) is not None
        pc.release(seg2)
        assert len(pc._segments) == 1    # within budget: nothing more

    def test_insert_dedupes_on_covered_prefix(self, engine):
        from repro.serving.kvcache import PrefixCache
        pc = PrefixCache(block=4)
        p = self.PREFIX + [1]
        env = engine.start_session(p)["env"]
        assert pc.insert(p, env) is not None
        assert pc.insert(p, env) is None    # same deepest block: skipped
        assert pc.stats.insertions == 1

    def test_disabled_cache_falls_back_to_cold(self, engine):
        ref = engine.generate(self.PREFIX + [1], max_new_tokens=3).tokens
        dec = engine.batched_decoder(max_seqs=1, prefix_block=0)
        assert dec.prefix_cache is None
        tok, cached = dec.prefill_ex(self.PREFIX + [1], 0)
        assert cached == 0
        assert self._decode_n(dec, 0, tok, 3) == ref


class TestCompiledDecodeTick:
    """An in-memory engine runs each batched decode tick as one compiled
    program per batch bucket, with the cache writeback inside it; paged,
    traced and sharded engines dispatch the plan eagerly.  Both give the
    same logits."""

    PREFIX = [5, 9, 2, 7, 11, 4, 6, 8]   # two full hash blocks (block=4)

    @pytest.fixture(scope="class")
    def params(self):
        from repro.core.llama_graph import init_llama_params
        return init_llama_params(self._spec(), seed=3)

    @staticmethod
    def _spec():
        from repro.core.llama_graph import LlamaSpec
        return LlamaSpec(vocab=64, d_model=32, n_layers=1, n_heads=4,
                         n_kv=2, d_ff=64, rope_theta=10000.0)

    def _engine(self, params, kind, metrics=None):
        from repro.obs import TraceRecorder
        from repro.serving.engine import RelationalEngine
        kw = {"paged": dict(residency="paged", budget_bytes=1 << 22),
              "traced": dict(tracer=TraceRecorder()),
              "sharded": dict(shards=2)}.get(kind, {})
        return RelationalEngine(self._spec(), params, chunk_size=8,
                                max_len=24, metrics=metrics, **kw)

    def _serve_script(self, eng):
        """Ticks over 4 slots, one of them bound to a shared prefix
        segment: the full batch twice (the second reuses its views), a
        smaller bucket, a bucket of one, then a slot reused by a new
        sequence in a padded bucket.  Returns each tick's logits."""
        dec = eng.batched_decoder(max_seqs=4, prefix_block=4,
                                  prefix_bind="share")
        prompts = [self.PREFIX + [1], [3, 4, 5], self.PREFIX + [2],
                   [7, 1, 4, 2, 9]]
        tok = {}
        for s, p in enumerate(prompts):
            tok[s], cached = dec.prefill_ex(p, s)
            assert cached == (len(self.PREFIX) if s == 2 else 0)
        assert 2 in dec.pool.bindings   # the share-bound slot
        got = []

        def tick(ids):
            out = dec.decode(ids, [tok[s] for s in ids])
            tok.update(zip(ids, out))
            got.append((ids, dec.last_logits))

        for ids in ([0, 1, 2, 3], [0, 1, 2, 3], [0, 2], [3]):
            tick(ids)
        dec.free(1)
        tok[1], _ = dec.prefill_ex([6, 6, 2, 8], 1)  # a new prefill length
        for ids in ([0, 1, 2], [0, 1, 2, 3]):
            tick(ids)
        return got

    def test_compiled_tick_matches_eager(self, params):
        """The compiled tick and the eager plan give the same logits, tick
        by tick, across a view reuse, bucket changes, a reused slot and a
        share-bound slot."""
        compiled = self._engine(params, "in_memory")
        eager = self._engine(params, "traced")
        assert (compiled.decode_path, eager.decode_path) == \
            ("compiled", "eager")
        for (ids, c), (_, e) in zip(self._serve_script(compiled),
                                    self._serve_script(eager)):
            assert c.shape == e.shape == (len(ids), 64)
            np.testing.assert_allclose(c, e, rtol=0, atol=1e-5)
            np.testing.assert_array_equal(c.argmax(1), e.argmax(1))

    @pytest.mark.parametrize("boundary", ["tables", "columns"])
    def test_jitted_batched_plan_matches_eager(self, params, boundary):
        """``run_pipeline`` over the batched plan under ``jax.jit`` gives
        the eager logits at 4 sequences.  Passed as plain column dicts
        (``columns``), the tables come back inside the jitted function
        with their columns in sorted-key order: the RoPE frequency table
        as ``(fi, fr)``, which the Scan has to read by name."""
        import jax
        from repro.core import llama_graph as lg
        from repro.core.executor import DenseTable
        from repro.core.pipeline import run_pipeline
        eng = self._engine(params, "in_memory")
        dec = eng.batched_decoder(max_seqs=4, prefix_block=0)
        prompts = [[5, 9, 2, 7], [1, 2, 3], [11, 4, 6, 8, 10], [3, 3]]
        toks = [dec.prefill(p, s) for s, p in enumerate(prompts)]
        positions = dec.pool.positions[:4].copy()
        env = eng._weights_env()
        env.update(dec.pool.gather_views([0, 1, 2, 3]))
        env["token_ids"] = lg.token_table(np.asarray(toks, np.int32),
                                          key="seq")
        env["freq_each_token"] = lg.rope_freq_table(
            positions, eng.spec.head_dim, eng.spec.rope_theta, key="seq")
        assert tuple(env["freq_each_token"].cols) == ("fr", "fi")
        pipe = eng._batched_decode_pipe(4)

        def logits(env, pos):
            outs, _ = run_pipeline(pipe, env, {"seq_positions": pos})
            return outs["logits"].cols["v"].reshape(4, -1)[:, :64]

        pos = jnp.asarray(positions, jnp.int32)
        want = np.asarray(logits(env, pos))
        if boundary == "tables":
            got = jax.jit(logits)(env, pos)
        else:
            def from_columns(cols, pos):
                return logits({n: DenseTable(keys=env[n].keys, cols=c,
                                             col_types=env[n].col_types)
                               for n, c in cols.items()}, pos)
            got = jax.jit(from_columns)(
                {n: t.cols for n, t in env.items()}, pos)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got).argmax(1),
                                      want.argmax(1))

    @pytest.mark.parametrize("kind,path", [("in_memory", "compiled"),
                                           ("paged", "eager"),
                                           ("traced", "eager"),
                                           ("sharded", "eager")])
    def test_decode_path_is_counted(self, params, kind, path):
        """``decoder_ticks_total`` counts each tick under the path it took;
        the compiled path compiles one program per bucket, however many
        ticks and view changes run in it."""
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        eng = self._engine(params, kind, metrics=reg)
        try:
            ticks = self._serve_script(eng)
        finally:
            if eng.shard_pool is not None:
                eng.shard_pool.shutdown()
        counted = {e["labels"]["path"]: e["value"]
                   for e in reg.to_dict()["decoder_ticks_total"]}
        assert counted == {path: len(ticks)}
        if path == "compiled":
            assert set(eng._decode_ticks) == {(4, 1), (2, 1), (1, 1)}
            # a table that a later plan adds to the weights leaves the
            # compiled ticks as they are
            eng.env_base["added_by_a_later_plan"] = eng.env_base["Final_Norm"]
            self._serve_script(eng)
            assert all(tick._cache_size() == 1
                       for tick, _ in eng._decode_ticks.values())
        else:
            assert not eng._decode_ticks


class TestCompiledPrefill:
    """An in-memory, untraced, unsharded engine runs each cold prefill as
    one compiled program per prompt-length bucket (``T`` rounded up to the
    chunk size, at most ``max_len``), the prompt padded at the end; suffix
    prefills of a prefix hit and paged, traced and sharded engines
    dispatch the plan eagerly.  Both give the same logits and cache rows
    for the prompt's tokens."""

    PREFIX = [5, 9, 2, 7, 11, 4, 6, 8]   # two full hash blocks (block=4)

    @staticmethod
    def _spec():
        from repro.core.llama_graph import LlamaSpec
        return LlamaSpec(vocab=64, d_model=32, n_layers=2, n_heads=4,
                         n_kv=2, d_ff=64, rope_theta=10000.0)

    @pytest.fixture(scope="class")
    def params(self):
        from repro.core.llama_graph import init_llama_params
        return init_llama_params(self._spec(), seed=5)

    def _engine(self, params, kind="in_memory", metrics=None, max_len=24):
        from repro.obs import TraceRecorder
        from repro.serving.engine import RelationalEngine
        kw = {"paged": dict(residency="paged", budget_bytes=1 << 22),
              "traced": dict(tracer=TraceRecorder()),
              "sharded": dict(shards=2)}.get(kind, {})
        return RelationalEngine(self._spec(), params, chunk_size=8,
                                max_len=max_len, metrics=metrics, **kw)

    @staticmethod
    def _cache_rows(env, T):
        """Each cache table's rows at positions ``< T``, positions first."""
        out = {}
        for name, t in env.items():
            if name.startswith(("k_cache_L", "v_cache_L")):
                [col] = t.cols.values()
                ax = t.key_names.index("tp")
                out[name] = np.moveaxis(np.asarray(col), ax, 0)[:T]
        return out

    @pytest.mark.parametrize("T", [8, 16, 5, 13])
    def test_compiled_prefill_matches_eager(self, params, T):
        """Last-row logits and the cache rows of the prompt's tokens, for
        a prompt that fills its bucket (8, 16) and for one that pads."""
        compiled = self._engine(params)
        eager = self._engine(params, "traced")
        assert compiled.runs_compiled and not eager.runs_compiled
        prompt = [int(t) for t in np.random.default_rng(T).integers(0, 64, T)]
        c, e = compiled.start_session(prompt), eager.start_session(prompt)
        assert c["logits"].shape == e["logits"].shape == (64,)
        np.testing.assert_allclose(c["logits"], e["logits"], rtol=0,
                                   atol=1e-5)
        assert c["tok"] == e["tok"] and c["pos"] == e["pos"] == T
        want = self._cache_rows(e["env"], T)
        got = self._cache_rows(c["env"], T)
        assert set(got) == set(want) and len(got) == 4
        for name in want:
            np.testing.assert_allclose(got[name], want[name], rtol=0,
                                       atol=1e-5)
        assert set(compiled._prefill_ticks) == {
            (compiled._prefill_bucket(T), 1)}
        assert not eager._prefill_ticks

    def _serve(self, eng):
        """Prefills through ``prefill_ex`` (padded, whole-bucket and a
        prefix hit), then decode ticks over changing batches; returns
        every token served."""
        dec = eng.batched_decoder(max_seqs=4, prefix_block=4)
        prompts = [self.PREFIX + [1], [3, 4, 5], self.PREFIX + [2],
                   [7, 1, 4, 2, 9, 3, 8, 6]]
        tok, served = {}, []
        for s, p in enumerate(prompts):
            tok[s], cached = dec.prefill_ex(p, s)
            assert cached == (len(self.PREFIX) if s == 2 else 0)
            served.append(tok[s])
        for ids in ([0, 1, 2, 3], [0, 1, 2, 3], [1, 3], [0, 2, 3],
                    [0, 1, 2, 3]):
            out = dec.decode(ids, [tok[s] for s in ids])
            tok.update(zip(ids, out))
            served += out
        return served

    def test_served_tokens_match_eager(self, params):
        """``prefill_ex`` then decode ticks serve the same tokens on both
        paths, a padded prompt and a suffix prefill over a compiled
        prefill's cache tables among them."""
        assert self._serve(self._engine(params)) == \
            self._serve(self._engine(params, "traced"))

    @pytest.mark.parametrize("max_len,T,bucket", [
        (24, 1, 8), (24, 8, 8), (24, 9, 16), (24, 17, 24), (24, 24, 24),
        (20, 13, 16), (20, 17, 20), (20, 20, 20)])
    def test_bucket_rule(self, params, max_len, T, bucket):
        """``T`` rounded up to the chunk size (8), at most ``max_len``."""
        eng = self._engine(params, max_len=max_len)
        assert eng._prefill_bucket(T) == bucket
        prompt = list(range(1, T + 1))
        np.testing.assert_allclose(
            eng.start_session(prompt)["logits"],
            self._engine(params, "traced", max_len=max_len).start_session(
                prompt)["logits"], rtol=0, atol=1e-5)
        assert set(eng._prefill_ticks) == {(bucket, 1)}

    def test_one_program_per_bucket(self, params):
        """Prompts of several lengths inside one bucket share one program;
        a table a later plan adds to the weights leaves it as it is."""
        eng = self._engine(params)
        for T in (9, 12, 16, 10):
            eng.start_session(list(range(3, 3 + T)))
        eng.env_base["added_by_a_later_plan"] = eng.env_base["Final_Norm"]
        eng.start_session(list(range(11)))
        [(key, (prefill, _))] = eng._prefill_ticks.items()
        assert key == (16, 1)
        assert prefill._cache_size() == 1

    @pytest.mark.parametrize("kind", ["paged", "traced", "sharded",
                                      "suffix"])
    def test_eager_paths(self, params, kind):
        """Paged, traced and sharded engines prefill op by op, and so does
        the suffix prefill of a prefix hit on an engine that compiles its
        cold prefills."""
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        eng = self._engine(params, "in_memory" if kind == "suffix" else kind,
                           metrics=reg)
        try:
            dec = eng.batched_decoder(max_seqs=2, prefix_block=4)
            dec.prefill_ex(self.PREFIX + [1], 0)
            _, cached = dec.prefill_ex(self.PREFIX + [2], 1)
        finally:
            if eng.shard_pool is not None:
                eng.shard_pool.shutdown()
        assert cached == len(self.PREFIX)
        counted = {e["labels"]["path"]: e["value"]
                   for e in reg.to_dict()["engine_prefill_total"]}
        if kind == "suffix":
            assert counted == {"compiled": 1, "eager": 1}
            assert set(eng._prefill_ticks) == {(16, 1)}
        else:
            assert counted == {"eager": 2}
            assert not eng._prefill_ticks
        assert reg.counter("engine_prefill_padded_tokens_total").value == \
            (16 - 9 if kind == "suffix" else 0)

    def test_series_count_a_hand_worked_sequence(self, params):
        """Cold prefills of 3, 8, 10 and 17 tokens pad 5, 0, 6 and 7 rows;
        ``generate`` prefills once more; a prefix hit runs eagerly."""
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        eng = self._engine(params, metrics=reg)
        for T in (3, 8, 10, 17):
            eng.start_session(list(range(2, 2 + T)))
        eng.generate([4, 4, 4], max_new_tokens=2)
        dec = eng.batched_decoder(max_seqs=2, prefix_block=4)
        dec.prefill_ex(self.PREFIX, 0)
        dec.prefill_ex(self.PREFIX + [3, 3], 1)
        counted = {e["labels"]["path"]: e["value"]
                   for e in reg.to_dict()["engine_prefill_total"]}
        assert counted == {"compiled": 6, "eager": 1}
        assert reg.counter("engine_prefill_padded_tokens_total").value == \
            5 + 0 + 6 + 7 + 5 + 0
