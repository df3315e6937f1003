"""Observability layer (ISSUE 6): metrics registry, span tracing, DuckDB
profile parsing/attribution (against checked-in fixtures — no duckdb
import), drift reporting, statement provenance, and the plan-feedback
calibration source.  The duckdb-gated live-profile test rides in
``test_duckdb_e2e.py``."""

import json
import logging
import os
import sqlite3
import threading
import warnings

import numpy as np
import pytest

from repro.core.graph import infer_shapes
from repro.core.llama_graph import (LlamaSpec, build_decode_graph,
                                    convert_weights, empty_cache_tables,
                                    init_llama_params, rope_freq_table,
                                    token_table)
from repro.core.opmap import op_map
from repro.core.passes import postoptimize, preoptimize
from repro.core.pipeline import run_pipeline
from repro.core.sqlgen import generate_sql, generate_sql_with_provenance
from repro.obs import (MetricsRegistry, TraceRecorder, attribute_statement,
                       coverage, drift_report, flatten_profile,
                       parse_profile, run_timed, set_event_registry,
                       split_statements, substitute_params)
from repro.obs.dbtrace import TickTrace
from repro.obs.profile import classify_operator, scanned_table
from repro.planner.calibrate import (fit_from_step_timings,
                                     pipeline_features, step_features)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

SPEC = LlamaSpec(vocab=16, d_model=8, n_layers=1, n_heads=2, n_kv=1,
                 d_ff=16, rope_theta=10000.0)
CS = 4


def _decode_pipe(**post_kw):
    g = build_decode_graph(SPEC, cache_len=4)
    infer_shapes(g)
    preoptimize(g)
    pipe = op_map(g, chunk_size=CS)
    postoptimize(pipe, **post_kw)
    return pipe


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        r = MetricsRegistry()
        r.counter("reqs_total").inc()
        r.counter("reqs_total").inc(2)
        assert r.counter("reqs_total").value == 3
        with pytest.raises(ValueError):
            r.counter("reqs_total").inc(-1)
        r.gauge("occupancy").set(0.5)
        r.gauge("occupancy").inc(0.25)
        assert r.gauge("occupancy").value == 0.75
        h = r.histogram("lat_seconds")
        for v in (0.001, 0.002, 0.004, 0.1):
            h.observe(v)
        assert h.count == 4 and h.sum == pytest.approx(0.107)
        assert 0.001 <= h.percentile(50) <= 0.004
        assert h.mean == pytest.approx(0.107 / 4)

    def test_labels_create_separate_series(self):
        r = MetricsRegistry()
        r.counter("cache_total", outcome="hit").inc(3)
        r.counter("cache_total", outcome="miss").inc()
        assert r.counter("cache_total", outcome="hit").value == 3
        assert r.counter("cache_total", outcome="miss").value == 1

    def test_kind_conflict_rejected(self):
        r = MetricsRegistry()
        r.counter("x_total")
        with pytest.raises(ValueError):
            r.gauge("x_total")

    def test_prometheus_exposition(self):
        r = MetricsRegistry()
        r.counter("hits_total", "cache hits", cache="plan").inc(5)
        r.histogram("tick_seconds", "tick latency").observe(0.003)
        text = r.render_prometheus()
        assert '# TYPE hits_total counter' in text
        assert 'hits_total{cache="plan"} 5' in text
        assert '# TYPE tick_seconds histogram' in text
        # cumulative buckets: every bound >= 0.003 counts the observation
        assert 'tick_seconds_bucket{le="0.005"} 1' in text
        assert 'tick_seconds_bucket{le="0.001"} 0' in text
        assert 'tick_seconds_bucket{le="+Inf"} 1' in text
        assert 'tick_seconds_count 1' in text

    def test_histogram_exemplars_track_buckets(self):
        r = MetricsRegistry()
        h = r.histogram("ttft_seconds", buckets=(0.01, 0.1, 1.0))
        h.observe(0.005, exemplar="aaa0")
        h.observe(0.05, exemplar="bbb1")
        h.observe(0.06, exemplar="ccc2")   # same bucket: last wins
        h.observe(5.0, exemplar="ddd3")    # above every bound: +Inf
        h.observe(0.5)                     # no exemplar: leaves none
        assert h.exemplars[0][0] == "aaa0"
        assert h.exemplars[1][0] == "ccc2"
        assert h.exemplars[len(h.bounds)][0] == "ddd3"
        assert 2 not in h.exemplars

    def test_openmetrics_render_carries_exemplars_and_eof(self):
        r = MetricsRegistry()
        r.counter("reqs_total", "requests").inc(2)
        h = r.histogram("ttft_seconds", buckets=(0.01, 1.0))
        h.observe(0.005, exemplar="cafe1234")
        om = r.render_openmetrics()
        assert om.endswith("# EOF\n")
        line = next(l for l in om.splitlines()
                    if l.startswith('ttft_seconds_bucket{le="0.01"}'))
        assert '# {trace_id="cafe1234"} 0.005' in line
        # buckets without an exemplar render bare
        bare = next(l for l in om.splitlines()
                    if l.startswith('ttft_seconds_bucket{le="1.0"}'))
        assert "#" not in bare
        # the classic exposition stays exemplar- and EOF-free
        prom = r.render_prometheus()
        assert "cafe1234" not in prom and "# EOF" not in prom
        # ... and the series names line up between the two renders
        def names(text):
            return {l.split("{")[0].split()[0] for l in text.splitlines()
                    if l and not l.startswith("#")}
        assert names(prom) == names(om)

    def test_exemplars_survive_merge(self):
        parent = MetricsRegistry()
        child = MetricsRegistry()
        child.histogram("lat_seconds").observe(0.003, exemplar="feed1")
        parent.merge(child, shard=0)
        merged = parent.histogram("lat_seconds", shard="0")
        assert any(tid == "feed1"
                   for tid, _, _ in merged.exemplars.values())

    def test_json_dump_roundtrips(self, tmp_path):
        r = MetricsRegistry()
        r.histogram("h_seconds").observe(0.01)
        r.histogram("empty_seconds")  # no observations: no percentiles
        path = str(tmp_path / "metrics.json")
        r.save_json(path)
        with open(path) as f:
            dump = json.load(f)
        assert dump["h_seconds"][0]["p50"] == pytest.approx(0.01)
        assert "p50" not in dump["empty_seconds"][0]


class TestMetricsConcurrency:
    """Regression: the sharded worker pool mutates shared series from N
    threads; unlocked ``+=`` read-modify-writes drop increments."""

    def test_concurrent_writers_keep_exact_totals(self):
        r = MetricsRegistry()
        threads_n, iters = 8, 400
        barrier = threading.Barrier(threads_n)

        def hammer():
            barrier.wait()  # maximise interleaving
            for _ in range(iters):
                # re-fetch through the registry each time: the lookup
                # path (get-or-create under the registry lock) is part
                # of what the worker threads exercise
                r.counter("stress_total").inc()
                r.counter("stress_total", shard="x").inc(2)
                r.gauge("stress_gauge").inc(0.5)
                r.histogram("stress_seconds").observe(0.001)

        ts = [threading.Thread(target=hammer) for _ in range(threads_n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = threads_n * iters
        assert r.counter("stress_total").value == total
        assert r.counter("stress_total", shard="x").value == 2 * total
        assert r.gauge("stress_gauge").value == pytest.approx(0.5 * total)
        h = r.histogram("stress_seconds")
        assert h.count == total
        assert h.sum == pytest.approx(0.001 * total)
        # cumulative buckets stayed consistent under contention
        assert h.bucket_counts[h.bounds.index(0.001)] == total

    def test_merge_relabels_and_adds(self):
        parent = MetricsRegistry()
        parent.counter("ticks_total").inc(5)
        for i in range(2):
            child = MetricsRegistry()
            child.counter("ticks_total").inc(10 * (i + 1))
            child.gauge("busy_frac").set(0.25 * (i + 1))
            child.histogram("lat_seconds").observe(0.002 * (i + 1))
            parent.merge(child, shard=i)
        # the parent's own unlabelled series is untouched …
        assert parent.counter("ticks_total").value == 5
        # … and each child landed under its shard label
        assert parent.counter("ticks_total", shard="0").value == 10
        assert parent.counter("ticks_total", shard="1").value == 20
        assert parent.gauge("busy_frac", shard="1").value == 0.5
        h0 = parent.histogram("lat_seconds", shard="0")
        assert h0.count == 1 and h0.percentile(50) == \
            pytest.approx(0.002)
        # merging is additive: a second merge doubles the counter
        child = MetricsRegistry()
        child.counter("ticks_total").inc(10)
        parent.merge(child, shard="0")
        assert parent.counter("ticks_total", shard="0").value == 20

    def test_merge_rejects_mismatched_histogram_bounds(self):
        parent = MetricsRegistry()
        parent.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
        child = MetricsRegistry()
        child.histogram("lat_seconds", buckets=(0.2, 2.0)).observe(0.05)
        with pytest.raises(ValueError):
            parent.merge(child)


class TestTraceRecorder:
    def test_span_nesting_and_chrome_export(self):
        t = [0.0]

        def clock():
            t[0] += 0.001
            return t[0]

        rec = TraceRecorder(clock=clock)
        with rec.span("outer", cat="step"):
            with rec.span("inner", cat="op"):
                pass
        assert {e.name: e.depth for e in rec.events} == \
            {"outer": 0, "inner": 1}
        chrome = rec.to_chrome()
        assert all(ev["ph"] == "X" for ev in chrome["traceEvents"])
        # events sorted by start time: outer opened first
        assert chrome["traceEvents"][0]["name"] == "outer"

    def test_step_times_sum_per_name(self):
        rec = TraceRecorder()
        rec.add_span("a", "step", 0, 100)
        rec.add_span("a", "step", 200, 50)
        rec.add_span("b", "step", 300, 10)
        rec.add_span("x", "op", 0, 999)
        assert rec.step_times_us() == {"a": 150.0, "b": 10.0}
        assert rec.total_us("op") == 999.0


class TestProfileParser:
    @pytest.mark.parametrize("fixture,total", [
        ("duckdb_profile_legacy.json", 0.0042),
        ("duckdb_profile_modern.json", 0.0051),
    ])
    def test_both_key_formats_normalise(self, fixture, total):
        with open(os.path.join(FIXTURES, fixture)) as f:
            root = parse_profile(f.read())
        assert root.operator == "QUERY"
        assert root.timing_s == pytest.approx(total)
        ops = {n.operator for n in flatten_profile(root)}
        assert {"PROJECTION", "HASH_JOIN", "HASH_GROUP_BY"} <= ops
        scans = [n for n in flatten_profile(root)
                 if classify_operator(n.operator) == "scan"]
        assert {scanned_table(n) for n in scans} == {"W__col", "x_embed"}

    def test_bare_operator_tree_gets_query_root(self):
        root = parse_profile({"name": "PROJECTION", "timing": 0.1,
                              "cardinality": 1, "children": []})
        assert root.operator == "QUERY" and len(root.children) == 1

    def test_classify_refines_by_provenance(self):
        class Prov:
            kind = "append"
            quantised = ("lm_head",)
        assert classify_operator("PROJECTION") == "project"
        assert classify_operator("PROJECTION", Prov()) == "dequant_project"
        assert classify_operator("INSERT", Prov()) == "cache_append"
        assert classify_operator("TOTALLY_NEW_OP") == "other"

    def test_attribution_and_coverage(self):
        with open(os.path.join(FIXTURES,
                               "duckdb_profile_modern.json")) as f:
            root = parse_profile(f.read())

        class Prov:
            kind = "bind"
            step = "linear_1"
            quantised = ()
        attributed = attribute_statement(root, Prov())
        assert all(a.step == "linear_1" for a in attributed)
        # all operator time lands on a named step → full coverage
        assert coverage(attributed) == pytest.approx(1.0)
        # against a larger external wall clock, coverage drops
        assert coverage(attributed, total_s=1.0) < 0.01
        # unattributed statements (step=None) dilute coverage
        class NoStep:
            kind = "ddl"
            step = None
            quantised = ()
        mixed = attributed + attribute_statement(root, NoStep())
        assert coverage(mixed) == pytest.approx(0.5)


class TestStatementProvenance:
    def test_provenance_matches_plain_generate(self):
        pipe = _decode_pipe(layout_mode="col", cache_mode="auto")
        sql = generate_sql(pipe, dialect="duckdb", include_conversion=True)
        pairs = generate_sql_with_provenance(pipe, dialect="duckdb",
                                             include_conversion=True)
        assert sql == "\n\n".join(s for s, _ in pairs)

    def test_bind_steps_named_like_pipeline_steps(self):
        pipe = _decode_pipe(layout_mode="col", cache_mode="auto")
        pairs = generate_sql_with_provenance(pipe, dialect="duckdb")
        tagged = {p.step for _, p in pairs if p.kind in ("bind", "append")}
        assert tagged == {s.name for s in pipe.steps}
        binds = [p for _, p in pairs if p.kind == "bind"]
        assert all("scan" in p.ops for p in binds)
        appends = [p for _, p in pairs if p.kind == "append"]
        assert appends and all("cache_append" in p.ops for p in appends)

    def test_quantised_tables_tagged(self):
        pipe = _decode_pipe(precision_mode="int8")
        pairs = generate_sql_with_provenance(pipe, dialect="duckdb",
                                             include_conversion=True)
        quant_binds = [p for _, p in pairs
                       if p.kind == "bind" and p.quantised]
        assert quant_binds  # the dequant projections scan __int8 tables
        assert all(t.endswith("__int8")
                   for p in quant_binds for t in p.quantised)

    def test_table_mode_materialises_steps(self):
        pipe = _decode_pipe(layout_mode="col", cache_mode="auto")
        pairs = generate_sql_with_provenance(pipe, dialect="duckdb",
                                             step_create="TABLE")
        binds = [s for s, p in pairs if p.kind == "bind"]
        assert binds
        assert all(s.lstrip().startswith("CREATE OR REPLACE TABLE")
                   for s in binds)
        # default stays VIEW — golden snapshots elsewhere depend on it
        views = [s for s, p in generate_sql_with_provenance(
            pipe, dialect="duckdb") if p.kind == "bind"]
        assert all(s.lstrip().startswith("CREATE OR REPLACE VIEW")
                   for s in views)


class TestDbTraceSqlite:
    """Engine-independent pieces of dbtrace, driven through SQLite."""

    def test_split_statements_drops_comments(self):
        stmts = split_statements(
            "-- planner annotation\nCREATE TABLE t (a INT);\n"
            "-- another\nINSERT INTO t VALUES (1);")
        assert stmts == ["CREATE TABLE t (a INT);",
                         "INSERT INTO t VALUES (1);"]

    def test_substitute_params_word_boundary(self):
        out = substitute_params("p = :pos AND q = :pos2",
                                {"pos": 3, "pos2": 9})
        assert out == "p = 3 AND q = 9"

    def test_run_timed_attributes_statement_wall_time(self):
        class Prov:
            kind = "bind"
            step = "s1"
            tables = ("t",)
            ops = ("scan",)
            quantised = ()
        con = sqlite3.connect(":memory:")
        tick = run_timed(con, [
            ("CREATE TABLE t (a INT);\nINSERT INTO t VALUES (1), (2);",
             Prov()),
            ("SELECT COUNT(*) FROM t WHERE a > :lo;", Prov()),
        ], params={"lo": 0})
        assert len(tick.statements) == 3
        assert tick.coverage() == pytest.approx(1.0)
        assert set(tick.step_times_us()) == {"s1"}
        assert tick.step_times_us()["s1"] > 0
        # the SELECT's wall time was split over its EXPLAIN QUERY PLAN
        # rows (scan); the DDL statements fell back to one
        # op_class="statement" record each
        classes = tick.class_times_us()
        assert set(classes) == {"statement", "scan"}
        assert sum(classes.values()) == pytest.approx(tick.wall_s * 1e6)

    def test_run_timed_eqp_join_surfaces_operator_structure(self):
        class Prov:
            kind = "bind"
            step = "join_step"
            tables = ("w", "x")
            ops = ("join",)
            quantised = ()
        con = sqlite3.connect(":memory:")
        con.execute("CREATE TABLE w (k INT PRIMARY KEY, v REAL)")
        con.execute("CREATE TABLE x (k INT, v REAL)")
        con.executemany("INSERT INTO w VALUES (?, ?)",
                        [(i, float(i)) for i in range(8)])
        con.executemany("INSERT INTO x VALUES (?, ?)",
                        [(i % 4, 1.0) for i in range(16)])
        tick = run_timed(con, [(
            "SELECT w.k, SUM(w.v * x.v) FROM w JOIN x ON w.k = x.k "
            "GROUP BY w.k ORDER BY w.k;", Prov())])
        ops = tick.attributed
        assert all(a.step == "join_step" for a in ops)
        # SQLite's nested-loop join: first table term is the outer
        # scan, the second (same parent) is the join inner loop
        classes = {a.op_class for a in ops}
        assert "join" in classes
        assert classes & {"scan", "search"}
        tables = {a.table for a in ops if a.table}
        assert tables <= {"w", "x"} and len(tables) == 2
        # uniform split keeps the per-step total exact
        assert tick.step_times_us()["join_step"] == \
            pytest.approx(tick.wall_s * 1e6)
        assert tick.coverage() == pytest.approx(1.0)

    def test_run_timed_explain_off_restores_fallback(self):
        class Prov:
            kind = "bind"
            step = "s"
            tables = ()
            ops = ()
            quantised = ()
        con = sqlite3.connect(":memory:")
        con.execute("CREATE TABLE t (a INT)")
        tick = run_timed(con, [("SELECT * FROM t;", Prov())],
                         explain=False)
        assert [a.op_class for a in tick.attributed] == ["statement"]

    def test_classify_eqp_detail_variants(self):
        from repro.obs.profile import classify_eqp_detail
        assert classify_eqp_detail("SCAN t") == ("scan", "SCAN", "t")
        assert classify_eqp_detail("SCAN TABLE t") == ("scan", "SCAN", "t")
        assert classify_eqp_detail(
            "SEARCH w USING INTEGER PRIMARY KEY (rowid=?)",
            first_in_parent=False) == ("join", "SEARCH", "w")
        assert classify_eqp_detail(
            "USE TEMP B-TREE FOR ORDER BY")[0] == "sort"
        assert classify_eqp_detail("")[0] == "other"

    def test_tick_trace_exports(self, tmp_path):
        class Prov:
            kind = "bind"
            step = "s1"
            tables = ()
            ops = ()
            quantised = ()
        con = sqlite3.connect(":memory:")
        tick = run_timed(con, [("SELECT 1;", Prov())])
        chrome = tick.to_recorder().to_chrome()
        cats = {e["cat"] for e in chrome["traceEvents"]}
        assert "statement" in cats
        path = str(tmp_path / "tick.json")
        tick.save_json(path)
        with open(path) as f:
            dump = json.load(f)
        assert dump["coverage"] == pytest.approx(1.0)
        assert dump["statements"][0]["step"] == "s1"


class TestDriftReport:
    def test_on_model_run_has_unit_ratios(self):
        feats = {"a": (100.0, 10.0), "b": (200.0, 40.0), "c": (50.0, 5.0)}
        obs = {s: 2.0 * (r + 1.0 * g) + 7.0 for s, (r, g) in feats.items()}
        rep = drift_report(feats, obs)
        assert rep.scale_us == pytest.approx(2.0)
        assert rep.intercept_us == pytest.approx(7.0)
        assert rep.rms_rel_drift == pytest.approx(0.0, abs=1e-9)
        assert all(s.ratio == pytest.approx(1.0) for s in rep.steps)

    def test_off_model_step_surfaces_as_worst(self):
        feats = {"a": (100.0, 0.0), "b": (100.0, 0.0), "c": (100.0, 0.0),
                 "slow": (100.0, 0.0)}
        obs = {"a": 100.0, "b": 100.0, "c": 100.0, "slow": 400.0}
        rep = drift_report(feats, obs)
        assert rep.worst(1)[0].step == "slow"
        assert rep.rms_rel_drift > 0.3

    def test_unattributed_time_counted(self):
        rep = drift_report({"a": (10.0, 0.0)},
                           {"a": 10.0, "mystery": 90.0})
        assert rep.unattributed_us == pytest.approx(90.0)
        assert rep.total_observed_us == pytest.approx(100.0)

    def test_fixed_scale_measures_absolute_drift(self):
        feats = {"a": (100.0, 0.0), "b": (300.0, 0.0)}
        obs = {"a": 300.0, "b": 900.0}  # 3 µs/unit, calibrated at 1.5
        rep = drift_report(feats, obs, scale_us=1.5)
        assert all(s.ratio == pytest.approx(2.0) for s in rep.steps)

    def test_empty_features_yield_empty_report(self):
        rep = drift_report({}, {"x": 5.0})
        assert rep.steps == [] and rep.rms_rel_drift == 0.0
        assert rep.unattributed_us == pytest.approx(5.0)
        assert rep.total_observed_us == pytest.approx(5.0)

    def test_fully_disjoint_observation_is_all_unattributed(self):
        # the watchdog's worst window: observed step names share nothing
        # with the priced features (e.g. a renamed pipeline)
        rep = drift_report({"a": (10.0, 1.0)}, {"b": 7.0, "c": 3.0})
        assert rep.steps == [] and rep.scale_us == 0.0
        assert rep.unattributed_us == pytest.approx(10.0)

    def test_zero_unit_step_gets_inf_ratio_not_crash(self):
        # a lone step priced at zero cost units: the fitted prediction
        # is 0 µs, the ratio degrades to inf and drops out of the RMS
        rep = drift_report({"z": (0.0, 0.0)}, {"z": 4.0})
        assert rep.steps[0].ratio == float("inf")
        assert rep.rms_rel_drift == 0.0

    def test_zero_observed_times_fit_zero_scale(self):
        rep = drift_report({"a": (10.0, 0.0), "b": (20.0, 0.0)},
                           {"a": 0.0, "b": 0.0})
        assert rep.scale_us == 0.0
        assert all(s.ratio == float("inf") for s in rep.steps)
        assert rep.rms_rel_drift == 0.0


class TestTracedRunPipeline:
    def test_step_spans_cover_all_steps(self):
        pipe = _decode_pipe(layout_mode="col", cache_mode="auto")
        params = init_llama_params(SPEC, seed=0)
        env = convert_weights(params, chunk_size=CS)
        env.update(empty_cache_tables(SPEC, 4, chunk_size=CS))
        env["token_ids"] = token_table(np.asarray([5], np.int32))
        env["freq_each_token"] = rope_freq_table(
            np.asarray([0]), SPEC.head_dim, SPEC.rope_theta)
        tracer = TraceRecorder()
        outs, _ = run_pipeline(pipe, env, scalars={"cache_position": 0},
                               tracer=tracer)
        ref, _ = run_pipeline(pipe, env, scalars={"cache_position": 0})
        np.testing.assert_allclose(
            np.asarray(outs["logits"].cols["v"]),
            np.asarray(ref["logits"].cols["v"]), rtol=1e-6)
        times = tracer.step_times_us()
        assert set(times) == {s.name for s in pipe.steps}
        assert all(t > 0 for t in times.values())
        # executor op sub-spans nest under the step spans
        op_events = [e for e in tracer.events if e.cat == "op"]
        assert op_events and all(e.depth >= 1 for e in op_events)


class TestCalibrationFeedback:
    def test_step_features_sum_to_pipeline_features(self):
        feats = step_features(SPEC, "decode", 1, CS, "col", cache_len=4)
        assert feats  # matmul sites were priced
        assert pipeline_features(SPEC, "decode", 1, CS, "col",
                                 cache_len=4) == (
            sum(r for r, _ in feats.values()),
            sum(g for _, g in feats.values()))

    def test_fit_recovers_synthetic_group_weight(self):
        feats = step_features(SPEC, "decode", 1, CS, "col", cache_len=4)
        obs = {s: 3.0 * (r + 2.5 * g) + 11.0
               for s, (r, g) in feats.items()}
        fit = fit_from_step_timings(feats, obs)
        assert fit.params.group_weight == pytest.approx(2.5, rel=1e-6)
        assert fit.scale_us == pytest.approx(3.0, rel=1e-6)
        assert fit.n_points == len(feats)

    def test_underdetermined_fit_emits_fallback_event(self, caplog):
        reg = MetricsRegistry()
        set_event_registry(reg)
        try:
            with warnings.catch_warnings(), \
                    caplog.at_level(logging.WARNING, logger="repro.obs"):
                warnings.simplefilter("ignore")
                fit = fit_from_step_timings({"a": (1.0, 1.0)}, {"a": 5.0})
        finally:
            set_event_registry(None)
        from repro.planner.cost import CostParams
        assert fit.params.group_weight == CostParams().group_weight
        assert any("calibration_fallback" in r.getMessage()
                   for r in caplog.records)
        dump = reg.to_dict()["obs_events_total"]
        assert dump[0]["labels"] == {"event": "calibration_fallback"}
        assert dump[0]["value"] == 1.0


class TestServingMetricsSmoke:
    def test_engine_records_metrics_and_traces(self):
        params = init_llama_params(SPEC, seed=0)
        from repro.serving.engine import RelationalEngine
        reg = MetricsRegistry()
        tracer = TraceRecorder()
        eng = RelationalEngine(SPEC, params, chunk_size=CS, max_len=8,
                               metrics=reg, tracer=tracer)
        eng.generate([3, 5], max_new_tokens=3)
        dump = reg.to_dict()
        assert dump["engine_decode_step_seconds"][0]["count"] == 2
        plan_lookups = {tuple(sorted(e["labels"].items())): e["value"]
                        for e in dump["engine_plan_cache_total"]}
        assert plan_lookups[(("cache", "prefill"),
                             ("outcome", "miss"))] == 1.0
        # every prefill + decode step span is on the trace
        assert tracer.step_times_us()
        # disabled observability leaves no trace of itself
        eng2 = RelationalEngine(SPEC, params, chunk_size=CS, max_len=8)
        assert eng2.metrics is None and eng2.tracer is None

    def test_scheduler_metrics(self):
        from repro.serving.engine import RelationalEngine
        from repro.serving.kvcache import PagedKVCache, PagedKVConfig
        from repro.serving.scheduler import ContinuousBatcher, Request
        cfg = PagedKVConfig(n_layers=SPEC.n_layers, n_kv=SPEC.n_kv,
                            head_dim=SPEC.head_dim, page_size=4,
                            n_pages=16, max_pages_per_seq=4)
        kv = PagedKVCache(cfg, max_seqs=4)
        reg = MetricsRegistry()
        eng = RelationalEngine(SPEC, init_llama_params(SPEC, seed=0),
                               chunk_size=CS, max_len=8, metrics=reg)
        dec = eng.batched_decoder(max_seqs=4)

        def prefill(req, seq_id):
            kv.ensure_capacity(seq_id, len(req.prompt))
            return dec.prefill_ex(req.context, seq_id)

        sched = ContinuousBatcher(kv, prefill, dec.decode, max_batch=2,
                                  release_fn=dec.free, metrics=reg)
        for r in range(3):
            sched.submit(Request(rid=r, prompt=[1, 2], max_new_tokens=2))
        done = sched.run()
        assert len(done) == 3
        dump = reg.to_dict()
        assert dump["serving_ttft_seconds"][0]["count"] == 3
        assert dump["serving_completed_total"][0]["value"] == 3.0
        # the decoder's own histogram times each tick; the scheduler's
        # copy of it and the two gauges that restated the occupancy
        # gauge are gone
        assert dump["decoder_tick_seconds"][0]["count"] == \
            sched.stats.decode_steps
        assert not {"serving_tick_seconds", "serving_active_sequences",
                    "decoder_bucket_occupancy"} & set(dump)
        assert 0 < reg.gauge("serving_batch_occupancy").value <= 1.0
        # each request's second token came from a decode tick
        assert dump["serving_decoded_tokens_total"][0]["value"] == 3.0
        assert dump["serving_queue_wait_seconds"][0]["count"] == 3
        assert dump["serving_prefill_seconds"][0]["count"] == \
            sched.stats.prefills == 3
        for series in ("engine_dispatch_seconds", "engine_sync_seconds"):
            counts = {e["labels"]["phase"]: e["count"] for e in dump[series]}
            assert counts == {"prefill": sched.stats.prefills,
                              "decode": sched.stats.decode_steps}

    def test_pager_metrics_mirror_stats(self, tmp_path):
        from repro.serving.pager import WeightPager
        reg = MetricsRegistry()
        pager = WeightPager(64, policy="clock", metrics=reg)
        pager.add("a", np.zeros(8, np.float32))   # 32 B
        pager.add("b", np.zeros(8, np.float32))
        pager.add("c", np.zeros(8, np.float32))
        pager.get("a"); pager.get("b"); pager.get("a")  # hit
        pager.get("c")                                  # evicts
        assert reg.counter("pager_hits_total").value == pager.stats.hits
        assert reg.counter("pager_misses_total").value == \
            pager.stats.misses
        assert reg.counter("pager_evictions_total").value == \
            pager.stats.evictions > 0
        assert reg.gauge("pager_held_bytes").value == pager.held_bytes


class TestBenchmarkMetadata:
    def test_run_metadata_stamp(self):
        common = pytest.importorskip("benchmarks.common")
        payload = common.stamp({"results": []})
        meta = payload["run_metadata"]
        assert {"timestamp_utc", "python", "cpu_count",
                "duckdb"} <= set(meta)
        json.dumps(payload)  # JSON-serialisable
