"""Spans of the serving path on the profiler's clock.

``repro.obs.trace.traced`` names each phase once, where it happens: a
``jax.profiler.TraceAnnotation`` always, an attached recorder's span and
a histogram where the site has them.  These tests capture a profile of
the tiny HTTP server on the CPU and check that every scheduler, engine
and step span is there, nested as the serving path nests them; that the
step annotations never wait on the device; and that a jitted pipeline's
HLO names the step each operation came from.
"""

import asyncio
import gc
import glob
import os
import time
from collections import defaultdict

import jax
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
from repro.obs import MetricsRegistry, TraceRecorder
from repro.obs.trace import GcSpans, traced
from repro.serving import client
from repro.serving.engine import RelationalEngine
from repro.serving.kvcache import PagedKVCache, PagedKVConfig
from repro.serving.server import AsyncLLMServer, ServerConfig

TINY = LlamaSpec(vocab=16, d_model=8, n_layers=1, n_heads=2, n_kv=1,
                 d_ff=16, rope_theta=10000.0)
SERVED = LlamaSpec(vocab=64, d_model=32, n_layers=1, n_heads=4, n_kv=2,
                   d_ff=64, rope_theta=10000.0)
CS = 4


def _decode_pipe_and_env():
    g = build_decode_graph(TINY, cache_len=4)
    infer_shapes(g)
    preoptimize(g)
    pipe = op_map(g, chunk_size=CS)
    postoptimize(pipe, layout_mode="col", cache_mode="auto")
    env = convert_weights(init_llama_params(TINY, seed=0), chunk_size=CS)
    env.update(empty_cache_tables(TINY, 4, chunk_size=CS))
    env["token_ids"] = token_table(np.asarray([5], np.int32))
    env["freq_each_token"] = rope_freq_table(
        np.asarray([0]), TINY.head_dim, TINY.rope_theta)
    return pipe, env


def test_traced_feeds_the_recorder_span_and_the_histogram():
    tracer, reg = TraceRecorder(), MetricsRegistry()
    hist = reg.histogram("phase_seconds")
    with traced("decode.views", tracer.span("cache_views", cat="decoder",
                                            outcome="hit"), hist, rid=3):
        pass
    with traced("decode.sync"):  # the profiler alone
        pass
    [ev] = tracer.events
    assert (ev.name, ev.cat, ev.args) == ("cache_views", "decoder",
                                         {"outcome": "hit"})
    assert hist.count == 1 and hist.sum >= ev.dur_us / 1e6
    with pytest.raises(ValueError):
        with traced("decode.sync", hist=hist):
            raise ValueError("a failed phase is not observed")
    assert hist.count == 1


@pytest.mark.parametrize("with_recorder", [False, True])
def test_step_annotations_never_block(with_recorder, monkeypatch):
    pipe, env = _decode_pipe_and_env()
    ref, _ = run_pipeline(pipe, env, scalars={"cache_position": 0})
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    tracer = TraceRecorder() if with_recorder else None
    outs, _ = run_pipeline(pipe, env, scalars={"cache_position": 0},
                           tracer=tracer)
    # only an attached recorder waits on each step, as it always has
    assert len(calls) == (len(pipe.steps) if with_recorder else 0)
    np.testing.assert_array_equal(np.asarray(outs["logits"].cols["v"]),
                                  np.asarray(ref["logits"].cols["v"]))


def test_jitted_pipeline_names_each_step_in_its_hlo():
    pipe, env = _decode_pipe_and_env()

    def f(pos):
        outs, _ = run_pipeline(pipe, env, scalars={"cache_position": pos})
        return outs["logits"].cols["v"]

    txt = jax.jit(f).lower(0).as_text(debug_info=True)
    missing = [s.name for s in pipe.steps if f"jit(f)/{s.name}/" not in txt]
    # a step that only relabels its input stages no operation to name
    assert all(n.startswith(("rename_", "concat_rows_")) for n in missing)
    assert len(missing) < len(pipe.steps) // 4


def test_gc_spans_close_each_collection_and_remove():
    spans = GcSpans()
    spans.install()
    try:
        assert gc.callbacks.count(spans) == 1
        gc.collect()
        assert spans._open is None  # closed at the collection's end
    finally:
        spans.remove()
    assert spans not in gc.callbacks


# -- the served path under the profiler --------------------------------------


def _host_events(logdir):
    """{thread line id: [(name, start_ns, end_ns, stats)]} of the host."""
    [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    lines = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    lines[(plane.name, i)].append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         {k: v for k, v in e.stats}))
    return lines


def _inside(events, parent, name):
    _, s, e, _ = parent
    return [ev for ev in events
            if ev[0] == name and s <= ev[1] and ev[2] <= e]


def test_served_spans_nest_on_the_profiler_clock(tmp_path):
    engine = RelationalEngine(SERVED, init_llama_params(SERVED, seed=3),
                              chunk_size=8, residency="in_memory",
                              max_len=24)
    kv = PagedKVCache(PagedKVConfig(
        n_layers=SERVED.n_layers, n_kv=SERVED.n_kv,
        head_dim=SERVED.head_dim, page_size=4, n_pages=32,
        max_pages_per_seq=6), max_seqs=4)
    srv = AsyncLLMServer(engine, kv, ServerConfig(port=0, max_batch=2),
                         metrics=MetricsRegistry())
    # compile the decode tick of both batch buckets before the capture
    dec = srv.decoder
    toks = [dec.prefill([1, 2, 3], s) for s in range(2)]
    dec.decode([0], toks[:1])
    dec.decode([0, 1], toks)
    for s in range(2):
        dec.free(s)
    srv.start_in_thread()

    async def complete(n):
        return await asyncio.gather(*[client.request(
            srv.cfg.host, srv.port, "POST", "/v1/completions",
            {"prompt": [1, 2, 3 + i], "max_tokens": 3, "stream": False})
            for i in range(n)])

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        asyncio.run(complete(2))  # compiles outside the capture
        stats = srv.batcher.stats
        p0, d0 = stats.prefills, stats.decode_steps
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert all(r.status == 200 for r in asyncio.run(complete(2)))
            gc.collect()
            time.sleep(0.2)  # idle waits of the scheduler, ended in capture
        finally:
            jax.profiler.stop_trace()
        prefills, ticks = stats.prefills - p0, stats.decode_steps - d0
    finally:
        srv.shutdown()
    assert srv._gc_spans not in gc.callbacks

    lines = _host_events(str(tmp_path))
    everything = [ev for evs in lines.values() for ev in evs]
    assert any(ev[0] == "gc" and ev[3].get("generation") == 2
               for ev in everything)
    # the scheduler thread's line holds every scheduler span
    [sched] = [evs for evs in lines.values()
               if any(ev[0] == "sched.prefill" for ev in evs)]
    named = lambda n: [ev for ev in sched if ev[0] == n]  # noqa: E731
    assert len(named("sched.prefill")) == prefills == 2
    assert len(named("sched.decode")) == len(named("sched.emit")) == ticks
    assert ticks >= 2 and named("sched.wait")
    assert {ev[3]["rid"] for ev in named("sched.prefill")} == {2, 3}
    # an in-memory engine runs each decode tick and each cold prefill as
    # one compiled program, whose steps annotate the host only while it is
    # traced (the HLO names them,
    # test_jitted_pipeline_names_each_step_in_its_hlo)
    assert engine.runs_compiled
    n_steps = {"prefill": 0, "decode": 0}

    def one_each(parent, names):
        kids = [_inside(sched, parent, n) for n in names]
        assert [len(k) for k in kids] == [1] * len(names), names
        starts = [k[0][1] for k in kids]
        assert starts == sorted(starts)
        return [k[0] for k in kids]

    def tick_of(ev):
        [tick] = [t for t in named("sched.tick")
                  if t[1] <= ev[1] and ev[2] <= t[2]]
        return tick

    for pre in named("sched.prefill"):
        assert tick_of(pre)[3]["tick"] >= 1
        dispatch, _, _ = one_each(pre, ["prefill.dispatch", "prefill.sync",
                                        "prefill.cache_fill"])
        steps = [ev for ev in sched if ev[0].startswith("step.")
                 and dispatch[1] <= ev[1] and ev[2] <= dispatch[2]]
        assert len(steps) == n_steps["prefill"]
    for dec in named("sched.decode"):
        [emit] = _inside(sched, tick_of(dec), "sched.emit")
        assert emit[1] >= dec[2]
        _, dispatch, _, _ = one_each(dec, ["decode.views", "decode.dispatch",
                                           "decode.writeback", "decode.sync"])
        steps = [ev for ev in sched if ev[0].startswith("step.")
                 and dispatch[1] <= ev[1] and ev[2] <= dispatch[2]]
        assert len(steps) == n_steps["decode"]
