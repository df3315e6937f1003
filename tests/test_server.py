"""End-to-end tests for the OpenAI-compatible HTTP serving front end.

Boots a real ``AsyncLLMServer`` (own event loop thread, ephemeral port)
over the tiny relational engine and drives it with the stdlib asyncio
client — concurrent SSE streams, admission control, error envelopes and
the Prometheus scrape, all over real sockets.

Because decoding is greedy/deterministic, every streamed token sequence
is checked EXACTLY against the sequential ``engine.generate`` reference:
a duplicated, dropped or replayed token anywhere in the batched serving
path is a hard failure, not a flake.
"""

import asyncio
import contextlib
import threading
import time

import pytest

from repro.core.llama_graph import LlamaSpec, init_llama_params
from repro.obs import MetricsRegistry
from repro.serving import client
from repro.serving.engine import RelationalEngine
from repro.serving.kvcache import PagedKVCache, PagedKVConfig
from repro.serving.server import AsyncLLMServer, ServerConfig

run = asyncio.run

SPEC = LlamaSpec(vocab=64, d_model=32, n_layers=1, n_heads=4, n_kv=2,
                 d_ff=64, rope_theta=10000.0)


@pytest.fixture(scope="module")
def engine():
    return RelationalEngine(SPEC, init_llama_params(SPEC, seed=3),
                            chunk_size=8, residency="in_memory",
                            max_len=24)


@pytest.fixture(scope="module")
def traced_engine():
    """An engine with a TraceRecorder attached: the server drains its
    spans into the flight ring, so /debug/trace can attribute ticks."""
    from repro.obs import TraceRecorder
    return RelationalEngine(SPEC, init_llama_params(SPEC, seed=3),
                            chunk_size=8, residency="in_memory",
                            max_len=24, tracer=TraceRecorder())


@contextlib.contextmanager
def _server(engine, n_pages=32, max_batch=3, max_seqs=8, **cfg_kw):
    kvcfg = PagedKVConfig(n_layers=SPEC.n_layers, n_kv=SPEC.n_kv,
                          head_dim=SPEC.head_dim, page_size=4,
                          n_pages=n_pages, max_pages_per_seq=6)
    kv = PagedKVCache(kvcfg, max_seqs=max_seqs)
    cfg = ServerConfig(port=0, max_batch=max_batch, **cfg_kw)
    srv = AsyncLLMServer(engine, kv, cfg, metrics=MetricsRegistry())
    srv.start_in_thread()
    try:
        yield srv
    finally:
        srv.shutdown()


class TestStreamingE2E:
    def test_concurrent_streams_with_preemption_are_exact(self, engine):
        """The acceptance scenario: 8 concurrent SSE streams through ONE
        batched decode loop, page pool sized so preemption must happen,
        and every stream's tokens exactly match the sequential
        reference — zero duplicated or dropped tokens."""
        prompts = [[(3 * i + j) % SPEC.vocab for j in range(4 + i % 3)]
                   for i in range(8)]
        refs = [engine.generate(p, max_new_tokens=6).tokens
                for p in prompts]
        # every request grows to 3 pages (ctx reaches 9-12 tokens,
        # page_size 4) before finishing, so 3 lockstep seqs demand 9
        # pages — an 8-page pool MUST preempt mid-decode
        with _server(engine, n_pages=8, max_batch=3, max_seqs=8,
                     max_queue_depth=32) as srv:

            async def drive():
                return await asyncio.gather(*(
                    client.stream_completion(
                        srv.cfg.host, srv.port,
                        {"model": srv.cfg.model_id, "prompt": p,
                         "max_tokens": 6})
                    for p in prompts))

            results = run(drive())
            for i, res in enumerate(results):
                assert res.status == 200
                # SSE chunks in order, no gaps, no duplicates
                assert res.token_indices == list(range(6))
                # exact tokens: batched + preempted == sequential
                assert res.tokens == refs[i]
            # everything went through the one batched decode loop
            assert srv.batcher.stats.decode_steps > 0
            assert srv.decoder.decode_calls == srv.batcher.stats.decode_steps
            # the pool really was tight enough to preempt at least once
            assert srv.batcher.stats.preemptions > 0

    def test_metrics_scrape_reports_slo_histograms(self, engine):
        with _server(engine, n_pages=32, max_batch=3,
                     max_tokens_cap=8) as srv:

            async def drive():
                await asyncio.gather(*(
                    client.stream_completion(
                        srv.cfg.host, srv.port,
                        {"prompt": [1 + i, 2, 3], "max_tokens": 4})
                    for i in range(8)))
                # one admission reject so the counter series exists
                await client.request(
                    srv.cfg.host, srv.port, "POST", "/v1/completions",
                    {"prompt": [1, 2], "max_tokens": 99})
                return await client.request(srv.cfg.host, srv.port,
                                            "GET", "/metrics")

            resp = run(drive())
            assert resp.status == 200
            assert resp.headers["content-type"].startswith("text/plain")
            text = resp.body.decode()
            assert "serving_ttft_seconds_count" in text
            assert "serving_tpot_seconds_count" in text
            assert 'serving_admission_rejects_total{reason="token_budget"}' \
                in text
            # 8 streams → at least 8 TTFT observations
            count = [line for line in text.splitlines()
                     if line.startswith("serving_ttft_seconds_count")]
            assert count and float(count[0].split()[-1]) >= 8

    def test_saturation_yields_429_with_retry_after(self, engine):
        with _server(engine, max_batch=1, max_seqs=1, max_queue_depth=1,
                     retry_after_s=2.0) as srv:

            async def drive():
                return await asyncio.gather(*(
                    client.stream_completion(
                        srv.cfg.host, srv.port,
                        {"prompt": [5, 9, 2, 7], "max_tokens": 8})
                    for _ in range(6)))

            results = run(drive())
            ok = [r for r in results if r.status == 200]
            rejected = [r for r in results if r.status == 429]
            assert ok and rejected  # some served, some shed
            for r in rejected:
                assert r.headers.get("retry-after") == "2"
                assert r.error["error"]["code"] == "saturated"
            for r in ok:
                assert r.token_indices == list(range(8))
            scrape = run(client.request(srv.cfg.host, srv.port,
                                        "GET", "/metrics"))
            assert 'serving_admission_rejects_total{reason="queue_full"}' \
                in scrape.body.decode()


class TestHttpApi:
    def test_models_endpoint(self, engine):
        with _server(engine) as srv:
            resp = run(client.request(srv.cfg.host, srv.port,
                                      "GET", "/v1/models"))
            assert resp.status == 200
            data = resp.json()
            assert data["object"] == "list"
            assert data["data"][0]["id"] == srv.cfg.model_id

    def test_blocking_completion_matches_reference(self, engine):
        prompt = [5, 9, 2, 7]
        ref = engine.generate(prompt, max_new_tokens=5).tokens
        with _server(engine) as srv:
            resp = run(client.request(
                srv.cfg.host, srv.port, "POST", "/v1/completions",
                {"prompt": prompt, "max_tokens": 5, "stream": False}))
            assert resp.status == 200
            data = resp.json()
            assert data["object"] == "text_completion"
            assert data["choices"][0]["token_ids"] == ref
            assert data["choices"][0]["finish_reason"] == "length"
            assert data["usage"]["completion_tokens"] == 5
            assert data["usage"]["prompt_tokens"] == len(prompt)

    def test_chat_completions_stream(self, engine):
        with _server(engine) as srv:
            res = run(client.stream_completion(
                srv.cfg.host, srv.port,
                {"messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 4},
                path="/v1/chat/completions"))
            assert res.status == 200
            assert res.token_indices == list(range(4))
            assert res.events[0]["object"] == "chat.completion.chunk"
            for e in res.events:
                assert "delta" in e["choices"][0]
            # tokens match the reference over the ToyTokenizer encoding
            prompt = [ord(c) % SPEC.vocab for c in "hi"]
            assert res.tokens == engine.generate(
                prompt, max_new_tokens=4).tokens

    def test_max_tokens_cap_is_400(self, engine):
        with _server(engine, max_tokens_cap=8) as srv:
            resp = run(client.request(
                srv.cfg.host, srv.port, "POST", "/v1/completions",
                {"prompt": [1, 2], "max_tokens": 9}))
            assert resp.status == 400
            assert resp.json()["error"]["code"] == "max_tokens_cap"

    def test_context_length_cap_is_400(self, engine):
        with _server(engine, max_tokens_cap=64) as srv:
            # 20-token prompt + 16 new > max_len 24
            resp = run(client.request(
                srv.cfg.host, srv.port, "POST", "/v1/completions",
                {"prompt": list(range(20)), "max_tokens": 16}))
            assert resp.status == 400
            assert resp.json()["error"]["code"] == "context_length"

    def test_bad_prompt_is_400(self, engine):
        with _server(engine) as srv:
            resp = run(client.request(
                srv.cfg.host, srv.port, "POST", "/v1/completions",
                {"prompt": [], "max_tokens": 4}))
            assert resp.status == 400

    def test_unknown_route_is_404(self, engine):
        with _server(engine) as srv:
            resp = run(client.request(srv.cfg.host, srv.port,
                                      "GET", "/v1/nope"))
            assert resp.status == 404
            assert resp.json()["error"]["code"] == "not_found"

    def test_healthz(self, engine):
        with _server(engine) as srv:
            resp = run(client.request(srv.cfg.host, srv.port,
                                      "GET", "/healthz"))
            assert resp.status == 200
            assert resp.json()["status"] == "ok"

    def test_scheduler_failure_fails_requests(self, engine):
        """An exception out of a scheduler tick ends the in-flight stream
        with an error instead of hanging it, fails /healthz and rejects
        new requests."""
        with _server(engine) as srv:
            def decode_raises(seq_ids, last_tokens):
                raise RuntimeError("device lost")

            srv.batcher.decode_fn = decode_raises
            host, port = srv.cfg.host, srv.port
            res = run(client.stream_completion(
                host, port, {"prompt": [1, 2, 3], "max_tokens": 4},
                timeout_s=30))
            assert res.status == 200  # the prefill token went out first
            assert len(res.tokens) == 1
            assert res.error["error"]["code"] == "scheduler_failed"
            assert "device lost" in res.error["error"]["message"]
            health = run(client.request(host, port, "GET", "/healthz",
                                        timeout_s=30))
            assert health.status == 503
            assert health.json()["status"] == "failed"
            late = run(client.request(
                host, port, "POST", "/v1/completions",
                {"prompt": [1, 2], "max_tokens": 2}, timeout_s=30))
            assert late.status == 503


async def _raw_get(host, port, path, extra_headers=""):
    """GET with caller-controlled headers (the stdlib client pins its
    own header set, so content negotiation needs a raw request)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        req = (f"GET {path} HTTP/1.1\r\nHost: localhost\r\n"
               f"{extra_headers}Connection: close\r\n\r\n")
        writer.write(req.encode())
        await writer.drain()
        status_line = await reader.readline()
        status = int(status_line.split()[1])
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        body = await reader.read()
        return status, headers, body
    finally:
        writer.close()
        with contextlib.suppress(ConnectionError):
            await writer.wait_closed()


class TestDebugEndpoints:
    """ISSUE 10: the flight recorder's live debug surface plus the
    trace_id extension field and OpenMetrics content negotiation."""

    def test_trace_id_rides_every_response_shape(self, engine):
        with _server(engine) as srv:
            stream = run(client.stream_completion(
                srv.cfg.host, srv.port, {"prompt": [4, 2], "max_tokens": 3}))
            assert stream.status == 200
            tid = stream.trace_id
            assert tid and len(tid) == 16 and int(tid, 16) >= 0
            # one id per request, stamped on every chunk
            assert {e["trace_id"] for e in stream.events} == {tid}
            blocking = run(client.request(
                srv.cfg.host, srv.port, "POST", "/v1/completions",
                {"prompt": [4, 2], "max_tokens": 3, "stream": False}))
            assert len(blocking.json()["trace_id"]) == 16
            assert blocking.json()["trace_id"] != tid

    def test_debug_flight_and_trace_reconstruction(self, traced_engine):
        with _server(traced_engine) as srv:
            stream = run(client.stream_completion(
                srv.cfg.host, srv.port, {"prompt": [7, 1, 9],
                                         "max_tokens": 4}))
            assert stream.status == 200
            flight = run(client.request(srv.cfg.host, srv.port,
                                        "GET", "/debug/flight")).json()
            assert flight["retained_ticks"] > 0
            kinds = {t["kind"] for t in flight["ticks"]}
            assert {"admission", "prefill", "decode"} <= kinds
            # the streamed request reconstructs end to end by trace_id
            trace = run(client.request(
                srv.cfg.host, srv.port, "GET",
                f"/debug/trace/{stream.trace_id}"))
            assert trace.status == 200
            data = trace.json()
            assert data["trace_id"] == stream.trace_id
            tick_kinds = [t["kind"] for t in data["ticks"]]
            assert tick_kinds[0] == "admission"
            assert "prefill" in tick_kinds and "decode" in tick_kinds
            # spans drained from the engine tracer attribute the ticks
            assert data["wall_us"] > 0
            assert data["coverage"] > 0.5
            assert any(e["cat"] == "step" for e in data["traceEvents"])
            # the scheduler rid is an equally valid key
            rid = data["request_id"]
            assert run(client.request(
                srv.cfg.host, srv.port, "GET",
                f"/debug/trace/{rid}")).json()["trace_id"] == \
                stream.trace_id

    def test_debug_trace_unknown_id_is_404(self, engine):
        with _server(engine) as srv:
            resp = run(client.request(srv.cfg.host, srv.port, "GET",
                                      "/debug/trace/deadbeefdeadbeef"))
            assert resp.status == 404
            assert resp.json()["error"]["code"] == "trace_not_found"

    def test_debug_drift_disabled_and_enabled(self, engine):
        with _server(engine) as srv:
            off = run(client.request(srv.cfg.host, srv.port,
                                     "GET", "/debug/drift")).json()
            assert off["enabled"] is False
        with _server(engine, drift_every=500) as srv:
            assert srv.watchdog is not None
            on = run(client.request(srv.cfg.host, srv.port,
                                    "GET", "/debug/drift")).json()
            assert on["every"] == 500 and on["replans"] == 0
            assert on["engine_replans"] == engine.replans

    def test_metrics_content_negotiation(self, engine):
        with _server(engine) as srv:
            run(client.stream_completion(
                srv.cfg.host, srv.port, {"prompt": [1, 2],
                                         "max_tokens": 2}))
            # default: classic Prometheus exposition
            plain = run(client.request(srv.cfg.host, srv.port,
                                       "GET", "/metrics"))
            assert plain.headers["content-type"].startswith("text/plain")
            assert "# EOF" not in plain.body.decode()

            async def negotiate():
                via_query = await _raw_get(
                    srv.cfg.host, srv.port, "/metrics?format=openmetrics")
                via_accept = await _raw_get(
                    srv.cfg.host, srv.port, "/metrics",
                    "Accept: application/openmetrics-text; "
                    "version=1.0.0\r\n")
                return via_query, via_accept

            for status, headers, body in run(negotiate()):
                assert status == 200
                assert headers["content-type"].startswith(
                    "application/openmetrics-text")
                text = body.decode()
                assert text.endswith("# EOF\n")
                # the SLO histograms carry trace_id exemplars
                assert 'serving_ttft_seconds_bucket' in text
                assert '# {trace_id="' in text


class TestQueueWait:
    def test_wait_in_pending_counts_in_queue_wait_and_ttft(self, engine):
        """A request admitted over HTTP just as a scheduler tick starts
        waits in the server's pending queue through that whole tick.  Its
        queue wait holds the tick, and so does its TTFT, which starts at
        HTTP admission and not when the scheduler thread picks it up."""
        with _server(engine, max_batch=2) as srv:
            batcher = srv.batcher
            decode, tick = batcher.decode_fn, batcher.tick
            held = {}

            def slow_decode(seq_ids, last_tokens):
                time.sleep(0.3)
                return decode(seq_ids, last_tokens)

            def send_b():
                held["resp"] = run(client.request(
                    srv.cfg.host, srv.port, "POST", "/v1/completions",
                    {"prompt": [7, 1], "max_tokens": 2}))

            def tick_holding_b():
                if held or not (batcher.queue or batcher.active):
                    return tick()
                held["sender"] = threading.Thread(target=send_b)
                held["sender"].start()
                deadline = time.monotonic() + 30.0
                while not srv._pending:
                    assert time.monotonic() < deadline
                    time.sleep(0.001)
                held["req"] = srv._pending[0]
                t0 = time.perf_counter()
                more = tick()
                held["tick_s"] = time.perf_counter() - t0
                return more

            batcher.decode_fn, batcher.tick = slow_decode, tick_holding_b
            resp_a = run(client.request(
                srv.cfg.host, srv.port, "POST", "/v1/completions",
                {"prompt": [3, 5, 8], "max_tokens": 3}))
            held["sender"].join(timeout=60.0)
            assert resp_a.status == 200 and held["resp"].status == 200
            wait = srv.metrics.histogram("serving_queue_wait_seconds")
            ttft = srv.metrics.histogram("serving_ttft_seconds")
            assert wait.count == ttft.count == 2
            assert held["tick_s"] >= 0.3
            assert wait.percentile(100) >= held["tick_s"]
            assert held["req"].first_token_s >= wait.percentile(100)
            assert ttft.percentile(100) == held["req"].first_token_s
