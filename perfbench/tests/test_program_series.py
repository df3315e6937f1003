"""CPU test that the program's own serving series agree with what the
benchmark's wrapper around the scheduler's callbacks records, in a traced
rehearsal: a later reader can take ``serving_prefill_seconds`` and
``serving_decoded_tokens_total`` in place of the wrapper."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import spec  # noqa: E402

SECONDS = 3.0


def test_program_series_match_the_wrapper_in_a_traced_rehearsal(
        monkeypatch):
    from perfbench import run as bench
    from perfbench import serve
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    seed = 2 ** 33 + 29
    cell = spec.load_cell("mistral-7b.batch", rehearse=True)
    compiles = bench.CompileCounter()
    server, recorder = bench.build(cell, bench.reference_model(cell), seed,
                                   compiles)
    tracer = bench.Tracer(SECONDS / 2)
    server.start_in_thread()
    try:
        bench.window(server, recorder, cell, seed, SECONDS, compiles, tracer)
    finally:
        serve.stop(server)
    assert tracer.reduce()["window_s"] > 0
    metrics = server.metrics
    prefill = metrics.histogram("serving_prefill_seconds")
    spent = sum(t1 - t0 for t0, t1, _ in recorder.prefills)
    assert prefill.count == len(recorder.prefills) > 0
    assert prefill.sum == pytest.approx(spent, rel=0.05)
    assert metrics.histogram("serving_queue_wait_seconds").count == \
        len(recorder.prefills)
    assert metrics.counter("serving_decoded_tokens_total").value == \
        sum(t[2] for t in recorder.ticks) > 0
