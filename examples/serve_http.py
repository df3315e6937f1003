"""Boot the OpenAI-compatible HTTP front end.

    PYTHONPATH=src python examples/serve_http.py [--port 8008]
        [--max-batch 3] [--paged] [--n-pages 48] [--max-queue 32]
        [--model tiny|<registry name>|<config.json>] [--layers N]
        [--max-len 96]

The default is the tiny model.  ``--model llama3-8b --layers 4`` serves
the registered Llama-3-8B at its published widths with the depth cut to 4
layers (random weights from seed 0); such a model wants a TPU, a larger
``--max-len``/``--n-pages`` and a larger ``--max-tokens-cap``.  The path
of a published ``config.json`` serves that model, read by its
``model_type`` (``perfbench/configs/deepseek-moe-16b.json`` is
DeepSeekMoE-16B with 16 of its 64 experts held).  JAX's
compile cache goes to ``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``
in the checkout.

Then drive it with curl:

    curl -s localhost:8008/v1/models
    curl -s localhost:8008/v1/completions -d '{
        "model": "transql-tiny", "prompt": [5, 9, 2, 7],
        "max_tokens": 6, "stream": true}'
    curl -s localhost:8008/metrics | grep serving_ttft

or with the load generator (``examples/load_client.py``), which also
verifies SSE chunk ordering and token exactness under concurrency.

With ``OBS_ARTIFACT_DIR`` set, shutdown (Ctrl-C or
``POST /admin/shutdown``) dumps the metrics registry (JSON + Prometheus
text), the flight recorder's Chrome trace and the flight dump there —
what the CI serving job uploads as artifacts.

Live debugging (no artifacts needed): ``GET /debug/flight`` for the
recent-tick ring, ``GET /debug/trace/{trace_id}`` for one request's
end-to-end Chrome trace (the id rides on every response / SSE chunk),
``GET /debug/drift`` for the watchdog state (``--drift-every N``
enables mid-flight re-planning on cost-model drift).
"""

import argparse
import asyncio
import contextlib
import json
import os
import tempfile

from repro.core.llama_graph import init_llama_params
from repro.obs import MetricsRegistry, TraceRecorder
from repro.serving.boot import build_server, enable_compile_cache, model_spec
from repro.serving.server import ServerConfig


def make_server(args, metrics, tracer, disk_dir=None):
    spec = model_spec(args.model, args.layers)
    params = init_llama_params(spec, seed=0)
    cfg = ServerConfig(host=args.host, port=args.port,
                       model_id=("transql-tiny" if args.model == "tiny"
                                 else args.model),
                       max_batch=args.max_batch,
                       max_queue_depth=args.max_queue,
                       max_tokens_cap=args.max_tokens_cap,
                       ttft_slo_s=args.ttft_slo_ms / 1e3
                       if args.ttft_slo_ms else None,
                       tpot_slo_s=args.tpot_slo_ms / 1e3
                       if args.tpot_slo_ms else None,
                       flight_capacity=args.flight_capacity,
                       drift_every=args.drift_every,
                       drift_threshold=args.drift_threshold)
    return build_server(spec, params, cfg, max_len=args.max_len,
                        n_pages=args.n_pages, paged=args.paged,
                        disk_dir=disk_dir, metrics=metrics, tracer=tracer)


def dump_artifacts(server, metrics, tracer, out: str) -> None:
    os.makedirs(out, exist_ok=True)
    metrics.save_json(os.path.join(out, "serve_http_metrics.json"))
    with open(os.path.join(out, "serve_http_metrics.prom"), "w") as f:
        f.write(metrics.render_prometheus())
    # the scheduler drains the tracer into the flight recorder per tick,
    # so the flight ring (not the tracer) holds the retained spans: the
    # Chrome trace artifact is its interleaved timeline, and the flight
    # dump is the same structure /debug/flight serves
    flight = getattr(server, "flight", None)
    if flight is not None:
        flight.save(os.path.join(out, "serve_http_trace.json"))
        with open(os.path.join(out, "serve_http_flight.json"), "w") as f:
            json.dump(flight.to_dict(), f, default=str)
    elif tracer is not None:
        with open(os.path.join(out, "serve_http_trace.json"), "w") as f:
            json.dump(tracer.to_chrome(), f)
    print(f"artifacts dumped to {out}/")


async def amain(args) -> None:
    metrics = MetricsRegistry()
    out = os.environ.get("OBS_ARTIFACT_DIR")
    tracer = TraceRecorder() if out else None
    with contextlib.ExitStack() as stack:
        disk = (stack.enter_context(tempfile.TemporaryDirectory())
                if args.paged else None)
        server = make_server(args, metrics, tracer, disk_dir=disk)
        await server.start()
        print(f"serving on http://{server.cfg.host}:{server.port} "
              f"(max_batch={server.batcher.max_batch}, "
              f"queue_depth={server.cfg.max_queue_depth}, "
              f"residency={'paged' if args.paged else 'in_memory'})",
              flush=True)
        try:
            await server._shutdown_ev.wait()
        except (KeyboardInterrupt, asyncio.CancelledError):
            pass
        finally:
            await server._aclose()
            if out:
                dump_artifacts(server, metrics, tracer, out)


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8008)
    ap.add_argument("--model", default="tiny",
                    help="'tiny', a registered dense Llama config "
                         "(e.g. llama3-8b) or a published config.json")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth to this many "
                         "layers (widths stay as published)")
    ap.add_argument("--max-len", type=int, default=96,
                    help="context cap: prompt + generated tokens")
    ap.add_argument("--max-batch", type=int, default=3)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--max-tokens-cap", type=int, default=64)
    ap.add_argument("--n-pages", type=int, default=48,
                    help="KV page pool size (small pools force preemption)")
    ap.add_argument("--paged", action="store_true",
                    help="disk+mem weight residency instead of in-memory")
    ap.add_argument("--ttft-slo-ms", type=float, default=None)
    ap.add_argument("--tpot-slo-ms", type=float, default=None)
    ap.add_argument("--flight-capacity", type=int, default=256,
                    help="scheduler ticks retained by the flight recorder")
    ap.add_argument("--drift-every", type=int, default=0,
                    help="drift-watchdog cadence in ticks (0 = off)")
    ap.add_argument("--drift-threshold", type=float, default=0.5,
                    help="RMS relative drift that triggers a re-plan")
    args = ap.parse_args()
    enable_compile_cache()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
