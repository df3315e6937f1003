"""Serve Llama-3-8B widths through the relational path on one TPU chip.

    python chip_smoke.py              # on a machine with one TPU chip
    python chip_smoke.py --rehearse   # the tiny registered Llama on any
                                      # backend; never reports "ok": true

One process holds the chip from start to end.  It builds the server
through ``repro.serving.boot`` (the construction path of
``examples/serve_http.py``), starts it in-process, and drives concurrent
streaming completions through ``repro.serving.client`` over a real socket.
It then compares the engine's logits against the plain f32 transformer
(``models/transformer`` through ``core/bridge``) at HIGHEST matmul
precision: the final-position prefill logits, and the logits of one
batched decode tick as ``BatchedDecoder.decode`` brought them to the host.

The widths are Llama-3-8B's as registered (``configs/llama3_8b.py``); only
the depth is cut, to ``LAYERS``.  Weights are random, drawn from ``SEED``,
and stay f32 as the relational path stores them.  Times printed here are
smoke numbers from one run, not a benchmark.

Exit status 0 and a last stdout line ``{"ok": true, "device": {...}}``
only when every phase passed on a TPU; without a TPU the script prints no
result and exits 2.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
# The cut: 4 of Llama-3-8B's 32 layers.  f32 weights are ~7.7 GB at this
# depth (embedding + lm_head 4.2 GB, ~0.87 GB a layer) before the planner's
# column-layout copies, on a 16 GB chip.
LAYERS = 4
FULL = dict(model="llama3-8b", layers=LAYERS, prompt_len=256, new_tokens=16,
            n_requests=4, max_len=512)
REHEARSAL = dict(model="llama3-tiny", layers=None, prompt_len=24,
                 new_tokens=6, n_requests=4, max_len=64)
REQUEST_TIMEOUT_S = 600.0  # the first request pays every cold compile
# max |Δlogit| allowed against the f32 reference, on logits up to ~4.6 in
# magnitude.  Both sides contract f32 at HIGHEST precision and differ in
# summation order and in the chip's f32 arithmetic: 3.8e-4 measured on a
# v5e at this cut.  With the executor's contractions at DEFAULT (bf16
# passes) the same run measured 5.6e-2.  5e-3 sits about a decade from
# each, so a contraction that drops to DEFAULT fails here.
LOGIT_TOL = 5e-3


class CompileCounter:
    """Backend compiles (persistent-cache hits included) and their time,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self, label, since=(0, 0.0, 0)):
        n0, s0, h0 = since
        print(f"compiles [{label}]: {self.n - n0} backend compiles, "
              f"{self.seconds - s0:.1f} s compiling, "
              f"{self.cache_hits - h0} persistent-cache hits", flush=True)
        return (self.n, self.seconds, self.cache_hits)


def memory_line(dev, label):
    stats = dev.memory_stats()
    if not stats:
        print(f"device memory [{label}]: not reported by this backend",
              flush=True)
        return
    print(f"device memory [{label}]: bytes_in_use={stats['bytes_in_use']} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)


def serve_phase(server, prompts, new_tokens):
    """Concurrent streaming completions over the server's socket; every
    stream must complete with contiguous token_index."""
    from repro.serving import client

    async def drive():
        host, port = server.cfg.host, server.port
        await client.wait_ready(host, port, timeout_s=60)
        t0 = time.perf_counter()
        results = await asyncio.gather(*(
            client.stream_completion(
                host, port, {"prompt": p, "max_tokens": new_tokens},
                timeout_s=REQUEST_TIMEOUT_S)
            for p in prompts))
        wall = time.perf_counter() - t0
        health = await client.request(host, port, "GET", "/healthz",
                                      timeout_s=60)
        return results, wall, health

    results, wall, health = asyncio.run(drive())
    for i, r in enumerate(results):
        if r.status != 200 or r.error is not None:
            raise RuntimeError(f"request {i}: status {r.status}, "
                               f"error {r.error}")
        if r.token_indices != list(range(new_tokens)):
            raise RuntimeError(f"request {i}: token_index "
                               f"{r.token_indices} is not 0..{new_tokens-1}")
    if health.status != 200:
        raise RuntimeError(f"/healthz after the run: {health.status} "
                           f"{health.body[:500]!r}")
    print(f"served {len(results)} concurrent streams x {new_tokens} tokens: "
          f"all complete, token_index contiguous", flush=True)
    print("smoke numbers (one cold run, not a benchmark):", flush=True)
    for i, r in enumerate(results):
        rate = (new_tokens - 1) / (r.total_s - r.ttft_s)
        print(f"  request {i}: ttft_s={r.ttft_s:.3f} total_s={r.total_s:.3f}"
              f" decode_tokens_per_s={rate:.3f}", flush=True)
    print(f"  all: wall_s={wall:.3f} "
          f"tokens_per_s={len(results) * new_tokens / wall:.3f}", flush=True)
    return [r.tokens for r in results]


def engine_logits(server, prompts):
    """Prefill logits per prompt, and the logits of one batched decode
    tick over all of them (read where the decoder brings them to host)."""
    import numpy as np
    eng, dec = server.engine, server.decoder
    prefill = np.stack([eng.prefill_logits(p) for p in prompts])
    slots = list(range(len(prompts)))
    first = [dec.prefill(p, s) for s, p in zip(slots, prompts)]
    dec.decode(slots, first)
    decode = np.array(dec.last_logits)
    for s in slots:
        dec.free(s)
    return prefill, decode, first


def reference_logits(spec, params, prompts, first):
    """The plain f32 transformer over prompt + first token: position T-1
    is the prefill's logits, position T the decode tick's."""
    import jax
    import jax.numpy as jnp
    from repro.core.bridge import llama_params_to_tree, spec_to_config
    from repro.models import transformer as tf
    cfg = spec_to_config(spec)
    tree = llama_params_to_tree(params, spec)
    tokens = jnp.asarray([p + [t] for p, t in zip(prompts, first)],
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda w, t: tf.forward(w, {"tokens": t}, cfg))(
            tree, tokens)
    T = len(prompts[0])
    out = jax.device_get(logits[:, T - 1:T + 1])
    return out[:, 0], out[:, 1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny registered Llama on whatever backend JAX "
                         "finds; checks the path, never reports ok")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    size = REHEARSAL if args.rehearse else FULL
    if args.rehearse:
        print("REHEARSAL: tiny model, not a chip run", flush=True)

    import numpy as np
    from repro.core.llama_graph import init_llama_params
    from repro.serving.boot import PAGE_SIZE, build_server, \
        enable_compile_cache, model_spec
    from repro.serving.server import ServerConfig

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)

    spec = model_spec(size["model"], size["layers"])
    print(f"model: {size['model']} widths d_model={spec.d_model} "
          f"n_heads={spec.n_heads} n_kv={spec.n_kv} "
          f"head_dim={spec.head_dim} d_ff={spec.d_ff} vocab={spec.vocab} "
          f"rope_theta={spec.rope_theta}; depth cut to {spec.n_layers} "
          f"layers; f32 weights, seed {SEED}", flush=True)
    t0 = time.perf_counter()
    params = init_llama_params(spec, seed=SEED)
    weight_bytes = sum(a.nbytes for a in params.values())
    print(f"weights: {weight_bytes} bytes generated on the host in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    t0 = time.perf_counter()
    cfg = ServerConfig(port=0, model_id=size["model"],
                       max_batch=size["n_requests"],
                       max_tokens_cap=size["new_tokens"])
    # pages for every request's whole context: no preemption in the smoke
    per_seq = -(-size["max_len"] // PAGE_SIZE)
    server = build_server(spec, params, cfg, max_len=size["max_len"],
                          n_pages=size["n_requests"] * per_seq)
    print(f"engine built in {time.perf_counter() - t0:.1f} s "
          f"(max_len={size['max_len']}, cache layout "
          f"{server.engine.cache_layout})", flush=True)
    memory_line(dev, "after construction")
    mark = compiles.report("construction")

    rng = np.random.default_rng(SEED)
    prompts = [[int(t) for t in rng.integers(0, spec.vocab,
                                             size["prompt_len"])]
               for _ in range(size["n_requests"])]
    thread = server.start_in_thread()
    try:
        served = serve_phase(server, prompts, size["new_tokens"])
    finally:
        server.shutdown()
    if thread.is_alive():
        raise RuntimeError("server thread still running after shutdown")
    print(f"cold start to last token: {time.perf_counter() - t_start:.1f} s",
          flush=True)
    mark = compiles.report("serving", mark)

    pre_eng, dec_eng, first = engine_logits(server, prompts)
    if first != [toks[0] for toks in served]:
        raise RuntimeError(f"first tokens {first} differ from the served "
                           f"ones {[toks[0] for toks in served]}")
    memory_line(dev, "after the run")
    mark = compiles.report("engine logits", mark)

    # the engine and the reference do not fit on the chip together
    del server
    gc.collect()
    memory_line(dev, "engine freed")
    pre_ref, dec_ref = reference_logits(spec, params, prompts, first)
    compiles.report("reference", mark)
    d_pre = float(np.max(np.abs(pre_eng - pre_ref)))
    d_dec = float(np.max(np.abs(dec_eng - dec_ref)))
    scale = float(np.max(np.abs(pre_ref)))
    print(f"max |dlogit| prefill={d_pre:.3e} decode={d_dec:.3e} "
          f"(max |logit| {scale:.3f}; tolerance {LOGIT_TOL:.0e}, "
          f"f32 reference at HIGHEST precision)", flush=True)
    compiles.report("total")
    if not (d_pre <= LOGIT_TOL and d_dec <= LOGIT_TOL):
        raise RuntimeError("logits differ from the f32 reference beyond "
                           "the tolerance")
    print(f"total wall: {time.perf_counter() - t_start:.1f} s", flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
