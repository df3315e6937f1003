"""Build the system under test and record what its callbacks do.

The server is built through ``repro.serving.boot.build_server``, the
construction path of the repository's HTTP entry point, from the program's
spec of the configuration (``programs/<reference>.py``) and weights drawn
by the benchmark.  The benchmark then wraps the scheduler's two callbacks
on the instance (no program file is edited):

* ``perfbench.prefill`` and ``perfbench.decode`` profiler annotations
  around them, so that a trace places device activity and idle gaps
  against what the scheduler thread was doing;
* host-clock start and end of each decode tick, its batch size and
  live context lengths, and the program's own ``decoder_tick_seconds``
  histogram after it; host-clock start and end of each prefill;
* the logits the timed path produced for each token it served: the
  final-position prefill logits (``RelationalEngine.start_session``) and
  each batched decode tick's rows (``BatchedDecoder.last_logits``).
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Dict, List, Tuple

import numpy as np

from perfbench import spec

PREFILL = "perfbench.prefill"
DECODE = "perfbench.decode"
KEY_TOKENS = 32  # a prompt's first tokens identify its request


def prompt_key(prompt) -> Tuple[int, ...]:
    return tuple(int(t) for t in prompt[:KEY_TOKENS])


def program_spec(cfg: dict):
    """The program's spec of the configuration's architecture; raises for
    a ``model_type`` the program does not build."""
    return spec.architecture("programs", cfg["reference"]).spec_from_config(
        cfg)


def build(cfg: dict, model_spec, params: Dict[str, np.ndarray], metrics):
    """An ``AsyncLLMServer`` with pages for every slot's whole context, so
    that no request is preempted (a resumed request would prefill a length
    that was never warmed)."""
    from repro.serving.boot import PAGE_SIZE, build_server
    from repro.serving.server import ServerConfig
    serve = cfg["serve"]
    max_len = int(serve["max_len"])
    sc = ServerConfig(port=0, model_id=cfg["name"],
                      max_batch=int(serve["max_batch"]),
                      max_tokens_cap=int(serve["max_tokens_cap"]),
                      max_queue_depth=int(serve.get("max_queue_depth", 32)))
    n_pages = sc.max_batch * -(-max_len // PAGE_SIZE)
    return build_server(model_spec, params, sc, max_len=max_len,
                        n_pages=n_pages, metrics=metrics)


@dataclasses.dataclass
class Recorder:
    """What the wrapped callbacks saw, on the host's monotonic clock."""

    # (start, end, batch, sum of live context lengths,
    #  decoder_tick_seconds sum, decoder_tick_seconds count)
    ticks: List[Tuple[float, float, int, int, float, int]] = \
        dataclasses.field(default_factory=list)
    # (start, end, prompt length)
    prefills: List[Tuple[float, float, int]] = dataclasses.field(
        default_factory=list)
    # prompt key -> logits rows, one per token served, in order
    rows: Dict[Tuple[int, ...], List[np.ndarray]] = dataclasses.field(
        default_factory=dict)


def instrument(server, metrics) -> Recorder:
    import jax
    rec = Recorder()
    batcher, dec, eng = server.batcher, server.decoder, server.engine
    prefill_fn, decode_fn = batcher.prefill_fn, batcher.decode_fn
    start, start_suffix = eng.start_session, eng.start_suffix_session
    hist = metrics.histogram("decoder_tick_seconds",
                             "batched decode tick latency")
    last = {}

    def start_session(prompt):
        sess = start(prompt)
        last["logits"] = sess["logits"]
        return sess

    def start_suffix_session(prompt, boundary, cache_tables):
        sess = start_suffix(prompt, boundary, cache_tables)
        last["logits"] = sess["logits"]
        return sess

    def prefill(req, seq_id):
        done = len(req.generated)  # a resumed request has served these
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(PREFILL):
            res = prefill_fn(req, seq_id)
        rec.prefills.append((t0, time.monotonic(), len(req.prompt)))
        rows = rec.rows.setdefault(prompt_key(req.prompt), [])
        del rows[done:]
        rows.append(last.pop("logits"))
        return res

    def decode(seq_ids, last_tokens):
        reqs = [batcher.active[s] for s in seq_ids]
        live = sum(len(r.prompt) + len(r.generated) for r in reqs)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(DECODE):
            toks = decode_fn(seq_ids, last_tokens)
        t1 = time.monotonic()
        logits = dec.last_logits
        for i, r in enumerate(reqs):
            rec.rows[prompt_key(r.prompt)].append(logits[i])
        rec.ticks.append((t0, t1, len(seq_ids), live, hist.sum, hist.count))
        return toks

    eng.start_session = start_session
    eng.start_suffix_session = start_suffix_session
    batcher.prefill_fn = prefill
    batcher.decode_fn = decode
    return rec


def warm(server, prompt_len: int, vocab: int, seed: int) -> None:
    """Compile what the cell's traffic will run, through the decoder the
    scheduler drives: a prefill at the prompt length, and decode ticks at
    every batch bucket up to ``max_batch`` (twice each, so that the reuse
    of cached batch views runs too).  Slots are freed after."""
    rng = random.Random(seed)
    dec = server.decoder
    B = server.batcher.max_batch

    def prompt(n):  # unshared, as the cells' prompts are
        return [rng.randrange(vocab) for _ in range(n)]

    first = [dec.prefill_ex(prompt(prompt_len), s)[0] for s in range(B)]
    b = 1
    while True:
        slots = list(range(min(b, B)))
        toks = [first[s] for s in slots]
        for _ in range(2):
            toks = dec.decode(slots, toks)
        if b >= B:
            break
        b *= 2
    for s in range(B):
        dec.free(s)


def stop(server, timeout_s: float = 30.0) -> None:
    """Stop a ``start_in_thread`` server once the load has gone.

    The clients have closed their connections, but a handler whose
    request the scheduler will no longer advance waits on its token queue
    for ever, and the server's close waits for every handler.  So the
    handlers are cancelled first, as a server cancels a request whose
    client has left; then the server is shut down and its thread joined,
    so that nothing still holds the program's device state."""
    import asyncio

    loop = server._loop

    def cancel_handlers():
        for task in asyncio.all_tasks(loop):
            if task.get_coro().__qualname__.endswith("._handle_conn"):
                task.cancel()

    loop.call_soon_threadsafe(cancel_handlers)
    server.shutdown(join_timeout=timeout_s)
    if server._server_thread.is_alive():
        raise RuntimeError(f"the server did not stop within {timeout_s} s")
