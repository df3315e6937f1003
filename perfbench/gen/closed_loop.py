"""Closed loop: a fixed number of clients, each sending its next request
when the previous one ends, as offline jobs over the rows of a table do.

The window opens once every client has its first token, so that all of
them are decoding.  The clients' first requests ask for
``output_len * (i + 1) / clients`` tokens, so that their later requests
end, and new prompts arrive, evenly spread over time rather than all in
one burst every ``output_len`` ticks.  Each client draws its prompts from
a generator seeded by the run's seed and its own index, so the same seed
sends the same prompts whatever the timing, and every seed the same
lengths.

Mix keys: ``clients``, ``prompt_len`` and ``output_len`` (whole numbers).
"""

from __future__ import annotations

import asyncio
import random
import time


def first_output_len(traffic, i):
    out = int(traffic["output_len"])
    return max(1, round(out * (i + 1) / int(traffic["clients"])))


def prompt(rng: random.Random, vocab: int, n: int):
    return [rng.randrange(vocab) for _ in range(n)]


async def run(traffic, seed, vocab, seconds, io):
    n = int(traffic["clients"])
    plen, olen = int(traffic["prompt_len"]), int(traffic["output_len"])
    recs = []
    window = {}
    decoding = asyncio.Event()
    firsts = []

    def on_first(now):
        firsts.append(now)
        if len(firsts) == n:
            window["t0"] = now
            decoding.set()

    async def client(i):
        rng = random.Random(seed * 4096 + i)
        k = 0
        while "t1" not in window or time.monotonic() < window["t1"]:
            rec = io.record(prompt(rng, vocab, plen),
                            first_output_len(traffic, i) if k == 0
                            else olen, time.monotonic())
            rec["client"] = i
            recs.append(rec)
            await io.send(rec, on_first if k == 0 else None)
            if rec["error"] is not None or rec["status"] != 200:
                return
            k += 1

    tasks = [asyncio.ensure_future(client(i)) for i in range(n)]
    waiter = asyncio.ensure_future(decoding.wait())
    await asyncio.wait([waiter, *tasks], return_when=asyncio.FIRST_COMPLETED)
    if not decoding.is_set():  # a client failed before all were decoding
        waiter.cancel()
        await io.close(tasks + [waiter])
        raise RuntimeError(f"clients stopped before all {n} were decoding: "
                           f"{[r['error'] for r in recs if r['error']]}")
    t0 = window["t0"]
    window["t1"] = t0 + seconds
    await io.mark_at("window_start", t0)
    await io.mark_at("window_end", window["t1"])
    await io.close(tasks)
    return {"window": [t0, window["t1"]], "requests": recs}
