"""Load generator: drives one traffic mix against a running server.

    python3 perfbench/loadgen.py --port P --traffic traffic/batch-128.json \
        --seed S --seconds N --vocab V

Runs in a process of its own, so that it never shares an interpreter lock
with the server, and imports no JAX, so that it never touches the chip.
The mix's ``kind`` names its generator, ``gen/<kind>.py``.  Standard
output carries one JSON object per line: ``{"event": "window_start" |
"window_end", "t": ...}`` as the window opens and closes, then
``{"event": "result", "window": [t0, t1], "requests": [...]}`` with every
request's record (``client.new_record``).  Times are ``time.monotonic()``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from perfbench import client, spec  # noqa: E402


class IO:
    """What a generator uses: records, sends, window marks."""

    def __init__(self, host: str, port: int, out=sys.stdout):
        self.host, self.port, self.out = host, port, out

    @staticmethod
    def record(prompt, max_tokens, due):
        return client.new_record(prompt, max_tokens, due)

    async def send(self, rec, on_first=None):
        return await client.stream(self.host, self.port, rec, on_first)

    def mark(self, event: str) -> None:
        self.out.write(json.dumps({"event": event, "t": time.monotonic()})
                       + "\n")
        self.out.flush()

    async def mark_at(self, event: str, t: float) -> None:
        await asyncio.sleep(max(0.0, t - time.monotonic()))
        self.mark(event)

    @staticmethod
    async def close(tasks) -> None:
        """Cancel what is still running and wait for it to end."""
        for t in tasks:
            t.cancel()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        for r in results:
            if isinstance(r, Exception) and not isinstance(
                    r, asyncio.CancelledError):
                raise r


async def drive(traffic: dict, seed: int, vocab: int, seconds: float,
                host: str, port: int, out=sys.stdout) -> dict:
    await client.wait_ready(host, port)
    gen = spec.generator(traffic["kind"])
    return await gen.run(traffic, seed, vocab, seconds, IO(host, port, out))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True, help="path of the mix")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    args = ap.parse_args(argv)
    traffic = spec.load_json(spec.Path(args.traffic))
    res = asyncio.run(drive(traffic, args.seed, args.vocab, args.seconds,
                            args.host, args.port))
    sys.stdout.write(json.dumps({"event": "result", **res}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
