"""Streaming completion client that stamps every token on arrival.

Follows ``repro.serving.client``'s SSE framing (a copy, so that the
yardstick does not move with the program), and adds what a tail needs:
the host-clock time at which each chunk arrived, the time the request was
due, and the time it was sent.  Times are ``time.monotonic()``, which on
Linux is one clock across processes, so the server's process can place
them on its own timeline.  Stdlib only: the load generator imports no JAX.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Callable, Dict, List, Optional


def new_record(prompt: List[int], max_tokens: int, due: float) -> Dict:
    return {"prompt": prompt, "max_tokens": max_tokens, "due": due,
            "sent": None, "status": None, "error": None, "done": False,
            "tokens": [], "index": [], "t": []}


async def _head(reader) -> tuple:
    status = int((await reader.readline()).split()[1])
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            return status, headers
        k, _, v = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()


def _encode(method: str, path: str, body: bytes) -> bytes:
    return (f"{method} {path} HTTP/1.1\r\nHost: localhost\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
            ).encode() + body


async def stream(host: str, port: int, rec: Dict,
                 on_first: Optional[Callable[[float], None]] = None) -> Dict:
    """POST ``rec``'s prompt as a streaming completion and fill ``rec``
    as chunks arrive; returns ``rec``.  A refusal or an error event is
    recorded, never raised; cancellation leaves what arrived so far."""
    rec["sent"] = time.monotonic()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps({"prompt": rec["prompt"],
                           "max_tokens": rec["max_tokens"],
                           "stream": True}).encode()
        writer.write(_encode("POST", "/v1/completions", body))
        await writer.drain()
        rec["status"], headers = await _head(reader)
        if rec["status"] != 200:
            data = await reader.read()
            rec["error"] = data.decode("utf-8", "replace")[:500]
            return rec
        buf = b""
        while True:
            chunk = await reader.read(65536)
            now = time.monotonic()
            if not chunk:
                if not rec["done"] and rec["error"] is None:
                    rec["error"] = "stream closed before [DONE]"
                return rec
            buf += chunk
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                for line in frame.splitlines():
                    if not line.startswith(b"data: "):
                        continue
                    data = line[len(b"data: "):]
                    if data == b"[DONE]":
                        rec["done"] = True
                        return rec
                    event = json.loads(data)
                    if "error" in event:
                        rec["error"] = json.dumps(event)[:500]
                        return rec
                    choice = event["choices"][0]
                    if not rec["t"] and on_first is not None:
                        on_first(now)
                    rec["tokens"].append(int(choice["token_id"]))
                    rec["index"].append(int(choice["token_index"]))
                    rec["t"].append(now)
    except (ConnectionError, asyncio.IncompleteReadError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except ConnectionError:
            pass


async def wait_ready(host: str, port: int, timeout_s: float = 60.0) -> None:
    """Poll ``/healthz`` until the server answers 200."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            reader, writer = await asyncio.open_connection(host, port)
            try:
                writer.write(_encode("GET", "/healthz", b""))
                await writer.drain()
                status, _ = await _head(reader)
                await reader.read()
            finally:
                writer.close()
            if status == 200:
                return
        except OSError:
            pass
        if time.monotonic() > deadline:
            raise TimeoutError(f"server at {host}:{port} not ready after "
                               f"{timeout_s} s")
        await asyncio.sleep(0.2)


def complete(rec: Dict) -> bool:
    """A stream that ended in ``[DONE]`` with every token, in order."""
    return (rec["done"] and rec["error"] is None
            and rec["index"] == list(range(rec["max_tokens"])))
