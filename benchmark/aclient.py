"""A minimal asyncio HTTP/1.1 client for the load generators.

One thread drives every in-flight request, so the generator's own cost
is small and steady. The gateway answers one request per connection and
closes it, which keeps this short: write the request, read to the end.
For a streamed reply it notes when the first ``data:`` event arrived.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field


@dataclass
class Reply:
    status: int = 0  # 0: no HTTP reply (connection error, time-out)
    error: str = ""
    t_sent: float = 0.0  # monotonic, request written
    t_first: float | None = None  # first SSE event, or the JSON body
    t_done: float = 0.0  # reply read to its end
    doc: dict = field(default_factory=dict)  # JSON body / last SSE doc
    events: int = 0  # SSE events that carried text


async def _exchange(host, port, method, path, body, headers, reply):
    reader, writer = await asyncio.open_connection(host, port)
    try:
        data = b"" if body is None else json.dumps(body).encode()
        head = [
            f"{method} {path} HTTP/1.1", f"Host: {host}:{port}",
            "Content-Type: application/json",
            f"Content-Length: {len(data)}", "Connection: close",
            *(f"{k}: {v}" for k, v in (headers or {}).items()),
        ]
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + data)
        await writer.drain()
        reply.t_sent = time.monotonic()
        status_line = await reader.readline()
        reply.status = int(status_line.split()[1])
        sse = False
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            if h.lower().startswith(b"content-type:") and b"event-stream" in h:
                sse = True
        if not sse:
            raw = await reader.read()
            reply.t_first = time.monotonic()
            reply.doc = json.loads(raw) if raw else {}
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: {"):
                continue
            if reply.t_first is None:
                reply.t_first = time.monotonic()
            doc = json.loads(line[6:])
            if "text" in doc:
                reply.events += 1
            else:
                reply.doc = doc  # the terminal summary, or an error
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


async def request(host: str, port: int, method: str, path: str,
                  body: dict | None = None, *, headers: dict | None = None,
                  timeout: float = 300.0) -> Reply:
    """One request; never raises for a failed exchange — a failure is a
    result the caller counts (status 0 and ``error``)."""
    reply = Reply()
    try:
        await asyncio.wait_for(
            _exchange(host, port, method, path, body, headers, reply), timeout
        )
    except (OSError, ValueError, IndexError, asyncio.TimeoutError,
            asyncio.IncompleteReadError) as e:
        reply.status = 0
        reply.error = f"{type(e).__name__}: {e}"
    reply.t_done = time.monotonic()
    if reply.status == 200 and "error" in reply.doc:
        reply.status, reply.error = 502, str(reply.doc["error"])
    return reply
