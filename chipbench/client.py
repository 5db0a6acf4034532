"""The load generator: streams ``POST /v1/generate`` over HTTP/1.1 and
records, on the client's monotonic clock, when each request was due, sent,
and when each of its token events arrived. Imports nothing of JAX."""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Stream:
    """One request as the client saw it."""
    prompt_ids: List[int]
    max_tokens: int
    slo_class: str
    due: float = 0.0                    # monotonic time it was due
    sent: Optional[float] = None
    events: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    token_ids: Optional[List[int]] = None
    finish_reason: Optional[str] = None
    req_id: Optional[int] = None
    error: Optional[str] = None

    @property
    def first(self) -> Optional[float]:
        return next((t for t, n in self.events if n > 0), None)

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None


async def generate(port: int, s: Stream) -> None:
    """Send one request and read its SSE stream to the end (or until the
    task is cancelled, which closes the socket and aborts it server-side)."""
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps(dict(prompt_ids=s.prompt_ids,
                               max_tokens=s.max_tokens,
                               slo_class=s.slo_class)).encode()
        s.sent = time.monotonic()
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        if status != 200:
            s.error = f"HTTP {status}: {(await reader.read(300))!r}"
            return
        while True:
            size = int((await reader.readuntil(b"\r\n")).strip(), 16)
            if size == 0:
                return
            data = await reader.readexactly(size + 2)
            now = time.monotonic()
            evt = json.loads(data[6:size].decode())
            s.events.append((now, len(evt["new_token_ids"])))
            s.req_id = evt["req_id"]
            if evt["finished"]:
                s.finish_reason = evt["finish_reason"]
                s.token_ids = evt["token_ids"]
                return
    except (ConnectionError, asyncio.IncompleteReadError, ValueError) as e:
        s.error = repr(e)
    finally:
        if writer is not None:
            writer.close()


class Driver:
    """Owns the in-flight request tasks of one run."""

    def __init__(self, port: int):
        self.port = port
        self.tasks: Dict[int, asyncio.Task] = {}

    def start(self, s: Stream) -> asyncio.Task:
        t = asyncio.ensure_future(generate(self.port, s))
        self.tasks[id(s)] = t
        return t

    async def open_loop(self, streams: List[Stream], t0: float,
                        offsets: List[float]) -> None:
        """Send each stream at ``t0 + offset`` whatever the server does (an
        open loop: a slow server builds a queue, it is not offered less)."""
        for s, off in zip(streams, offsets):
            s.due = t0 + off
            delay = s.due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            self.start(s)

    async def wait_first(self, streams: List[Stream], timeout: float) -> None:
        """Wait until every stream has a first token, ended, or timed out."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if all(s.first is not None or s.error or s.finished
                   or self.tasks.get(id(s), None) is None
                   or self.tasks[id(s)].done() for s in streams):
                return
            await asyncio.sleep(0.02)

    async def wait_done(self, streams: List[Stream], timeout: float) -> None:
        tasks = [self.tasks[id(s)] for s in streams if id(s) in self.tasks]
        if tasks:
            await asyncio.wait(tasks, timeout=timeout)

    async def cancel(self, streams: Optional[List[Stream]] = None) -> None:
        keys = ([id(s) for s in streams] if streams is not None
                else list(self.tasks))
        tasks = [self.tasks[k] for k in keys if k in self.tasks]
        for t in tasks:
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)


def to_streams(reqs: List[Dict[str, Any]]) -> List[Stream]:
    return [Stream(prompt_ids=r["prompt_ids"], max_tokens=r["max_tokens"],
                   slo_class=r["slo_class"]) for r in reqs]
