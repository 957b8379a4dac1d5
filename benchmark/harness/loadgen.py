"""The load generator: one thread, one event loop, one connection per
request, every streamed token stamped on the host's monotonic clock.

A traffic kind drives a :class:`Window`; the window sends, records and,
when its seconds are over, gives what is still in flight ``DRAIN_GRACE_S``
to finish before counting it as failed.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Any, Awaitable, Callable, Optional

from benchmark.harness.traffic import Request

# In-flight requests at the end of the window may finish for this long;
# then they are cancelled and count as failed. Longer than the longest
# request of either mix takes on an idle server, shorter than a hang.
DRAIN_GRACE_S = 45.0


@dataclasses.dataclass
class Record:
    """One request as the client saw it. Times are seconds into the window."""

    index: int
    prompt_tokens: int
    asked_tokens: int
    due_s: float                 # when it was due (open) or sent (closed)
    sent_s: float = 0.0
    token_s: list = dataclasses.field(default_factory=list)  # one per token
    finish_reason: Optional[str] = None
    done_s: Optional[float] = None
    error: Optional[str] = None
    gave_up_s: Optional[float] = None  # when the harness stopped waiting

    @property
    def ok(self) -> bool:
        """Returned what it asked for: every token, or fewer on an EOS that
        random weights can emit (``finish_reason: "stop"``)."""
        if self.error is not None or self.done_s is None:
            return False
        n = len(self.token_s)
        if self.finish_reason == "length":
            return n == self.asked_tokens
        return self.finish_reason == "stop" and 1 <= n <= self.asked_tokens

    def why_not(self) -> str:
        """A failed request in one line, for the log."""
        return (f"request {self.index} ({self.prompt_tokens} prompt tokens): "
                f"{self.error or self.finish_reason!r} after "
                f"{len(self.token_s)}/{self.asked_tokens} tokens")


async def stream_completion(
    port: int, request: Request, record: Record, now: Callable[[], float],
) -> None:
    """POST /v1/completions with ``stream`` and read the SSE events.
    The body is chunked; every event is whole inside one chunk, so lines
    that start with ``data: `` are the events and the chunk framing is
    skipped with the rest."""
    payload = json.dumps(request.body()).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            b"Content-Type: application/json\r\nConnection: close\r\n"
            b"Content-Length: " + str(len(payload)).encode() + b"\r\n\r\n"
            + payload
        )
        await writer.drain()
        status_line = await reader.readline()
        if b" 200 " not in status_line:
            rest = await reader.read(600)
            record.error = (status_line + rest).decode("utf-8", "replace")[:300]
            return
        while True:
            line = await reader.readline()
            if not line:
                record.error = "stream closed before [DONE]"
                return
            if not line.startswith(b"data: "):
                continue
            stamp = now()
            data = line[6:].strip()
            if data == b"[DONE]":
                record.done_s = stamp
                return
            event = json.loads(data)
            if "error" in event:
                record.error = json.dumps(event["error"])[:300]
                return
            for choice in event["choices"]:
                record.token_s.extend([stamp] * len(choice.get("token_ids", ())))
                if choice.get("finish_reason"):
                    record.finish_reason = choice["finish_reason"]
    finally:
        writer.close()


class Window:
    """The measured window, as a traffic kind sees it."""

    def __init__(self, port: int, seconds: float) -> None:
        self.port = port
        self.seconds = float(seconds)
        self.records: list[Record] = []
        self.notes: list[str] = []
        self._tasks: list[asyncio.Task] = []
        self._t0 = 0.0
        self.ended = asyncio.Event()  # set when the window's seconds are over

    def now(self) -> float:
        return time.monotonic() - self._t0

    def note(self, text: str) -> None:
        if text not in self.notes:
            self.notes.append(text)

    async def sleep_until(self, at_s: float) -> None:
        delay = at_s - self.now()
        if delay > 0:
            await asyncio.sleep(delay)

    def start(self, request: Request, due_s: Optional[float]) -> asyncio.Task:
        """Send now. ``due_s`` is when an open loop owed the request; a
        closed loop passes None and the request is due when it is sent."""
        sent = self.now()
        record = Record(
            index=request.index, prompt_tokens=len(request.prompt),
            asked_tokens=request.max_tokens,
            due_s=sent if due_s is None else due_s, sent_s=sent,
        )
        self.records.append(record)
        task = asyncio.ensure_future(self._run(request, record))
        self._tasks.append(task)
        return task

    async def _run(self, request: Request, record: Record) -> None:
        try:
            await stream_completion(self.port, request, record, self.now)
        except (OSError, ValueError, asyncio.IncompleteReadError) as exc:
            record.error = f"{type(exc).__name__}: {exc}"

    async def run(
        self, drive: Callable[..., Awaitable[None]], params: dict,
        requests: list,
        during: Optional[Callable[["Window"], Awaitable[Any]]] = None,
    ) -> float:
        """Run the kind for ``seconds``, then drain. Returns the host's
        monotonic time of the window's start. ``during`` runs beside the
        traffic (samplers, the trace capture); it watches ``ended`` and is
        waited for."""
        self._t0 = time.monotonic()
        side = asyncio.ensure_future(during(self)) if during else None
        driver = asyncio.ensure_future(drive(params, requests, self))
        await self.sleep_until(self.seconds)
        self.ended.set()
        if side is not None:
            await side
        # The kind sends nothing after the window (open: every due time is
        # inside it; closed: clients stop at the first end past it), so
        # what is left is in flight.
        pending = [driver, *self._tasks]
        done, late = await asyncio.wait(pending, timeout=DRAIN_GRACE_S)
        for task in late:
            task.cancel()
        await asyncio.gather(*late, return_exceptions=True)
        if driver in done and driver.exception() is not None:
            raise driver.exception()  # a fault in the kind, not in a request
        gave_up = self.now()
        for record in self.records:
            if record.done_s is None and record.error is None:
                record.error = f"unfinished {DRAIN_GRACE_S:.0f}s after the window"
            if record.done_s is None:
                record.gave_up_s = gave_up
        return self._t0
