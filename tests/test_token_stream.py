"""A stream that waits long holds no executor thread (PR 33).

Until PR 33 every SSE handler parked ``req.stream.get`` on the event loop's
default executor (``min(32, cores + 4)`` workers) for as long as its stream
had nothing. With more open streams than workers, the streams still waiting
for their first token held every worker and the decoding streams' tokens sat
in their queues: 32 long-prompt streams on a 13-core host (17 workers) saw
no second token before the 16th stream's first. ``next_token`` gives the
worker back after ``POOL_READ_S`` and waits on the loop (``TokenStream.aget``).
"""

from __future__ import annotations

import asyncio
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from gofr_tpu.serving import types
from gofr_tpu.serving.types import TokenStream, _GenRequest, next_token

HOLD_S = 0.05


@pytest.fixture(autouse=True)
def _short_hold(monkeypatch):
    monkeypatch.setattr(types, "POOL_READ_S", HOLD_S)


def run(coro):
    return asyncio.run(coro)


def test_a_request_streams_through_a_token_stream():
    req = _GenRequest(
        prompt_ids=[1], max_new_tokens=1, temperature=0.0, stop_on_eos=False
    )
    assert isinstance(req.stream, TokenStream)


def test_a_blocking_consumer_still_gets():
    stream = TokenStream()
    threading.Timer(0.05, stream.put, args=(7,)).start()
    assert stream.get(timeout=5) == 7
    stream.put(None)
    assert stream.get_nowait() is None


def test_items_put_before_the_first_aget_are_delivered_in_order():
    stream = TokenStream()
    for item in (1, 2, 3, None):
        stream.put(item)

    async def drain():
        return [await stream.aget() for _ in range(4)]

    assert run(drain()) == [1, 2, 3, None]


def test_a_put_from_another_thread_wakes_the_waiter():
    stream = TokenStream()

    def produce():
        for item in (10, 11, 12):
            time.sleep(0.02)
            stream.put(item)
        stream.put(None)

    async def consume():
        threading.Thread(target=produce, daemon=True).start()
        got = []
        while (item := await asyncio.wait_for(stream.aget(), 5)) is not None:
            got.append(item)
        return got

    assert run(consume()) == [10, 11, 12]


def test_a_burst_of_puts_sends_one_wake_up():
    """A decode window puts window_k tokens at once: the scheduler thread
    pays one ``call_soon_threadsafe`` a stream a window, not one a token.
    (The burst is put from the loop's own thread, so the waiter cannot
    wake and lower the flag half-way through it.)"""
    stream = TokenStream()

    async def scenario():
        loop = asyncio.get_running_loop()
        waiter = asyncio.ensure_future(stream.aget())
        await asyncio.sleep(0.01)  # the waiter is registered and asleep
        calls = []
        real = loop.call_soon_threadsafe
        loop.call_soon_threadsafe = lambda *a: (calls.append(a), real(*a))[1]
        try:
            for token in range(8):
                stream.put(token)
            first = await asyncio.wait_for(waiter, 5)
            rest = [await stream.aget() for _ in range(7)]
        finally:
            del loop.call_soon_threadsafe
        return calls, [first, *rest]

    calls, got = run(scenario())
    assert got == list(range(8))
    assert len(calls) == 1


def test_next_token_reads_a_stream_to_its_end():
    stream = TokenStream()
    stream.put(5)
    stream.put(None)

    async def read():
        return [await next_token(stream), await next_token(stream)]

    assert run(read()) == [5, None]


@pytest.mark.parametrize("waiting", [1, 4, 40])
def test_streams_that_wait_long_hold_no_worker(waiting):
    """Two executor workers and ``waiting`` streams that have nothing: once
    each has held a worker for POOL_READ_S they all wait on the loop, a
    stream with a token is read at once and the executor is free."""
    workers = 2

    async def scenario():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(max_workers=workers))
        idle = [TokenStream() for _ in range(waiting)]
        waiters = [asyncio.ensure_future(next_token(s)) for s in idle]
        await asyncio.sleep(HOLD_S * -(-waiting // workers) + 1.0)
        ready = TokenStream()
        ready.put(42)
        got = await asyncio.wait_for(next_token(ready), 5)
        free = await asyncio.wait_for(loop.run_in_executor(None, int, "3"), 5)
        assert not any(w.done() for w in waiters)
        for s in idle:
            s.put(None)
        ended = await asyncio.wait_for(asyncio.gather(*waiters), 5)
        return got, free, ended

    got, free, ended = run(scenario())
    assert (got, free) == (42, 3)
    assert ended == [None] * waiting


def test_a_token_that_comes_soon_is_read_on_the_pool(monkeypatch):
    """Inside POOL_READ_S the read is the blocking one it always was: the
    stream never registers with the loop."""
    monkeypatch.setattr(types, "POOL_READ_S", 5.0)
    stream = TokenStream()

    async def scenario():
        threading.Timer(0.05, stream.put, args=(9,)).start()
        return await asyncio.wait_for(next_token(stream), 5)

    assert run(scenario()) == 9
    assert stream._waker is None


def test_a_read_that_keeps_its_worker_is_what_starved():
    """The control: what the handlers did until PR 33, a blocking ``get`` on
    the executor for as long as the stream has nothing. One waiting stream
    takes the one worker, and the ready stream's token is not read until
    the waiting one gets something."""

    async def scenario():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(max_workers=1))
        idle, ready = queue.Queue(), queue.Queue()
        waiter = loop.run_in_executor(None, idle.get)
        await asyncio.sleep(0.02)
        ready.put(42)
        reader = loop.run_in_executor(None, ready.get)
        done, _ = await asyncio.wait({reader}, timeout=0.3)
        starved = not done
        idle.put(None)
        return starved, await asyncio.wait_for(reader, 5), await waiter

    assert run(scenario()) == (True, 42, None)


def test_a_decoding_stream_keeps_its_pace_beside_streams_in_a_long_prefill():
    """Through the engine: two executor workers, one stream that decodes and
    three whose prefill takes a second (every prefill step is slowed). The
    waiting streams give their workers back after POOL_READ_S, so the
    decoding stream's tokens keep coming; when every read held its worker
    until a token came, it stood still until the first of them had one."""
    import numpy as np

    from gofr_tpu import faults
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    chunk, chunks, step_s = 16, 14, 0.08
    engine = InferenceEngine(
        "llama-tiny", n_slots=8, max_len=1024, prefill_chunk=chunk,
        prefill_batch=2, tokenizer=ByteTokenizer(),
    )

    def slow_prefill(**fired):
        if fired.get("engine") is engine and fired.get("kind") == "prefill":
            time.sleep(step_s)

    def prompt(seed: int, n: int) -> list[int]:
        return [int(t) for t in np.random.default_rng(seed).integers(3, 500, n)]

    async def scenario():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(max_workers=2))
        stamps: list[float] = []
        first_of_the_others: list[float] = []

        async def decoding():
            async for _ in engine.generate_stream(
                prompt(0, 4), max_new_tokens=200, stop_on_eos=False
            ):
                stamps.append(time.monotonic())

        async def waiting(seed: int):
            async for _ in engine.generate_stream(
                prompt(seed, chunks * chunk), max_new_tokens=2,
                stop_on_eos=False,
            ):
                first_of_the_others.append(time.monotonic())
                break

        live = asyncio.ensure_future(decoding())
        while len(stamps) < 9:  # its first window has been read
            await asyncio.sleep(0.01)
        began = time.monotonic()
        await asyncio.wait_for(
            asyncio.gather(*(waiting(seed) for seed in range(1, 4))), 120
        )
        live.cancel()
        return began, stamps, min(first_of_the_others)

    with faults.armed("scheduler.device_step", action=slow_prefill):
        engine.start_sync()
        try:
            began, stamps, first_other = run(scenario())
        finally:
            engine.stop_sync()
            faults.reset()
    # 3 prompts of 14 chunks, 2 rows a step: no first token of theirs
    # before 14 slowed steps.
    assert first_other - began > chunks * step_s
    during = [t for t in stamps if began <= t <= first_other]
    gaps = [b - a for a, b in zip(during, during[1:])]
    assert len(during) > 30 and max(gaps) < 0.5 * (first_other - began)
