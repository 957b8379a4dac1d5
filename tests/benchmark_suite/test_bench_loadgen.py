"""The load generator against a scripted SSE server: stamps, failures and
the drain, with no engine."""

import asyncio
import json

import bench_paths  # noqa: F401
from benchmark.harness import cells, loadgen, stats, traffic


async def scripted_server(reader, writer):
    """Answers like the app: chunked SSE, tokens in groups. A prompt that
    starts with 9 is refused; one that starts with 8 hangs mid-stream."""
    head = await reader.readuntil(b"\r\n\r\n")
    length = int(head.lower().split(b"content-length:")[1].split(b"\r\n")[0])
    body = json.loads(await reader.readexactly(length))
    if body["prompt"][0] == 9:
        writer.write(b"HTTP/1.1 429 Too Many Requests\r\n\r\nbusy")
        writer.close()
        return
    writer.write(b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n")

    def chunk(event):
        data = b"data: " + (event if isinstance(event, bytes)
                            else json.dumps(event).encode()) + b"\n\n"
        writer.write(hex(len(data))[2:].encode() + b"\r\n" + data + b"\r\n")

    left = body["max_tokens"]
    while left:
        group = min(left, 3)
        await asyncio.sleep(0.02)
        chunk({"choices": [{"text": "", "token_ids": list(range(group))}]})
        await writer.drain()
        left -= group
        if body["prompt"][0] == 8:
            await reader.read()  # hangs until the client gives up
            writer.close()
            return
    chunk({"choices": [{"text": "", "finish_reason": "length", "token_ids": []}]})
    chunk(b"[DONE]")
    writer.write(b"0\r\n\r\n")
    await writer.drain()
    writer.close()


def test_open_loop_stamps_tokens_and_counts_failures(monkeypatch):
    monkeypatch.setattr(loadgen, "DRAIN_GRACE_S", 0.5)
    kind = cells.load_module("traffic_kinds", "open_poisson")
    params = {"rate": 20.0, "pool_seed": 1}

    def request(i, first):
        return traffic.Request(index=i, prompt=(first, 5, 6), max_tokens=7,
                               temperature=0.0, seed=i)

    requests = [request(i, 9 if i == 3 else 8 if i == 5 else 4) for i in range(20)]

    async def scenario():
        server = await asyncio.start_server(scripted_server, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        window = loadgen.Window(port, 1.0)
        async with server:
            await window.run(kind.drive, params, requests)
        return window

    window = asyncio.run(scenario())
    records = sorted(window.records, key=lambda r: r.index)
    assert len(records) == 20
    good = [r for r in records if r.ok]
    assert len(good) == 18 and all(len(r.token_s) == 7 for r in good)
    assert "429" in records[3].error and "unfinished" in records[5].error
    assert len(records[5].token_s) == 3 and records[5].gave_up_s > 1.0
    for r in good:  # groups of 3, 3, 1 about 20 ms apart; due before sent
        assert r.due_s <= r.sent_s < r.token_s[0] <= r.token_s[-1] <= r.done_s
        assert r.token_s[0] == r.token_s[2] < r.token_s[3]
        assert 5 < stats.tpot_ms(r) < 40
    assert stats.ttft_ms(records[3]) > 300  # refused: waits until given up
    lags = [r.sent_s - r.due_s for r in records]
    assert max(lags) < 0.05


def test_closed_loop_keeps_its_clients_busy_and_stops_at_the_window():
    kind = cells.load_module("traffic_kinds", "closed")
    params = {"clients": 3, "requests": 500}
    requests = [
        traffic.Request(index=i, prompt=(4, 5), max_tokens=6, temperature=0.0,
                        seed=i)
        for i in range(kind.count(params, 1.0))
    ]

    async def scenario():
        server = await asyncio.start_server(scripted_server, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        window = loadgen.Window(port, 0.6)
        async with server:
            await window.run(kind.drive, params, requests)
        return window

    window = asyncio.run(scenario())
    records = window.records
    assert all(r.ok for r in records) and 9 <= len(records) <= 45
    assert all(r.sent_s < 0.6 for r in records)  # nothing starts after it
    assert max(r.done_s for r in records) >= 0.6  # the last ones drain
    # never more than three in flight
    events = sorted([(r.sent_s, 1) for r in records] + [(r.done_s, -1) for r in records])
    level = peak = 0
    for _, step in events:
        level += step
        peak = max(peak, level)
    assert peak == 3
    assert all(r.due_s == r.sent_s for r in records)  # due when sent
