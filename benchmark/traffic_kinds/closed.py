"""Closed loop: callers that wait for a reply. ``clients`` callers each
send their next request when the last one ends, until the window ends.

Parameters: ``clients``, and ``requests`` — how many the seed orders (a
window that outruns them stops early and says so).
"""

from __future__ import annotations

import asyncio
from typing import Any


def count(params: dict, seconds: float) -> int:  # noqa: ARG001
    return int(params["requests"])


async def drive(params: dict, requests: list, window: Any) -> None:
    queue = iter(requests)

    async def client() -> None:
        while window.now() < window.seconds:
            request = next(queue, None)
            if request is None:
                window.note("closed: the seed's requests ran out before the window did")
                return
            await window.start(request, None)

    await asyncio.gather(*(client() for _ in range(int(params["clients"]))))
