"""Open loop: independent users. Requests are sent when they are due,
whether or not earlier ones have finished.

Parameters: ``rate`` (requests/s, fixed in the mix: found once by
``benchmark/sweep.py``, never searched for in a run).

``floor(rate * seconds)`` requests; their gaps are exponential draws from
the mix's ``pool_seed``, scaled to fill the window exactly. The schedule is
the mix's, the same for every ``--seed`` (see ``harness/traffic.py``).
"""

from __future__ import annotations

import random
from typing import Any


def count(params: dict, seconds: float) -> int:
    return max(1, int(params["rate"] * seconds))


def due_times(params: dict, n: int, seconds: float) -> list[float]:
    pool = random.Random(params.get("pool_seed", 0))
    gaps = [pool.expovariate(1.0) for _ in range(n + 1)]
    scale = seconds / sum(gaps)
    due, t = [], 0.0
    for gap in gaps[:n]:
        t += gap * scale
        due.append(t)
    return due


async def drive(params: dict, requests: list, window: Any) -> None:
    """Sleep until each request is due, send it, and let it run on."""
    dues = due_times(params, len(requests), window.seconds)
    for request, due in zip(requests, dues):
        await window.sleep_until(due)
        window.start(request, due)
