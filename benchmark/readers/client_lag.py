"""How late the load generator ran: send time minus due time over the
window's requests, a percentile in milliseconds.

    {"reader": "client_lag", "args": {"q": 95}}

A closed loop sends when it is due by definition and reads 0.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness.stats import percentile


def read(run: Any, q: float) -> Optional[float]:
    lags = [(r.sent_s - r.due_s) * 1e3 for r in run.records]
    return percentile(lags, q) if lags else None
