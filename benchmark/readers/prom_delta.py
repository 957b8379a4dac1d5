"""A counter, or a histogram's sum over its count, window end minus
window start.

    {"reader": "prom_delta", "args": {"name": "app_tpu_queue_wait_seconds",
                                      "histogram": true, "scale": 1000}}

``histogram``: (delta of ``<name>_sum``) / (delta of ``<name>_count``) —
the mean over the window's observations; nothing observed reads nothing.
Otherwise the counter's delta. Summed over label sets.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import prom


def read(run: Any, name: str, histogram: bool = False,
         scale: float = 1.0) -> Optional[float]:
    def delta(series: str) -> float:
        return prom.total(run.prom_end, series) - prom.total(
            run.prom_start, series
        )

    if histogram:
        count = delta(f"{name}_count")
        if count <= 0:
            return None
        return delta(f"{name}_sum") / count * scale
    return delta(name) * scale
