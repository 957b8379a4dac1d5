"""A percentile of the client's own samples, for a tail that is recorded
and not judged.

    {"reader": "client_percentile", "args": {"family": "ttft", "q": 90}}

``family`` is ``ttft`` or ``tpot`` (``harness/stats.py``); the arithmetic is
the end-to-end metrics' own.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import stats


def read(run: Any, family: str, q: float) -> Optional[float]:
    samples = stats.SAMPLES[family](run.records)
    return stats.percentile(samples, q) if samples else None
