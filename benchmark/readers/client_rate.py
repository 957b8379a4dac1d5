"""Output tokens the clients received inside the window, per second of the
window: a rate that is recorded and not judged.

    {"reader": "client_rate"}

The arithmetic is ``harness/stats.py``'s own (``out_tok_per_s``): all the
tokens stamped inside the window over all its seconds.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import stats


def read(run: Any) -> Optional[float]:
    if not run.records or run.seconds <= 0:
        return None
    return stats.tokens_in_window(run.records, run.seconds) / run.seconds
