"""The longest any stream stood still between two of its tokens, in
milliseconds, over every request of the window.

    {"reader": "client_gap"}

Tokens arrive in groups (one decode window at a time), so the usual gap is
one window plus the prefill steps in between. A run in which the whole
engine stood still for seconds reads those seconds here, whatever the
medians say; time to first token is not a gap and is left out.
"""

from __future__ import annotations

from typing import Any, Optional


def read(run: Any) -> Optional[float]:
    gaps = [
        b - a for r in run.records for a, b in zip(r.token_s, r.token_s[1:])
    ]
    return max(gaps) * 1e3 if gaps else None
