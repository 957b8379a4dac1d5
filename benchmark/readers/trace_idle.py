"""1 - (union of device-op intervals) / traced span, from the one
``/debug/tpu-trace`` capture taken in the middle of the window.

    {"reader": "trace_idle", "args": {}}

Averaged over the chips traced. No capture, or one with no device op,
reads nothing (the CPU rehearsal has no device plane).
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import trace as tr


def read(run: Any) -> Optional[float]:
    if run.trace is None or not any(run.trace.devices.values()):
        return None
    window = run.trace.window_s()
    if window <= 0:
        return None
    return 1.0 - tr.busy_s(run.trace) / window
