"""The ``breakdown`` of a traced run: the programs and the device
operations that took most time, under the names the trace prints, and the
idle gaps.

First the jitted programs (``module jit_spec_window``: the seconds its
executions ran), which split the device's time by prefill and decode with no
name added to the program. Then the operations, each with its self time: an
event that encloses others on the same line (a ``while`` around its body's
fusions) is charged only what its children do not cover, so the operations
sum to the busy time. Pallas kernels carry their function names; XLA fusions
are anonymous today (``op %fusion.322 bf16[14,4096]``). Idle gaps
carry no host phase yet (no ``TraceAnnotation`` in the program), so they
are ``unattributed`` and grouped by length, which still tells a thousand
launch gaps from one long stall.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import trace as tr

TOP = 10
GAP_CLASSES = (
    (1e-4, "unattributed_under_100us"),
    (1e-3, "unattributed_100us_to_1ms"),
    (1e-2, "unattributed_1ms_to_10ms"),
    (float("inf"), "unattributed_over_10ms"),
)


def self_times(ops: list) -> dict[str, float]:
    """name -> seconds of self time, over one device's sorted ops."""
    totals: dict[str, float] = {}
    stack: list[list] = []  # [name, end_ns, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, start, dur in ops:
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def read(run: Any) -> Optional[dict]:
    if run.trace is None or not any(run.trace.devices.values()):
        return None
    n = len(run.trace.devices)
    totals: dict[str, float] = {}
    modules: dict[str, float] = {}
    for ops in run.trace.devices.values():
        for name, seconds in self_times(ops).items():
            name = f"op {tr.short_name(name)}"
            totals[name] = totals.get(name, 0.0) + seconds / n
    for executions in run.trace.modules.values():
        for name, _, dur in executions:
            name = f"module {tr.short_name(name)}"
            modules[name] = modules.get(name, 0.0) + dur / 1e9 / n
    gaps: dict[str, float] = {}
    for gap in tr.idle_gaps(run.trace):
        label = next(name for limit, name in GAP_CLASSES if gap < limit)
        gaps[label] = gaps.get(label, 0.0) + gap / n
    by_time = lambda kv: -kv[1]  # noqa: E731
    programs = sorted(modules.items(), key=by_time)[:TOP // 2]
    return {
        "device_ops": [[name, s] for name, s in programs] + [
            [name, s] for name, s in
            sorted(totals.items(), key=by_time)[:TOP - len(programs)]
        ],
        "idle_gaps": [
            [name, s] for name, s in sorted(gaps.items(), key=by_time)[:TOP]
        ],
    }
