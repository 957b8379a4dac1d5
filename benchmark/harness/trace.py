"""From the profiler's ``.xplane.pb`` to device-op intervals.

``/debug/tpu-trace`` runs ``jax.profiler`` inside the process that holds
the chip and leaves ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
A device plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per operation that ran on that chip's core, with a start and a
duration in nanoseconds, named by its whole HLO instruction and nested
(a ``while`` encloses its body's fusions); its line ``XLA Modules`` holds one
event per execution of a jitted program, named ``jit_<function>(<hash>)``.
Busy time is the union of the op intervals; the traced span of a chip runs
from the first start to the last end on any device of the capture, so that
every chip is measured over the same span. (Seen by hand in a capture on the
v5e under ``mistral-7b.chat``, PR 24: 163,530 op events and 9 module events
in 2.6 s, 13 MB.)

Reading needs ``jax.profiler.ProfileData`` and so imports jax: the parent
does it under ``JAX_PLATFORMS=cpu`` and only after the server child, the
owner of the chip, has exited.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Any, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Events = list[tuple[str, float, float]]  # (name, start_ns, duration_ns), sorted


@dataclasses.dataclass
class DeviceTrace:
    """Per device, every op and every program execution of the capture."""

    devices: dict[str, Events]
    modules: dict[str, Events] = dataclasses.field(default_factory=dict)

    @property
    def span_ns(self) -> tuple[float, float]:
        starts = [ops[0][1] for ops in self.devices.values() if ops]
        ends = [
            max(s + d for _, s, d in ops)
            for ops in self.devices.values() if ops
        ]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)

    def window_s(self) -> float:
        lo, hi = self.span_ns
        return (hi - lo) / 1e9

    def to_json(self) -> dict:
        return {
            key: {name: [list(e) for e in events] for name, events in group.items()}
            for key, group in (("devices", self.devices), ("modules", self.modules))
        }

    @classmethod
    def from_json(cls, data: dict) -> "DeviceTrace":
        return cls(**{
            key: {name: _sorted(events) for name, events in data.get(key, {}).items()}
            for key in ("devices", "modules")
        })


def _sorted(events: Any) -> Events:
    return sorted(
        ((str(n), float(s), float(d)) for n, s, d in events),
        key=lambda e: e[1],
    )


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def read_xplane(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    trace = DeviceTrace(devices={})
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            into = {OPS_LINE: trace.devices, MODULES_LINE: trace.modules}.get(line.name)
            if into is not None:
                into[plane.name] = _sorted(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                )
    return trace


def short_name(name: str) -> str:
    """``%fusion.322 bf16[14,4096]`` from an op's whole HLO instruction,
    ``jit_spec_window`` from ``jit_spec_window(7887516207321774033)``."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name.partition("(")[0] or name
    shape = "(tuple)" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} {shape}"[:96]


def read_recorded(path: str) -> DeviceTrace:
    """A trace kept as ``DeviceTrace.to_json`` (gzip or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        return DeviceTrace.from_json(json.load(fh))


def busy_intervals(ops: Events) -> list[tuple[float, float]]:
    """The union of the op intervals, as disjoint (start, end) in order.
    Nested and overlapping ops (a fusion inside a while loop's event)
    count once."""
    merged: list[tuple[float, float]] = []
    for _, start, dur in ops:
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_s(trace: DeviceTrace) -> float:
    """Seconds in which an op ran, averaged over the chips traced."""
    per_device = [
        sum(e - s for s, e in busy_intervals(ops)) / 1e9
        for ops in trace.devices.values()
    ]
    return sum(per_device) / len(per_device) if per_device else 0.0


def idle_gaps(trace: DeviceTrace) -> list[float]:
    """Every gap between busy intervals inside the traced span, seconds,
    over all chips (head and tail of the span included)."""
    lo, hi = trace.span_ns
    gaps: list[float] = []
    for ops in trace.devices.values():
        cursor = lo
        for start, end in busy_intervals(ops):
            if start > cursor:
                gaps.append((start - cursor) / 1e9)
            cursor = max(cursor, end)
        if hi > cursor:
            gaps.append((hi - cursor) / 1e9)
    return gaps


def summarize(path: str, top: int = 25) -> dict:
    """Every plane and line of an .xplane.pb with its event count, and the
    names that took most time on each device line: for looking at one
    trace by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            totals: dict[str, list] = {}
            first, last, n = None, 0.0, 0
            for e in line.events:
                n += 1
                entry = totals.setdefault(e.name, [0, 0.0])
                entry[0] += 1
                entry[1] += e.duration_ns
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
            keep = plane.name.startswith(DEVICE_PLANE_PREFIX)
            lines.append({
                "line": line.name, "events": n,
                "span_ms": (last - (first or 0.0)) / 1e6,
                "top": sorted(
                    ([name[:120], c, ns / 1e6] for name, (c, ns) in totals.items()),
                    key=lambda row: -row[2],
                )[: top if keep else 5],
            })
        planes.append({"plane": plane.name, "lines": lines})
    return {"path": path, "planes": planes}


if __name__ == "__main__":
    import sys

    print(json.dumps(summarize(sys.argv[1]), indent=1))
