"""The loop profiler's ready stamps against the device's own program ends,
in a capture kept with ``run.py --trace 1 --keep-trace`` (PR 37):

    python3 scripts/device_wait_check.py chiprun_out/benchmark/<cell>/trace.xplane.pb

The watcher waits on each dispatched program inside
``TraceAnnotation("device_wait/<program>")`` on a host line; the device's
``XLA Modules`` line holds one event per execution (``jit_decode_window``,
``jit_prefill_chunk_step``). One JSON line a program: its executions in the
capture and their mean length; how many a wait ended near (within 50 ms),
and of those the share whose wait ended within 1 ms of the execution's end,
the offsets' median, 95th percentile and largest (wait end minus execution
end); the same against the TPU runtime's own completion, the first
``CompleteCallbacks`` event on the host's lines that ends after the
execution (on the v5e the runtime reads the program's sync flag 1.4 to 3.3
ms after its end, PR 37: no host-side reader learns of it sooner); and, over
the matched executions, their mean length beside the mean time from the
wait end before theirs (any program's) to their own, which is the
timeline's ``ready_i - ready_{i-1}``: the device time it records when the
program was queued. Executions cut by the capture's edges have no wait near
them and are counted apart. Nothing here is a metric of the harness.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import statistics
import sys

MODULES_LINE = "XLA Modules"
WAIT = "device_wait/"
RUNTIME_DONE = "CompleteCallbacks"
NEAR_NS = 50e6


def read(path: str) -> tuple[dict, dict, list]:
    """(executions by module name, wait ends by program, the runtime's
    completion ends): stamps in ns."""
    from jax.profiler import ProfileData

    modules: dict[str, list[tuple[float, float]]] = collections.defaultdict(list)
    waits: dict[str, list[float]] = collections.defaultdict(list)
    done: list[float] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if line.name == MODULES_LINE:
                    name = e.name.partition("(")[0]
                    modules[name].append((e.start_ns, e.start_ns + e.duration_ns))
                elif e.name.startswith(WAIT):
                    waits[e.name[len(WAIT):]].append(e.start_ns + e.duration_ns)
                elif e.name == RUNTIME_DONE:
                    done.append(e.start_ns + e.duration_ns)
    return modules, {p: sorted(ends) for p, ends in waits.items()}, sorted(done)


def summary(offsets: list[float], prefix: str) -> dict:
    """The share within 1 ms and the spread of ``offsets`` (ms)."""
    if not offsets:
        return {}
    absolute = sorted(abs(o) for o in offsets)
    return {
        f"{prefix}within_1ms_share": round(
            sum(a <= 1.0 for a in absolute) / len(absolute), 4),
        f"{prefix}offset_median_ms": round(statistics.median(offsets), 4),
        f"{prefix}abs_offset_p95_ms": round(
            absolute[int(0.95 * (len(absolute) - 1))], 4),
        f"{prefix}abs_offset_max_ms": round(absolute[-1], 4),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("xplane", help="a capture's .xplane.pb")
    args = parser.parse_args()
    modules, waits, done = read(args.xplane)
    every_end = sorted(end for ends in waits.values() for end in ends)
    for program, ends in sorted(waits.items()):
        runs = sorted(
            span for name, spans in modules.items() if program in name
            for span in spans
        )
        offsets, from_done, lengths, since = [], [], [], []
        for start, end in runs:
            i = bisect.bisect_left(ends, end)
            near = [ends[j] for j in (i - 1, i) if 0 <= j < len(ends)]
            best = min(near, key=lambda w: abs(w - end), default=None)
            if best is None or abs(best - end) > NEAR_NS:
                continue
            offsets.append((best - end) / 1e6)
            k = bisect.bisect_left(done, end)
            if k < len(done):
                from_done.append((best - done[k]) / 1e6)
            i = bisect.bisect_left(every_end, best)
            if i > 0:
                lengths.append((end - start) / 1e6)
                since.append((best - every_end[i - 1]) / 1e6)
        print(json.dumps({
            "program": program,
            "executions": len(runs),
            "waits": len(ends),
            "matched": len(offsets),
            "unmatched": len(runs) - len(offsets),
            **summary(offsets, ""),
            **summary(from_done, "runtime_done_"),
            "matched_execution_mean_ms":
                round(statistics.fmean(lengths), 4) if lengths else None,
            "ready_since_previous_mean_ms":
                round(statistics.fmean(since), 4) if since else None,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
