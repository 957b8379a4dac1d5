"""Step times by program, and each program's ops by name, from a kept capture
(the hand reductions of PERF.md sections 5 and 6 since PR 34):

    python3 benchmark/run.py --workload mixtral-8x7b-d4.batch --seed N \\
        --seconds 51 --trace 1 --keep-trace          # on the chip
    python3 scripts/trace_steps.py chiprun_out/benchmark/<cell>/trace.xplane.pb

Reads the capture's ``XLA Modules`` and ``XLA Ops`` lines through the
benchmark's own reader (``benchmark/harness/trace.py``). A device, one JSON
line a program: how many executions the capture holds, their sum, median and
every length in ms (the first and the last may be cut by the capture's
edges); then its ``--top`` ops by total time, each with its count and its ms
an execution. An op belongs to the program execution it starts in. What
PERF.md derives from these lines by hand (a decode window's expert fusions a
layer-step = their sum / (windows x 8 steps x layers); a tile product's
TFLOP/s against ``benchmark/harness/peaks.py``) is arithmetic on them, not a
metric of the harness.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("xplane", help="a capture's .xplane.pb")
    parser.add_argument("--top", type=int, default=14)
    args = parser.parse_args()

    from benchmark.harness import trace as tr

    capture = tr.read_xplane(args.xplane)
    for device, modules in capture.modules.items():
        runs = collections.defaultdict(list)  # program -> [(start, end)]
        for name, start, duration in modules:
            runs[tr.short_name(name)].append((start, start + duration))
        for program, spans in runs.items():
            ops = collections.defaultdict(lambda: [0.0, 0])  # name -> [ns, n]
            i = 0
            for name, start, duration in capture.devices.get(device, []):
                while i < len(spans) and spans[i][1] < start:
                    i += 1
                if i < len(spans) and spans[i][0] <= start:
                    entry = ops[tr.short_name(name)]
                    entry[0] += duration
                    entry[1] += 1
            lengths = sorted((end - start) / 1e6 for start, end in spans)
            top = sorted(ops.items(), key=lambda kv: -kv[1][0])[:args.top]
            print(json.dumps({
                "device": device, "program": program, "n": len(lengths),
                "sum_ms": round(sum(lengths), 3),
                "median_ms": round(statistics.median(lengths), 3),
                "ms": [round(ms, 2) for ms in lengths],
                "top_ops": [
                    {"op": name, "sum_ms": round(ns / 1e6, 3), "n": n,
                     "ms_each": round(ns / 1e6 / n, 4)}
                    for name, (ns, n) in top
                ],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
