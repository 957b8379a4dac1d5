"""Find the knee of an open-loop cell, once, on the chip.

    python3 benchmark/sweep.py --workload mistral-7b.chat --rates 1,2,3,4,5,6

Boots the cell's server once and offers the cell's mix at each rate for
``--step-seconds`` (one process, the same warm-up and fence as a run,
drained between steps). A rate is sustained when at least 98% of the
requests offered complete and the median time to first token of the
step's last third is not above 1.5 times that of its first third (the
queue is not growing). The knee is the highest sustained rate; write 0.8
of it, rounded down to 0.1 requests/s, into the mix as a number. A run
never searches: the rate in the mix is fixed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
os.environ["JAX_PLATFORMS"] = "cpu"  # this process never touches the chip

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import cells, stats  # noqa: E402
from benchmark.harness.loadgen import Window  # noqa: E402
from benchmark.harness.server import (  # noqa: E402
    BenchFailure, Server, device_of, log,
)
from benchmark.harness.traffic import requests_for  # noqa: E402

COMPLETE_SHARE = 0.98
GROWTH_LIMIT = 1.5


def step_row(rate: float, records: list, seconds: float) -> dict:
    ordered = sorted(records, key=lambda r: r.due_s)
    third = max(1, len(ordered) // 3)
    first = stats.percentile(stats.ttft_samples(ordered[:third]), 50)
    last = stats.percentile(stats.ttft_samples(ordered[-third:]), 50)
    done = sum(1 for r in records if r.ok)
    ttft = stats.ttft_samples(records)
    tpot = stats.tpot_samples(records)
    return {
        "rate": rate, "offered": len(records), "completed": done,
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p95_ms": stats.percentile(ttft, 95),
        "tpot_p50_ms": stats.percentile(tpot, 50) if tpot else None,
        "tpot_p95_ms": stats.percentile(tpot, 95) if tpot else None,
        "out_tok_per_s": stats.tokens_in_window(records, seconds) / seconds,
        "ttft_p50_first_third_ms": first, "ttft_p50_last_third_ms": last,
        "sustained": done >= COMPLETE_SHARE * len(records)
        and last <= GROWTH_LIMIT * first,
    }


def sweep(args: argparse.Namespace) -> dict:
    cell = cells.load_cell(args.cells, args.workload)
    platform = "cpu" if args.rehearse_cpu else "tpu"
    vocab = int(cell.config["vocab_size"])
    directory = os.path.join(bench_run.out_dir(args.workload), "sweep")
    server = Server(
        args.workload, cell.config["path"], cell.config.get("env", {}),
        args.rehearse_cpu, directory,
    )
    rows = []
    try:
        server.wait_ready()
        device = device_of(server, cell.chips, platform)
        asyncio.run(bench_run.warm_up(cell, server, args.seed + 1, vocab))
        server.arm_fence()
        for i, rate in enumerate(args.rates):
            params = dict(cell.mix.get("params", {}), rate=rate,
                          pool_seed=cell.mix.get("pool_seed", 0))
            n = cell.kind.count(params, args.step_seconds)
            requests = requests_for(cell.mix, n, args.seed + i, vocab)
            window = Window(server.http_port, args.step_seconds)
            asyncio.run(window.run(cell.kind.drive, params, requests))
            row = step_row(rate, window.records, args.step_seconds)
            rows.append(row)
            print(json.dumps(row), flush=True)
        recompiles = server.capacity()["compiles"]["steady_state_recompiles"]
        server.stop()
    finally:
        server.close()
    sustained = [r["rate"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    out = {
        "workload": args.workload, "device": device,
        "step_seconds": args.step_seconds, "rows": rows, "knee": knee,
        "rate_for_the_mix": int(0.8 * knee * 10 + 1e-9) / 10 if knee else None,
        "steady_state_recompiles": recompiles,
    }
    with open(os.path.join(directory, "sweep.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True,
                        type=lambda s: [float(x) for x in s.split(",")])
    parser.add_argument("--step-seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--cells", default="BENCHMARK.json")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()
    try:
        out = sweep(args)
    except BenchFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
