"""One cell, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Boots the example server as a child under the cell's configuration, warms
up the cell's own shapes, arms the warm-up fence, offers the cell's traffic
over HTTP for ``--seconds``, drains, probes the outputs against the plain
reference, stops the child. The last line of stdout is the result: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics and the breakdown of one device trace taken mid-window.

This process never touches a JAX device: the chip belongs to the child.
Off the chip the run exits non-zero and prints no result. ``--rehearse-cpu``
runs the same phases on the CPU, for the tests' tiny cells; what it prints
says ``"platform": "cpu"`` and is never a measurement.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python gives it

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
# Reading an .xplane.pb imports jax; it must never reach for the chip.
os.environ["JAX_PLATFORMS"] = "cpu"

from benchmark.harness import cells, prom, stats, trace as tr  # noqa: E402
from benchmark.harness.loadgen import Window  # noqa: E402
from benchmark.harness.probe import compared, probe_reference  # noqa: E402
from benchmark.harness.rundata import RunData  # noqa: E402
from benchmark.harness.server import (  # noqa: E402
    ENTRY_POINT, BenchFailure, Server, check, device_of, log,
    peak_memory_bytes,
)
from benchmark.harness.traffic import (  # noqa: E402
    describe, requests_for, warmup_requests,
)

SAMPLE_HZ = 2.0          # gauges, in a traced run
TRACE_MS = 2000          # one capture, in the middle of the window
WARMUP_REQUESTS = 4


def out_dir(workload: str) -> str:
    return os.path.join(CHECKOUT, "chiprun_out", "benchmark", workload)


def fact(what: str, **fields: Any) -> None:
    """A line worth keeping that is not the result: stdout, before it."""
    print(json.dumps({"fact": what, **fields}), flush=True)


async def warm_up(cell: cells.Cell, server: Server, seed: int, vocab: int) -> float:
    """The cell's own shapes and no others, sent together so that a batch
    forms (see ``traffic.warmup_requests``). Returns the seconds it took."""
    requests = warmup_requests(cell.mix, WARMUP_REQUESTS, seed, vocab)
    window = Window(server.http_port, 0.0)
    t0 = time.monotonic()

    async def burst(params: dict, reqs: list, w: Window) -> None:  # noqa: ARG001
        await asyncio.gather(*(w.start(r, None) for r in reqs))

    await window.run(burst, {}, requests)
    bad = [r.why_not() for r in window.records if not r.ok]
    check(not bad, f"warm-up requests failed: {bad}")
    return time.monotonic() - t0


async def sample_gauges(server: Server, window: Window, into: list) -> None:
    loop = asyncio.get_running_loop()
    while not window.ended.is_set():
        await asyncio.sleep(1.0 / SAMPLE_HZ)
        text = await loop.run_in_executor(None, server.metrics_text)
        into.append(prom.parse(text))


async def capture_trace(server: Server, window: Window, into: dict) -> None:
    loop = asyncio.get_running_loop()
    await window.sleep_until(max(0.0, (window.seconds - TRACE_MS / 1e3) / 2))
    into["at_s"] = window.now()
    into.update(await loop.run_in_executor(
        None, lambda: server.get_json(
            f"/debug/tpu-trace?ms={TRACE_MS}", ops=True, timeout=120.0
        ),
    ))


def endpoints_at_window_end(server: Server) -> dict:
    return {
        "debug_loop": server.get_json("/debug/loop", ops=True).get("tpu"),
        "health": server.tpu_health().get("details"),
        "capacity": server.capacity(),
    }


def end_to_end_metrics(cell: cells.Cell, records: list, seconds: float,
                       setup_s: float) -> tuple[dict, dict]:
    metrics, samples = {}, {}
    for entry in cell.end_to_end:
        name = entry["name"]
        if name == "setup_s":
            metrics[name] = {"value": setup_s, "unit": entry["unit"]}
            continue
        value, n = stats.end_to_end(name, records, seconds)
        metrics[name] = {"value": value, "unit": entry["unit"]}
        q = stats.quantile_of(name)
        samples[name] = {
            "samples": n,
            **({"needs": stats.samples_needed(q),
                "supported": stats.tail_supported(n, q)} if q else {}),
        }
    return metrics, samples


def per_layer_metrics(cell: cells.Cell, run: RunData) -> dict:
    metrics = {}
    for entry in cell.per_layer:
        spec = cells.layer_metric(entry["name"])
        reader = cells.load_module("readers", spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:  # nothing to read: left out of the line
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics


@dataclasses.dataclass
class Gathered:
    """What the run's one server child told it, kept after the child is gone."""

    device: dict
    phases: dict
    setup_s: float
    window: Window
    run: RunData
    compiled_in_window: bool
    compile_seconds: dict
    memory_peak: Any
    loop_stalls: list           # the loop profiler's anomalies inside the window
    probe: dict
    capture: dict
    up: bool


def serve_and_measure(cell: cells.Cell, args: argparse.Namespace) -> Gathered:
    """Boot, warm up, fence, window, drain, probe, stop: every phase that
    needs the child alive."""
    rehearse, traced = args.rehearse_cpu, bool(args.trace)
    seconds = float(args.seconds)
    vocab = int(cell.config["vocab_size"])
    kind_params = dict(cell.mix.get("params", {}),
                       pool_seed=cell.mix.get("pool_seed", 0))
    server = Server(
        cell.name, cell.config["path"], cell.config.get("env", {}),
        rehearse, out_dir(cell.name),
    )
    phases: dict[str, float] = {}
    try:
        # The seed's requests are drawn while the child boots.
        n = cell.kind.count(kind_params, seconds)
        requests = requests_for(cell.mix, n, args.seed, vocab)
        fact("traffic", workload=cell.name, seed=args.seed,
             kind=cell.mix["kind"], **describe(requests))

        phases["boot_s"] = server.wait_ready()
        device = device_of(server, cell.chips, "cpu" if rehearse else "tpu")
        log(f"serving after {phases['boot_s']:.1f}s on {device}")

        phases["warm_up_s"] = asyncio.run(
            warm_up(cell, server, args.seed + 1, vocab)
        )
        server.arm_fence()
        compiles_warm = server.capacity()["compiles"]
        prom_start = prom.parse(server.metrics_text())
        passes_warm = (server.get_json("/debug/loop", ops=True).get("tpu")
                       or {}).get("passes", 0)

        samples: list = []
        capture: dict = {}

        async def during(window: Window) -> None:
            await asyncio.gather(
                sample_gauges(server, window, samples),
                capture_trace(server, window, capture),
            )

        window = Window(server.http_port, seconds)
        t0 = asyncio.run(window.run(
            cell.kind.drive, kind_params, requests,
            during if traced else None,
        ))
        phases["window_and_drain_s"] = time.monotonic() - t0

        prom_end = prom.parse(server.metrics_text())
        endpoints = endpoints_at_window_end(server)
        memory_peak = peak_memory_bytes(server)  # before the reference runs
        recompiles = "app_tpu_steady_state_recompiles_total"
        compiles_end = endpoints["capacity"]["compiles"]
        loop = endpoints["debug_loop"] or {}
        loop_stalls = [
            a for a in (*loop.get("pinned_anomalies", ()), *loop.get("anomalies", ()))
            if a.get("pass", 0) > passes_warm
        ]

        t_probe = time.monotonic()
        probe = probe_reference(server, cell.config, args.seed, vocab)
        phases["probe_s"] = time.monotonic() - t_probe
        fact("probe", **probe)

        up = server.tpu_health().get("status") == "UP"
        server.stop()
    finally:
        server.close()
    return Gathered(
        device=device, phases=phases, setup_s=t0 - T_START, window=window,
        run=RunData(
            seconds=seconds, records=window.records, prom_start=prom_start,
            prom_end=prom_end, prom_samples=samples, endpoints=endpoints,
        ),
        compiled_in_window=(
            prom.total(prom_end, recompiles) != prom.total(prom_start, recompiles)
            or compiles_end["total"] != compiles_warm["total"]
        ),
        compile_seconds={
            k: v["seconds_total"] for k, v in compiles_end["programs"].items()
            if v["compiles"]
        },
        memory_peak=memory_peak, loop_stalls=loop_stalls, probe=probe,
        capture=capture, up=up,
    )


def read_capture(capture: dict, keep_in: str = "") -> Any:
    """The capture's device trace (None without one); the profiler's
    directory, which lies under TMPDIR, is removed."""
    if not capture.get("trace_dir"):
        return None
    trace = None
    xplane = tr.find_xplane(capture["trace_dir"])
    if xplane is not None:
        trace = tr.read_xplane(xplane)
        if keep_in:
            shutil.copy(xplane, os.path.join(keep_in, "trace.xplane.pb"))
    shutil.rmtree(capture["trace_dir"], ignore_errors=True)
    return trace


def run_cell(args: argparse.Namespace) -> dict:
    for path in (ENTRY_POINT, os.path.join(CHECKOUT, "gofr_tpu")):
        check(os.path.exists(path), f"not a checkout of the repo: no {path}")
    cell = cells.load_cell(args.cells, args.workload)
    rehearse, traced = args.rehearse_cpu, bool(args.trace)
    if rehearse:
        log("REHEARSAL on the CPU: this is not a measurement")
    got = serve_and_measure(cell, args)
    directory = out_dir(cell.name)

    run = got.run
    if traced:
        run.trace = read_capture(
            got.capture, directory if args.keep_trace else ""
        )
    has_device_ops = run.trace is not None and any(run.trace.devices.values())
    check(
        has_device_ops or rehearse or not traced,
        f"the traced run captured no device operation: {got.capture}",
    )

    failed = [r for r in run.records if not r.ok]
    for note in got.window.notes:
        log(note)
    for r in failed[:5]:
        log(r.why_not())
    correct = (
        got.device["platform"] == ("cpu" if rehearse else "tpu") and got.up
        and not got.compiled_in_window and not failed and got.probe["agrees"]
    )
    e2e, sample_counts = end_to_end_metrics(
        cell, run.records, run.seconds, got.setup_s
    )
    fact("window", seconds=run.seconds, phases=got.phases, setup_s=got.setup_s,
         samples=sample_counts, compiled_in_window=got.compiled_in_window,
         compile_seconds=got.compile_seconds, loop_stalls=got.loop_stalls,
         end_to_end={k: v["value"] for k, v in e2e.items()},
         server_log=os.path.relpath(
             os.path.join(directory, "server.log"), CHECKOUT))

    result: dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(run.records),
        "failed": len(failed),
        "metrics": per_layer_metrics(cell, run) if traced else e2e,
        "device": {
            "platform": got.device["platform"], "kind": got.device["kind"],
            "count": got.device["count"], "memory_peak_bytes": got.memory_peak,
        },
    }
    if has_device_ops:
        result["device"]["busy_s"] = tr.busy_s(run.trace)
        result["device"]["window_s"] = run.trace.window_s()
        result["breakdown"] = cells.load_module(
            "readers", "trace_top_ops"
        ).read(run)
    # What ``correct`` was decided from, each number beside its limit: last
    # in the line and last on standard error.
    result["compared"] = {
        "failed_requests": {"value": len(failed), "limit": 0, "rule": "=="},
        "compiled_in_window": {
            "value": int(got.compiled_in_window), "limit": 0, "rule": "=="},
        **compared(got.probe),
    }
    with open(os.path.join(directory, f"result.trace{int(traced)}.json"), "w") as fh:
        json.dump({
            "result": result, "phases": got.phases, "samples": sample_counts,
            "probe": got.probe, "loop_stalls": got.loop_stalls,
            # every request as the client saw it, for a run that reads far off
            "requests": [
                {"index": r.index, "prompt_tokens": r.prompt_tokens,
                 "asked_tokens": r.asked_tokens, "due_s": r.due_s,
                 "sent_s": r.sent_s, "tokens": len(r.token_s),
                 "first_token_s": r.token_s[0] if r.token_s else None,
                 "last_token_s": r.token_s[-1] if r.token_s else None,
                 "longest_gap_s": max(
                     (b - a for a, b in zip(r.token_s, r.token_s[1:])),
                     default=None),
                 "finish_reason": r.finish_reason, "error": r.error}
                for r in run.records
            ],
        }, fh, indent=1)
    return result


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--cells", default="BENCHMARK.json",
        help="file of BENCHMARK.json's shape that names the cell",
    )
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="same phases on the CPU for the tests' tiny cells; never a "
             "measurement",
    )
    parser.add_argument(
        "--keep-trace", action="store_true",
        help="copy the capture's .xplane.pb beside the server log",
    )
    args = parser.parse_args()
    try:
        result = run_cell(args)
    except BenchFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    for name, found in result["compared"].items():
        log(f"compared {name}: {found['value']} {found['rule']} {found['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
