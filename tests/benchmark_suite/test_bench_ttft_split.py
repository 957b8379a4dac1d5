"""The metrics that split time to first token, the prefill step's fill
and the loop's time over exactly the window (PR 25): the
``prom_delta_where`` reader on hand-made ``/metrics`` text, every new
metric's file against its ``BENCHMARK.json`` entry, and a rehearsal on
the CPU that prints them for the tests' tiny cells."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import CHECKOUT
from benchmark.harness import cells, prom
from benchmark.harness.rundata import RunData

CELLS = "tests/benchmark_suite/rehearsal_cells_ttft_split.json"
EVERYWHERE = {
    "ttft_entry_mean_ms", "ttft_prefill_wait_mean_ms",
    "ttft_prefill_dispatch_mean_ms", "ttft_first_token_wait_mean_ms",
    "ttft_delivery_mean_ms", "prefill_fill_mean", "gc_pause_in_window_s",
}
BY_CELL = {"window_occupancy_mean", "loop_host_share"}

# Two models' loops in one process, and a collector that ran.
START = """# TYPE app_tpu_loop_phase_seconds_total counter
app_tpu_loop_phase_seconds_total{model="a",phase="idle"} 100.0
app_tpu_loop_phase_seconds_total{model="a",phase="device_window"} 8.0
app_tpu_loop_phase_seconds_total{model="a",phase="prefill"} 1.0
app_tpu_loop_phase_seconds_total{model="a",phase="other"} 1.0
app_tpu_loop_phase_seconds_total{model="b",phase="device_window"} 50.0
app_tpu_gc_pause_seconds_total{generation="0"} 0.5
"""
END = """app_tpu_loop_phase_seconds_total{model="a",phase="idle"} 110.0
app_tpu_loop_phase_seconds_total{model="a",phase="device_window"} 44.0
app_tpu_loop_phase_seconds_total{model="a",phase="prefill"} 3.0
app_tpu_loop_phase_seconds_total{model="a",phase="other"} 1.5
app_tpu_loop_phase_seconds_total{model="a",phase="dispatch"} 1.5
app_tpu_loop_phase_seconds_total{model="b",phase="device_window"} 50.0
app_tpu_gc_pause_seconds_total{generation="0"} 0.75
app_tpu_gc_pause_seconds_total{generation="2"} 0.5
"""


def run_data(start=START, end=END):
    return RunData(
        seconds=40.0, records=[], prom_start=prom.parse(start),
        prom_end=prom.parse(end), prom_samples=[], endpoints={},
    )


def test_prom_delta_where_selects_series_by_their_labels():
    reader = cells.load_module("readers", "prom_delta_where")
    run = run_data()
    name = "app_tpu_loop_phase_seconds_total"
    assert reader.read(run, name) == 50.0  # every series, as prom_delta
    assert reader.read(run, name, where={"phase": "device_window"}) == 36.0
    assert reader.read(run, name, where={"phase": "device_window",
                                         "model": "b"}) == 0.0
    assert reader.read(run, name, where={"phase": ["prefill", "other"]}) == 2.5
    # A series that starts inside the window counts from 0 ...
    assert reader.read(run, name, where={"phase": "dispatch"}) == 1.5
    assert reader.read(run, "app_tpu_gc_pause_seconds_total",
                       where={"generation": "2"}, scale=1000.0) == 500.0
    # ... a label that nothing carries selects nothing ...
    assert reader.read(run, name, where={"phase": "reap"}) == 0.0
    # ... and a metric the program does not export reads nothing at all,
    # as a ratio over it does: the parent's line leaves the metric out.
    assert reader.read(run, "app_tpu_not_there_total") is None
    assert reader.read(run, "app_tpu_not_there_total",
                       over={"without": {"phase": "idle"}}) is None
    assert reader.read(run_data(end=START), name,
                       over={"without": {"phase": "idle"}}) is None
    # The loop's host share of its busy time over the window: every phase
    # but idle and device_window (2 + 0.5 + 1.5), over every phase but
    # idle (those and 36 of device_window), whatever the model.
    share = dict(without={"phase": ["idle", "device_window"]},
                 over={"without": {"phase": ["idle"]}})
    assert reader.read(run, name, **share) == pytest.approx(4.0 / 40.0)
    assert reader.read(run, name, where={"model": "a"}, **share) == (
        pytest.approx(4.0 / 40.0)  # `over` selects for itself
    )
    assert reader.matches('{model="a\\"b",phase="idle"}', {"model": 'a\\"b'}, {})
    assert not reader.matches("", {"phase": "idle"}, {})
    assert reader.matches("", {}, {"phase": "idle"})


def test_the_new_metrics_files_agree_with_both_cells_files():
    for cells_file, chat, batch in (
        ("BENCHMARK.json", "mistral-7b.chat", "mixtral-8x7b-d4.batch"),
        (CELLS, "tiny-dense.open", "tiny-moe.closed"),
    ):
        with open(os.path.join(CHECKOUT, cells_file)) as fh:
            per_layer = {m["name"]: m for m in json.load(fh)["per_layer"]}
        for name in EVERYWHERE:
            assert "workloads" not in per_layer[name], name
        for stem in BY_CELL:
            # a list may grow: each holds its first cell, and none holds both
            in_chat = per_layer[f"{stem}.chat"]["workloads"]
            in_batch = per_layer[f"{stem}.batch"]["workloads"]
            assert chat in in_chat and batch in in_batch
            assert not set(in_chat) & set(in_batch)
            # both paces are judged by their median since PR 27; the split
            # by cell stays, so each cell's reading keeps a name of its own
            assert per_layer[f"{stem}.chat"]["moves"] == "tpot_p50_ms"
            assert per_layer[f"{stem}.batch"]["moves"] == "tpot_p50_ms"
        for name in EVERYWHERE | {f"{s}.{c}" for s in BY_CELL
                                  for c in ("chat", "batch")}:
            spec = cells.layer_metric(name)
            for key in ("layer", "unit", "better", "source", "moves"):
                assert spec[key] == per_layer[name][key], (name, key)
            assert spec["what"] and hasattr(
                cells.load_module("readers", spec["reader"]), "read"
            )


CELLS_FILES = ["BENCHMARK.json", CELLS] + [
    f"tests/benchmark_suite/rehearsal_cells{which}.json"
    for which in ("", "_looped", "_share")
]


@pytest.mark.parametrize("cells_file", CELLS_FILES)
def test_a_split_list_holds_the_cells_of_its_kind_of_loop(cells_file):
    """The rule the lists of one cell each stood for: a quantity split by
    cell takes an open loop's cells under ``.chat`` and a closed loop's
    under ``.batch``, by the kind that each cell's traffic file names; a
    cell that joins a list of the other kind would be read as the same
    quantity under another regime."""
    with open(os.path.join(CHECKOUT, cells_file)) as fh:
        bench = json.load(fh)
    loop = {
        w["name"]: cells.loop_kind(cells.load_cell(cells_file, w["name"]).mix)
        for w in bench["workloads"]
    }
    wanted = {"chat": "open", "batch": "closed"}
    split = [m for m in bench["per_layer"]
             if m["name"].rpartition(".")[2] in wanted]
    assert split
    for m in split:
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert loop[cell] == wanted[m["name"].rpartition(".")[2]], (
                m["name"], cell)


@pytest.mark.parametrize("workload,suffix", [
    ("tiny-dense.open", "chat"), ("tiny-moe.closed", "batch"),
])
def test_rehearsal_prints_the_split_for_a_tiny_cell(workload, suffix, tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", workload, "--seed", str(2**31 + 25), "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(metrics) == EVERYWHERE | {f"{s}.{suffix}" for s in BY_CELL}
    assert all(isinstance(v, float) and v >= 0 for v in metrics.values())
    # Ratios are ratios; a tiny prompt fills little of a prefill step; the
    # handler's two phases are there because the requests came over HTTP.
    for name in ("prefill_fill_mean", f"window_occupancy_mean.{suffix}",
                 f"loop_host_share.{suffix}"):
        assert 0 < metrics[name] <= 1, name
    assert metrics["ttft_entry_mean_ms"] > 0
    assert metrics["ttft_delivery_mean_ms"] > 0
