"""The metrics that read the device's own timeline and the entry layer's
hand-off (PR 37): every new metric's file against its ``BENCHMARK.json``
entry, each reader on hand-made ``/metrics`` text with two models and both
states of the device, and a rehearsal on the CPU that prints all five for
the tests' tiny cells."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import CHECKOUT
from benchmark.harness import cells, prom
from benchmark.harness.rundata import RunData

CELLS = "tests/benchmark_suite/rehearsal_cells_device_timeline.json"
NEW = {
    "prefill_step_device_ms": ("programs (serving/programs.py)", "ttft_p50_ms"),
    "decode_window_device_ms": ("programs (serving/programs.py)", "tpot_p50_ms"),
    "prefill_step_queued_ms": ("scheduler (serving/scheduler.py)", "ttft_p50_ms"),
    "device_starved_share": ("device", "tpot_p50_ms"),
    "token_handoff_mean_ms": ("entry (serving/openai_compat.py)", "tpot_p50_ms"),
}

# Two models in one process; the window sees both states of the device and
# a cause of each kind: the loop's own wait for work and a phase with work.
START = """# TYPE app_tpu_program_device_seconds histogram
app_tpu_program_device_seconds_sum{model="a",program="prefill_chunk"} 1.0
app_tpu_program_device_seconds_count{model="a",program="prefill_chunk"} 10
app_tpu_program_device_seconds_sum{model="a",program="decode_window"} 2.0
app_tpu_program_device_seconds_count{model="a",program="decode_window"} 20
app_tpu_program_queued_seconds_sum{model="a",program="prefill_chunk"} 0.5
app_tpu_program_queued_seconds_count{model="a",program="prefill_chunk"} 10
app_tpu_device_seconds_total{cause="prefill_chunk",model="a",state="busy"} 1.0
app_tpu_device_seconds_total{cause="decode_window",model="a",state="busy"} 2.0
app_tpu_device_seconds_total{cause="idle",model="a",state="idle"} 5.0
app_tpu_token_handoff_seconds_sum{model="a"} 0.25
app_tpu_token_handoff_seconds_count{model="a"} 100
"""
END = """app_tpu_program_device_seconds_sum{model="a",program="prefill_chunk"} 2.5
app_tpu_program_device_seconds_count{model="a",program="prefill_chunk"} 30
app_tpu_program_device_seconds_sum{model="a",program="decode_window"} 6.0
app_tpu_program_device_seconds_count{model="a",program="decode_window"} 60
app_tpu_program_device_seconds_sum{model="b",program="prefill_chunk"} 0.5
app_tpu_program_device_seconds_count{model="b",program="prefill_chunk"} 10
app_tpu_program_queued_seconds_sum{model="a",program="prefill_chunk"} 2.5
app_tpu_program_queued_seconds_count{model="a",program="prefill_chunk"} 30
app_tpu_device_seconds_total{cause="prefill_chunk",model="a",state="busy"} 2.5
app_tpu_device_seconds_total{cause="decode_window",model="a",state="busy"} 6.0
app_tpu_device_seconds_total{cause="idle",model="a",state="idle"} 5.5
app_tpu_device_seconds_total{cause="prefill",model="a",state="idle"} 0.25
app_tpu_device_seconds_total{cause="decode_window",model="b",state="busy"} 1.5
app_tpu_device_seconds_total{cause="device_window",model="b",state="idle"} 0.25
app_tpu_token_handoff_seconds_sum{model="a"} 0.75
app_tpu_token_handoff_seconds_count{model="a"} 200
app_tpu_token_handoff_seconds_sum{model="b"} 0.5
app_tpu_token_handoff_seconds_count{model="b"} 100
"""


def run_data(start=START, end=END):
    return RunData(
        seconds=40.0, records=[], prom_start=prom.parse(start),
        prom_end=prom.parse(end), prom_samples=[], endpoints={},
    )


def read(name, run):
    spec = cells.layer_metric(name)
    return cells.load_module("readers", spec["reader"]).read(run, **spec["args"])


def test_the_new_metrics_files_agree_with_both_cells_files():
    for cells_file in ("BENCHMARK.json", CELLS):
        with open(os.path.join(CHECKOUT, cells_file)) as fh:
            per_layer = {m["name"]: m for m in json.load(fh)["per_layer"]}
        for name, (layer, moves) in NEW.items():
            entry = per_layer[name]
            # every cell: no list, read by an existing reader from what
            # the program exports
            assert "workloads" not in entry, name
            assert (entry["layer"], entry["moves"]) == (layer, moves)
            assert entry["source"] == "program_span"
            spec = cells.layer_metric(name)
            for key in ("layer", "unit", "better", "source", "moves"):
                assert spec[key] == entry[key], (name, key)
            assert spec["reader"] in ("prom_delta", "prom_delta_where")
            assert spec["what"]


def test_each_reader_reads_the_window_of_every_model():
    run = run_data()
    # (2.5 + 0.5 - 1.0) / (30 + 10 - 10) s over both models' steps
    assert read("prefill_step_device_ms", run) == pytest.approx(2000.0 / 30)
    assert read("decode_window_device_ms", run) == pytest.approx(100.0)
    assert read("prefill_step_queued_ms", run) == pytest.approx(100.0)
    # dry while the host had work (0.25 + 0.25) over busy and dry
    # (1.5 + 4.0 + 1.5 busy; 0.5 + 0.25 + 0.25 dry): the loop's own wait
    # for work is dry time, but not starved time.
    assert read("device_starved_share", run) == pytest.approx(0.5 / 8.0)
    # (0.5 + 0.5) s over 200 records
    assert read("token_handoff_mean_ms", run) == pytest.approx(5.0)


def test_a_program_without_the_timeline_leaves_every_metric_out():
    """The parent exports none of the series: each metric reads nothing,
    not 0, and the parent's line leaves it out."""
    bare = run_data(start="", end="app_tpu_loop_phase_seconds_total 1.0\n")
    for name in NEW:
        assert read(name, bare) is None, name
    # a device that ran no program in the window reads nothing either
    assert read("prefill_step_device_ms", run_data(end=START)) is None
    assert read("device_starved_share", run_data(end=START)) is None


def test_rehearsal_prints_the_timeline_for_a_tiny_cell(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", "tiny-dense.open", "--seed", str(2**31 + 37),
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(metrics) == set(NEW)
    assert all(isinstance(v, float) and v >= 0 for v in metrics.values())
    assert metrics["prefill_step_device_ms"] > 0
    assert metrics["decode_window_device_ms"] > 0
    assert metrics["token_handoff_mean_ms"] > 0
    assert 0 <= metrics["device_starved_share"] < 1
