"""A looped decoder through the harness (ISSUE 28): the new files of
``ouro-2.6b.reason`` against ``BENCHMARK.json``, and a rehearsal on the CPU
of the tests' tiny looped model, which goes through by the reference module
its configuration names (``looped``) and not by the shared one-pass
decoder."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import CHECKOUT
from benchmark.harness import cells, prom
from benchmark.harness.rundata import RunData

CELLS = "tests/benchmark_suite/rehearsal_cells_looped.json"
CELL = "ouro-2.6b.reason"
NEW_METRICS = {"kv_bytes_per_token.reason", "kv_live_share_mean.reason"}
ABLATIONS = {"causal", "passes", "pass_norm", "post_norm", "pass_cache"}


def rehearse(workload, tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", workload, "--seed", str(2**31 + 28), "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    return lines[-1], {line["fact"]: line for line in lines[:-1]}


def test_rehearsal_of_a_looped_cell_is_correct_with_every_ablation_applied(
    tmp_path,
):
    result, facts = rehearse("tiny-looped.closed", tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    probe = facts["probe"]
    limits = probe["tolerances"]
    assert probe["agrees"] and probe["median"] < limits["median"] / 2
    # Each of the reference's five pieces, removed, fails the probe. On this
    # 2 x 3 stack in bfloat16 the norm between passes moves a token by 0.12
    # nats at the median (two branches a pass leave the stream near the rms
    # the norm would give it), the others by 0.3 to 2.3; the plain
    # comparison reads 0.008.
    assert set(probe["ablated"]) == ABLATIONS
    for name, found in probe["ablated"].items():
        assert not found["agrees"], name
        assert found["median"] > 1.25 * limits["median"], name
        assert found["median"] > 10 * probe["median"], name
    assert result["compared"]["ablations_applied"]["value"] == 5
    assert result["compared"]["ablations_still_agreeing"]["value"] == 0
    # The two metrics this cell brings read the program's new counters:
    # 6 entries x (k, v) x 4 heads x 16 x 2 B a token, and a live share of
    # the 4 x 256 positions that is a share.
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert NEW_METRICS <= set(metrics)
    assert metrics["kv_bytes_per_token.reason"] == 1536.0
    assert 0 < metrics["kv_live_share_mean.reason"] < 1


def test_the_one_pass_decoder_does_not_pass_for_a_looped_model(tmp_path):
    """The same tiny looped model, its configuration pointed at the shared
    decoder (one pass, no sandwich norms): not correct."""
    result, facts = rehearse("tiny-looped-shared.closed", tmp_path)
    probe = facts["probe"]
    assert result["correct"] is False and result["failed"] == 0
    assert probe["agrees"] is False
    assert probe["median"] > 3 * probe["tolerances"]["median"]
    assert set(probe["ablated"]) == {"causal"}  # the decoder's own pieces


def test_the_new_cells_files_agree_with_benchmark_json():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = cells.load_cell("BENCHMARK.json", CELL)
    assert cell.chips == 1 and cell.config["reference"] == "looped"
    assert cell.config["reduced"] == {} and cell.config["overrides"] == {}
    env = cell.config["env"]
    clients = cell.mix["params"]["clients"]
    assert cell.mix["kind"] == "closed" and clients == int(env["TPU_KV_SLOTS"])
    assert "TPU_QUANT" not in env and "TPU_KV_BLOCK" not in env
    # the cache the configuration asks for, by the program's own arithmetic
    from gofr_tpu.models.registry import get_model

    program = get_model(cell.config["base"]).config
    positions = int(env["TPU_KV_SLOTS"]) * int(env["TPU_MAX_LEN"])
    assert program.kv_bytes_per_token == 1_572_864
    assert positions * program.kv_bytes_per_token == 4_831_838_208  # 4.5 GiB
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["moves"] == "tpot_p50_ms"
        assert per_layer[name]["layer"] == per_layer["hbm_peak_gb"]["layer"]
    # A list may grow: the cell is in each list it belongs to, wherever,
    # and beside whichever cells joined later (since PR 32 also the two
    # lists of the scheduler's readings that the program exports for it).
    joined = {"tpot_p90_ms.batch", "out_tok_per_s.batch",
              "device_idle_share.batch", "window_occupancy_mean.batch",
              "loop_host_share.batch"}
    assert joined | NEW_METRICS <= {m["name"] for m in cell.per_layer}
    for name in joined | NEW_METRICS:
        assert CELL in per_layer[name]["workloads"], name


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """On the parent commit the gauge and the histogram do not exist: both
    readers return None and the line leaves the metrics out."""
    text = "app_tpu_window_occupancy_sum{model=\"m\"} 3.0\n"
    without = RunData(
        seconds=3.0, records=[], prom_start=prom.parse(text),
        prom_end=prom.parse(text), prom_samples=[prom.parse(text)],
        endpoints={},
    )
    series = (
        'app_tpu_kv_bytes_per_token{model="m"} 1572864.0\n'
        'app_tpu_kv_live_ratio_sum{model="m"} 4.0\n'
        'app_tpu_kv_live_ratio_count{model="m"} 10.0\n'
    )
    with_them = RunData(
        seconds=3.0, records=[], prom_start=prom.parse(text),
        prom_end=prom.parse(series), prom_samples=[prom.parse(series)] * 2,
        endpoints={},
    )
    want = {"kv_bytes_per_token.reason": 1572864.0,
            "kv_live_share_mean.reason": 0.4}
    for name in NEW_METRICS:
        spec = cells.layer_metric(name)
        read = cells.load_module("readers", spec["reader"]).read
        assert read(without, **spec["args"]) is None
        assert read(with_them, **spec["args"]) == pytest.approx(want[name])
