"""``run.py --rehearse-cpu`` end to end: the example server as a child, a
tiny dense model whose window binds, a tiny MoE model and a tiny model
with another gate whose reference is a file of its own, the probe against
the plain reference that each configuration names over HTTP, and the last
line's keys."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import CHECKOUT

CELLS = "tests/benchmark_suite/rehearsal_cells.json"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
END_TO_END = {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
# What a CPU has to read: no device plane, no runtime memory stats.
PER_LAYER_ON_CPU = {
    "loadgen_lag_p95_ms", "ttft_p90_ms", "tpot_p90_ms.batch",
    "out_tok_per_s.batch", "token_gap_max_ms", "queue_wait_mean_ms",
    "recompiles_in_window",
}


def rehearse(workload, trace, tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", workload, "--seed", str(2**31 + 77), "--seconds", "3",
         "--trace", str(trace), "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    facts = {line["fact"]: line for line in lines[:-1]}
    return lines[-1], facts


@pytest.mark.parametrize("workload,trace,removed,by,metrics", [
    ("tiny-dense.open", 0, "window", 3.0, END_TO_END),
    ("tiny-moe.closed", 1, "expert", 3.0, PER_LAYER_ON_CPU),
    # Another mathematics: the configuration names a reference of its own
    # (tests/benchmark_suite/reference/decoder_gelu.py), whose ablations
    # are its own too. SiLU for GELU moves a token by 0.17-0.19 nats over
    # this model's 12 layers, a mask or an expert by a nat and more.
    ("tiny-gelu.open", 0, "act", 1.5, END_TO_END),
])
def test_rehearsal_runs_a_cell_end_to_end(workload, trace, removed, by, metrics, tmp_path):
    result, facts = rehearse(workload, trace, tmp_path)
    assert set(result) == RESULT_KEYS  # no breakdown without a device plane
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert result["device"]["platform"] == "cpu"  # never read as a chip
    assert set(result["metrics"]) == metrics
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    # The reference agrees with the served path, and would not if the
    # causal mask, the binding window or one expert were missing.
    probe = facts["probe"]
    limits = probe["tolerances"]
    assert probe["agrees"] and probe["median"] < limits["median"] / 2
    assert probe["max"] < limits["token"] / 2  # at this size, every token
    assert set(probe["ablated"]) == {"causal", removed}
    for found in probe["ablated"].values():
        assert not found["agrees"] and found["median"] > by * limits["median"]
    compared = result["compared"]
    assert compared["probe_median_nats"] == {
        "value": probe["median"], "limit": limits["median"], "rule": "<="}
    assert compared["ablations_applied"]["value"] == 2
    assert compared["ablations_still_agreeing"]["value"] == 0
    assert facts["window"]["compiled_in_window"] is False
    assert facts["traffic"]["requests"] >= result["attempted"]


def test_the_dispatch_is_what_made_another_mathematics_pass(tmp_path):
    """The same tiny GELU model, its configuration pointed at the shared
    SwiGLU decoder: served and reference differ by the gate, the probe
    does not agree, and the run is not correct."""
    result, facts = rehearse("tiny-gelu-shared.open", 0, tmp_path)
    probe = facts["probe"]
    assert result["correct"] is False and result["failed"] == 0
    assert probe["agrees"] is False
    assert probe["median"] > 1.5 * probe["tolerances"]["median"]
    # the shared decoder's own pieces were asked for, not the GELU file's
    assert set(probe["ablated"]) == {"causal"}
    assert result["compared"]["probe_median_nats"]["value"] == probe["median"]
