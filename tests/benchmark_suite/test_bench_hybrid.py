"""A stack of sparse and lightning layers through the harness (ISSUE 35): the
new files of ``minicpm-sala-d16.longctx`` against ``BENCHMARK.json``, and a
rehearsal on the CPU of the tests' tiny cut (``tiny-sala-d5``: ``sala-tiny``
cut on the same two keys, layers 0-4 of its 8), which goes through by the
reference module its configuration names (``hybrid_sparse_linear``); never a
measurement."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from bench_paths import CHECKOUT
from benchmark.harness import cells, prom
from benchmark.harness.rundata import RunData

CELLS = "tests/benchmark_suite/rehearsal_cells_hybrid.json"
CELL = "minicpm-sala-d16.longctx"
NEW_METRICS = {
    "sparse_selected_share", "sparse_read_ratio_mean", "state_bytes_per_slot",
}
CLOSED_LOOP_LISTS = {
    "tpot_p90_ms.batch", "out_tok_per_s.batch", "device_idle_share.batch",
    "window_occupancy_mean.batch", "loop_host_share.batch",
    "kv_bytes_per_token.reason", "kv_live_share_mean.reason",
}
CANDIDATES = {
    "causal", "decay", "lin_rope", "qk_norm", "out_gate", "out_norm",
    "residual_scale", "logit_scale", "select", "forced_blocks",
}


def test_rehearsal_of_a_hybrid_cut_is_correct_with_every_ablation_failing(
    tmp_path,
):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", "tiny-sala-d5.closed", "--seed", str(2**31 + 35),
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    result, facts = lines[-1], {line["fact"]: line for line in lines[:-1]}
    assert result["correct"] is True and result["failed"] == 0
    probe = facts["probe"]
    limits = probe["tolerances"]
    assert probe["agrees"] and probe["median"] < limits["median"] / 2
    # Every piece the reference names, removed, fails the probe, the
    # selection's two among them: the probe's 96 + 8 tokens lie past the
    # tiny dense length of 32, so they apply.
    from benchmark.reference import hybrid_sparse_linear as hybrid

    assert set(probe["ablated"]) == set(hybrid.ABLATIONS) <= CANDIDATES
    assert set(hybrid.SELECTION_ONLY) & set(hybrid.ABLATIONS)
    for name, found in probe["ablated"].items():
        assert not found["agrees"], name
    assert result["compared"]["ablations_still_agreeing"]["value"] == 0

    # The child served the base with both overrides and nothing else.
    from gofr_tpu.models.registry import get_model

    with open(os.path.join(CHECKOUT, facts["window"]["server_log"])) as fh:
        (line,) = [ln for ln in fh if ln.startswith("benchmark: serving ")]
    served = json.loads(line.removeprefix("benchmark: serving "))
    base = get_model("sala-tiny").config
    want = dataclasses.replace(
        base, n_layers=5, layer_kinds=list(base.layer_kinds)[:5]
    )
    assert served["config"] == json.loads(
        json.dumps(dataclasses.asdict(want), default=str)
    )
    assert want.layer_offset == 0 and (
        want.n_sparse_layers, want.n_lin_layers) == (3, 2)

    # The three metrics this cell brings read the program's new counters,
    # and the cache's read a hybrid cache as any other: K, V and a
    # compressed key every 2 tokens of 3 layers x 2 heads x 16 x 2 B.
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    on_cpu = (CLOSED_LOOP_LISTS | NEW_METRICS) - {"device_idle_share.batch"}
    assert on_cpu <= set(metrics)
    assert metrics["kv_bytes_per_token.reason"] == 3 * 2 * 16 * 2 * 2.5
    assert metrics["state_bytes_per_slot"] == 2 * 4 * 16 * 16 * 4
    # prompts of 8-64 tokens and 4-16 out against a dense length of 32
    assert 0.1 < metrics["sparse_selected_share"] < 0.9
    assert 0.1 < metrics["sparse_read_ratio_mean"] <= 0.5
    for name in ("window_occupancy_mean.batch", "loop_host_share.batch",
                 "kv_live_share_mean.reason"):
        assert 0 < metrics[name] < 1, name


def test_the_new_cells_files_agree_with_benchmark_json():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = cells.load_cell("BENCHMARK.json", CELL)
    config, env = cell.config, cell.config["env"]
    assert cell.chips == 1 and config["reference"] == "hybrid_sparse_linear"
    assert sorted(config["reduced"]) == ["mixer_types", "num_hidden_layers"]
    with open(os.path.join(
        CHECKOUT, "tests", "benchmark_suite", "published", "minicpm-sala-d16.json"
    )) as fh:
        published = json.load(fh)
    assert config["overrides"] == {
        "n_layers": 16, "layer_kinds": published["mixer_types"][9:25],
    }
    assert config["mixer_types"].count("minicpm4") == 4
    assert config["mixer_types"].count("lightning-attn") == 12
    assert {"decay", "sparse_config", "per_query_switch", "mup_denominator",
            "norm_and_gate", "rope", "state", "weights", "served_context"} <= set(
        config["assumed"])
    assert config["probe"] == {"prompt_tokens": 9216, "new_tokens": 8}
    assert "stage 2 of 2" in config["deployment"]
    assert "TPU_QUANT" not in env and "TPU_KV_BLOCK" not in env
    clients = cell.mix["params"]["clients"]
    assert cell.mix["kind"] == "closed" and clients == int(env["TPU_KV_SLOTS"]) == 16
    assert cell.mix["params"]["requests"] == 768
    assert cell.mix["prompt_tokens"] == {
        "median": 14336, "sigma": 0.4, "min": 4096, "max": 30720}
    assert cell.mix["output_tokens"] == {
        "median": 128, "sigma": 0.5, "min": 32, "max": 512}
    assert cell.mix["temperature"] == 0.0 and cell.mix["pool_seed"] == 35
    assert 30720 + 512 + 73 <= int(env["TPU_MAX_LEN"]) == 32768

    # the cut by the program's own arithmetic, in bf16: 10.08 GB of weights
    # and 16 slots of 32,768 positions x 4,224 B + 25,165,824 B of state
    from gofr_tpu.models.registry import get_model

    program = dataclasses.replace(
        get_model(config["base"]).config, **config["overrides"]
    )
    ffn = 3 * 4096 * 16384
    lightning, sparse = 5 * 4096 * 4096 + ffn, (3 * 4096 + 2 * 256) * 4096 + ffn
    n_params = 12 * lightning + 4 * sparse + 2 * 73448 * 4096
    assert round(n_params / 1e9, 2) == 5.04  # norms aside
    whole = 24 * lightning + 8 * sparse + 2 * 73448 * 4096
    assert round(2 * whole / 1e9, 2) == 18.95  # one chip does not hold it
    cache = 16 * (32768 * program.kv_bytes_per_token + program.state_bytes_per_slot)
    assert round(cache / 1e9, 2) == 2.62
    assert (2 * n_params + cache) / 16e9 >= 0.6  # the fullest device

    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "tpot_p50_ms"
    assert per_layer["sparse_selected_share"]["layer"] == (
        "model, kernels (models/transformer.py)")
    assert per_layer["state_bytes_per_slot"]["layer"] == (
        "cache and device memory (ops/kv_cache.py)")
    assert (CLOSED_LOOP_LISTS | NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for name in CLOSED_LOOP_LISTS:
        assert per_layer[name]["workloads"][-1] == CELL, name
    # ... and the four cells that were there are where they were
    assert [w["name"] for w in bench["workloads"]] == [
        "mistral-7b.chat", "mixtral-8x7b-d4.batch", "ouro-2.6b.reason",
        "openpangu-ultra-moe-718b-ep16.longdoc", CELL]
    # the reference's two copies are one text
    here = os.path.join(CHECKOUT, "benchmark", "reference", "hybrid_sparse_linear.py")
    copy = os.path.join(CHECKOUT, "tests", "benchmark_suite", "reference",
                        "hybrid_sparse_linear.py")
    with open(here) as a, open(copy) as b:
        assert a.read() == b.read()


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """On the parent commit the counter, the histogram and the gauge do not
    exist: the readers return None and the line leaves the metrics out."""
    text = "app_tpu_window_occupancy_sum{model=\"m\"} 3.0\n"
    without = RunData(
        seconds=3.0, records=[], prom_start=prom.parse(text),
        prom_end=prom.parse(text), prom_samples=[prom.parse(text)],
        endpoints={},
    )

    def series(selected_d, dense_d, selected_p, ratio_sum, ratio_count):
        q = "app_tpu_sparse_attn_queries_total"
        return (
            f'{q}{{branch="selected",model="m",program="decode_window"}} {selected_d}\n'
            f'{q}{{branch="dense",model="m",program="decode_window"}} {dense_d}\n'
            f'{q}{{branch="selected",model="m",program="prefill_chunk"}} {selected_p}\n'
            f'{q}{{branch="dense",model="m",program="prefill_chunk"}} 500.0\n'
            f'app_tpu_sparse_attn_read_ratio_sum{{model="m"}} {ratio_sum}\n'
            f'app_tpu_sparse_attn_read_ratio_count{{model="m"}} {ratio_count}\n'
            'app_tpu_state_bytes_per_slot{model="m"} 25165824.0\n'
        )

    start = series(100.0, 50.0, 7.0, 1.0, 4.0)
    end = series(1000.0, 150.0, 9000.0, 3.8, 14.0)
    with_them = RunData(
        seconds=3.0, records=[], prom_start=prom.parse(start),
        prom_end=prom.parse(end), prom_samples=[prom.parse(end)],
        endpoints={},
    )
    want = {"sparse_selected_share": 900 / 1000,  # the decode program's own
            "sparse_read_ratio_mean": 0.28, "state_bytes_per_slot": 25165824.0}
    for name in NEW_METRICS:
        spec = cells.layer_metric(name)
        read = cells.load_module("readers", spec["reader"]).read
        assert read(without, **spec["args"]) is None
        assert read(with_them, **spec["args"]) == pytest.approx(want[name])


def test_the_hybrid_cells_file_keeps_the_rule_for_split_lists():
    from test_bench_ttft_split import (
        test_a_split_list_holds_the_cells_of_its_kind_of_loop as rule,
    )

    rule(CELLS)
