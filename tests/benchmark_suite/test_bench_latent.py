"""One chip's share of a latent-attention expert model through the harness
(ISSUE 33): the new files of ``openpangu-ultra-moe-718b-ep16.longdoc`` against
``BENCHMARK.json``, and a rehearsal on the CPU of the tests' tiny share
(``tiny-mla-moe-share``: ``mla-moe-tiny`` holding 4 of its 8 routed experts
and half its vocabulary), which goes through by the reference module its
configuration names (``latent_moe``); never a measurement."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from bench_paths import CHECKOUT
from benchmark.harness import cells, prom
from benchmark.harness.rundata import RunData

CELLS = "tests/benchmark_suite/rehearsal_cells_latent.json"
CELL = "openpangu-ultra-moe-718b-ep16.longdoc"
NEW_METRICS = {"moe_routes_held_share", "moe_expert_load_peak_ratio"}
CLOSED_LOOP_LISTS = {
    "tpot_p90_ms.batch", "out_tok_per_s.batch", "device_idle_share.batch",
    "window_occupancy_mean.batch", "loop_host_share.batch",
    "kv_bytes_per_token.reason", "kv_live_share_mean.reason",
}
CANDIDATES = {"causal", "rope_key", "latent_norm", "shared_expert", "routed",
              "route_scale", "sandwich"}


def test_rehearsal_of_a_latent_share_is_correct_with_every_ablation_failing(
    tmp_path,
):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", "tiny-mla-moe-share.closed", "--seed", str(2**31 + 33),
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    result, facts = lines[-1], {line["fact"]: line for line in lines[:-1]}
    assert result["correct"] is True and result["failed"] == 0
    probe = facts["probe"]
    limits = probe["tolerances"]
    assert probe["agrees"] and probe["median"] < limits["median"] / 2
    # Every piece the reference names, removed, fails the probe: on this
    # 3-layer stack in bfloat16 the least of them (the routed scale read as
    # 1) moves a token by 0.2 nats at the median, the plain comparison 0.01.
    from benchmark.reference import latent_moe

    assert set(probe["ablated"]) == set(latent_moe.ABLATIONS) <= CANDIDATES
    for name, found in probe["ablated"].items():
        assert not found["agrees"], name
        assert found["median"] > 1.5 * limits["median"], name
    assert result["compared"]["ablations_still_agreeing"]["value"] == 0

    # The child served the base with both overrides and nothing else.
    from gofr_tpu.models.registry import get_model

    with open(os.path.join(CHECKOUT, facts["window"]["server_log"])) as fh:
        (line,) = [ln for ln in fh if ln.startswith("benchmark: serving ")]
    served = json.loads(line.removeprefix("benchmark: serving "))
    base = get_model("mla-moe-tiny").config
    want = dataclasses.replace(base, n_experts_held=4, vocab_size=256)
    assert served["config"] == json.loads(
        json.dumps(dataclasses.asdict(want), default=str)
    )
    assert want.held_range == (0, 4) and base.held_range == (0, 8)

    # The two metrics this cell brings read the program's new counters, and
    # the cache's read a latent cache as any other: 3 entries x one 24-value
    # row in a 128-lane plane x 2 B a token.
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    on_cpu = (CLOSED_LOOP_LISTS | NEW_METRICS) - {"device_idle_share.batch"}
    assert on_cpu <= set(metrics)
    assert metrics["kv_bytes_per_token.reason"] == 3 * 128 * 2
    # 4 of 8 experts held under a random router: about half the routes
    assert 0.3 < metrics["moe_routes_held_share"] < 0.7
    assert 1.0 <= metrics["moe_expert_load_peak_ratio"] <= 4.0
    for name in ("window_occupancy_mean.batch", "loop_host_share.batch",
                 "kv_live_share_mean.reason"):
        assert 0 < metrics[name] < 1, name


def test_the_new_cells_files_agree_with_benchmark_json():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = cells.load_cell("BENCHMARK.json", CELL)
    config, env = cell.config, cell.config["env"]
    assert cell.chips == 1 and config["reference"] == "latent_moe"
    assert sorted(config["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size",
    ]
    assert config["overrides"] == {
        "n_layers": 5, "n_dense_layers": 1, "n_experts_held": 16,
        "vocab_size": 19200,
    }
    assert "head_dim" not in config and config["num_key_value_heads"] == 128
    assert {"router_score", "multi_token_prediction"} <= set(config["assumed"])
    assert config["num_nextn_predict_layers"] == 1  # as published, not built
    assert "TPU_QUANT" not in env and "TPU_KV_BLOCK" not in env
    clients = cell.mix["params"]["clients"]
    assert cell.mix["kind"] == "closed" and clients == int(env["TPU_KV_SLOTS"]) == 32
    assert cell.mix["prompt_tokens"] == {
        "median": 4096, "sigma": 0.5, "min": 1024, "max": 7680}
    assert cell.mix["output_tokens"] == {
        "median": 64, "sigma": 0.5, "min": 16, "max": 256}
    assert cell.mix["temperature"] == 0.0 and cell.mix["pool_seed"] == 24
    assert 7680 + 256 + 73 <= int(env["TPU_MAX_LEN"]) == 8192
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert "sixteen times its share" in entry["why"]

    # the share by the program's own arithmetic: 4.92 B parameters, and a
    # cache row of 576 values of content in a 640-lane plane
    from gofr_tpu.models.registry import get_model

    program = dataclasses.replace(
        get_model(config["base"]).config, **config["overrides"]
    )
    base = get_model(config["base"]).config
    assert (base.n_layers, base.n_dense_layers, base.experts_held,
            base.vocab_size) == (61, 3, 256, 153600)
    assert program.n_experts == 256 and program.n_experts_active == 8
    assert program.held_range == (0, 16) and program.n_moe_layers == 4
    assert program.kv_bytes_per_token == 5 * 576 * 2 == 5_760
    attention = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
                 + 512 * 128 * 256 + 128 * 128 * 7680)
    routed, shared, router = 3 * 7680 * 2048, 3 * 7680 * 2048, 7680 * 256
    n_params = (
        5 * attention + 3 * 7680 * 18432
        + 4 * (16 * routed + shared + router) + 2 * 19200 * 7680
    )
    assert round(n_params / 1e9, 2) == 4.92  # norms aside

    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "model, kernels (models/transformer.py)"
    assert per_layer["moe_routes_held_share"]["moves"] == "tpot_p50_ms"
    assert per_layer["moe_expert_load_peak_ratio"]["moves"] == "ttft_p50_ms"
    assert (CLOSED_LOOP_LISTS | NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for name in CLOSED_LOOP_LISTS:
        assert CELL in per_layer[name]["workloads"], name
    # ... and the three cells that were there are where they were
    assert [w["name"] for w in bench["workloads"]][:3] == [
        "mistral-7b.chat", "mixtral-8x7b-d4.batch", "ouro-2.6b.reason"]


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """On the parent commit the counter and the histogram do not exist: both
    readers return None and the line leaves the metrics out."""
    text = "app_tpu_window_occupancy_sum{model=\"m\"} 3.0\n"
    without = RunData(
        seconds=3.0, records=[], prom_start=prom.parse(text),
        prom_end=prom.parse(text), prom_samples=[prom.parse(text)],
        endpoints={},
    )
    start = (
        'app_tpu_moe_routes_total{model="m",where="held"} 10.0\n'
        'app_tpu_moe_routes_total{model="m",where="absent"} 150.0\n'
        'app_tpu_moe_expert_load_ratio_sum{model="m"} 3.0\n'
        'app_tpu_moe_expert_load_ratio_count{model="m"} 2.0\n'
    )
    end = (
        'app_tpu_moe_routes_total{model="m",where="held"} 110.0\n'
        'app_tpu_moe_routes_total{model="m",where="absent"} 1650.0\n'
        'app_tpu_moe_expert_load_ratio_sum{model="m"} 18.0\n'
        'app_tpu_moe_expert_load_ratio_count{model="m"} 12.0\n'
    )
    with_them = RunData(
        seconds=3.0, records=[], prom_start=prom.parse(start),
        prom_end=prom.parse(end), prom_samples=[prom.parse(end)],
        endpoints={},
    )
    want = {"moe_routes_held_share": 100 / 1600,
            "moe_expert_load_peak_ratio": 1.5}
    for name in NEW_METRICS:
        spec = cells.layer_metric(name)
        read = cells.load_module("readers", spec["reader"]).read
        assert read(without, **spec["args"]) is None
        assert read(with_them, **spec["args"]) == pytest.approx(want[name])


def test_the_latent_cells_file_keeps_the_rule_for_split_lists():
    """``test_bench_ttft_split`` holds the cells files it lists to the rule
    (a ``.batch`` list takes closed-loop cells, none in both halves); this
    PR's rehearsal file goes through the same function."""
    from test_bench_ttft_split import (
        test_a_split_list_holds_the_cells_of_its_kind_of_loop as rule,
    )

    rule(CELLS)
