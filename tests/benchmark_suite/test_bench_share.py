"""A configuration cut on several keys through the whole harness (ISSUE 32):
``tiny-moe-share``, the tests' one chip's share of ``moe-tiny`` (1 of its 2
layers, 256 of its 512 vocabulary rows), whose cell joins every list a
closed-loop cell belongs to; one rehearsal on the CPU, never a measurement."""

import dataclasses
import json
import os
import subprocess
import sys

from bench_paths import CHECKOUT
from benchmark.harness import cells
from benchmark.harness.traffic import requests_for, warmup_requests

CELLS = "tests/benchmark_suite/rehearsal_cells_share.json"
CELL = "tiny-moe-share.closed"
# What a closed-loop cell with a cache worth sizing joins: an entry each.
CLOSED_LOOP_LISTS = {
    "tpot_p90_ms.batch", "out_tok_per_s.batch", "device_idle_share.batch",
    "window_occupancy_mean.batch", "loop_host_share.batch",
    "kv_bytes_per_token.reason", "kv_live_share_mean.reason",
}
SEED = 2**31 + 32


def test_a_share_cut_on_two_keys_rehearses_correct_with_both_overrides_applied(
    tmp_path,
):
    cell = cells.load_cell(CELLS, CELL)
    config = cell.config
    assert sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert config["overrides"] == {"n_layers": 1, "vocab_size": 256}

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--cells", CELLS,
         "--workload", CELL, "--seed", str(SEED), "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    result, facts = lines[-1], {line["fact"]: line for line in lines[:-1]}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8

    # The probe has its teeth on the share: every piece of the reference
    # that applies (no window binds here), removed, fails it.
    probe = facts["probe"]
    assert probe["agrees"] and probe["median"] < probe["tolerances"]["median"] / 2
    assert set(probe["ablated"]) == {"causal", "expert"}
    assert not any(found["agrees"] for found in probe["ablated"].values())
    assert result["compared"]["ablations_still_agreeing"]["value"] == 0

    # The child served the base with both overrides and nothing else: the
    # program's config as its registry gave it, from the server's log.
    from gofr_tpu.models.registry import get_model

    with open(os.path.join(CHECKOUT, facts["window"]["server_log"])) as fh:
        (line,) = [ln for ln in fh if ln.startswith("benchmark: serving ")]
    served = json.loads(line.removeprefix("benchmark: serving "))
    base = get_model(config["base"]).config
    want = dataclasses.replace(base, n_layers=1, vocab_size=256)
    assert served["model"] == config["env"]["TPU_MODEL"] == "tiny-moe-share"
    assert served["config"] == json.loads(
        json.dumps(dataclasses.asdict(want), default=str)
    )
    assert (base.n_layers, base.vocab_size) == (2, 512)  # a cut, both ways

    # The window's and the warm-up's prompts draw their ids below the cut
    # vocabulary (the generator is the run's own, on the run's own seed).
    vocab = int(config["vocab_size"])
    drawn = requests_for(cell.mix, facts["traffic"]["requests"], SEED, vocab)
    drawn += warmup_requests(cell.mix, 4, SEED + 1, vocab)
    assert facts["traffic"]["prompt_tokens"]["sum"] == sum(
        len(r.prompt) for r in drawn[:facts["traffic"]["requests"]]
    )
    assert 0 < max(t for r in drawn for t in r.prompt) < vocab == 256

    # A new closed-loop cell reads every list it joined: an entry each, no
    # copy of a metric under a suffix of its own. One layer of moe-tiny
    # holds (k, v) x 2 heads x 32 x 2 B a token: the depth cut, seen from
    # the child's cache.
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    on_cpu = CLOSED_LOOP_LISTS - {"device_idle_share.batch"}  # no device plane
    assert on_cpu <= set(metrics)
    assert metrics["kv_bytes_per_token.reason"] == 256.0
    for name in ("window_occupancy_mean.batch", "loop_host_share.batch",
                 "kv_live_share_mean.reason"):
        assert 0 < metrics[name] < 1, name


def test_a_closed_loop_cell_joins_the_lists_by_an_entry_each():
    """In the share's cells file as in ``BENCHMARK.json``: the lists are
    the same seven names, and ``ouro-2.6b.reason`` is in all of them."""
    for cells_file, cell in ((CELLS, CELL), ("BENCHMARK.json", "ouro-2.6b.reason")):
        with open(os.path.join(CHECKOUT, cells_file)) as fh:
            per_layer = {m["name"]: m for m in json.load(fh)["per_layer"]}
        for name in CLOSED_LOOP_LISTS:
            assert cell in per_layer[name]["workloads"], (cells_file, name)
            spec = cells.layer_metric(name)  # the one file, whoever joins
            for key in ("layer", "unit", "better", "source", "moves"):
                assert spec[key] == per_layer[name][key], (name, key)
        loaded = cells.load_cell(cells_file, cell)
        assert cells.loop_kind(loaded.mix) == "closed"
        assert CLOSED_LOOP_LISTS <= {m["name"] for m in loaded.per_layer}
