"""``BENCHMARK.json`` against its contract, and every cell from its files:
adding a cell is adding files and one entry."""

import json
import os
import re

import pytest

from bench_paths import CHECKOUT
from benchmark.harness import cells, stats
from benchmark.harness.loadgen import Record

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(
    r"(hidden|intermediate|latent|state|proj|head).*(size|dim)|_dim$|_rank$"
    r"|expansion|experts_per_tok"
)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_names_units_sources_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 runs a cell at run_seconds + 60 s, 180 s a cell to compile and
    # 1,200 s spare must fit 43,200 s with the full 24 cells.
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        if m["name"] != "setup_s":  # the harness has arithmetic for it
            done = Record(0, 1, 2, 0.0, token_s=[0.1, 0.2], done_s=0.2,
                          finish_reason="length")
            stats.end_to_end(m["name"], [done], 1.0)
    assert "setup_s" in names
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    cell_names = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        # What it moves is reported wherever it is.
        moved = end_to_end[m["moves"]]
        assert set(m.get("workloads", cell_names)) <= set(
            moved.get("workloads", cell_names)
        )
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4
    )
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert c["source"].startswith("https://")
        assert not any(WIDTHS.search(key) for key in c["reduced"]), c["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))


def test_every_cell_loads_from_its_files_and_nothing_else(bench):
    for w in bench["workloads"]:
        cell = cells.load_cell("BENCHMARK.json", w["name"])
        assert cell.config["env"]["TPU_MODEL"] == cell.config["name"]
        assert hasattr(cell.kind, "count") and hasattr(cell.kind, "drive")
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        # reduced names exactly what the file says it changed from the source
        assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])
        for key in entry["reduced"]:
            change = cell.config["reduced"][key]
            assert cell.config[key] == change["here"] != change["published"]
            assert cell.config["overrides"] == {change["program_key"]: change["here"]}
        # the longest request fits a slot with the scheduler's margin
        longest = (cell.mix["prompt_tokens"]["max"]
                   + cell.mix["output_tokens"]["max"])
        assert longest + 73 <= int(cell.config["env"]["TPU_MAX_LEN"])
    for m in bench["per_layer"]:
        spec = cells.layer_metric(m["name"])
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert hasattr(cells.load_module("readers", spec["reader"]), "read")


def test_published_widths_are_kept(bench):
    published = {
        "mistral-7b": dict(hidden_size=4096, intermediate_size=14336,
                           num_attention_heads=32, num_key_value_heads=8,
                           num_hidden_layers=32, vocab_size=32000,
                           sliding_window=4096, rope_theta=10000.0),
        "mixtral-8x7b-d4": dict(hidden_size=4096, intermediate_size=14336,
                                num_attention_heads=32, num_key_value_heads=8,
                                num_local_experts=8, num_experts_per_tok=2,
                                vocab_size=32000, rope_theta=1000000.0),
    }
    from gofr_tpu.models.registry import get_model

    for c in bench["configs"]:
        with open(os.path.join(CHECKOUT, c["file"])) as fh:
            config = json.load(fh)
        for key, value in published[c["name"]].items():
            assert config[key] == value, (c["name"], key)
        # ... and the program's registry entry the file builds on agrees.
        program = get_model(config["base"]).config
        assert (program.d_model, program.d_ff, program.n_heads,
                program.n_kv_heads, program.vocab_size, program.head_dim) == (
            config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["vocab_size"], 128)
        assert program.n_experts == config.get("num_local_experts", 0)
        assert program.sliding_window == (config["sliding_window"] or 0)
        assert program.rope_theta == config["rope_theta"]
