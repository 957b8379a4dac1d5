"""``BENCHMARK.json`` against its contract, and every cell from its files:
adding a cell is adding files and one entry."""

import dataclasses
import json
import os
import re

import pytest

from bench_paths import CHECKOUT, SUITE
from benchmark.harness import cells, stats
from benchmark.harness.loadgen import Record
from benchmark.harness.server import BenchFailure

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(
    r"(hidden|intermediate|latent|state|proj|head).*(size|dim)|_dim$|_rank$"
    r"|expansion|experts_per_tok"
)


def load_bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    return load_bench()


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
        # a cut is held to the harness's one rule (load_cell has asked it
        # already: a chip run refuses what this refuses)
        cells.check_cut(entry, cell.config)
        # the longest request fits a slot with the scheduler's margin
        longest = (cell.mix["prompt_tokens"]["max"]
                   + cell.mix["output_tokens"]["max"])
        assert longest + 73 <= int(cell.config["env"]["TPU_MAX_LEN"])
    for m in bench["per_layer"]:
        spec = cells.layer_metric(m["name"])
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert hasattr(cells.load_module("readers", spec["reader"]), "read")


# The widths every configuration shows, under the names its source
# publishes them by; the feed-forward width goes by one of several.
WIDTHS_SHOWN = ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "vocab_size")
FEED_FORWARD_WIDTHS = ("intermediate_size", "moe_intermediate_size")


def configurations():
    """One case a configuration: a new entry is a new case."""
    return [pytest.param(c, id=c["name"]) for c in load_bench()["configs"]]


def published_widths_kept(entry, config, published):
    """A configuration's file against the keys of its source's own
    ``config.json`` verbatim, and against the program through the file's own
    map ``program_keys``. It names no configuration, no size and no field of
    the program's config; ``reduced`` is taken key by key, so a file cut on
    several keys goes through the same lines as one cut on none."""
    assert published["source"] == entry["source"] == config["source"]
    for key, value in published.items():
        if key in config.get("reduced", {}):
            assert config["reduced"][key]["published"] == value, key
        elif key in config:
            assert config[key] == value, (entry["name"], key)
    shown = [*WIDTHS_SHOWN, *(["head_dim"] if "head_dim" in published else [])]
    for key in shown:
        assert key in published and key in config, (entry["name"], key)
    assert any(k in published and k in config for k in FEED_FORWARD_WIDTHS)
    if "head_dim" in config and "head_dim" not in published:
        assert config["head_dim"] * config["num_attention_heads"] == config["hidden_size"]
    # ... and the program agrees: the registry entry the file builds on,
    # after its overrides, field by field of the file's own map.
    from gofr_tpu.models.registry import get_model

    program = dataclasses.replace(
        get_model(config["base"]).config, **config["overrides"]
    )
    mapped = config["program_keys"]
    feed_forward = [k for k in FEED_FORWARD_WIDTHS if k in config]
    assert set(shown) | set(feed_forward) | set(config.get("reduced", {})) <= set(mapped)
    for key, field in mapped.items():
        # JSON's null is the program's 0: the mechanism is off
        assert getattr(program, field) == (config[key] or 0), (key, field)


@pytest.mark.parametrize("entry", configurations())
def test_published_widths_are_kept(entry):
    """Once a configuration of ``BENCHMARK.json``, against
    ``published/<name>.json``."""
    with open(os.path.join(CHECKOUT, entry["file"])) as fh:
        config = json.load(fh)
    path = os.path.join(SUITE, "published", f"{entry['name']}.json")
    assert os.path.isfile(path), (
        f"configuration {entry['name']!r} has no published sizes: add {path}, "
        f"the keys of {entry['source']} verbatim, with that URL as \"source\""
    )
    with open(path) as fh:
        published_widths_kept(entry, config, json.load(fh))


@pytest.mark.parametrize("entry", configurations())
def test_the_child_can_load_the_reference_a_configuration_names(entry):
    """As the server child does before the engine boots: the module is
    found through the configuration's file and has the two things the
    harness asks of one."""
    from benchmark.harness.serve_child import load_reference

    path = os.path.join(CHECKOUT, entry["file"])
    with open(path) as fh:
        reference = load_reference(path, json.load(fh))
    assert reference.ABLATIONS and callable(reference.reference_logprobs)


def cells_file_with(tmp_path, cells_file="rehearsal_cells.json", listed=None,
                    **changes):
    """A cells file of one tiny cell whose configuration file, a copy under
    ``tmp_path``, has ``changes`` applied (None removes the key) and whose
    entry lists ``listed`` as reduced (None leaves the entry's list). The
    copy finds the tests' references and traffic beside it."""
    with open(os.path.join(SUITE, cells_file)) as fh:
        bench = json.load(fh)
    entry = bench["configs"][0]
    with open(os.path.join(CHECKOUT, entry["file"])) as fh:
        config = {**json.load(fh), **changes}
    (tmp_path / "configs").mkdir()
    for beside in ("reference", "traffic"):
        os.symlink(os.path.join(SUITE, beside), tmp_path / beside)
    entry["file"] = str(tmp_path / "configs" / "copy.json")
    if listed is not None:
        entry["reduced"] = listed
    with open(entry["file"], "w") as fh:
        json.dump({k: v for k, v in config.items() if v is not None}, fh)
    with open(tmp_path / "cells.json", "w") as fh:
        json.dump(bench, fh)
    cell = next(w["name"] for w in bench["workloads"] if w["config"] == entry["name"])
    return str(tmp_path / "cells.json"), cell


@pytest.mark.parametrize("changes,says", [
    ({"reference": None}, 'it needs a key "reference"'),
    ({"reference": "no_such_mathematics"}, "no_such_mathematics.py is missing"),
])
def test_a_configuration_without_its_reference_is_refused_before_any_boot(
    tmp_path, changes, says,
):
    cells_file, cell = cells_file_with(tmp_path, **changes)
    with pytest.raises(BenchFailure) as refused:
        cells.load_cell(cells_file, cell)
    # the message gives the path it looked for, beside the configuration
    assert says in str(refused.value)
    assert str(tmp_path / "reference") in str(refused.value)


# Planted cuts: copies of the tests' two-key share of moe-tiny
# (configs/tiny-moe-share.json: depth 2 -> 1, vocabulary 512 -> 256), whose
# "published" sizes are the registry's entry read through program_keys.
SHARE = "rehearsal_cells_share.json"
DEPTH = {"published": 2, "here": 1, "program_key": "n_layers", "why": "planted"}
EXPERTS = {"published": 4, "here": 2, "program_key": "n_experts", "why": "planted"}
VOCABULARY = {"published": 512, "here": 256, "program_key": "vocab_size",
              "why": "planted"}
UNCUT = {"num_hidden_layers": 2, "num_local_experts": 4, "vocab_size": 512}


def cut_on(**cuts):
    """The changes to the share's file that cut it on exactly ``cuts``."""
    return {
        **UNCUT, **{key: cut["here"] for key, cut in cuts.items()},
        "reduced": cuts,
        "overrides": {cut["program_key"]: cut["here"] for cut in cuts.values()},
    }


def registry_as_published(config):
    from gofr_tpu.models.registry import get_model

    base = get_model(config["base"]).config
    return {"source": config["source"],
            **{key: getattr(base, field)
               for key, field in config["program_keys"].items()}}


@pytest.mark.parametrize("cuts", [
    pytest.param({}, id="no-cut"),
    pytest.param({"num_hidden_layers": DEPTH}, id="one-key"),
    pytest.param({"num_hidden_layers": DEPTH, "num_local_experts": EXPERTS,
                  "vocab_size": VOCABULARY}, id="three-keys"),
])
def test_a_cut_on_any_number_of_keys_loads_and_keeps_the_published_widths(
    tmp_path, cuts,
):
    cells_file, cell = cells_file_with(
        tmp_path, SHARE, listed=sorted(cuts), **cut_on(**cuts),
        **({} if cuts else {"deployment": None}),
    )
    config = cells.load_cell(cells_file, cell).config
    assert config["overrides"] == {c["program_key"]: c["here"] for c in cuts.values()}
    with open(cells_file) as fh:
        (entry,) = json.load(fh)["configs"]
    published_widths_kept(entry, config, registry_as_published(config))


BOTH = cut_on(num_hidden_layers=DEPTH, vocab_size=VOCABULARY)


@pytest.mark.parametrize("cells_file,listed,changes,says", [
    pytest.param(
        SHARE, None, {"overrides": {**BOTH["overrides"], "sliding_window": 24}},
        ["overrides sets 'sliding_window' to 24", "reduced declares nothing"],
        id="an-override-that-reduced-does-not-declare"),
    pytest.param(
        SHARE, None, {"overrides": {"n_layers": 1}},
        ["reduced declares 'vocab_size' cut to 256",
         "overrides gives it 'nothing'"],
        id="a-declared-cut-with-no-override"),
    pytest.param(
        SHARE, None, {"overrides": {"n_layers": 1, "vocab_size": 384}},
        ["reduced declares 'vocab_size' cut to 256", "overrides gives it 384"],
        id="an-override-of-another-value"),
    pytest.param(
        SHARE, None,
        {"reduced": {**BOTH["reduced"], "vocab_size": {**VOCABULARY, "published": 256}}},
        ["reduced['vocab_size']", "here 256 equal to published 256"],
        id="here-equal-to-published"),
    pytest.param(
        SHARE, None, {"vocab_size": 384},
        ["its own 'vocab_size' is 384", "says here 256"],
        id="the-file's-own-key-differs-from-here"),
    pytest.param(
        SHARE, None,
        {"reduced": {**BOTH["reduced"], "vocab_size": {**VOCABULARY, "why": ""}}},
        ["reduced['vocab_size'] has no 'why'"],
        id="a-cut-without-its-why"),
    pytest.param(
        SHARE, None, {"deployment": " "},
        ["cut on ['num_hidden_layers', 'vocab_size']", "states no deployment"],
        id="a-cut-with-an-empty-deployment"),
    pytest.param(
        SHARE, ["num_hidden_layers"], {},
        ["the entry's reduced lists ['num_hidden_layers']",
         "declares ['num_hidden_layers', 'vocab_size']", "differ in ['vocab_size']"],
        id="the-entry's-list-differs-from-the-file's"),
    # The one exemption is for the tests' tiny models alone: tiny-dense
    # overrides its window and declares nothing, which any other source
    # may not.
    pytest.param(
        "rehearsal_cells.json", None, {"source": "https://example.org/config.json"},
        ["overrides sets 'sliding_window' to 24", "reduced declares nothing"],
        id="an-undeclared-override-outside-the-tests"),
])
def test_a_cut_that_is_not_declared_whole_is_refused_before_any_boot(
    tmp_path, cells_file, listed, changes, says,
):
    planted, cell = cells_file_with(tmp_path, cells_file, listed=listed, **changes)
    with pytest.raises(BenchFailure) as refused:
        cells.load_cell(planted, cell)
    # the message names the file, the key and both values
    assert str(tmp_path / "configs" / "copy.json") in str(refused.value)
    for part in says:
        assert part in str(refused.value)


@pytest.mark.parametrize("changes,fails_on", [
    # run at another size than published, and not listed
    pytest.param({"reduced": {"num_hidden_layers": DEPTH}}, "vocab_size",
                 id="a-cut-not-listed"),
    # listed, with another published value than the source's
    pytest.param(
        {"reduced": {**BOTH["reduced"], "vocab_size": {**VOCABULARY, "published": 1024}}},
        "vocab_size", id="listed-with-another-published-value"),
    # listed as published, and the program is told another size
    pytest.param({"overrides": {"n_layers": 1, "vocab_size": 128}},
                 "vocab_size", id="the-program-is-told-another-size"),
    # a width changed is a width changed, listed or not
    pytest.param({"hidden_size": 64}, "hidden_size", id="a-width"),
])
def test_a_cut_of_the_vocabulary_passes_the_published_check_only_as_listed(
    changes, fails_on,
):
    with open(os.path.join(SUITE, SHARE)) as fh:
        (entry,) = json.load(fh)["configs"]
    with open(os.path.join(CHECKOUT, entry["file"])) as fh:
        config = json.load(fh)
    assert "vocab_size" in WIDTHS_SHOWN and "vocab_size" in config["reduced"]
    published = registry_as_published(config)
    published_widths_kept(entry, config, published)  # as committed, it passes
    with pytest.raises(AssertionError) as failed:
        published_widths_kept(entry, {**config, **changes}, published)
    assert fails_on in str(failed.value)
