"""Everything a run is made of, found by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration is
``benchmark/configs/<config>.json`` (or the ``file`` its entry gives), the
mix ``benchmark/traffic/<traffic>.json``, the mix's kind
``benchmark/traffic_kinds/<kind>.py``, the plain reference
``benchmark/reference/<reference>.py`` that the configuration's file names,
a per-layer metric ``benchmark/layer_metrics/<metric>.json`` and its reader
``benchmark/readers/<reader>.py``. Adding any of them is adding a file and
one entry; nothing here lists them.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any

from benchmark.harness.server import BENCHMARK, CHECKOUT, BenchFailure, check

# A source that starts so is one of the tests' tiny models: a registry entry
# bent to a purpose (a window that binds, another gate), with nothing published.
TESTS_ONLY = "tests only"
ABSENT = "nothing"


def load_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_file(name: str, path: str) -> Any:
    """Import a file that is not on a package path, under ``name``: a
    dataclass in it looks its own module up in ``sys.modules``."""
    spec = importlib.util.spec_from_file_location(name, path)
    assert spec is not None and spec.loader is not None
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_module(kind: str, name: str) -> Any:
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCHMARK, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchFailure(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return load_file(f"benchmark.{kind}.{name}", path)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, with "name" and "path"
    mix: dict             # the traffic file
    kind: Any             # the traffic kind's module
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def loop_kind(mix: dict) -> str:
    """``open`` or ``closed``: the first word of the mix's traffic kind
    (``open_poisson``, ``closed``). A per-layer metric split by cell takes
    an open-loop cell in its ``.chat`` list and a closed-loop one in its
    ``.batch`` list."""
    word = mix["kind"].split("_")[0]
    check(word in ("open", "closed"),
          f"traffic kind {mix['kind']!r} names neither an open nor a closed loop")
    return word


def metrics_of(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def beside_configs(config_path: str, kind: str, file_name: str) -> str:
    """What belongs to a configuration lies where its file does:
    ``<dir>/configs/x.json`` goes with ``<dir>/<kind>/<file_name>``
    (``benchmark/``, or the tests' own directory)."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(config_path))),
        kind, file_name,
    )


def reference_path(config_path: str, config: dict) -> str:
    """The file of the plain reference that the configuration names. This
    only looks: the module imports jax, so the child alone loads it."""
    name = config.get("reference")
    if not name:
        raise BenchFailure(
            f"{config_path} names no plain reference: it needs a key "
            f'"reference", the name of a module '
            f"{beside_configs(config_path, 'reference', '<name>.py')}"
        )
    path = beside_configs(config_path, "reference", f"{name}.py")
    if not os.path.isfile(path):
        raise BenchFailure(
            f"{config_path} names the plain reference {name!r}: "
            f"{path} is missing"
        )
    return path


def check_cut(entry: dict, config: dict) -> None:
    """The one rule for a configuration that is cut from its source, on any
    number of keys. ``entry`` is the configuration's entry in the cells file,
    ``config`` its file with ``path``. The file's ``reduced`` declares each
    cut as ``{"published", "here", "program_key", "why"}`` under the
    source's own key, and then

    * the entry's ``reduced`` lists the same keys;
    * the file's own value of each key is ``here``, which is not ``published``;
    * ``overrides`` is the union of the declared cuts,
      ``{program_key: here}`` of each and nothing else: what the program is
      told to change is what the file says it changed;
    * the file states the ``deployment`` that it is a share of.

    A file without ``reduced`` declares no cut and overrides nothing. The
    one exemption: such a file whose ``source`` starts with "tests only"
    may override what it likes. Every refusal names the file, the key and
    both values."""
    path = config["path"]
    exempt = "reduced" not in config and str(
        config.get("source", "")
    ).startswith(TESTS_ONLY)
    declared = config.get("reduced") or {}
    listed = sorted(entry["reduced"])
    check(
        listed == sorted(declared),
        f"{path}: the entry's reduced lists {listed} and the file's reduced "
        f"declares {sorted(declared)}; they differ in "
        f"{sorted(set(listed) ^ set(declared))}",
    )
    if exempt:
        return
    cuts = {}
    for key, cut in declared.items():
        for field in ("published", "here", "program_key", "why"):
            check(
                field in cut and (field in ("published", "here") or cut[field]),
                f"{path}: reduced[{key!r}] has no {field!r}: a cut says what "
                f"the source publishes, what is run here, which field of "
                f"the program's config it sets, and why",
            )
        here, published = cut["here"], cut["published"]
        check(
            here != published,
            f"{path}: reduced[{key!r}] has here {here!r} equal to published "
            f"{published!r}: that is no cut",
        )
        check(
            key in config and config[key] == here,
            f"{path}: its own {key!r} is {config.get(key, ABSENT)!r} and "
            f"reduced[{key!r}] says here {here!r}",
        )
        cuts[cut["program_key"]] = here
    overrides = config.get("overrides") or {}
    stray = sorted(set(overrides) - set(cuts))
    if stray:
        raise BenchFailure(
            f"{path}: overrides sets {stray[0]!r} to {overrides[stray[0]]!r} "
            f"and reduced declares {ABSENT} for it: an override is a "
            f"declared cut"
        )
    for field, here in cuts.items():
        check(
            field in overrides and overrides[field] == here,
            f"{path}: reduced declares {field!r} cut to {here!r} and "
            f"overrides gives it {overrides.get(field, ABSENT)!r}: the "
            f"program is told every cut",
        )
    check(
        not declared or str(config.get("deployment", "")).strip(),
        f"{path}: cut on {sorted(declared)} and states no deployment: say how "
        f"many chips share a layer and how, so that the share can be judged",
    )


def load_cell(cells_file: str, workload: str) -> Cell:
    """``cells_file`` is ``BENCHMARK.json`` or a file of the same shape
    (the tests' rehearsal cells), relative to the checkout."""
    bench = load_json(os.path.join(CHECKOUT, cells_file))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchFailure(
            f"no workload {workload!r} in {cells_file}; it has {sorted(by_name)}"
        )
    entry = by_name[workload]
    config_entry = next(
        c for c in bench["configs"] if c["name"] == entry["config"]
    )
    config_path = os.path.join(CHECKOUT, config_entry["file"])
    config = dict(load_json(config_path))
    config["name"], config["path"] = entry["config"], config_path
    reference_path(config_path, config)  # fails here, before any boot
    check_cut(config_entry, config)      # and so does a cut not declared
    mix = load_json(
        beside_configs(config_path, "traffic", f"{entry['traffic']}.json")
    )
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config, mix=mix,
        kind=load_module("traffic_kinds", mix["kind"]),
        end_to_end=metrics_of(bench["end_to_end"], workload),
        per_layer=metrics_of(bench["per_layer"], workload),
    )


def layer_metric(name: str) -> dict:
    path = os.path.join(BENCHMARK, "layer_metrics", f"{name}.json")
    if not os.path.isfile(path):
        raise BenchFailure(f"no per-layer metric file {path}")
    return load_json(path)
