"""One number out of a JSON endpoint fetched at window end.

    {"reader": "json_path", "args": {"endpoint": "debug_loop",
                                     "path": ["host_overhead_ratio"]}}
    {"reader": "json_path", "args": {"endpoint": "health",
        "path": ["hbm", "*", "peak_bytes_in_use"], "reduce": "max",
        "scale": 1e-9}}

``endpoint`` is a key of ``run.endpoints``: ``debug_loop`` (``/debug/loop``
-> ``tpu``), ``health`` (``details.tpu.details``), ``capacity``
(``/debug/capacity`` -> ``tpu``). ``*`` fans out over a list; ``reduce``
folds what it finds (``max``, ``sum`` or ``mean``). A missing key reads
nothing (the CPU reports no ``hbm``).
"""

from __future__ import annotations

from typing import Any, Optional


def walk(node: Any, path: list) -> list:
    if not path:
        return [node] if isinstance(node, (int, float)) else []
    head, rest = path[0], path[1:]
    if head == "*":
        return [v for item in (node or []) for v in walk(item, rest)]
    if isinstance(node, dict) and head in node:
        return walk(node[head], rest)
    return []


def read(run: Any, endpoint: str, path: list, reduce: str = "max",
         scale: float = 1.0) -> Optional[float]:
    values = walk(run.endpoints.get(endpoint), path)
    if not values:
        return None
    folded = {
        "max": max(values), "sum": sum(values),
        "mean": sum(values) / len(values),
    }[reduce]
    return folded * scale
