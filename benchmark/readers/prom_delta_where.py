"""A counter's window delta over the series whose labels match, or the
ratio of two such deltas.

    {"reader": "prom_delta_where",
     "args": {"name": "app_tpu_gc_pause_seconds_total",
              "where": {"generation": "2"}}}
    {"reader": "prom_delta_where",
     "args": {"name": "app_tpu_loop_phase_seconds_total",
              "without": {"phase": ["idle", "device_window"]},
              "over": {"without": {"phase": ["idle"]}}}}

``prom_delta`` sums over every label set; this sums over the series whose
label text contains every ``key="value"`` of ``where`` and none of
``without``. A value may be a list: any of them matches. ``over`` is a
second selection of the same form on the same metric (or on its own
``name``); the reading is then the first delta over the second, and
nothing when the second is not positive. The second example is the
scheduler loop's host share of its busy time over exactly the window:
every phase but the idle wait and the device-window seam, over every
phase but the idle wait.

A metric that does not exist yet (a counter is exported after its first
increment; a program without it never exports it) reads nothing, not 0:
a parent commit that lacks the counter leaves the metric out of its line.
"""

from __future__ import annotations

import re
from typing import Any, Optional

LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def matches(label_text: str, where: dict, without: dict) -> bool:
    labels = dict(LABEL.findall(label_text))

    def one_of(key: str, values: Any) -> bool:
        values = values if isinstance(values, list) else [values]
        return labels.get(key) in [str(v) for v in values]

    return all(one_of(k, v) for k, v in where.items()) and not any(
        one_of(k, v) for k, v in without.items()
    )


def delta(run: Any, name: str, where: Optional[dict] = None,
          without: Optional[dict] = None) -> Optional[float]:
    """End minus start over the matching series of ``name``; None when
    the metric is at neither end."""
    if name not in run.prom_end and name not in run.prom_start:
        return None

    def total(samples: dict) -> float:
        return sum(
            value for labels, value in samples.get(name, {}).items()
            if matches(labels, where or {}, without or {})
        )

    return total(run.prom_end) - total(run.prom_start)


def read(run: Any, name: str, where: Optional[dict] = None,
         without: Optional[dict] = None, over: Optional[dict] = None,
         scale: float = 1.0) -> Optional[float]:
    top = delta(run, name, where, without)
    if top is None:
        return None
    if over is None:
        return top * scale
    bottom = delta(run, **{"name": name, **over})
    if bottom is None or bottom <= 0:
        return None
    return top / bottom * scale
