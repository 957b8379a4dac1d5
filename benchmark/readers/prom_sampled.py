"""A gauge polled inside the window (2 Hz), mean of the samples.

    {"reader": "prom_sampled", "args": {"name": "app_tpu_batch_occupancy"}}

A gauge holds its last value only, so the mean of polls is a sample of it
and not an integral; it says so by its name in BENCHMARK.json.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import prom


def read(run: Any, name: str, scale: float = 1.0) -> Optional[float]:
    values = [
        prom.total(sample, name) for sample in run.prom_samples
        if name in sample
    ]
    if not values:
        return None
    return sum(values) / len(values) * scale
