"""Prometheus text exposition -> {metric name: {label text: value}}."""

from __future__ import annotations


def parse(text: str) -> dict[str, dict[str, float]]:
    out: dict[str, dict[str, float]] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, brace, labels = head.partition("{")
        try:
            out.setdefault(name, {})[brace + labels] = float(value)
        except ValueError:
            continue
    return out


def total(samples: dict[str, dict[str, float]], name: str) -> float:
    """Sum over every label set; 0 for a series that does not exist yet
    (a counter is exported only after its first increment)."""
    return sum(samples.get(name, {}).values())
