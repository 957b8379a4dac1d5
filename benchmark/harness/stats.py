"""From the client's records to the end-to-end metrics.

Every number here is over all the requests and all the seconds of the
window. A request that failed, was refused or did not finish counts in
``failed`` and, in the latency samples, as missing: its sample is the time
at which the harness stopped waiting for it, so a failure can only make a
tail worse.
"""

from __future__ import annotations

import math
from typing import Optional

from benchmark.harness.loadgen import Record

# A percentile wants ten samples beyond it (choosing-metrics, section 1).
SAMPLES_BEYOND = 10


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def samples_needed(q: float) -> int:
    """The fewest samples at which the ``q``-th percentile still has
    ``SAMPLES_BEYOND`` beyond it: 200 for the 95th, 100 for the 90th."""
    return math.ceil(SAMPLES_BEYOND / (1.0 - q / 100.0) - 1e-9)


def tail_supported(n: int, q: float) -> bool:
    return n >= samples_needed(q)


def ttft_ms(record: Record) -> float:
    """First streamed token minus the time the request was due (open loop)
    or sent (closed loop). Missing: until the harness gave up."""
    if record.token_s:
        return (record.token_s[0] - record.due_s) * 1e3
    end = record.gave_up_s if record.gave_up_s is not None else record.done_s
    return ((end if end is not None else record.sent_s) - record.due_s) * 1e3


def tpot_ms(record: Record) -> Optional[float]:
    """Per request (last token - first token) / (tokens - 1): per request
    and not per gap, because the decode window delivers tokens in groups
    and a raw gap percentile would measure the group size. None for a
    request with fewer than two tokens."""
    n = len(record.token_s)
    if n < 2:
        return None
    return (record.token_s[-1] - record.token_s[0]) / (n - 1) * 1e3


def ttft_samples(records: list[Record]) -> list[float]:
    return [ttft_ms(r) for r in records]


def tpot_samples(records: list[Record]) -> list[float]:
    """Completed requests with at least two tokens."""
    return [
        t for r in records if r.ok and (t := tpot_ms(r)) is not None
    ]


def tokens_in_window(records: list[Record], seconds: float) -> int:
    return sum(1 for r in records for t in r.token_s if t <= seconds)


SAMPLES = {"ttft": ttft_samples, "tpot": tpot_samples}


def quantile_of(name: str) -> Optional[float]:
    """95.0 for ``ttft_p95_ms``; None for a metric that is no percentile."""
    for part in name.split("_"):
        if part.startswith("p") and part[1:].isdigit():
            return float(part[1:])
    return None


def end_to_end(name: str, records: list[Record], seconds: float) -> tuple[float, int]:
    """(value, samples it stands on) of ``ttft_p<q>_ms``, ``tpot_p<q>_ms`` or
    ``out_tok_per_s``; KeyError for a name with no arithmetic here.
    ``setup_s`` is the harness's own clock and is added by run.py."""
    if name == "out_tok_per_s":
        n = tokens_in_window(records, seconds)
        return n / seconds, n
    family, q = name.split("_")[0], quantile_of(name)
    if family not in SAMPLES or q is None or name != f"{family}_p{q:g}_ms":
        raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
    samples = SAMPLES[family](records)
    return percentile(samples, q), len(samples)
