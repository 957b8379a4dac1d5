"""Wave admission is bounded between two decode windows (PR 33).

While live streams fill under a quarter of the slots the scheduler keeps
dispatching prefill steps before the next decode window. That drain holds
every live stream still, so it ends at ``WAVE_STEPS`` full steps' rows:
a short prompt's few single-row chunks are drained whole as before, while
prompts of many chunks (full steps, each dearer than a window) no longer
freeze the live streams until a quarter of the slots is live again.
"""

from __future__ import annotations

import numpy as np
import pytest

from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.scheduler import WAVE_STEPS
from gofr_tpu.serving.tokenizer import ByteTokenizer

CHUNK = 16
BATCH = 2


def tokens_of(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, 500, n)]


def logged_engine() -> tuple[InferenceEngine, list]:
    """An engine of 8 slots (wave admission while under 2 are live) whose
    dispatches are logged: ("P", rows) a prefill step, ("W", live) a window."""
    engine = InferenceEngine(
        "llama-tiny", n_slots=8, max_len=1024, prefill_chunk=CHUNK,
        prefill_batch=BATCH, tokenizer=ByteTokenizer(),
    )
    log: list = []
    prefill, window = engine._dispatch_prefill_chunk, engine._dispatch_window

    def logged_prefill(**kw):
        rows = prefill(**kw)
        if rows:
            log.append(("P", rows))
        return rows

    def logged_window():
        log.append(("W", sum(1 for s in engine._slots if s is not None)))
        return window()

    engine._dispatch_prefill_chunk = logged_prefill
    engine._dispatch_window = logged_window
    return engine, log


def drains(log: list) -> list[list[int]]:
    """The rows of the prefill steps dispatched between two windows, one
    list a gap that holds any."""
    gaps, gap = [], []
    for kind, n in log:
        if kind == "P":
            gap.append(n)
        elif gap:
            gaps.append(gap)
            gap = []
    return gaps + ([gap] if gap else [])


def serve(prompts: list[list[int]]) -> list[list[int]]:
    """One stream goes live and stays; then ``prompts`` arrive together.
    The drains that ran while that stream was live."""
    engine, log = logged_engine()
    engine.start_sync()
    try:
        live = engine.submit_generate(
            tokens_of(0, 4), max_new_tokens=600, stop_on_eos=False
        )
        assert live.stream.get(timeout=120) is not None
        start = len(log)
        waves = [
            engine.submit_generate(p, max_new_tokens=2, stop_on_eos=False)
            for p in prompts
        ]
        for req in waves:
            req.future.result(timeout=300)
        assert not live.future.done(), "the live stream ended too early"
        live.future.cancel()
    finally:
        engine.stop_sync()
    return drains(log[start:])


def test_full_steps_stop_at_the_bound():
    """Four prompts of 10 chunks, two rows a step: 20 full steps. Under a
    quarter of the slots is live all the while, and no gap between two
    windows gets a further step once it holds WAVE_STEPS full steps' rows
    (the prompts may arrive over two passes, so a step may hold one row)."""
    gaps = serve([tokens_of(i, 10 * CHUNK) for i in range(1, 5)])
    assert sum(sum(g) for g in gaps) == 4 * 10
    # no step is added to a gap that holds WAVE_STEPS full steps' rows
    assert all(sum(g[:-1]) < WAVE_STEPS * BATCH for g in gaps)
    assert max(len(g) for g in gaps) >= 2  # and it still drains


@pytest.mark.parametrize("chunks", [2, 4])
def test_a_short_prompt_is_drained_whole(chunks):
    """One prompt of a few chunks is one row a step: all of its steps go
    out between two windows, as they did before the bound."""
    gaps = serve([tokens_of(9, chunks * CHUNK)])
    assert [len(g) for g in gaps] == [chunks]
    assert all(rows == 1 for rows in gaps[0])


def test_nothing_live_means_nothing_to_hold_back():
    """With no stream live the loop runs prefill steps only, pass after
    pass: a cold start is not slowed by windows nobody needs."""
    engine, log = logged_engine()
    engine.start_sync()
    try:
        req = engine.submit_generate(
            tokens_of(3, 12 * CHUNK), max_new_tokens=2, stop_on_eos=False
        )
        req.future.result(timeout=300)
    finally:
        engine.stop_sync()
    first_window = next(i for i, (kind, _) in enumerate(log) if kind == "W")
    assert [kind for kind, _ in log[:first_window]] == ["P"] * 12
