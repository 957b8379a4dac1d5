"""Per-request sampling seeds: counter-based keys
(fold_in(fold_in(base, seed), n_sampled)) make a seeded stream a pure
function of (engine seed, request seed, prompt, params) — independent of
batch composition, window size, and pipelined/mega scheduling."""

from __future__ import annotations

import pytest

from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

PROMPT = "the quick brown fox"


def _engine(**kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("window_k", 4)
    kw.setdefault("tokenizer", ByteTokenizer())
    return InferenceEngine("llama-tiny", **kw)


def _sample(eng, **kw):
    return eng.generate_sync(
        PROMPT, max_new_tokens=16, temperature=0.9, stop_on_eos=False,
        timeout=120, **kw
    ).token_ids


@pytest.fixture(scope="module")
def eng():
    e = _engine()
    e.start_sync()
    yield e
    e.stop_sync()


def test_same_seed_reproduces(eng):
    assert _sample(eng, seed=42) == _sample(eng, seed=42)


def test_different_seeds_differ(eng):
    assert _sample(eng, seed=1) != _sample(eng, seed=2)


def test_unseeded_requests_differ(eng):
    # OpenAI semantics: no seed → independent draws per request.
    assert _sample(eng) != _sample(eng)


def test_seeded_stream_scheduling_invariant(eng):
    # The SAME seeded stream must come out of a different window size and
    # alongside concurrent traffic — the key depends only on (seed,
    # n_sampled), never on how steps were batched.
    want = _sample(eng, seed=7)
    for kw in ({"window_k": 8}, {"window_k": 2}):
        other = _engine(**kw)
        other.start_sync()
        try:
            assert _sample(other, seed=7) == want, kw
        finally:
            other.stop_sync()
    # Concurrent batch-mate on the same engine.
    a = eng.submit_generate(
        PROMPT, max_new_tokens=16, temperature=0.9, stop_on_eos=False,
        seed=7,
    )
    b = eng.submit_generate(
        "completely different prompt", max_new_tokens=16, temperature=0.7,
        stop_on_eos=False,
    )
    assert a.future.result(timeout=120).token_ids == want
    b.future.result(timeout=120)


def test_greedy_unaffected_by_seed(eng):
    g = lambda **kw: eng.generate_sync(  # noqa: E731
        PROMPT, max_new_tokens=16, temperature=0.0, stop_on_eos=False,
        timeout=120, **kw
    ).token_ids
    assert g(seed=1) == g(seed=99)
