"""A request's stream does not depend on how the scheduler cuts decoding
into dispatches (ISSUE 30).

The engine has one decode program, ``decode_window``, and two numbers that
say how it is dispatched: ``window_k`` steps a dispatch and
``pipeline_depth`` dispatches in flight. Neither may reach a request:
every shape below, on both cache layouts, serves what one baseline engine
at ``(4, 1)`` on the contiguous cache serves. All on the CPU at tiny
widths; sampling keys are counter-based (``serving/programs.py``
``row_keys``), so a seeded stream is held to the same standard as a greedy
one.

The second half keeps the fork from growing back: the three keys that
chose another dispatch shape until PR 30 are read, reported and ignored,
and an engine holds no serving program but the two.
"""

from __future__ import annotations

import pytest

from gofr_tpu.config import MockConfig
from gofr_tpu.logging.level import Level
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.testutil.mock_logger import MockLogger

PROMPT = "the quick brown fox"
BUDGETS = (3, 9, 17, 24)
SHAPES = [
    (window_k, depth, kv_block)
    for window_k, depth in ((1, 1), (2, 1), (4, 2), (8, 2))
    for kv_block in (0, 32)
]


def engine_of(window_k: int, depth: int, kv_block: int) -> InferenceEngine:
    return InferenceEngine(
        "llama-tiny", n_slots=4, max_len=128, window_k=window_k,
        pipeline_depth=depth, kv_block=kv_block, tokenizer=ByteTokenizer(),
    )


def greedy(engine: InferenceEngine, n: int = 24, **kw):
    return engine.generate_sync(
        PROMPT, max_new_tokens=n, temperature=0.0, stop_on_eos=False,
        timeout=120, **kw,
    )


def sampled(engine: InferenceEngine) -> list[int]:
    return engine.generate_sync(
        PROMPT, max_new_tokens=24, temperature=0.8, seed=7,
        stop_on_eos=False, timeout=120,
    ).token_ids


@pytest.fixture(scope="module")
def baseline() -> dict:
    engine = engine_of(4, 1, 0)
    engine.start_sync()
    try:
        full = greedy(engine)
        # A stop text that the stream reaches inside a window, not at an
        # edge of one, at every shape's window length.
        stop = full.text[2:6]
        return {
            "tokens": full.token_ids,
            "stop": stop,
            "stopped_text": greedy(engine, stop=[stop]).text,
            "sampled": sampled(engine),
        }
    finally:
        engine.stop_sync()


@pytest.fixture(
    scope="module", params=SHAPES,
    ids=[f"k{k}-d{d}-{'paged' if b else 'contiguous'}" for k, d, b in SHAPES],
)
def engine(request):
    e = engine_of(*request.param)
    e.start_sync()
    yield e
    e.stop_sync()


def test_greedy_stream_is_the_baselines(engine, baseline):
    result = greedy(engine)
    assert result.token_ids == baseline["tokens"]
    assert result.finish_reason == "length"


def test_concurrent_requests_each_get_exactly_their_budget(engine, baseline):
    requests = [
        engine.submit_generate(
            PROMPT, max_new_tokens=n, temperature=0.0, stop_on_eos=False
        )
        for n in BUDGETS
    ]
    results = [r.future.result(timeout=120) for r in requests]
    assert [len(r.token_ids) for r in results] == list(BUDGETS)
    assert all(r.finish_reason == "length" for r in results)
    # Overshoot past a budget is dropped, never delivered or reordered.
    assert all(
        r.token_ids == baseline["tokens"][:n]
        for r, n in zip(results, BUDGETS)
    )


def test_a_stop_text_inside_a_window_retires_the_request(engine, baseline):
    result = greedy(engine, stop=[baseline["stop"]])
    assert result.finish_reason == "stop"
    assert baseline["stop"] not in result.text
    assert result.text == baseline["stopped_text"]
    # The tokens the window computed past the stop are dropped, and the
    # slot serves the next request from a clean state.
    assert greedy(engine, n=8).token_ids == baseline["tokens"][:8]


def test_seeded_sampled_stream_is_the_baselines(engine, baseline):
    assert sampled(engine) == baseline["sampled"]


# ----------------------------------------------------------------------
# who shares the batch (ISSUE 31)
# ----------------------------------------------------------------------

# The decode step reads the rung of the cache that holds the LONGEST live
# slot, so what a neighbour holds decides how much of every slot is read.
# That may not reach a request either. 256 positions have the rungs 128
# and 256; the neighbour's 122-token prompt crosses 128 inside the first
# decode windows, while the short request beside it is decoding.
NEIGHBOUR = "n" * 122


@pytest.fixture(
    scope="module", params=["llama-tiny", "moe-tiny", "looped-tiny"],
    ids=["dense", "moe", "looped"],
)
def rung_engine(request):
    e = InferenceEngine(
        request.param, n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
    )
    assert e.decode_read_rungs == (128, 256)
    e.start_sync()
    yield e
    e.stop_sync()


@pytest.mark.parametrize("sampling", [
    {"temperature": 0.0}, {"temperature": 0.8, "seed": 7},
], ids=["greedy", "seeded"])
def test_a_stream_is_the_same_beside_a_neighbour_that_crosses_a_rung(
    rung_engine, sampling,
):
    def submit(prompt: str):
        return rung_engine.submit_generate(
            prompt, max_new_tokens=24, stop_on_eos=False, **sampling
        )

    alone = submit(PROMPT).future.result(timeout=120)
    neighbour, beside = submit(NEIGHBOUR), submit(PROMPT)
    beside = beside.future.result(timeout=120)
    crossed = neighbour.future.result(timeout=120)
    assert len(crossed.token_ids) == 24
    assert crossed.prompt_tokens < 128 < crossed.prompt_tokens + 24
    assert beside.token_ids == alone.token_ids
    assert len(alone.token_ids) == 24


# ----------------------------------------------------------------------
# the retired keys, and the programs an engine holds
# ----------------------------------------------------------------------

SERVING_PROGRAMS = {"prefill_chunk", "decode_window"}
# What a cache layout brings beside them today. A new name is added here
# on purpose, or not at all.
PAGED_PROGRAMS = {
    "paged_copy_block", "paged_insert_block", "paged_extract_block",
    "paged_move_block",
}
BASE_ENV = {"TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "4", "TPU_MAX_LEN": "128"}


def booted_from(env: dict) -> tuple[InferenceEngine, list[str]]:
    """An engine from ``from_config`` (the byte tokenizer put in: the
    baseline's prompts are its), and the WARN lines its boot logged."""
    logger = MockLogger()
    engine = InferenceEngine.from_config(
        MockConfig({**BASE_ENV, **env}), logger=logger
    )
    engine.tokenizer = ByteTokenizer()
    return engine, [str(m) for m in logger.messages_at(Level.WARN)]


@pytest.mark.parametrize("key,value", [
    ("TPU_SPEC_TOKENS", "2"), ("TPU_MEGA_WINDOWS", "4"),
    ("TPU_PREFILL_DEPTH", "4"),
])
def test_a_retired_key_is_reported_once_and_the_plain_path_serves(
    baseline, key, value,
):
    engine, warnings = booted_from({key: value, "TPU_DECODE_WINDOW": "4"})
    assert len(warnings) == 1 and key in warnings[0], warnings
    assert "retired" in warnings[0]
    engine.start_sync()
    try:
        assert greedy(engine).token_ids == baseline["tokens"]
    finally:
        engine.stop_sync()
    assert set(engine.compile_stats()["programs"]) == SERVING_PROGRAMS


@pytest.mark.parametrize("env", [
    {},
    {"TPU_SPEC_TOKENS": "auto", "TPU_MEGA_WINDOWS": "0",
     "TPU_PREFILL_DEPTH": "1"},
    {"TPU_SPEC_TOKENS": "0"},
], ids=["unset", "old-defaults", "spec-0"])
def test_a_retired_key_unset_or_at_its_old_default_says_nothing(env):
    _, warnings = booted_from(env)
    assert warnings == []


@pytest.mark.parametrize("model,kw,beside", [
    ("llama-tiny", {}, set()),
    ("moe-tiny", {}, set()),
    ("looped-tiny", {}, set()),
    ("llama-tiny", {"kv_block": 16, "auto_prefix": True, "lora_slots": 1,
                    "lora_rank": 4}, PAGED_PROGRAMS),
], ids=["dense", "moe", "looped", "paged-radix-lora"])
def test_an_engine_holds_two_serving_programs_and_no_other(model, kw, beside):
    """After a warm-up and one request the compile tracker names
    ``prefill_chunk`` (one compile a rung) and ``decode_window`` (one),
    and nothing compiled once the engine served."""
    engine = InferenceEngine(
        model, n_slots=4, max_len=128, tokenizer=ByteTokenizer(), **kw
    )
    engine.start_sync()
    try:
        greedy(engine, n=4)
        engine.mark_steady_state()
        greedy(engine, n=12)
        stats = engine.compile_stats()
    finally:
        engine.stop_sync()
    assert set(stats["programs"]) == SERVING_PROGRAMS | beside
    assert stats["programs"]["prefill_chunk"]["compiles"] == len(
        engine.prefill_rungs
    )
    assert stats["programs"]["decode_window"]["compiles"] == 1
    assert stats["steady_state_recompiles"] == 0
