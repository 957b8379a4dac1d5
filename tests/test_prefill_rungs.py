"""The prefill step's row count is chosen at each dispatch (ISSUE 29).

The engine compiles ``[rows, prefill_chunk]`` for both rungs of
``prefill_rungs`` (1 and ``prefill_batch``) before it serves, and
``_dispatch_prefill_chunk`` runs the smallest rung that holds the rows
that wait. All on the CPU at tiny widths: which rung a dispatch picks,
that a row's result does not depend on its co-riders, that no rung
compiles once the engine serves, and what the counter and the fill ratio
say.

A test decides how many rows wait by parking the scheduler at the top of
a pass (the ``scheduler.window`` fault point), submitting, and letting go:
the pass then admits every request together.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.models.registry import get_model, register_model
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.programs import prefill_rungs
from gofr_tpu.serving.tokenizer import ByteTokenizer

CHUNK = 16
STEPS = "app_tpu_prefill_steps_total"
FILL = "app_tpu_prefill_fill_ratio"

for _name in ("moe-tiny", "looped-tiny"):
    _spec = get_model(_name)
    register_model(dataclasses.replace(
        _spec, name=_name + "-f32",
        config=dataclasses.replace(_spec.config, dtype=jnp.float32),
    ))


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def tokens_of(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, 500, n)]


@contextlib.contextmanager
def parked(engine: InferenceEngine):
    """The engine's scheduler held at the top of a pass with every slot
    free, until the block ends: what is submitted inside waits together."""
    gate_in, gate_out = threading.Event(), threading.Event()

    def park(**fired):  # the point is process-global: every engine's loop
        if fired.get("engine") is engine and not gate_out.is_set():
            gate_in.set()
            gate_out.wait(timeout=120)

    with faults.armed("scheduler.window", action=park):
        try:
            assert gate_in.wait(60), "the scheduler never reached a pass"
            assert all(s is None for s in engine._slots)
            assert not engine._prefilling
            yield
        finally:
            gate_out.set()


def serve_together(engine: InferenceEngine, prompts: list, new_tokens: int = 4):
    """Submit ``prompts`` so that one pass admits them all; their results,
    in order."""
    with parked(engine):
        requests = [
            engine.submit_generate(
                p, max_new_tokens=new_tokens, temperature=0.0,
                stop_on_eos=False,
            )
            for p in prompts
        ]
    return [r.future.result(timeout=300) for r in requests]


def steps_by_rows(metrics, model: str) -> dict[int, int]:
    inst = {i.name: i for i in metrics.instruments()}[STEPS]
    return {
        int(dict(labels)["rows"]): int(n)
        for labels, n in inst.collect().items()
        if ("model", model) in labels
    }


def fill_sum_count(metrics, model: str) -> tuple[float, int]:
    inst = {i.name: i for i in metrics.instruments()}[FILL]
    for labels, (_buckets, (total, n)) in inst.collect().items():
        if ("model", model) in labels:
            return total, n
    return 0.0, 0


# ----------------------------------------------------------------------
# (a) the ladder, and the rung a dispatch picks
# ----------------------------------------------------------------------


@pytest.mark.parametrize("prefill_batch,rungs", [
    (1, (1,)), (2, (1, 2)), (3, (1, 3)), (8, (1, 8)), (64, (1, 64)),
])
def test_the_ladder_is_one_row_and_the_batch(prefill_batch, rungs):
    assert prefill_rungs(prefill_batch) == rungs


@pytest.mark.parametrize("n_slots,prefill_batch,rungs", [
    (2, 8, (1, 2)),     # prefill_batch is capped at the slots
    (3, 8, (1, 3)),
    (4, 1, (1,)),       # TPU_PREFILL_BATCH=1: the one program of old
])
def test_an_engine_compiles_one_program_a_rung_before_it_serves(
    n_slots, prefill_batch, rungs,
):
    e = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), n_slots=n_slots,
        max_len=64, prefill_chunk=CHUNK, prefill_batch=prefill_batch,
    )  # built, never started: nothing ran
    assert e.prefill_rungs == rungs
    stats = e.compile_stats()
    assert stats["programs"]["prefill_chunk"]["compiles"] == len(rungs)
    assert stats["total"] == len(rungs)


@pytest.fixture(scope="module")
def metrics():
    return Container.create(MockConfig({"APP_NAME": "rungs-test"})).metrics


@pytest.fixture(scope="module")
def engine(metrics):
    e = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), n_slots=8, max_len=128,
        prefill_chunk=CHUNK, metrics=metrics,
    )
    e.start_sync()
    yield e
    e.close()


@pytest.mark.parametrize("waiting,rung", [
    (1, 1), (2, 8), (3, 8), (4, 8), (5, 8), (6, 8), (7, 8), (8, 8),
])
def test_a_step_runs_at_the_smallest_rung_that_holds_the_waiting_rows(
    engine, metrics, waiting, rung,
):
    """``waiting`` prompts of one chunk or less, admitted by one pass, are
    one step at ``rung``; the ratio's denominator is that step's token
    rows, and the counter and the engine's own count of steps agree."""
    before = steps_by_rows(metrics, "llama-tiny")
    fill0, n0 = fill_sum_count(metrics, "llama-tiny")
    steps0 = engine._prefill_chunk_steps
    prompts = [tokens_of(waiting * 10 + i, 5 + i) for i in range(waiting)]
    results = serve_together(engine, prompts)
    assert [len(r.token_ids) for r in results] == [4] * waiting
    after = steps_by_rows(metrics, "llama-tiny")
    moved = {r: after[r] - before.get(r, 0) for r in after}
    assert {r: n for r, n in moved.items() if n} == {rung: 1}
    fill1, n1 = fill_sum_count(metrics, "llama-tiny")
    assert n1 - n0 == 1
    assert fill1 - fill0 == pytest.approx(
        sum(len(p) for p in prompts) / (rung * CHUNK)
    )
    assert engine._prefill_chunk_steps - steps0 == 1
    assert sum(after.values()) == engine._prefill_chunk_steps


def test_a_prompt_of_several_chunks_rides_alone_once_the_others_finish(
    engine, metrics,
):
    """Three prompts of 1, 1 and 3 chunks: the first step holds three
    rows (rung 8), the long prompt's other two chunks ride alone."""
    before = steps_by_rows(metrics, "llama-tiny")
    prompts = [tokens_of(1, 9), tokens_of(2, 12), tokens_of(3, 2 * CHUNK + 3)]
    serve_together(engine, prompts)
    after = steps_by_rows(metrics, "llama-tiny")
    moved = {r: after[r] - before.get(r, 0) for r in after}
    assert {r: n for r, n in moved.items() if n} == {8: 1, 1: 2}


# ----------------------------------------------------------------------
# (c) nothing compiles once the engine serves
# ----------------------------------------------------------------------


def test_no_rung_compiles_after_the_fence_however_many_rows_wait():
    """Both rungs are compiled when the constructor returns. One request
    goes first because the decode window still compiles at its first use
    (it draws rung 1 only); after the fence, 1, 2, 3, 5 and 8 requests at
    a time compile nothing."""
    e = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), n_slots=8, max_len=128,
        prefill_chunk=CHUNK,
    )
    compiled = lambda: e.compile_stats()["programs"]["prefill_chunk"]["compiles"]  # noqa: E731
    assert compiled() == len(e.prefill_rungs) == 2
    e.start_sync()
    try:
        serve_together(e, [tokens_of(0, 7)])
        e.mark_steady_state()
        for waiting in (1, 2, 3, 5, 8):
            serve_together(
                e, [tokens_of(waiting + i, 6 + 3 * i) for i in range(waiting)]
            )
        stats = e.compile_stats()
        assert stats["steady_state_recompiles"] == 0
        assert compiled() == 2
        assert stats["programs"]["decode_window"]["compiles"] == 1
    finally:
        e.close()


# ----------------------------------------------------------------------
# (b) a row's result does not depend on its co-riders
# ----------------------------------------------------------------------

STACKS = {
    "dense": ("llama-tiny-f32", {}),
    "moe": ("moe-tiny-f32", {}),
    "looped": ("looped-tiny-f32", {}),
    "paged": ("llama-tiny-f32", {"kv_block": 16}),
}
# Two chunks and a ragged third, so the prompt also rides steps that the
# shorter co-riders have left.
PROBE = tokens_of(99, 2 * CHUNK + 5)


@pytest.fixture(scope="module")
def stacks():
    """name -> (engine, the probe prompt's result when prefilled alone),
    each built when a test first asks for it: under xdist's load
    distribution a worker meets only some of the stacks."""
    built = {}

    def stack(name):
        if name not in built:
            model, kw = STACKS[name]
            e = InferenceEngine(
                model, tokenizer=ByteTokenizer(), n_slots=8, max_len=128,
                prefill_chunk=CHUNK, window_k=4, **kw,
            )
            e.start_sync()
            built[name] = (e, serve_together(e, [PROBE], new_tokens=6)[0])
        return built[name]

    yield stack
    for e, _ in built.values():
        e.close()


@pytest.mark.parametrize("others", [1, 2, 3, 7])
@pytest.mark.parametrize("stack", list(STACKS))
def test_a_prompt_prefills_to_the_same_tokens_beside_any_number_of_others(
    stacks, stack, others,
):
    """The probe prompt beside 1, 2, 3 and 7 others (rung 8 with 6, 5, 4
    and no padding rows, then rung 1 once the others finished; the probe
    is the last row) yields the greedy tokens it yields alone at rung 1
    and, in float32, the same first-token log-probability to 1e-5: what
    is left is the order of the sums in products of other shapes."""
    e, alone = stacks(stack)
    prompts = [tokens_of(others * 7 + i, 4 + 9 * i) for i in range(others)]
    got = serve_together(e, prompts + [PROBE], new_tokens=6)[-1]
    assert got.token_ids == alone.token_ids
    assert got.token_logprobs[0] == pytest.approx(
        alone.token_logprobs[0], abs=1e-5
    )
    np.testing.assert_allclose(
        got.token_logprobs, alone.token_logprobs, atol=1e-4
    )


# ----------------------------------------------------------------------
# (c) a prompt's stream does not depend on where its chunks are cut
# ----------------------------------------------------------------------

CHUNKS = (16, 32, 64)


@pytest.fixture(scope="module")
def by_chunk():
    """prefill_chunk -> a started engine, built at a test's first ask."""
    built = {}

    def engine(chunk):
        if chunk not in built:
            e = InferenceEngine(
                "llama-tiny-f32", tokenizer=ByteTokenizer(), n_slots=4,
                max_len=128, prefill_chunk=chunk, window_k=4,
            )
            e.start_sync()
            built[chunk] = e
        return built[chunk]

    yield engine
    for e in built.values():
        e.close()


@pytest.mark.parametrize("beside", [0, 3], ids=["alone", "beside-3"])
@pytest.mark.parametrize("spans", [1, 3, 5])
def test_a_prompts_stream_is_the_same_wherever_its_chunks_are_cut(
    by_chunk, spans, beside,
):
    """A prompt that spans 1, 3 or 5 chunks of 16 (so 1, 2, 3 of 32 and 1,
    1, 2 of 64), alone and beside three others of mixed lengths, yields
    the same greedy tokens at every ``prefill_chunk`` and, in float32, the
    same log-probabilities to 1e-4: chunking is a dispatch shape, and a
    request must not see it."""
    probe = tokens_of(40 + spans, (spans - 1) * 16 + 9)
    others = [tokens_of(50 + i, 5 + 23 * i) for i in range(beside)]
    results = [
        serve_together(by_chunk(chunk), others + [probe], new_tokens=6)[-1]
        for chunk in CHUNKS
    ]
    for got in results[1:]:
        assert got.token_ids == results[0].token_ids
        np.testing.assert_allclose(
            got.token_logprobs, results[0].token_logprobs, atol=1e-4
        )
