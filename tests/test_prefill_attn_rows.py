"""A blocked prefill attention runs each row of a step over its own blocks
(ISSUE 36), through the engine on the CPU.

``latent_chunk_attention`` and ``sparse_chunk_attention`` bound their loop
over blocks of positions by each row's own last position; the scheduler
records how far that engages (``app_tpu_prefill_attn_visit_ratio``: the rows'
own blocks over rows x the longest row's). Here, for ``mla-moe-tiny`` (a
latent cache) and ``sala-tiny`` (a hybrid cache) in float32: a prompt served
alone (the one-row rung) and the same prompt beside seven others that stand at
other depths give the same greedy tokens; the histogram reads 1.0 for the
lone steps and under 1 for the mixed ones; a dense cache records nothing.

Rows at other depths are made by submitting in groups a scheduler pass apart
(the ``scheduler.window`` fault point counts the passes; a pass runs prefill
steps until two full steps' rows went, ``WAVE_STEPS``): prompts that are
admitted together advance together, a chunk a step.
"""

from __future__ import annotations

import dataclasses
import threading

import jax.numpy as jnp
import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.models.registry import get_model, register_model
from gofr_tpu.ops.attention import LATENT_CHUNK_BLOCK, SPARSE_CHUNK_BLOCK
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from tests.test_prefill_rungs import serve_together, tokens_of

CHUNK = 64
VISIT = "app_tpu_prefill_attn_visit_ratio"
MAX_LEN = 3 * max(LATENT_CHUNK_BLOCK, SPARSE_CHUNK_BLOCK)  # >= three blocks a slot
BLOCK = {"mla-moe-tiny-f32-long": LATENT_CHUNK_BLOCK,
         "sala-tiny-f32-long": SPARSE_CHUNK_BLOCK}

for _name in ("mla-moe-tiny", "sala-tiny"):
    _spec = get_model(_name)
    register_model(dataclasses.replace(
        _spec, name=_name + "-f32-long",  # float32, MAX_LEN positions
        config=dataclasses.replace(
            _spec.config, dtype=jnp.float32, max_len=MAX_LEN
        ),
    ))


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def visit_sum_count(metrics, model: str) -> tuple[float, int]:
    inst = {i.name: i for i in metrics.instruments()}[VISIT]
    for labels, (_buckets, (total, n)) in inst.collect().items():
        if ("model", model) in labels:
            return total, n
    return 0.0, 0


def serve_in_groups(engine, groups: list, gap: int, new_tokens: int = 6):
    """Submit each group of prompts ``gap`` scheduler passes after the one
    before: the groups' rows then stand some chunks apart. Results in order,
    group by group."""
    passes = [0]
    reached = [threading.Event() for _ in groups]
    go = [threading.Event() for _ in groups]

    def gate(**fired):
        if fired.get("engine") is not engine:
            return
        k, passes[0] = passes[0], passes[0] + 1
        if k % gap == 0 and k // gap < len(groups):
            reached[k // gap].set()
            go[k // gap].wait(timeout=120)

    requests = []
    with faults.armed("scheduler.window", action=gate):
        try:
            for i, prompts in enumerate(groups):
                assert reached[i].wait(120), f"pass {i * gap} never came"
                requests += [
                    engine.submit_generate(
                        p, max_new_tokens=new_tokens, temperature=0.0,
                        stop_on_eos=False,
                    )
                    for p in prompts
                ]
                go[i].set()
        finally:
            for e in go:
                e.set()
        return [r.future.result(timeout=600) for r in requests]


@pytest.mark.parametrize("model", list(BLOCK))
def test_a_prompt_alone_and_beside_rows_at_other_depths(model):
    """The one-row rung runs the prompt's own blocks (ratio 1.0 a step); in
    an eight-row step beside prompts at other depths every row still runs
    its own, so the tokens are the same and the ratio falls under 1."""
    metrics = Container.create(MockConfig({"APP_NAME": "visit-test"})).metrics
    engine = InferenceEngine(
        model, tokenizer=ByteTokenizer(), n_slots=8, max_len=MAX_LEN,
        prefill_chunk=CHUNK, window_k=4, pipeline_depth=1, metrics=metrics,
    )
    assert engine.prefill_attn_block == BLOCK[model]
    assert engine.prefill_rungs == (1, 8)
    engine.start_sync()
    try:
        prompt = tokens_of(36, 11 * CHUNK + 9)  # ends in the slot's second block
        (alone,) = serve_together(engine, [prompt], new_tokens=6)
        total, n = visit_sum_count(metrics, model)
        assert n == 12 and total == 12.0  # twelve lone steps, 1.0 each
        block = MAX_LEN // 3
        others = [  # seven prompts, in three groups that start a pass apart
            [tokens_of(1, 2 * block + 300), tokens_of(2, 2 * block + 150),
             tokens_of(3, 2 * block + 40)],
            [tokens_of(4, block + 400), tokens_of(5, block + 200)],
            [tokens_of(6, block + 100), tokens_of(7, block - 60)],
        ]
        results = serve_in_groups(engine, others + [[prompt]], gap=1)
        assert results[-1].token_ids == alone.token_ids
        assert len(alone.token_ids) == 6
        mixed_total, mixed_n = visit_sum_count(metrics, model)
        mixed_total, mixed_n = mixed_total - total, mixed_n - n
        assert mixed_n >= 12  # the prompt's own twelve chunks at the least
        # rows apart: steps ran under rows x the longest row's blocks (about
        # 0.87 a step here, with the padding rows as deep as the deepest)
        assert mixed_n - mixed_total >= 1.0
        assert engine.compile_stats()["programs"]["prefill_chunk"]["compiles"] == 2
    finally:
        engine.close()


def test_a_dense_cache_records_no_visit_ratio():
    metrics = Container.create(MockConfig({"APP_NAME": "visit-test"})).metrics
    engine = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), n_slots=2, max_len=128,
        prefill_chunk=16, metrics=metrics,
    )
    assert engine.prefill_attn_block == 0
    engine.start_sync()
    try:
        serve_together(engine, [tokens_of(0, 40)])
        assert visit_sum_count(metrics, "llama-tiny") == (0.0, 0)
    finally:
        engine.close()
