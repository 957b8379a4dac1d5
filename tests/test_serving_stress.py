"""Serving engine concurrency stress: many submitters, mixed
temperatures and lengths, interleaved prefix registrations, mid-flight
cancellations, and a stop/start cycle — no request may hang, leak a
slot, or land on an unresolved future. This is the adversarial
counterpart to test_serving.py's single-behavior tests: the scheduler's
invariants under concurrent load."""

from __future__ import annotations

import random
import threading
from concurrent.futures import CancelledError

import pytest

from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

PREFIX = "System: stress. "


def _wait_slots_free(engine, timeout: float = 15.0) -> None:
    """The scheduler clears a slot AFTER resolving its future — poll
    briefly instead of racing that window."""
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(s is None for s in engine._slots) and not engine._prefilling:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"slots never drained: {engine._slots} {engine._prefilling}"
    )


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=128, tokenizer=ByteTokenizer(),
        prefix_slots=2,
    )
    eng.start_sync()
    yield eng
    eng.stop_sync()


def test_concurrent_mixed_load_all_requests_resolve(engine):
    rng = random.Random(0)
    results, errors = [], []
    lock = threading.Lock()

    def client(seed: int) -> None:
        r = random.Random(seed)
        for i in range(4):
            prompt = (PREFIX if r.random() < 0.5 else "") + f"client {seed} msg {i}"
            try:
                out = engine.generate_sync(
                    prompt,
                    max_new_tokens=r.randint(1, 12),
                    temperature=r.choice([0.0, 0.8]),
                    stop_on_eos=False,
                    timeout=120,
                )
                with lock:
                    results.append(out)
            except Exception as exc:  # noqa: BLE001
                with lock:
                    errors.append(exc)

    def registrar() -> None:
        try:
            engine.register_prefix_sync(PREFIX, timeout=120)
            engine.register_prefix_sync("Other prefix. ", timeout=120)
            engine.register_prefix_sync(PREFIX + "deeper ", timeout=120)
        except Exception as exc:  # noqa: BLE001
            with lock:
                errors.append(exc)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(8)]
    threads.append(threading.Thread(target=registrar))
    rng.shuffle(threads)
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "stress client hung"

    assert not errors, errors
    assert len(results) == 32
    for out in results:
        assert 1 <= len(out.token_ids) <= 12
        assert out.ttft_s >= 0
    # All slots drained back to free.
    _wait_slots_free(engine)


def test_cancellations_under_load_free_all_slots(engine):
    reqs = [
        engine.submit_generate(
            f"cancel target {i}", max_new_tokens=64, temperature=0.0,
            stop_on_eos=False,
        )
        for i in range(12)
    ]
    # Partition by cancel()'s actual outcome: a fast scheduler may finish
    # a target before the cancel loop reaches it (cancel() → False).
    cancelled = [
        r for i, r in enumerate(reqs) if i % 3 == 0 and r.future.cancel()
    ]
    survivors = [r for r in reqs if r not in cancelled]
    assert cancelled, "no cancel landed before completion — inconclusive"
    for req in survivors:
        out = req.future.result(timeout=120)
        assert len(out.token_ids) == 64
    # Cancelled requests' streams must terminate too (None sentinel).
    deadline = 12.0
    for req in cancelled:
        with pytest.raises(CancelledError):
            req.future.result(timeout=1)
        got = req.stream.get(timeout=deadline)
        while got is not None:
            got = req.stream.get(timeout=deadline)
    # Engine healthy afterwards.
    out = engine.generate_sync(
        "after cancels", max_new_tokens=4, temperature=0.0,
        stop_on_eos=False, timeout=120,
    )
    assert len(out.token_ids) == 4
    _wait_slots_free(engine)


def test_stop_start_cycle_preserves_service_and_prefixes(engine):
    engine.register_prefix_sync(PREFIX + "cycle ", timeout=120)
    before = engine.generate_sync(
        PREFIX + "cycle check", max_new_tokens=6, temperature=0.0,
        stop_on_eos=False, timeout=120,
    )
    engine.stop_sync()
    with pytest.raises(RuntimeError):
        engine.submit_generate("down", max_new_tokens=1)
    engine.start_sync()
    after = engine.generate_sync(
        PREFIX + "cycle check", max_new_tokens=6, temperature=0.0,
        stop_on_eos=False, timeout=120,
    )
    # Pool and params survive the cycle; greedy output is reproducible.
    assert after.token_ids == before.token_ids


def test_mixed_sampling_features_concurrent_stress():
    """Cross-feature interaction stress: concurrent requests mixing
    seeds, penalties, logit_bias, top_logprobs, and uneven budgets on
    one engine — per-request invariants must hold even as the
    slot-state/admission uploads interleave."""
    import random

    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=128, window_k=4,
        enable_penalties=True, top_logprobs=2, tokenizer=ByteTokenizer(),
    )
    eng.start_sync()
    rng = random.Random(0)
    try:
        reqs = []
        for i in range(24):
            kw = {"max_new_tokens": rng.choice([3, 7, 12, 20])}
            style = i % 4
            if style == 0:
                kw.update(temperature=0.9, seed=1234)  # repro pair group
            elif style == 1:
                kw.update(temperature=0.0, frequency_penalty=1.2)
            elif style == 2:
                kw.update(temperature=0.0, logit_bias={9: -100})
            else:
                kw.update(temperature=0.0, top_logprobs=2)
            prompt = f"prompt {i % 3}"
            kw["_prompt"] = prompt
            reqs.append((kw, eng.submit_generate(
                prompt, stop_on_eos=False,
                **{k: v for k, v in kw.items() if k != "_prompt"}
            )))
        results = [(kw, r.future.result(timeout=180)) for kw, r in reqs]
        seeded = {}
        for kw, res in results:
            assert len(res.token_ids) == kw["max_new_tokens"]
            if "seed" in kw:
                key = (kw["max_new_tokens"], kw["_prompt"])
                if key in seeded:
                    assert res.token_ids == seeded[key]  # same seed+params
                else:
                    seeded[key] = res.token_ids
            if "logit_bias" in kw:
                assert 9 not in res.token_ids
            if "top_logprobs" in kw:
                assert len(res.token_top_logprobs) == len(res.token_ids)
                for tok, alts in zip(res.token_ids, res.token_top_logprobs):
                    assert alts[0][0] == tok  # greedy == top-1
            else:
                assert res.token_top_logprobs is None
    finally:
        eng.stop_sync()


def test_lora_cross_feature_concurrent_stress():
    """Adapters join the cross-feature stress: concurrent requests mix
    LoRA adapters with seeds, penalties, logit_bias and uneven budgets
    on one engine. Invariants: greedy same-adapter repeats
    are identical, adapters differ from base, budgets exact, bias bans
    hold under adapters too."""
    import random

    import jax

    from gofr_tpu.models.transformer import lora_dims
    from gofr_tpu.models.registry import get_model
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    cfg = get_model("llama-tiny").config
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=128, window_k=4,
        enable_penalties=True, tokenizer=ByteTokenizer(),
        lora_slots=2, lora_rank=4,
    )
    eng.start_sync()
    rng = random.Random(1)
    try:
        for ai, name in enumerate(("a1", "a2")):
            leaves = {}
            for ti, t in enumerate(("wq", "wv")):
                d_in, d_out = lora_dims(cfg, t)
                k1, k2 = jax.random.split(
                    jax.random.fold_in(jax.random.PRNGKey(40 + ai), ti)
                )
                leaves[t] = (
                    0.5 * jax.random.normal(k1, (cfg.n_layers, d_in, 4)),
                    0.5 * jax.random.normal(k2, (cfg.n_layers, 4, d_out)),
                )
            eng.load_lora(name, leaves)
        reqs = []
        for i in range(24):
            kw = {
                "max_new_tokens": rng.choice([4, 9, 15]),
                "adapter": ("", "a1", "a2")[i % 3],
                "temperature": 0.0,
            }
            if i % 4 == 0:
                kw["frequency_penalty"] = 1.1
            if i % 5 == 0:
                kw["logit_bias"] = {7: -100}
            reqs.append((kw, eng.submit_generate(
                "same prompt", stop_on_eos=False, **kw
            )))
        results = [(kw, r.future.result(timeout=180)) for kw, r in reqs]
        groups: dict = {}
        for kw, res in results:
            assert len(res.token_ids) == kw["max_new_tokens"]
            if "logit_bias" in kw:
                assert 7 not in res.token_ids
            key = (
                kw["adapter"], kw["max_new_tokens"],
                kw.get("frequency_penalty", 0), "logit_bias" in kw,
            )
            if key in groups:
                assert res.token_ids == groups[key]  # deterministic
            else:
                groups[key] = res.token_ids
        # Adapter isolation: same budget/features, different adapter →
        # different streams (random adapters shift greedy paths).
        plain = {
            k: v for k, v in groups.items() if k[2] == 0 and not k[3]
        }
        by_budget: dict = {}
        for (ad, n, _, _), toks in plain.items():
            by_budget.setdefault(n, {})[ad] = toks
        checked = 0
        for n, outs in by_budget.items():
            if len(outs) >= 2:
                assert len({tuple(v) for v in outs.values()}) == len(outs)
                checked += 1
        assert checked >= 1
    finally:
        eng.stop_sync()
