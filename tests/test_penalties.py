"""Frequency/presence penalties (TPU_PENALTIES): OpenAI-parity sampling
controls, compiled into the sampler as a per-slot generated-token count
plane. Greedy requests honor them too (penalties apply before argmax)."""

from __future__ import annotations

import pytest

from gofr_tpu.errors import ErrorInvalidParam
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

PROMPT = "the quick brown fox"


def _engine(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 128)
    kw.setdefault("window_k", 4)
    kw.setdefault("tokenizer", ByteTokenizer())
    return InferenceEngine("llama-tiny", **kw)


def _greedy(eng, n=24, **kw):
    return eng.generate_sync(
        PROMPT, max_new_tokens=n, temperature=0.0, stop_on_eos=False,
        timeout=120, **kw
    ).token_ids


@pytest.fixture(scope="module")
def base_tokens():
    eng = _engine()
    eng.start_sync()
    try:
        yield _greedy(eng)
    finally:
        eng.stop_sync()


def _max_run_frequency(tokens):
    from collections import Counter

    return max(Counter(tokens).values())


def test_zero_penalties_identical_to_base(base_tokens):
    # The penalties COMPILE path with zero coefficients must not perturb
    # the stream: penalized logits == raw logits when both are 0.
    eng = _engine(enable_penalties=True)
    eng.start_sync()
    try:
        assert _greedy(eng) == base_tokens
    finally:
        eng.stop_sync()


def test_frequency_penalty_breaks_repetition(base_tokens):
    # Random-weight greedy decode loops hard; a strong frequency penalty
    # must reduce the most-repeated token's count and change the stream.
    eng = _engine(enable_penalties=True)
    eng.start_sync()
    try:
        toks = _greedy(eng, frequency_penalty=1.5)
        assert toks != base_tokens
        assert _max_run_frequency(toks) < _max_run_frequency(base_tokens)
        # And independence: a concurrent zero-penalty request on the SAME
        # engine still matches the base stream (per-slot counts/coeffs).
        pen = eng.submit_generate(
            PROMPT, max_new_tokens=24, temperature=0.0, stop_on_eos=False,
            frequency_penalty=1.5,
        )
        plain = eng.submit_generate(
            PROMPT, max_new_tokens=24, temperature=0.0, stop_on_eos=False,
        )
        assert plain.future.result(timeout=120).token_ids == base_tokens
        assert pen.future.result(timeout=120).token_ids == toks
    finally:
        eng.stop_sync()


def test_presence_penalty_deviates_and_mild_frequency_differs(base_tokens):
    # Presence penalizes each seen token ONCE (not per occurrence). At a
    # strong coefficient both penalties suppress any repeat, so the
    # distinguishing case is a MILD coefficient: frequency accumulates
    # per occurrence and eventually overtakes the one-shot presence hit.
    eng = _engine(enable_penalties=True)
    eng.start_sync()
    try:
        base48 = _greedy(eng, n=48)
        p = _greedy(eng, n=48, presence_penalty=0.3)
        f = _greedy(eng, n=48, frequency_penalty=0.3)
        assert p != base48 and f != base48
        assert _max_run_frequency(f) <= _max_run_frequency(p)
    finally:
        eng.stop_sync()


def test_penalties_require_flag_and_range():
    eng = _engine()  # feature compiled OUT
    eng.start_sync()
    try:
        with pytest.raises(ErrorInvalidParam, match="TPU_PENALTIES"):
            eng.submit_generate(PROMPT, frequency_penalty=0.5)
    finally:
        eng.stop_sync()
    eng = _engine(enable_penalties=True)
    eng.start_sync()
    try:
        with pytest.raises(ErrorInvalidParam, match=r"\[-2, 2\]"):
            eng.submit_generate(PROMPT, presence_penalty=3.0)
    finally:
        eng.stop_sync()


class TestLogitBias:
    """OpenAI logit_bias: sparse per-request (token, bias) planes applied
    to raw logits before penalties, argmax, and sampling."""

    def test_minus_100_bans_and_plus_forces(self, base_tokens):
        eng = _engine()
        eng.start_sync()
        try:
            # Ban the greedy stream's first token: the stream must change
            # and never contain it.
            banned = int(base_tokens[0])
            toks = _greedy(eng, logit_bias={banned: -100})
            assert banned not in toks
            # +100 on one token forces it everywhere (greedy).
            forced = 7
            toks = _greedy(eng, n=8, logit_bias={forced: 100})
            assert toks == [forced] * 8
            # No bias → base stream intact on the same engine.
            assert _greedy(eng) == base_tokens
        finally:
            eng.stop_sync()

    def test_bias_validation(self):
        from gofr_tpu.errors import ErrorInvalidParam

        eng = _engine()
        eng.start_sync()
        try:
            with pytest.raises(ErrorInvalidParam, match="at most"):
                eng.submit_generate(
                    PROMPT, logit_bias={i: 1.0 for i in range(301)}
                )
            with pytest.raises(ErrorInvalidParam, match="integral"):
                eng.submit_generate(PROMPT, logit_bias={7.9: -100.0})
            with pytest.raises(ErrorInvalidParam, match="token ids"):
                eng.submit_generate(PROMPT, logit_bias={10_000_000: 1.0})
            with pytest.raises(ErrorInvalidParam, match="object"):
                eng.submit_generate(PROMPT, logit_bias=[5])
        finally:
            eng.stop_sync()


class TestTopLogprobs:
    """OpenAI top_logprobs alternatives (TPU_TOP_LOGPROBS compile gate)."""

    def test_alternatives_align_and_contain_chosen(self):
        eng = _engine(top_logprobs=4)
        eng.start_sync()
        try:
            r = eng.generate_sync(
                PROMPT, max_new_tokens=12, temperature=0.0,
                stop_on_eos=False, top_logprobs=3, timeout=120,
            )
            assert r.token_top_logprobs is not None
            assert len(r.token_top_logprobs) == len(r.token_ids) == 12
            for tok, lp, alts in zip(
                r.token_ids, r.token_logprobs, r.token_top_logprobs
            ):
                assert len(alts) == 3
                # Greedy: the chosen token IS the top-1 alternative and
                # its logprob matches.
                assert alts[0][0] == tok
                assert abs(alts[0][1] - lp) < 1e-4
                # Sorted descending.
                assert alts[0][1] >= alts[1][1] >= alts[2][1]
        finally:
            eng.stop_sync()

    def test_requires_compile_flag_and_cap(self):
        eng = _engine()
        eng.start_sync()
        try:
            with pytest.raises(ErrorInvalidParam, match="TPU_TOP_LOGPROBS"):
                eng.submit_generate(PROMPT, top_logprobs=2)
        finally:
            eng.stop_sync()
        eng = _engine(top_logprobs=2)
        eng.start_sync()
        try:
            with pytest.raises(ErrorInvalidParam, match=r"\[1, 2\]"):
                eng.submit_generate(PROMPT, top_logprobs=5)
        finally:
            eng.stop_sync()

    def test_without_request_flag_no_alternatives(self):
        eng = _engine(top_logprobs=2)
        eng.start_sync()
        try:
            r = eng.generate_sync(
                PROMPT, max_new_tokens=6, temperature=0.0,
                stop_on_eos=False, timeout=120,
            )
            assert r.token_top_logprobs is None
        finally:
            eng.stop_sync()
