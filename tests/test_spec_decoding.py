"""Speculative decoding exactness suite (ISSUE 20 acceptance gate).

The exact-verify redesign makes the spec window run the LITERAL
decode-step program once per candidate position (same shapes, same
bf16 reduction order), so spec-on streams are byte-identical to
spec-off BY CONSTRUCTION — at bf16, where the old batched ``[S, G+1]``
verify forward flipped near-tie argmaxes on 4/8 bench prompts. This
suite pins that contract everywhere the stream contract already
reaches:

* bf16 byte-identity (tokens AND logprob floats) at ``spec=2`` vs
  ``spec=0`` on the exact BENCH_SPEC_WORKLOAD prompt set — the four
  previously-flipping prompts included;
* seeded-sampled streams identical too, and sampled slots now ACCEPT
  drafts (the counter-keyed draw is reproduced inside the verify scan,
  so acceptance is no longer pinned to zero off the greedy path);
* ``logit_bias`` composes with speculation (the per-request bias plane
  rides the same shared sampling closure);
* byte-identity across prefix-cache warm hits, disaggregated-tier
  KV-block transfers, mid-stream supervisor replay, and tp=2;
* acceptance-counter math: tokens-per-step lives in [1, G+1], and the
  n-gram-friendly repeated-text shape accepts well above 1;
* zero steady-state recompiles with spec on (the exit-6 fence's
  invariant, asserted engine-side);
* the tokens-per-step counter says what engaged: accepted counts on a
  spec engine, exactly 1.0 a plain window on a ``spec_tokens == 0`` one;
* the ``TPU_SPEC_TOKENS=auto`` default seam: 0 on every backend with an
  attributable boot note (the exact verify can tie the plain window in
  device time and never beat it; measured at 1.0 tokens a step on the
  chip), explicit integers pass through, and an explicit contradiction
  with penalties/top_logprobs still raises.

Determinism: engines share the default seed; faults fire on exact hit
counts through ``gofr_tpu/faults``; supervisor backoff sleeps are
recorded, not slept.
"""

from __future__ import annotations

import random
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.serving.engine import (
    InferenceEngine,
    resolve_spec_tokens,
)
from gofr_tpu.serving.supervisor import EngineSupervisor
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.replica_pool import EngineReplica, ReplicaPool

#: The BENCH_SPEC_WORKLOAD prompt set verbatim: repeated text with a
#: per-request rotation — the n-gram draft's best case, and the set on
#: which the old batched verify diverged on 4 of 8.
BENCH_PROMPTS = [
    ("abcdefgh"[i % 4:] + "abcdefgh" * 12)[:64] for i in range(8)
]

#: 96 tokens = exactly 3 full 32-token KV blocks, so prefix hits and
#: tier transfers engage their block-aligned paths (tier-suite idiom).
BLOCK_PROMPT = list(range(2, 200, 3)) + [7] * 30
assert len(BLOCK_PROMPT) == 96

G = 2

#: Shared serving geometry so both engines compile the same programs.
ENG_KW = dict(n_slots=4, max_len=256, window_k=4)

GREEDY = dict(max_new_tokens=24, temperature=0.0, stop_on_eos=False)
SAMPLED = dict(max_new_tokens=24, temperature=0.8, seed=42,
               stop_on_eos=False)


def _spec_metrics():
    # Only the acceptance histogram is registered (the bench serve()
    # idiom) — the metrics manager tolerates records against
    # unregistered instruments.
    m = new_metrics_manager()
    m.new_histogram("app_tpu_spec_tokens_per_step")
    return m


def _acceptance(metrics):
    """(sum, count) of the acceptance histogram — tokens emitted per
    live spec step, aggregated over every record so far."""
    for inst in metrics.instruments():
        if inst.name == "app_tpu_spec_tokens_per_step":
            agg_sum = agg_n = 0.0
            for _, (_, (s_, n_)) in inst.collect().items():
                agg_sum += s_
                agg_n += n_
            return agg_sum, agg_n
    return 0.0, 0.0


def _make_engine(spec_tokens, metrics=None, **kw):
    eng = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(),
        spec_tokens=spec_tokens, metrics=metrics, **{**ENG_KW, **kw},
    )
    eng.start_sync()
    return eng


@pytest.fixture(scope="module")
def spec_metrics():
    return _spec_metrics()


@pytest.fixture(scope="module")
def plain_metrics():
    return _spec_metrics()


@pytest.fixture(scope="module")
def engines(spec_metrics, plain_metrics):
    """The shared pair: a spec=0 reference and a spec=2 engine, both
    bf16 llama-tiny with prefix pools. Module-scoped — construction
    and first-dispatch compiles dominate this suite's wall clock."""
    ref = _make_engine(0, prefix_slots=2, metrics=plain_metrics)
    spec = _make_engine(G, prefix_slots=2, metrics=spec_metrics)
    yield ref, spec
    faults.reset()
    for eng in (ref, spec):
        eng.close()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def _drain_stream(req, timeout=120.0):
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


# ----------------------------------------------------------------------
# bf16 byte-identity: the exact-verify contract, on the bench prompts
# ----------------------------------------------------------------------


def test_bf16_greedy_byte_identical_on_bench_prompts(engines):
    """spec=2 == spec=0 at bf16 on all 8 BENCH_SPEC_WORKLOAD prompts —
    tokens AND per-token logprob floats, which pins the verify LOGITS,
    not just the argmax (log_softmax is injective in the chosen row)."""
    ref, spec = engines
    want = [ref.generate_sync(p, **GREEDY) for p in BENCH_PROMPTS]
    reqs = [spec.submit_generate(p, **GREEDY) for p in BENCH_PROMPTS]
    got = [r.future.result(timeout=120) for r in reqs]
    for w, g in zip(want, got):
        assert g.token_ids == w.token_ids
        assert g.token_logprobs == w.token_logprobs  # exact floats
        assert g.finish_reason == w.finish_reason


def test_bf16_seeded_sampled_byte_identical_and_accepting(
    engines, spec_metrics
):
    """Satellite regression: sampled slots are no longer draft-free.
    The verify scan reproduces the counter-keyed categorical draw at
    every candidate position, so (a) seeded-sampled streams stay
    byte-identical at spec=G vs spec=0, and (b) acceptance can exceed
    the old hard floor of exactly 1.0 token per step (acc pinned 0)."""
    ref, spec = engines
    sum0, n0 = _acceptance(spec_metrics)
    # Repeated text at a LOW temperature: the seeded draw mostly
    # follows the mode, so n-gram drafts land often enough that a
    # single pinned-zero acceptance path would show mean == 1.0.
    near_greedy = dict(max_new_tokens=32, temperature=0.2, seed=7,
                       stop_on_eos=False)
    for params in (SAMPLED, near_greedy):
        for prompt in BENCH_PROMPTS[:4]:
            want = ref.generate_sync(prompt, **params)
            got = spec.generate_sync(prompt, **params)
            assert got.token_ids == want.token_ids
            assert got.token_logprobs == want.token_logprobs
    sum1, n1 = _acceptance(spec_metrics)
    assert n1 > n0
    mean = (sum1 - sum0) / (n1 - n0)
    assert mean > 1.0  # sampled slots accepted at least some drafts


def test_logit_bias_composes_with_speculation(engines):
    """The per-request bias plane rides the shared sampling closure
    inside the verify scan, so logit_bias no longer disables (or
    refuses) speculation — and the biased stream is byte-identical."""
    ref, spec = engines
    banned = ref.tokenizer.encode("a")[0]
    params = dict(max_new_tokens=16, temperature=0.0, stop_on_eos=False,
                  logit_bias={int(banned): -100.0})
    want = ref.generate_sync(BENCH_PROMPTS[0], **params)
    got = spec.generate_sync(BENCH_PROMPTS[0], **params)
    assert got.token_ids == want.token_ids
    assert banned not in got.token_ids  # the bias actually bit


# ----------------------------------------------------------------------
# identity across the stream contract's existing features
# ----------------------------------------------------------------------


def test_prefix_cache_warm_hit_byte_identical(engines):
    """A pooled-prefix warm hit changes the prefill path (admission
    copy instead of chunked prefill) but not one emitted byte — with
    speculation drafting over the copied history from token one."""
    ref, spec = engines
    system = "You are a terse assistant. Answer in one word. "
    ref.register_prefix_sync(system)
    spec.register_prefix_sync(system)
    prompt = system + "go go go go"
    want_cold = ref.generate_sync(prompt, **GREEDY)
    got_cold = spec.generate_sync(prompt, **GREEDY)
    # Second pass re-hits the pool on both engines (warm path).
    want_warm = ref.generate_sync(prompt, **GREEDY)
    got_warm = spec.generate_sync(prompt, **GREEDY)
    assert got_cold.token_ids == want_cold.token_ids
    assert got_warm.token_ids == want_warm.token_ids == want_cold.token_ids


def test_tier_transfer_byte_identical_with_spec():
    """Prefill-on-A → KV-block ship → decode-on-B with spec=2 on both
    replicas: greedy and seeded-sampled streams match a fused spec=0
    single-engine reference byte for byte."""
    paged = dict(
        n_slots=4, max_len=256, window_k=4, pipeline_depth=1,
        prefill_chunk=32, kv_block=32, auto_prefix=True,
    )
    ref = _make_engine(0, **paged)
    pf = _make_engine(G, **paged)
    dc = _make_engine(G, **paged)
    pool = ReplicaPool(
        [
            EngineReplica("pf", pf, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        probe_interval_s=0, probe_timeout_s=60.0, hedge_delay_s=300.0,
        transfer_retries=2, transfer_backoff_s=0.01,
        sleep=lambda s: None, rng=random.Random(7),
    )
    try:
        for params in (
            dict(max_new_tokens=12, temperature=0.0),
            dict(max_new_tokens=10, temperature=0.8, seed=42),
        ):
            want = ref.generate_sync(BLOCK_PROMPT, timeout=120, **params)
            req = pool.submit_generate(BLOCK_PROMPT, **params)
            toks = _drain_stream(req)
            assert toks == want.token_ids
            assert req.future.result(timeout=5).token_ids == want.token_ids
    finally:
        pool.stop_prober()
        for eng in (pf, dc, ref):
            eng.close()


def test_supervisor_replay_byte_identical_with_spec(engines):
    """A device crash mid-generation on the spec engine: the supervisor
    warm-restarts, the request replays, and what the client streamed —
    pre-crash tokens plus the continuation — is exactly the spec=0
    fault-free sequence. Speculation state (history plane, acceptance
    counters) rebuilds from the replay without changing a byte."""
    ref, _ = engines
    want = ref.generate_sync("the quick brown fox", max_new_tokens=32,
                             temperature=0.0, stop_on_eos=False)
    eng = _make_engine(G)
    sleeps = []
    sup = EngineSupervisor(
        eng, max_restarts=3, backoff_s=0.25, backoff_reset_s=60.0,
        join_timeout_s=5.0, rng=random.Random(1234),
        sleep=lambda s: sleeps.append((eng.state, s)),
    ).start()
    try:
        # Warm the compile caches fault-free first.
        warm = eng.generate_sync("the quick brown fox", max_new_tokens=32,
                                 temperature=0.0, stop_on_eos=False)
        assert warm.token_ids == want.token_ids
        # Crash at the 4th device dispatch — past the prefill chunk and
        # the first spec windows, so tokens are already on the stream.
        faults.arm(
            "scheduler.device_step",
            raises=RuntimeError("injected device loss"),
            after=3, times=1,
        )
        req = eng.submit_generate("the quick brown fox", max_new_tokens=32,
                                  temperature=0.0, stop_on_eos=False)
        pre = [req.stream.get(timeout=120) for _ in range(3)]
        assert all(t is not None for t in pre)
        rest = _drain_stream(req)
        result = req.future.result(timeout=120)
        assert pre + rest == want.token_ids
        assert result.token_ids == want.token_ids
        assert req.replays == 1
        assert [s for s, _ in sleeps] == ["RESTARTING"]
    finally:
        faults.reset()
        sup.stop()
        eng.close()


def test_tp2_spec_byte_identical(engines):
    """tp=2 with spec=2 == the unsharded spec=0 reference: the verify
    scan runs the same GSPMD-sharded decode-step program, so sharding
    and speculation compose without touching the stream."""
    import jax

    devs = jax.devices()
    assert len(devs) >= 2, "suite needs the conftest's virtual devices"
    ref, _ = engines
    tp2 = _make_engine(G, devices=devs[:2], tp=2)
    try:
        for params in (GREEDY, SAMPLED):
            want = ref.generate_sync("shard me please", **params)
            got = tp2.generate_sync("shard me please", timeout=240, **params)
            assert got.token_ids == want.token_ids
    finally:
        tp2.close()


# ----------------------------------------------------------------------
# acceptance-counter math + the recompile fence
# ----------------------------------------------------------------------


def test_acceptance_counter_math(engines, spec_metrics):
    """Tokens-per-live-step ∈ [1, G+1] always (one bonus token even at
    zero accepted drafts; at most G drafts + the bonus), and the
    n-gram-friendly repeated-text shape accepts well above the floor."""
    _, spec = engines
    sum0, n0 = _acceptance(spec_metrics)
    results = [
        spec.generate_sync(p, max_new_tokens=32, temperature=0.0,
                           stop_on_eos=False)
        for p in BENCH_PROMPTS[:4]
    ]
    assert all(len(r.token_ids) == 32 for r in results)
    sum1, n1 = _acceptance(spec_metrics)
    assert n1 > n0
    mean = (sum1 - sum0) / (n1 - n0)
    assert 1.0 <= mean <= G + 1
    # "abcabc…" is the prompt-lookup best case — if drafting or the
    # verify scan silently stopped accepting, this drops to ~1.0.
    assert mean > 1.2


@pytest.mark.parametrize("which", ["plain", "spec"])
def test_tokens_per_step_counter_says_what_engaged(
    engines, plain_metrics, spec_metrics, which
):
    """``app_tpu_spec_tokens_per_step`` stays alive at spec_tokens == 0:
    a plain step emits one token per live slot by definition, so every
    plain window that had a live slot records exactly 1.0; a spec engine
    still records its accepted counts."""
    ref, spec = engines
    eng, metrics = (
        (ref, plain_metrics) if which == "plain" else (spec, spec_metrics)
    )
    assert eng.spec_tokens == (0 if which == "plain" else G)
    sum0, n0 = _acceptance(metrics)
    result = eng.generate_sync(BENCH_PROMPTS[0], **GREEDY)
    assert len(result.token_ids) == GREEDY["max_new_tokens"]
    sum1, n1 = _acceptance(metrics)
    assert n1 > n0  # one record a processed window
    mean = (sum1 - sum0) / (n1 - n0)
    if which == "plain":
        assert mean == 1.0
    else:
        assert 1.0 < mean <= G + 1  # the repeated text accepts drafts


def test_zero_steady_state_recompiles_with_spec():
    """The warm-up fence with spec on: after greedy, seeded-sampled,
    and logit_bias variants have each compiled once, further traffic
    of any of those shapes recompiles NOTHING (bench exit-6 fence)."""
    eng = _make_engine(G)
    try:
        variants = (
            dict(max_new_tokens=8, temperature=0.0, stop_on_eos=False),
            dict(max_new_tokens=8, temperature=0.8, seed=3,
                 stop_on_eos=False),
            dict(max_new_tokens=8, temperature=0.0, stop_on_eos=False,
                 logit_bias={5: -100.0}),
        )
        for params in variants:
            eng.generate_sync(BENCH_PROMPTS[0], **params)
        eng.mark_steady_state()
        for params in variants:
            eng.generate_sync(BENCH_PROMPTS[1], **params)
        stats = eng.compile_stats()
        assert stats["steady_state_recompiles"] == 0, stats
    finally:
        eng.close()


# ----------------------------------------------------------------------
# the TPU_SPEC_TOKENS=auto default seam
# ----------------------------------------------------------------------


def test_resolve_spec_tokens_auto_seam():
    # The plain decode window on every backend, the chip included: the
    # sequential exact verify pays G+1 decode forwards a step, so it
    # can tie the plain window in device time and never beat it. The
    # note says what was resolved, where, and what was measured.
    for backend in ("tpu", "cpu"):
        n, note = resolve_spec_tokens("auto", backend, False, 0)
        assert n == 0 and f"backend={backend!r}" in note
        assert "off by default" in note and "1.0 tokens a step" in note
        assert "TPU_PENALTIES" not in note
    assert resolve_spec_tokens("", "tpu", False, 0)[0] == 0  # unset
    # The note names the features an opt-in would contradict.
    n, note = resolve_spec_tokens("auto", "tpu", True, 0)
    assert n == 0 and "not alongside TPU_PENALTIES" in note
    n, note = resolve_spec_tokens("auto", "tpu", False, 3)
    assert n == 0 and "not alongside TPU_TOP_LOGPROBS" in note
    # Explicit integers pass through untouched (backend-independent):
    # opting in still serves the spec window exactly as before; the
    # constructor owns explicit-conflict errors.
    assert resolve_spec_tokens("3", "cpu", True, 5) == (3, None)
    assert resolve_spec_tokens("2", "tpu", False, 0) == (2, None)
    assert resolve_spec_tokens("0", "tpu", False, 0) == (0, None)
    assert resolve_spec_tokens("-2", "tpu", False, 0) == (0, None)
    with pytest.raises(ValueError, match="integer or 'auto'"):
        resolve_spec_tokens("bogus", "tpu", False, 0)


class _RecordingLogger:
    def __init__(self):
        self.lines = []

    def infof(self, fmt, *args):
        self.lines.append(fmt % args if args else fmt)

    warnf = errorf = debugf = infof


def _cfg(**extra):
    return MockConfig({
        "TPU_KV_SLOTS": "2", "TPU_MAX_LEN": "128", **extra,
    })


def test_from_config_auto_resolves_per_backend_and_logs():
    # auto resolves to the plain window with an attributable boot
    # note; nothing raises, nothing needs TPU_SPEC_TOKENS set.
    logger = _RecordingLogger()
    eng = InferenceEngine.from_config(_cfg(), logger=logger)
    try:
        assert eng.spec_tokens == 0
        assert eng.health_check()["details"]["spec_tokens"] == 0
        assert any(
            "speculative decoding off by default" in ln
            for ln in logger.lines
        )
    finally:
        eng.close()
    # An explicit integer opts in to the spec window.
    eng = InferenceEngine.from_config(_cfg(TPU_SPEC_TOKENS="2"))
    try:
        assert eng.spec_tokens == 2
    finally:
        eng.close()


def test_spec_feature_precedence_both_directions():
    # Direction 1: the default never contradicts — a deployment with
    # penalties (or top_logprobs) enabled boots at spec 0, and the note
    # says an opt-in would not go alongside them.
    for extra in ({"TPU_PENALTIES": "true"}, {"TPU_TOP_LOGPROBS": "3"}):
        logger = _RecordingLogger()
        eng = InferenceEngine.from_config(_cfg(**extra), logger=logger)
        try:
            assert eng.spec_tokens == 0
            assert any("not alongside TPU_" in ln for ln in logger.lines)
        finally:
            eng.close()
    # Direction 2: an EXPLICIT contradiction the user typed still
    # raises — both through from_config and the constructor.
    with pytest.raises(ValueError, match="mutually exclusive"):
        InferenceEngine.from_config(
            _cfg(TPU_PENALTIES="true", TPU_SPEC_TOKENS="2")
        )
    with pytest.raises(ValueError, match="mutually"):
        InferenceEngine(
            "llama-tiny", n_slots=2, max_len=128,
            tokenizer=ByteTokenizer(), top_logprobs=2, spec_tokens=2,
        )
