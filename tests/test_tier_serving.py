"""Disaggregated prefill/decode tier chaos suite (ISSUE 8 acceptance
gate).

Everything is deterministic: faults fire on exact hit counts through
``gofr_tpu/faults`` (``tier.prefill_done`` / ``tier.transfer`` /
``tier.import``), backoff sleeps go through a recording hook, deadlines
ride injectable clocks, and the prober never runs as a thread. Engines
share the default seed, so the transfer failure matrix's byte-identical
contract is checkable against a fused single-engine reference.

Covered:

* tiered happy path: a greedy AND a seeded-sampled stream served
  prefill-on-A → KV-block ship → decode-on-B are byte-identical to the
  fused reference, with ``app_tpu_tier_transfers_total{result="ok"}``,
  a ``tpu.transfer`` timeline annotation, ONE trace id, and the flight
  record in the ORIGIN replica's recorder;
* transfer retry with jittered backoff (one flaky attempt → success,
  sleep recorded — graftlint GL013's contract, lived);
* THE acceptance path: the prefill replica dying mid-transfer (every
  transfer attempt fails) → the request fails over WITHOUT its blocks
  to the decode replica, which re-prefills — byte-identical stream,
  zero 5xx, one trace id, ``result="failed_over"`` == 1;
* decode-side import rejection (``tier.import`` raise: pool pressure /
  version mismatch) → same fused fallback;
* corrupt / short payloads → ``"fused"`` import (re-prefill on the
  decode replica), never a wrong answer;
* deadline expiry and caller cancellation mid-transfer → the request
  is reaped within one window and leaks zero pool blocks on either
  engine;
* tier collapse: draining the only prefill replica flips
  ``app_tpu_tier_mode`` to fused with requests still served;
* import dedupe: re-shipping already-cached content allocates nothing.
"""

from __future__ import annotations

import dataclasses
import random
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.errors import ErrorDeadlineExceeded, ErrorRequestCancelled
from gofr_tpu.ops.kv_cache import export_blocks
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.lifecycle import Deadline
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.serving.types import _GenRequest
from gofr_tpu.service.replica_pool import EngineReplica, ReplicaPool

TIER_COUNTERS = (
    "app_tpu_tier_transfers_total",
    "app_tpu_tier_transfer_bytes_total",
    "app_tpu_failovers_total",
    "app_tpu_requests_replayed_total",
    "app_tpu_requests_cancelled_total",
    "app_tpu_deadline_exceeded_total",
    "app_tpu_requests_shed_total",
    "app_tpu_tokens_generated",
    "app_tpu_prefix_lookup_total",
    "app_tpu_prefix_hit_tokens_total",
    "app_tpu_probe_failures_total",
    "app_tpu_hedged_requests_total",
)
TIER_GAUGES = (
    "app_tpu_tier_mode",
    "app_tpu_engine_state",
    "app_tpu_replica_state",
    "app_tpu_pool_replicas",
    "app_tpu_queue_depth",
    "app_tpu_kv_slots_in_use",
    "app_tpu_kv_blocks_free",
    "app_tpu_prefix_cached_blocks",
    "app_tpu_hbm_used_bytes",
)
TIER_HISTOGRAMS = (
    "app_tpu_tier_transfer_seconds",
    "app_tpu_infer_latency",
    "app_tpu_batch_size",
)

#: 96 tokens = exactly 3 full 32-token KV blocks — the whole-prompt-
#: cached edge (COW boundary) rides every transfer.
PROMPT = list(range(2, 200, 3)) + [7] * 30
assert len(PROMPT) == 96


def _metrics_manager():
    m = new_metrics_manager()
    for name in TIER_COUNTERS:
        m.new_counter(name)
    for name in TIER_GAUGES:
        m.new_gauge(name)
    for name in TIER_HISTOGRAMS:
        m.new_histogram(name)
    return m


def counter_total(metrics, name, **labels):
    inst = {i.name: i for i in metrics.instruments()}[name]
    total = 0.0
    for key, value in inst.collect().items():
        if all((k, str(v)) in key for k, v in labels.items()):
            total += value
    return total


def gauge_value(metrics, name):
    inst = {i.name: i for i in metrics.instruments()}[name]
    values = list(inst.collect().values())
    return values[0] if values else None


@pytest.fixture(scope="module")
def metrics():
    return _metrics_manager()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def _make_engine(metrics, **kw):
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, window_k=4,
        pipeline_depth=1, prefill_chunk=32, kv_block=32, auto_prefix=True,
        tokenizer=ByteTokenizer(), metrics=metrics, **kw,
    )
    eng.start_sync()
    return eng


@pytest.fixture(scope="module")
def engines(metrics):
    """One prefill + one decode engine shared by the suite (compile
    cost), plus a fused single-engine reference for byte-identity.
    Every test that wounds something restores it before finishing."""
    pf = _make_engine(metrics)
    dc = _make_engine(metrics)
    ref = _make_engine(metrics)
    yield pf, dc, ref
    faults.reset()
    for eng in (pf, dc, ref):
        eng.close()


@pytest.fixture()
def tier_pool(metrics, engines):
    """A fresh 1-prefill + 1-decode pool around the shared engines with
    recording backoff sleeps; hedging is parked far out so unary calls
    never race a second attempt into the determinism assertions."""
    pf, dc, _ = engines
    sleeps: list[float] = []
    pool = ReplicaPool(
        [
            EngineReplica("pf", pf, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        probe_interval_s=0,
        probe_timeout_s=60.0,
        hedge_delay_s=300.0,
        transfer_retries=2,
        transfer_backoff_s=0.01,
        sleep=sleeps.append,
        rng=random.Random(7),
        metrics=metrics,
    )
    pool._test_sleeps = sleeps
    yield pool
    pool.stop_prober()
    for replica in pool.replicas:
        replica.set_handoff(None)
        replica.set_tier_exporter(None)


def _drain_stream(req, timeout=120.0):
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


def _wait_idle(eng, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if (
            all(s is None for s in eng._slots)
            and not eng._prefilling
            and eng._pending.empty()
        ):
            return
        time.sleep(0.01)
    raise AssertionError("engine did not go idle")


def _engine_block_invariant(eng):
    """Every pool block is free, or accounted for by exactly its
    referencing slot tables plus the radix index (the zero-leak
    contract the cancel/deadline-mid-transfer tests pin)."""
    refs: dict[int, int] = {}
    for row in eng._slot_blocks:
        for bid in row:
            refs[bid] = refs.get(bid, 0) + 1
    for bid in eng._radix.cached_block_ids():
        refs[bid] = refs.get(bid, 0) + 1
    alloc = eng._allocator
    free = set(alloc.free_blocks)
    assert len(free) == len(alloc.free_blocks)
    for bid in range(1, alloc.n_blocks):
        expected = refs.get(bid, 0)
        assert alloc.refcount(bid) == expected, (bid,)
        assert (bid in free) == (expected == 0), (bid,)


def _reference(engines, **kw):
    _, _, ref = engines
    return ref.generate_sync(PROMPT, timeout=120.0, **kw)


# ----------------------------------------------------------------------
# happy path: tiered serving is byte-identical and observable
# ----------------------------------------------------------------------


def test_tiered_greedy_stream_byte_identical(metrics, engines, tier_pool):
    pf, dc, _ = engines
    want = _reference(engines, max_new_tokens=12, temperature=0.0)
    ok0 = counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok"
    )
    req = tier_pool.submit_generate(
        PROMPT, max_new_tokens=12, temperature=0.0
    )
    toks = _drain_stream(req)
    result = req.future.result(timeout=5)  # zero 5xx: resolves cleanly
    assert toks == result.token_ids == want.token_ids
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok"
    ) == ok0 + 1
    # The transfer rides the request's ONE timeline: same trace id end
    # to end, with a tpu.transfer hop naming both replicas.
    tl = req.timeline
    assert tl is not None and len(tl.trace_id) == 32
    assert [(s, d, r) for s, d, _, _, r, _ in tl.transfers] == [
        ("pf", "dc", "ok")
    ]
    # The flight record lands ONCE, in the ORIGIN (prefill) replica's
    # recorder, with the transfer annotation.
    records = pf.flight_records()
    entries = [
        e for e in records["records"] + records["pinned"]
        if e["rid"] == tl.rid
    ]
    assert len(entries) == 1
    assert entries[0]["transfers"] == [{
        "source": "pf", "target": "dc",
        "duration_s": entries[0]["transfers"][0]["duration_s"],
        "result": "ok",
        "leg": entries[0]["transfers"][0]["leg"],
    }]
    assert entries[0]["transfers"][0]["leg"] in ("device", "host")
    assert entries[0]["outcome"] == "ok"
    # The shipped blocks live in the DECODE replica's radix index now.
    assert dc._radix.n_cached_blocks >= 3
    _wait_idle(pf)
    _wait_idle(dc)
    _engine_block_invariant(pf)
    _engine_block_invariant(dc)


def test_tiered_seeded_sampled_stream_byte_identical(engines, tier_pool):
    want = _reference(engines, max_new_tokens=10, temperature=0.8, seed=42)
    req = tier_pool.submit_generate(
        PROMPT, max_new_tokens=10, temperature=0.8, seed=42
    )
    toks = _drain_stream(req)
    assert toks == want.token_ids
    assert req.future.result(timeout=5).token_ids == want.token_ids


def test_import_dedupes_already_cached_content(metrics, engines, tier_pool):
    """Re-shipping content the decode replica already caches allocates
    zero new blocks — the lookup-first import path."""
    pf, dc, _ = engines
    # Warm: first transfer populates dc's radix.
    req = tier_pool.submit_generate(PROMPT, max_new_tokens=6, temperature=0.0)
    _drain_stream(req)
    _wait_idle(dc)
    cached = dc._radix.n_cached_blocks
    free = dc._allocator.n_free
    req2 = tier_pool.submit_generate(PROMPT, max_new_tokens=6, temperature=0.0)
    _drain_stream(req2)
    _wait_idle(dc)
    assert dc._radix.n_cached_blocks == cached
    assert dc._allocator.n_free == free
    _engine_block_invariant(dc)


# ----------------------------------------------------------------------
# the transfer failure matrix
# ----------------------------------------------------------------------


def test_transfer_retry_with_jittered_backoff(metrics, engines, tier_pool):
    """One flaky transfer attempt → a recorded backoff sleep → success
    on the retry. The stream is byte-identical either way."""
    want = _reference(engines, max_new_tokens=8, temperature=0.0)
    ok0 = counter_total(metrics, "app_tpu_tier_transfers_total", result="ok")
    tier_pool._test_sleeps.clear()
    with faults.armed(
        "tier.transfer", raises=RuntimeError("flaky leg"), times=1
    ):
        req = tier_pool.submit_generate(
            PROMPT, max_new_tokens=8, temperature=0.0
        )
        toks = _drain_stream(req)
    assert toks == want.token_ids
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok"
    ) == ok0 + 1
    assert len(tier_pool._test_sleeps) == 1  # one backoff before the retry
    assert tier_pool._test_sleeps[0] > 0.0


def test_prefill_death_mid_transfer_fails_over_byte_identically(
    metrics, engines, tier_pool
):
    """THE acceptance path: the prefill replica dies mid-transfer
    (every ship attempt fails), so the request fails over WITHOUT its
    blocks to the decode replica, which re-prefills — the client
    stream is byte-identical to the fault-free run, zero 5xx, one
    trace id, and ``result="failed_over"`` counts exactly 1."""
    want = _reference(engines, max_new_tokens=12, temperature=0.0)
    fo0 = counter_total(
        metrics, "app_tpu_tier_transfers_total", result="failed_over"
    )
    with faults.armed(
        "tier.transfer", raises=RuntimeError("prefill replica lost")
    ):
        req = tier_pool.submit_generate(
            PROMPT, max_new_tokens=12, temperature=0.0
        )
        toks = _drain_stream(req)
    result = req.future.result(timeout=5)  # zero 5xx
    assert toks == result.token_ids == want.token_ids
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="failed_over"
    ) == fo0 + 1
    tl = req.timeline
    assert tl is not None
    # One trace: the failover annotation and the abandoned transfer ride
    # the same timeline (same trace id) the prefill phase recorded.
    assert [(s, r) for s, _, _, _, r, _ in tl.transfers] == [
        ("pf", "failed_over")
    ]
    assert any(name == "tpu.failover" for name, _, _ in tl.annotations)


def test_decode_import_rejection_falls_back_to_fused(
    metrics, engines, tier_pool
):
    """The decode replica rejecting every import (pool pressure /
    version mismatch modeled by the ``tier.import`` raise) degrades to
    the same fused fallback, byte-identically."""
    want = _reference(engines, max_new_tokens=8, temperature=0.0)
    fo0 = counter_total(
        metrics, "app_tpu_tier_transfers_total", result="failed_over"
    )
    with faults.armed(
        "tier.import", raises=RuntimeError("importer said no")
    ):
        req = tier_pool.submit_generate(
            PROMPT, max_new_tokens=8, temperature=0.0
        )
        toks = _drain_stream(req)
    assert toks == want.token_ids
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="failed_over"
    ) == fo0 + 1


def test_corrupt_and_stale_payloads_degrade_to_fused_import(engines):
    """A corrupt (checksum-broken) or geometry-stale payload is never
    aliased: ``handoff_prefilled`` downgrades to ``"fused"`` and the
    request re-prefills on the decode replica, byte-identically."""
    pf, dc, _ = engines
    want = _reference(engines, max_new_tokens=8, temperature=0.0)
    _wait_idle(dc)
    cached0 = dc._radix.n_cached_blocks
    payload = export_blocks(
        pf.cache, [1, 2, 3], PROMPT, src="unit"
    )
    corrupt = dataclasses.replace(payload, checksum=payload.checksum ^ 1)
    stale = dataclasses.replace(payload, geometry=("bogus",))
    for bad in (corrupt, stale):
        req = _GenRequest(
            prompt_ids=list(PROMPT), max_new_tokens=8, temperature=0.0,
            stop_on_eos=True,
        )
        assert dc.handoff_prefilled(req, bad) == "fused"
        toks = _drain_stream(req)
        assert toks == want.token_ids
    _wait_idle(dc)
    # Neither bad payload may have landed blocks under its content keys
    # beyond what the re-prefill retirement itself caches.
    _engine_block_invariant(dc)
    assert dc._radix.n_cached_blocks >= cached0


def test_deadline_expired_mid_transfer_reaps_without_leaks(
    metrics, engines, tier_pool
):
    """A request whose deadline expires DURING the transfer is not
    shipped: it is released to the scheduler's reap (one window), the
    caller gets the deadline error (504 — the caller's budget, not a
    replica 5xx), and zero pool blocks leak on either engine."""
    pf, dc, _ = engines
    clk = [0.0]
    deadline = Deadline(60.0, clock=lambda: clk[0])

    def expire(**ctx):
        clk[0] = 120.0

    exp0 = counter_total(
        metrics, "app_tpu_tier_transfers_total", result="expired"
    )
    with faults.armed("tier.transfer", action=expire):
        req = tier_pool.submit_generate(
            PROMPT, max_new_tokens=8, temperature=0.0, deadline=deadline
        )
        with pytest.raises(ErrorDeadlineExceeded):
            req.future.result(timeout=60)
    assert _drain_stream(req) == []
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="expired"
    ) == exp0 + 1
    _wait_idle(pf)
    _wait_idle(dc)
    _engine_block_invariant(pf)
    _engine_block_invariant(dc)


def test_cancel_mid_transfer_leaks_zero_blocks(metrics, engines, tier_pool):
    """Satellite regression: a caller cancelling mid-transfer is reaped
    on whichever side holds the request, and every pool block on both
    engines is freed or accounted for — zero leaks."""
    pf, dc, _ = engines

    def cancel(**ctx):
        ctx["request"].cancel.cancel()

    with faults.armed("tier.transfer", action=cancel):
        req = tier_pool.submit_generate(
            PROMPT, max_new_tokens=8, temperature=0.0
        )
        with pytest.raises(ErrorRequestCancelled):
            req.future.result(timeout=60)
    assert _drain_stream(req) == []
    _wait_idle(pf)
    _wait_idle(dc)
    _engine_block_invariant(pf)
    _engine_block_invariant(dc)


# ----------------------------------------------------------------------
# tier collapse → fused degradation
# ----------------------------------------------------------------------


def test_draining_last_prefill_replica_collapses_to_fused(
    metrics, engines, tier_pool
):
    """Draining the only prefill replica flips ``app_tpu_tier_mode`` to
    fused (0) with requests still served — on the surviving decode
    replica, byte-identically."""
    pf_replica = tier_pool.replicas[0]
    want = _reference(engines, max_new_tokens=8, temperature=0.0)
    assert tier_pool.tier_mode == "tiered"
    assert gauge_value(metrics, "app_tpu_tier_mode") == 1.0
    pf_replica.draining = True
    tier_pool._publish_tier_mode()
    try:
        assert tier_pool.tier_mode == "fused"
        assert gauge_value(metrics, "app_tpu_tier_mode") == 0.0
        req = tier_pool.submit_generate(
            PROMPT, max_new_tokens=8, temperature=0.0
        )
        toks = _drain_stream(req)
        assert toks == want.token_ids
        # Served fused on the decode replica — no transfer involved.
        assert req.timeline is None or req.timeline.transfers == []
    finally:
        pf_replica.draining = False
    assert tier_pool.tier_mode == "tiered"
    assert gauge_value(metrics, "app_tpu_tier_mode") == 1.0


def test_probe_requests_never_transfer(engines, tier_pool):
    """A synthetic probe pinned to the prefill replica must measure
    THAT replica end to end — prefill AND decode run locally."""
    pf, _, _ = engines
    before = [r for r in (pf._obs.recorder,)]  # recorder exists
    assert before
    result = pf.synthetic_probe(timeout_s=60.0)
    assert len(result.token_ids) == 1
    _wait_idle(pf)


def test_tier_routing_prefers_prefill_replicas(metrics, engines, tier_pool):
    """While tiered, fresh submits land on the prefill tier; pick()
    only falls through to other roles when the preferred tier has no
    routable replica."""
    assert tier_pool.pick(prefer_roles=("prefill",)).name == "pf"
    assert tier_pool.pick(prefer_roles=("decode",)).name == "dc"
    # Preference dissolves instead of 502ing when the tier is empty.
    pf_replica = tier_pool.replicas[0]
    pf_replica.draining = True
    try:
        assert tier_pool.pick(prefer_roles=("prefill",)).name == "dc"
    finally:
        pf_replica.draining = False
