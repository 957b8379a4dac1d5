"""Multi-replica chaos suite for the replica-tier failover router
(ISSUE 4 acceptance gate).

Everything is driven deterministically through ``gofr_tpu/faults`` —
no TPU, no sleeps-as-synchronization: faults target ONE replica via
the injection context's ``engine=`` argument, backoff waits go through
recording sleep hooks, the prober runs inline (``probe_once()``, no
thread), and budgets/deadlines ride injectable clocks.

Covered:

* routing policy: least-loaded among SERVING, spill to DEGRADED, never
  RESTARTING/DOWN or probe-demoted; no routable replica → 502;
* THE acceptance path: a replica forced DOWN mid-stream (crash loop
  exhausts ``TPU_RESTART_MAX``) hands its live request to a sibling —
  the client's NON-greedy token stream is byte-identical to a
  fault-free run, zero 5xx, the pool stays SERVING, and the dead
  replica is re-admitted only after a passing synthetic probe;
* probe-driven recovery: a failed synthetic generation demotes a
  replica that still claims SERVING and asks its supervisor to
  restart; a passing probe re-admits it and resets the crash-loop
  counter;
* hedged unary retries: a slow primary is raced by a budgeted hedge on
  a second replica (first success wins, loser cancelled); the hedge
  budget is a deterministic token bucket and hedging is deadline-aware;
* submit-time rerouting: a draining replica's 503 reroutes to a
  sibling instead of failing the caller;
* seeded-sampling replay continuity (single engine): a non-greedy
  stream crosses a mid-generation restart byte-identically because the
  sampling counter is restored, not restarted at 0;
* remote replicas: HTTPReplica serves unary generations and its health
  probe demotes an unreachable upstream.
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.errors import ErrorNoHealthyReplica, ErrorServiceUnavailable
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.lifecycle import Deadline, HedgeBudget
from gofr_tpu.serving.supervisor import EngineSupervisor
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.serving.types import _GenRequest
from gofr_tpu.service.replica_pool import (
    EngineReplica,
    Replica,
    ReplicaPool,
)

POOL_INSTRUMENTS_COUNTERS = (
    "app_tpu_engine_restarts_total",
    "app_tpu_requests_replayed_total",
    "app_tpu_watchdog_trips_total",
    "app_tpu_requests_shed_total",
    "app_tpu_requests_cancelled_total",
    "app_tpu_deadline_exceeded_total",
    "app_tpu_tokens_generated",
    "app_tpu_prefix_hits",
    "app_tpu_failovers_total",
    "app_tpu_probe_failures_total",
    "app_tpu_hedged_requests_total",
)
POOL_INSTRUMENTS_GAUGES = (
    "app_tpu_engine_state",
    "app_tpu_replica_state",
    "app_tpu_queue_depth",
    "app_tpu_kv_slots_in_use",
    "app_tpu_hbm_used_bytes",
    "app_tpu_kv_blocks_free",
)


def _metrics_manager():
    m = new_metrics_manager()
    for name in POOL_INSTRUMENTS_COUNTERS:
        m.new_counter(name)
    for name in POOL_INSTRUMENTS_GAUGES:
        m.new_gauge(name)
    for name in ("app_tpu_infer_latency", "app_tpu_batch_size"):
        m.new_histogram(name)
    return m


def counter_total(metrics, name: str) -> float:
    inst = {i.name: i for i in metrics.instruments()}[name]
    return sum(inst.collect().values())


@pytest.fixture(scope="module")
def metrics():
    return _metrics_manager()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def _drain_stream(req, timeout=120.0) -> list[int]:
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


def _wait_until(cond, timeout=30.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _make_supervised(metrics, *, max_restarts=3, **eng_kw):
    """One engine + supervisor, every timing seam injected (recording
    sleep — backoff adds no wall clock). Replicas built this way share
    the default engine seed, so params AND the counter-based sampling
    base key are identical across the pool — the precondition for
    byte-identical cross-replica replay."""
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
        metrics=metrics, **eng_kw,
    )
    sleeps: list[tuple[str, float]] = []
    sup = EngineSupervisor(
        eng,
        max_restarts=max_restarts,
        backoff_s=0.25,
        backoff_reset_s=60.0,
        rng=random.Random(1234),
        sleep=lambda s: sleeps.append((eng.state, s)),
        metrics=metrics,
    ).start()
    eng.start_sync()
    return eng, sup, sleeps


def _make_pool(metrics, replicas, **kw):
    kw.setdefault("probe_interval_s", 0)  # no thread: tests drive probes
    kw.setdefault("probe_timeout_s", 60.0)
    kw.setdefault("rng", random.Random(7))
    return ReplicaPool(replicas, metrics=metrics, **kw)


@pytest.fixture(scope="module")
def engines(metrics):
    """ONE supervised engine pair shared by the chaos tests below:
    engine construction + first-dispatch compiles dominate this suite's
    wall clock, and every test that wounds an engine restores it to
    SERVING before finishing. max_restarts=1 so a targeted persistent
    fault exhausts the crash-loop budget with exactly two crashes."""
    eng_a, sup_a, _ = _make_supervised(metrics, max_restarts=1)
    eng_b, sup_b, _ = _make_supervised(metrics, max_restarts=1)
    yield (eng_a, sup_a), (eng_b, sup_b)
    faults.reset()
    sup_a.stop()
    sup_b.stop()
    eng_a.stop_sync()
    eng_b.stop_sync()


def _pool_of(metrics, eng_a, eng_b, **kw):
    return _make_pool(
        metrics,
        [EngineReplica("a", eng_a), EngineReplica("b", eng_b)],
        **kw,
    )


def _release_pool(pool):
    """Detach a per-test pool WITHOUT closing the shared engines (which
    ``pool.close()`` would)."""
    pool.stop_prober()
    for replica in pool.replicas:
        if isinstance(replica, EngineReplica):
            replica.engine.set_replica_handoff(None)


# ----------------------------------------------------------------------
# routing policy (stub replicas — pure policy, no jax)
# ----------------------------------------------------------------------


class _StubReplica(Replica):
    supports_stream = True

    def __init__(self, name, state="SERVING", load=0, tput=0.0):
        super().__init__(name)
        self.state_value = state
        self.load_value = load
        self.tput = tput
        self.submits = 0

    def state(self):
        return self.state_value

    def load(self):
        return self.load_value

    def throughput(self):
        return self.tput

    def submit(self, prompt, **kw):
        self.submits += 1
        req = _GenRequest(
            prompt_ids=[1], max_new_tokens=1, temperature=0.0,
            stop_on_eos=False,
        )
        req.future.set_result(f"ok-{self.name}")
        req.stream.put(None)
        return req

    def probe(self, timeout_s):
        return "pass", ""


def test_pick_least_loaded_serving_spills_to_degraded():
    a = _StubReplica("a", load=5)
    b = _StubReplica("b", load=1)
    c = _StubReplica("c", state="DEGRADED", load=0)
    pool = _make_pool(None, [a, b, c])
    # Least-loaded among SERVING wins — DEGRADED never preferred while
    # any SERVING replica exists, even at load 0.
    assert pool.pick().name == "b"
    # SERVING gone → spill to DEGRADED.
    a.state_value = "DOWN"
    b.state_value = "RESTARTING"
    assert pool.pick().name == "c"
    # Nothing routable → 502, fast.
    c.state_value = "DOWN"
    with pytest.raises(ErrorNoHealthyReplica):
        pool.pick()


def test_pick_round_robin_tie_break_and_exclude():
    a, b = _StubReplica("a"), _StubReplica("b")
    pool = _make_pool(None, [a, b])
    first = pool.pick()
    second = pool.pick()
    # Equal load: consecutive picks rotate instead of pinning one
    # replica.
    assert {first.name, second.name} == {"a", "b"}
    assert pool.pick(exclude=[a]).name == "b"
    with pytest.raises(ErrorNoHealthyReplica):
        pool.pick(exclude=[a, b])


def test_weighted_pick_routes_by_estimated_completion_time():
    # Equal queues, 4× throughput difference: the faster replica has
    # the lower estimated completion time.
    a = _StubReplica("a", load=4, tput=100.0)
    b = _StubReplica("b", load=4, tput=400.0)
    pool = _make_pool(None, [a, b])
    assert pool.pick().name == "b"
    # A deeper queue on the fast replica still wins while its ECT is
    # lower: (7+1)/400 = 0.02s < (1+1)/50 = 0.04s.
    a.load_value, a.tput = 1, 50.0
    b.load_value, b.tput = 7, 400.0
    assert pool.pick().name == "b"
    # ...until the queue outweighs the speed: (39+1)/400 > (1+1)/50.
    b.load_value = 39
    assert pool.pick().name == "a"


def test_weighted_pick_degrades_to_least_loaded_without_signal():
    # No replica reports throughput (cold pool, HTTP-only) → the scores
    # collapse to load ordering, and equal loads still round-robin.
    a = _StubReplica("a", load=3)
    b = _StubReplica("b", load=1)
    pool = _make_pool(None, [a, b])
    assert pool.pick().name == "b"
    # A replica WITHOUT a measurement is assumed as fast as the fastest
    # measured sibling (cold ≈ idle), so its shorter queue wins.
    a.load_value, a.tput = 2, 100.0
    b.load_value, b.tput = 1, 0.0
    assert pool.pick().name == "b"


def test_unweighted_pick_restores_raw_queue_length_routing():
    a = _StubReplica("a", load=1, tput=10.0)
    b = _StubReplica("b", load=5, tput=1000.0)
    pool = _make_pool(None, [a, b], weighted=False)
    assert pool.pick().name == "a"  # raw least-loaded ignores speed
    pool_w = _make_pool(None, [a, b])
    assert pool_w.pick().name == "b"  # default weighted pick uses it


def test_probe_demotion_blocks_routing_even_while_serving():
    a, b = _StubReplica("a"), _StubReplica("b")
    pool = _make_pool(None, [a, b])
    a.probe_failed = True  # demoted: state() still says SERVING
    assert pool.pick().name == "b"
    assert pool.pick().name == "b"
    b.probe_failed = True
    with pytest.raises(ErrorNoHealthyReplica):
        pool.pick()


def test_pool_health_aggregation_and_state_gauge(metrics):
    a = _StubReplica("a")
    down = _StubReplica("d", state="DOWN")
    pool = _make_pool(metrics, [a, down])
    health = pool.health_check()
    assert health["status"] == "UP"  # one replica down ≠ pool down
    assert health["state"] == "SERVING"
    assert health["details"]["serving"] == 1
    assert health["details"]["total"] == 2
    assert health["details"]["replicas"]["d"]["state"] == "DOWN"
    gauge = {
        i.name: i for i in metrics.instruments()
    }["app_tpu_replica_state"].collect()
    assert sorted(gauge.values()) == [0.0, 3.0]
    # Every replica unroutable → pool DOWN on the health surface too.
    a.state_value = "DEGRADED"
    assert pool.health_check()["state"] == "DEGRADED"
    a.state_value = "DOWN"
    health = pool.health_check()
    assert health["status"] == "DOWN"
    assert health["state"] == "DOWN"


def test_hedge_budget_token_bucket_deterministic():
    now = [0.0]
    budget = HedgeBudget(burst=2.0, rate_per_s=1.0, clock=lambda: now[0])
    assert budget.try_acquire()
    assert budget.try_acquire()
    assert not budget.try_acquire()  # drained — no partial takes
    now[0] = 0.5
    assert not budget.try_acquire()  # half a token refilled: not enough
    now[0] = 1.5
    assert budget.try_acquire()
    # Refill caps at burst, never beyond.
    now[0] = 1000.0
    assert budget.available() == pytest.approx(2.0)


def test_probe_busy_verdict_never_demotes_or_restarts():
    """Overload is NOT failure: a probe the replica SHEDS (429) or that
    times out behind real queued work must leave routing state and the
    supervisor untouched — demoting a merely-busy replica would cascade
    its load onto the siblings until the whole pool restarts."""
    import concurrent.futures as cf

    from gofr_tpu.errors import ErrorTooManyRequests

    class _BusyEngine:
        state = "SERVING"
        family = "stub"  # EngineReplica.load() reads queues on llm only

        def __init__(self, exc):
            self._exc = exc
            self._supervisor = None
            self._handoff = None

        def set_replica_handoff(self, handoff):
            self._handoff = handoff

        def synthetic_probe(self, timeout_s):
            raise self._exc

    shed = EngineReplica("shed", _BusyEngine(ErrorTooManyRequests("full")))
    verdict, reason = shed.probe(timeout_s=1.0)
    assert verdict == "busy"

    class _CongestedReplica(EngineReplica):
        def load(self):
            return 5  # probe queued behind real work

    congested = _CongestedReplica(
        "congested", _BusyEngine(cf.TimeoutError())
    )
    verdict, _ = congested.probe(timeout_s=0.0)
    assert verdict == "busy"

    class _WedgedIdleReplica(EngineReplica):
        def load(self):
            return 1  # nothing queued but the probe: truly broken

    wedged = _WedgedIdleReplica("wedged", _BusyEngine(cf.TimeoutError()))
    verdict, _ = wedged.probe(timeout_s=0.0)
    assert verdict == "fail"

    # Pool-level: a busy sweep changes nothing — still routable, no
    # probe-failure metric, no supervisor notification.
    pool = _make_pool(None, [shed])
    sweep = pool.probe_once()
    assert sweep["shed"].startswith("busy")
    assert not shed.probe_failed
    assert pool.pick().name == "shed"


def test_fast_fail_retry_spends_the_hedge_budget():
    """A fast-failing primary is retried on a sibling ONLY while the
    token bucket has budget; drained, the caller gets the primary's
    error instead of an unbudgeted retry storm."""

    class _FailingResultReplica(_StubReplica):
        def submit(self, prompt, **kw):
            self.submits += 1
            req = _GenRequest(
                prompt_ids=[1], max_new_tokens=1, temperature=0.0,
                stop_on_eos=False,
            )
            req.future.set_exception(ErrorServiceUnavailable("mid-flight"))
            req.stream.put(None)
            return req

    bad, good = _FailingResultReplica("bad"), _StubReplica("good")
    pool = _make_pool(
        None, [bad, good],
        hedge_delay_s=0.0,
        hedge_budget=HedgeBudget(burst=1.0, rate_per_s=0.0),
    )
    # Budget has one token: the first request's failed primary (bad,
    # picked by rotation) retries on good and succeeds.
    assert pool.generate_sync("x", timeout=10) == "ok-good"
    assert bad.submits == 1 and good.submits == 1
    # Bucket drained: the next failed primary may NOT retry even though
    # a healthy sibling is right there.
    bad.submits = good.submits = 0
    with pytest.raises(ErrorServiceUnavailable):
        pool.generate_sync("x", timeout=10)
    assert bad.submits == 1 and good.submits == 0

    # And with NO routable sibling at all, the budget is never consumed
    # for a hedge that cannot launch — tokens wait for a sibling to
    # recover instead of draining on impossible attempts.
    solo_budget = HedgeBudget(burst=1.0, rate_per_s=0.0)
    solo = _make_pool(
        None, [_FailingResultReplica("solo")],
        hedge_delay_s=0.0, hedge_budget=solo_budget,
    )
    with pytest.raises(ErrorServiceUnavailable):
        solo.generate_sync("x", timeout=10)
    assert solo_budget.available() == pytest.approx(1.0)


def test_should_hedge_is_budgeted_and_deadline_aware():
    clock = [0.0]
    pool = _make_pool(
        None, [_StubReplica("a"), _StubReplica("b")],
        hedge_budget=HedgeBudget(burst=1.0, rate_per_s=0.0,
                                 clock=lambda: clock[0]),
    )
    expired = Deadline(10.0, clock=lambda: 20.0)
    assert not pool.should_hedge(expired)  # never hedge doomed work
    live = Deadline(10.0, clock=lambda: 0.0)
    assert pool.should_hedge(live)  # spends the single token
    assert not pool.should_hedge(live)  # budget drained → ride primary
    assert not pool.should_hedge(None)


# ----------------------------------------------------------------------
# THE acceptance path: replica DOWN mid-stream → sibling completes it
# ----------------------------------------------------------------------


def test_replica_down_mid_stream_fails_over_byte_identical(metrics, engines):
    """Force replica A into a crash loop that exhausts its restart
    budget MID-STREAM: the pool hands the live request to replica B,
    the client's non-greedy SSE stream is byte-identical to a
    fault-free run (counter-restored sampling), there are zero 5xx,
    the pool stays SERVING around the DOWN replica, and A is
    re-admitted only after a passing synthetic probe."""
    (eng_a, sup_a), (eng_b, sup_b) = engines
    pool = _pool_of(metrics, eng_a, eng_b)
    params = dict(
        max_new_tokens=32, temperature=0.9, seed=4242, stop_on_eos=False,
    )
    try:
        failovers0 = counter_total(metrics, "app_tpu_failovers_total")
        # Fault-free reference — and the cross-replica determinism
        # precondition: both replicas (same params, same engine seed)
        # produce the identical sampled stream.
        ref = eng_b.generate_sync("failover mid-stream", **params)
        ref_a = eng_a.generate_sync("failover mid-stream", **params)
        assert ref_a.token_ids == ref.token_ids
        assert len(ref.token_ids) == 32

        # Replica A's device dies from its 5th dispatch ON — persistent,
        # targeted: B never sees the fault. Crash 1 lands mid-stream;
        # the recovery replay's prefill is crash 2, which exhausts
        # max_restarts=1 and lands A in DOWN.
        a_hits = {"n": 0}

        def crash_a(engine=None, **kw):
            if engine is eng_a:
                a_hits["n"] += 1
                if a_hits["n"] >= 5:
                    raise RuntimeError("injected: replica A device loss")

        faults.arm("scheduler.device_step", action=crash_a)
        req = pool.submit_generate("failover mid-stream", **params)
        # Tokens consumed BEFORE the crash prove this is a continuation,
        # not a fresh retry.
        pre = [req.stream.get(timeout=120) for _ in range(3)]
        assert all(t is not None for t in pre)
        rest = _drain_stream(req)
        result = req.future.result(timeout=120)

        # Byte-identical NON-GREEDY stream across the replica loss: the
        # sampling counter resumed at the delivered-token count on B.
        assert pre + rest == ref.token_ids
        assert result.token_ids == ref.token_ids
        assert result.finish_reason == ref.finish_reason
        # Zero 5xx: the future resolved with a result, never an error.
        # Carried twice: A's own replay attempt, then the adoption by B.
        assert req.replays == 2
        assert counter_total(
            metrics, "app_tpu_failovers_total"
        ) == failovers0 + 1

        # A is DOWN and routed AROUND: the pool stays SERVING and new
        # work lands on B.
        assert _wait_until(lambda: eng_a.state == "DOWN")
        assert pool.state == "SERVING"
        assert pool.health_check()["status"] == "UP"
        assert pool.pick() .name == "b"
        after = pool.generate_sync(
            "failover mid-stream", timeout=120, **params
        )
        assert after.token_ids == ref.token_ids

        # Re-admission ONLY after a passing synthetic probe: with the
        # fault still armed, the revive's probe fails and A stays out of
        # rotation; once disarmed, one probe sweep re-admits it.
        sweep = pool.probe_once()
        assert sweep["a"].startswith("fail") or sweep["a"] == "down"
        assert pool.replicas[0].probe_failed
        assert pool.pick().name == "b"

        faults.reset()
        assert _wait_until(lambda: eng_a.state in ("SERVING", "DOWN"))
        sweep = pool.probe_once()
        assert _wait_until(
            lambda: pool.probe_once().get("a") == "pass", timeout=60
        )
        assert not pool.replicas[0].probe_failed
        assert eng_a.state == "SERVING"
        assert sup_a.consecutive_failures == 0
        # And A serves identical streams again (params were reused).
        again = eng_a.generate_sync("failover mid-stream", **params)
        assert again.token_ids == ref.token_ids
    finally:
        faults.reset()
        _release_pool(pool)


# ----------------------------------------------------------------------
# probe-driven demotion + supervisor restart
# ----------------------------------------------------------------------


def test_probe_failure_demotes_and_restarts_supervised_replica(
    metrics, engines
):
    """A replica that still CLAIMS SERVING but fails its synthetic
    generation is demoted from routing AND its supervisor restarts it —
    recovery on probe evidence, not just on crash/trip."""
    (eng_a, sup_a), (eng_b, sup_b) = engines
    pool = _pool_of(metrics, eng_a, eng_b)
    try:
        probe_fail0 = counter_total(metrics, "app_tpu_probe_failures_total")
        ref = eng_b.generate_sync(
            "probe demotion", max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )

        def fail_submit_a(engine=None, **kw):
            if engine is eng_a:
                raise RuntimeError("injected: submit path broken on A")

        faults.arm("engine.submit", action=fail_submit_a)
        restarts_before = sup_a.restarts
        sweep = pool.probe_once()
        assert sweep["a"].startswith("fail")
        assert sweep["b"] == "pass"
        assert pool.replicas[0].probe_failed
        assert counter_total(
            metrics, "app_tpu_probe_failures_total"
        ) == probe_fail0 + 1
        # Routed around while demoted — even though eng_a's own state
        # machine may still say SERVING.
        assert pool.pick().name == "b"
        via_pool = pool.generate_sync(
            "probe demotion", timeout=120, max_new_tokens=8,
            temperature=0.0, stop_on_eos=False,
        )
        assert via_pool.token_ids == ref.token_ids

        # The supervisor treated the failed probe as a detected failure
        # and warm-restarted the engine.
        assert _wait_until(lambda: sup_a.restarts == restarts_before + 1)
        faults.reset()
        assert _wait_until(lambda: eng_a.state == "SERVING")
        # Passing probe → re-admitted, crash-loop counter reset.
        assert _wait_until(
            lambda: pool.probe_once().get("a") == "pass", timeout=60
        )
        assert not pool.replicas[0].probe_failed
        assert sup_a.consecutive_failures == 0
    finally:
        faults.reset()
        _release_pool(pool)


# ----------------------------------------------------------------------
# hedged unary retries
# ----------------------------------------------------------------------


def test_hedged_unary_request_wins_on_second_replica(metrics, engines):
    """A stalled primary triggers one budgeted hedge on a sibling; the
    first success answers the caller and the loser is cancelled so no
    replica decodes for a caller that already has its result."""
    (eng_a, sup_a), (eng_b, sup_b) = engines
    pool = _pool_of(
        metrics, eng_a, eng_b,
        hedge_delay_s=0.0,  # hedge immediately: deterministic, no sleeps
        hedge_budget=HedgeBudget(burst=4.0, rate_per_s=0.0),
    )
    try:
        hedged0 = counter_total(metrics, "app_tpu_hedged_requests_total")
        ref = eng_b.generate_sync(
            "hedge me", max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall_a(engine=None, **kw):
            # The fault point is process-global and B's loop passes it
            # too: a ``times=1`` budget is B's to spend first as often
            # as not. The closure parks A once; only A's thread gets here.
            if engine is eng_a and not gate_in.is_set():
                gate_in.set()
                gate_out.wait(timeout=120)

        faults.arm("scheduler.window", action=stall_a)
        assert gate_in.wait(30)  # A's scheduler is parked: requests hang
        result = pool.generate_sync(
            "hedge me", timeout=120, max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        assert result.token_ids == ref.token_ids
        assert counter_total(
            metrics, "app_tpu_hedged_requests_total"
        ) == hedged0 + 1
        # The loser (parked on A) was cancelled, not left to decode.
        gate_out.set()
        assert _wait_until(
            lambda: all(s is None for s in eng_a._slots)
            and eng_a._pending.empty()
        )
    finally:
        faults.reset()
        _release_pool(pool)


def test_submit_reroutes_around_draining_replica(metrics, engines):
    """A graceful-draining replica 503s its submits; the router treats
    that as a reroute signal and places the request on a sibling —
    the caller never sees the 503."""
    (eng_a, sup_a), (eng_b, sup_b) = engines
    pool = _pool_of(metrics, eng_a, eng_b)
    try:
        ref = eng_b.generate_sync(
            "reroute", max_new_tokens=6, temperature=0.0, stop_on_eos=False
        )
        with eng_a._submit_lock:
            eng_a._draining = True  # graceful drain: submits 503
        try:
            req = pool.submit_generate(
                "reroute", max_new_tokens=6, temperature=0.0,
                stop_on_eos=False,
            )
            result = req.future.result(timeout=120)
            assert result.token_ids == ref.token_ids
        finally:
            with eng_a._submit_lock:
                eng_a._draining = False
        # With EVERY replica draining, the pool answers 503/502 fast
        # (the last shed error wins so Retry-After semantics survive).
        with eng_a._submit_lock:
            eng_a._draining = True
        with eng_b._submit_lock:
            eng_b._draining = True
        try:
            with pytest.raises(
                (ErrorNoHealthyReplica, ErrorServiceUnavailable)
            ):
                pool.submit_generate(
                    "reroute", max_new_tokens=6, temperature=0.0,
                    stop_on_eos=False,
                )
        finally:
            with eng_a._submit_lock:
                eng_a._draining = False
            with eng_b._submit_lock:
                eng_b._draining = False
    finally:
        faults.reset()
        _release_pool(pool)


# ----------------------------------------------------------------------
# container seam: TPU_REPLICAS builds the pool
# ----------------------------------------------------------------------


def test_pool_from_config_builds_supervised_engine_replicas():
    """`TPU_REPLICAS > 1` makes container.tpu a ReplicaPool: N
    supervised engines with pool handoffs installed, serving through
    the same engine-shaped surface."""
    from gofr_tpu.config import MockConfig
    from gofr_tpu.serving.backend import new_tpu_from_config

    pool = new_tpu_from_config(MockConfig({
        "TPU_MODEL": "llama-tiny",
        "TPU_REPLICAS": "2",
        "TPU_KV_SLOTS": "2",
        "TPU_MAX_LEN": "128",
        "TPU_DECODE_WINDOW": "4",
        "TPU_RESTART_MAX": "2",
        "TPU_PROBE_INTERVAL_S": "0",
        "TPU_POOL_MAX_REPLICAS": "3",
        "TPU_SCALE_UP_WAIT_S": "7",
        "TPU_SCALE_INTERVAL_S": "0",
    }))
    try:
        assert isinstance(pool, ReplicaPool)
        assert pool.model_name == "llama-tiny"
        assert pool.family == "llm"
        assert len(pool.replicas) == 2
        for replica in pool.replicas:
            assert replica.engine._supervisor is not None
            assert replica.engine._handoff is not None
        pool.start_sync()
        assert pool.state == "SERVING"
        # Wiring only — no generate here: routing/serving through a pool
        # is covered above, and a from_config generate would pay two
        # more engine compiles for no new coverage.
        health = pool.health_check()
        assert health["status"] == "UP"
        assert health["details"]["total"] == 2
        assert pool.pick().name in ("engine-0", "engine-1")
        # TPU_POOL_MAX_REPLICAS above the configured fleet arms a
        # PoolScaler with an in-proc engine spawn factory (decision
        # logic is covered in tests/test_remote_failover.py).
        assert pool.scaler is not None
        assert pool.scaler.min_replicas == 2
        assert pool.scaler.max_replicas == 3
        assert pool.scaler.scale_up_wait_s == 7.0
    finally:
        pool.close()


# ----------------------------------------------------------------------
# remote replicas (HTTPService-backed)
# ----------------------------------------------------------------------


class _Harness:
    """Boot a gofr_tpu App on an ephemeral port (httptest.Server role)."""

    def __init__(self, app):
        import asyncio

        self.app = app
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, daemon=True
        )

    def __enter__(self):
        import asyncio

        self._thread.start()
        asyncio.run_coroutine_threadsafe(
            self.app.start(), self._loop
        ).result(10)
        return self

    def __exit__(self, *exc):
        import asyncio

        asyncio.run_coroutine_threadsafe(
            self.app.stop(), self._loop
        ).result(10)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        self._loop.close()

    @property
    def address(self):
        return f"http://127.0.0.1:{self.app.http_port}"


def test_http_replica_serves_unary_and_probe_demotes_dead_upstream():
    """A UNARY-ONLY remote replica (``stream=False`` — any plain
    OpenAI-compatible upstream) answers unary generations through its
    endpoint; once the upstream dies, the next probe demotes it and the
    pool fails fast with 502. Streaming remotes are covered by
    tests/test_remote_failover.py."""
    from gofr_tpu import App
    from gofr_tpu.config import MockConfig
    from gofr_tpu.http.response import Raw
    from gofr_tpu.service import new_http_service
    from gofr_tpu.service.replica_pool import HTTPReplica

    app = App(config=MockConfig({"HTTP_PORT": "0", "METRICS_PORT": "0"}))

    @app.post("/v1/completions")
    def completions(ctx):  # noqa: ARG001
        return Raw({
            "choices": [
                {"text": "remote completion", "finish_reason": "stop"}
            ],
            "usage": {"prompt_tokens": 2},
        })

    with _Harness(app) as harness:
        svc = new_http_service(harness.address)
        replica = HTTPReplica("remote-0", svc, stream=False)
        pool = _make_pool(None, [replica])
        try:
            result = pool.generate_sync(
                "hello remote", timeout=30, max_new_tokens=4,
                temperature=0.0,
            )
            assert result.text == "remote completion"
            assert result.finish_reason == "stop"
            assert pool.probe_once() == {"remote-0": "pass"}
            assert pool.state == "SERVING"
            # STREAM handles never route to a unary-only remote replica
            # — a 200 SSE with zero tokens would be worse than an
            # honest 502.
            with pytest.raises(ErrorNoHealthyReplica):
                pool.submit_generate("hello remote", max_new_tokens=4)
        finally:
            pool_alive = pool
    # The upstream is gone: the probe demotes the replica and routing
    # fails fast instead of hanging on a dead address.
    sweep = pool_alive.probe_once()
    assert sweep["remote-0"] != "pass"
    assert pool_alive.replicas[0].probe_failed
    assert pool_alive.state == "DOWN"
    with pytest.raises(ErrorNoHealthyReplica):
        pool_alive.generate_sync("hello remote", timeout=10, max_new_tokens=4)
    pool_alive.close()


# ----------------------------------------------------------------------
# seeded-sampling replay continuity (single engine)
# ----------------------------------------------------------------------


def test_replay_state_snapshots_sampling_counter():
    req = _GenRequest(
        prompt_ids=[1, 2], max_new_tokens=10, temperature=0.9,
        stop_on_eos=False, seed=7,
    )
    req.token_ids.extend([5, 6, 7])
    snap = req.replay_state()
    assert snap is not None
    assert snap.n_sampled == 3  # one counter step per delivered token
    assert snap.emitted_ids == [5, 6, 7]


def test_non_greedy_stream_byte_identical_across_restart(metrics, engines):
    """Satellite acceptance: a SAMPLED (non-greedy) stream crosses a
    mid-generation engine restart byte-identically. Before the exact
    (regeneration) replay, the continuation's re-prefilled K/V differed
    from the decode-written original by bf16 rounding and sampled a
    different — still valid, but different — path."""
    (eng, sup), _unused = engines
    eng.set_replica_handoff(None)  # single-engine scenario: no pool
    sup.note_probe_success()  # fresh crash-loop window for this test
    # 40 tokens = 5 decode windows: the 5th dispatch (after=4) lands
    # deterministically MID-generation, with window 1 already streamed.
    params = dict(
        max_new_tokens=40, temperature=0.9, seed=777, stop_on_eos=False,
    )
    try:
        ref = eng.generate_sync("sampled continuity", **params)
        greedy = eng.generate_sync(
            "sampled continuity", max_new_tokens=40, temperature=0.0,
            stop_on_eos=False,
        )
        # Sanity: the reference really is a sampled path, not greedy.
        assert ref.token_ids != greedy.token_ids
        faults.arm(
            "scheduler.device_step",
            raises=RuntimeError("injected mid-sample device loss"),
            after=4, times=1,
        )
        req = eng.submit_generate("sampled continuity", **params)
        toks = _drain_stream(req)
        result = req.future.result(timeout=120)
        assert req.replays == 1
        assert toks == ref.token_ids
        assert result.token_ids == ref.token_ids
    finally:
        faults.reset()


def test_fast_replay_mode_restores_counter_without_regeneration(
    metrics, engines
):
    """TPU_REPLAY_EXACT=false: sampled replays take the FAST re-prefill
    path — one prefill pass covering the delivered prefix, sampling
    counter restored (ReplayState.n_sampled → the noff plane) so the
    continuation stays on the same counter path. Byte-exactness is the
    regeneration mode's contract, not this one's (prefill-kernel bf16
    rounding may flip a token); what must hold: no duplicates, no gaps,
    exact budget."""
    (eng, sup), _unused = engines
    eng.set_replica_handoff(None)  # single-engine scenario: no pool
    sup.note_probe_success()  # fresh crash-loop window for this test
    eng.replay_exact = False
    params = dict(
        max_new_tokens=40, temperature=0.9, seed=31337, stop_on_eos=False,
    )
    try:
        ref = eng.generate_sync("fast replay path", **params)
        faults.arm(
            "scheduler.device_step",
            raises=RuntimeError("injected fast-replay device loss"),
            after=4, times=1,
        )
        req = eng.submit_generate("fast replay path", **params)
        toks = _drain_stream(req)
        result = req.future.result(timeout=120)
        assert req.replays == 1
        assert req.replay_skip == 0  # fast path: nothing re-generated
        assert req.replayed_tokens > 0  # the prefix was RE-PREFILLED
        # Exact budget, the pre-crash prefix intact on the stream, and
        # the result mirrors exactly what the client streamed.
        assert len(toks) == 40
        prefix = req.replayed_tokens
        assert toks[:prefix] == ref.token_ids[:prefix]
        assert result.token_ids == toks
    finally:
        eng.replay_exact = True
        faults.reset()
