"""Chaos suite for the engine supervisor (ISSUE 3 acceptance gate).

Self-healing serving: every recovery path is driven deterministically
through the existing fault-injection points (``gofr_tpu/faults``) — no
TPU, no sleeps-as-synchronization. Stalls are test-controlled
``threading.Event``s, the watchdog trips by *stating* a time
(``check(now=)``), backoff waits go through an injectable sleep that
records instead of sleeping, and the crash-loop clock is a fake.

Covered:

* a device crash mid-generation → supervisor warm-restarts within the
  backoff policy → the still-streaming request REPLAYS and completes
  with the full, correct token sequence (no duplicates, no gaps),
  while ``app_tpu_engine_restarts_total`` /
  ``app_tpu_requests_replayed_total`` and the
  SERVING→RESTARTING→SERVING transitions are asserted;
* a WEDGED scheduler (hung device step) → watchdog trip → the thread
  is abandoned behind the epoch fence and the engine restarts around
  it — including the zombie's eventual wake-up being inert;
* a crash-looping engine (fault armed forever) lands in DOWN after
  ``TPU_RESTART_MAX`` attempts instead of restarting forever;
* non-retryable requests (expired deadline) get the existing terminal
  error while retryable neighbors are carried across the restart;
* SSE streams resume from the last emitted token across a restart —
  same bytes as a fault-free run, no error event;
* the reused Watchdog instance re-arms cleanly after trip + restart.
"""

from __future__ import annotations

import asyncio
import json
import random
import threading
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.errors import ErrorServiceUnavailable
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.lifecycle import Deadline
from gofr_tpu.serving.supervisor import EngineSupervisor
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.serving.types import _GenRequest
from gofr_tpu.serving.watchdog import Watchdog

SUPERVISOR_INSTRUMENTS = (
    "app_tpu_engine_restarts_total",
    "app_tpu_requests_replayed_total",
    "app_tpu_watchdog_trips_total",
    "app_tpu_requests_shed_total",
    "app_tpu_requests_cancelled_total",
    "app_tpu_deadline_exceeded_total",
    "app_tpu_tokens_generated",
    "app_tpu_prefix_hits",
)


def _metrics_manager():
    m = new_metrics_manager()
    for name in SUPERVISOR_INSTRUMENTS:
        m.new_counter(name)
    for name in ("app_tpu_engine_state", "app_tpu_queue_depth",
                 "app_tpu_kv_slots_in_use", "app_tpu_hbm_used_bytes",
                 "app_tpu_kv_blocks_free"):
        m.new_gauge(name)
    for name in ("app_tpu_infer_latency", "app_tpu_batch_size"):
        m.new_histogram(name)
    return m


def counter_total(metrics, name: str) -> float:
    inst = {i.name: i for i in metrics.instruments()}[name]
    return sum(inst.collect().values())


def gauge_value(metrics, name: str) -> float:
    inst = {i.name: i for i in metrics.instruments()}[name]
    values = list(inst.collect().values())
    return values[-1] if values else -1.0


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
    """Poll a host-side condition a background thread publishes. The
    ordering edges in these tests are stream sentinels and futures; this
    only absorbs the supervisor's final bookkeeping writes."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _make_supervised(metrics, *, max_restarts=3, watchdog_s=0.0,
                     join_timeout_s=5.0, clock=time.monotonic, **eng_kw):
    """One engine + supervisor with every timing seam injected: the
    sleep hook records (engine state, delay) instead of sleeping, so
    backoff never adds wall clock and RESTARTING is observable."""
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
        watchdog_s=watchdog_s, metrics=metrics, **eng_kw,
    )
    sleeps: list[tuple[str, float]] = []
    sup = EngineSupervisor(
        eng,
        max_restarts=max_restarts,
        backoff_s=0.25,
        backoff_reset_s=60.0,
        join_timeout_s=join_timeout_s,
        clock=clock,
        rng=random.Random(1234),
        sleep=lambda s: sleeps.append((eng.state, s)),
        metrics=metrics,
    ).start()
    eng.start_sync()
    return eng, sup, sleeps


# ----------------------------------------------------------------------
# policy units: backoff + retryability
# ----------------------------------------------------------------------


def test_backoff_policy_exponential_jittered_capped():
    class _Eng:  # policy math needs no real engine
        def attach_supervisor(self, sup):
            pass

    sup = EngineSupervisor(
        _Eng(), max_restarts=5, backoff_s=1.0, backoff_cap_s=8.0,
        rng=random.Random(7),
    )
    delays = [sup.backoff_delay(a) for a in range(6)]
    for attempt, d in enumerate(delays):
        base = min(8.0, 1.0 * 2 ** attempt)
        # Jitter scales into [50%, 100%] of the exponential base.
        assert base * 0.5 <= d <= base, (attempt, d)
    # The cap holds: attempts 3+ (base 8.0) never exceed 8s.
    assert max(delays[3:]) <= 8.0
    # Jitter actually varies (not a constant factor).
    ratios = {round(d / min(8.0, 2 ** a), 6) for a, d in enumerate(delays)}
    assert len(ratios) > 1


def test_replay_state_retryability_rules():
    req = _GenRequest(
        prompt_ids=[1, 2, 3], max_new_tokens=10, temperature=0.5,
        stop_on_eos=True, top_p=0.9, seed=42, stop_texts=["END"],
    )
    req.token_ids.extend([7, 8])
    snap = req.replay_state()
    assert snap is not None
    assert snap.prompt_ids == [1, 2, 3]
    assert snap.emitted_ids == [7, 8]
    assert snap.remaining_tokens == 8
    assert (snap.temperature, snap.top_p, snap.seed) == (0.5, 0.9, 42)
    assert snap.stop_texts == ["END"]
    # prefill_ids covers the delivered continuation.
    assert req.prefill_ids() == [1, 2, 3, 7, 8]

    # Cancelled → not retryable.
    req.cancel.cancel()
    assert req.replay_state() is None

    # Expired deadline → not retryable (fake clock states the expiry).
    now = [0.0]
    req2 = _GenRequest(
        prompt_ids=[1], max_new_tokens=4, temperature=0.0,
        stop_on_eos=False, deadline=Deadline(10.0, clock=lambda: now[0]),
    )
    assert req2.replay_state() is not None
    now[0] = 11.0
    assert req2.replay_state() is None

    # Prefix registrations → never replayed (pool rows died with the
    # engine; the caller re-registers).
    req3 = _GenRequest(
        prompt_ids=[1], max_new_tokens=1, temperature=0.0,
        stop_on_eos=False, prefix_store=True,
    )
    assert req3.replay_state() is None

    # Resolved future → nothing to carry.
    req4 = _GenRequest(
        prompt_ids=[1], max_new_tokens=4, temperature=0.0,
        stop_on_eos=False,
    )
    req4.future.set_result(object())
    assert req4.replay_state() is None


# ----------------------------------------------------------------------
# the acceptance path: device crash mid-generation → seamless recovery
# ----------------------------------------------------------------------


def test_device_crash_mid_generation_recovers_seamlessly(metrics):
    eng, sup, sleeps = _make_supervised(metrics)
    try:
        restarts0 = counter_total(metrics, "app_tpu_engine_restarts_total")
        replays0 = counter_total(metrics, "app_tpu_requests_replayed_total")
        # Warm the compile caches, and produce the fault-free REFERENCE
        # sequence (greedy: deterministic given the same warm params).
        ref = eng.generate_sync(
            "the quick brown fox", max_new_tokens=40, temperature=0.0,
            stop_on_eos=False,
        )
        assert len(ref.token_ids) == 40
        assert eng.state == "SERVING"

        # The device dies at the 5th dispatch — deterministically MID-
        # generation (hit 1 is the prefill chunk, hits 2-4 the first
        # three pipelined windows; window 1's 8 tokens are processed and
        # on the stream before hit 5 fires), exactly once.
        faults.arm(
            "scheduler.device_step",
            raises=RuntimeError("injected device loss"),
            after=4, times=1,
        )
        req = eng.submit_generate(
            "the quick brown fox", max_new_tokens=40, temperature=0.0,
            stop_on_eos=False,
        )
        # The client consumes tokens BEFORE the crash lands, so the
        # recovery is provably a continuation, not a fresh retry.
        pre = [req.stream.get(timeout=120) for _ in range(3)]
        assert all(t is not None for t in pre)
        rest = _drain_stream(req)
        result = req.future.result(timeout=120)

        # Full, correct token sequence: what the client streamed is
        # exactly the fault-free reference — nothing duplicated by the
        # re-prefill, nothing dropped by the crash.
        assert pre + rest == ref.token_ids
        assert result.token_ids == ref.token_ids
        assert result.finish_reason == ref.finish_reason
        assert req.replays == 1

        # State machine walked SERVING → RESTARTING → SERVING: the
        # backoff hook observed RESTARTING, and recovery re-entered
        # SERVING (where new submissions work again).
        assert [s for s, _ in sleeps] == ["RESTARTING"]
        assert _wait_until(lambda: eng.state == "SERVING")
        # Backoff policy respected: first attempt waits within
        # [0.5, 1.0] × backoff_s.
        assert 0.125 <= sleeps[0][1] <= 0.25
        assert sup.restarts == 1
        assert counter_total(
            metrics, "app_tpu_engine_restarts_total"
        ) == restarts0 + 1
        assert counter_total(
            metrics, "app_tpu_requests_replayed_total"
        ) == replays0 + 1

        # Params were warm-reused, not re-initialized: the restarted
        # engine still greedy-decodes the identical sequence.
        again = eng.generate_sync(
            "the quick brown fox", max_new_tokens=40, temperature=0.0,
            stop_on_eos=False,
        )
        assert again.token_ids == ref.token_ids
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


def test_watchdog_trip_wedged_scheduler_abandoned_and_replayed(metrics):
    """A HUNG device step (not a raise): the watchdog trips, the
    supervisor cannot join the wedged thread, abandons it behind the
    epoch fence, restarts, and replays — and the zombie's eventual
    wake-up is inert (SchedulerSuperseded, no drain, no flag damage)."""
    eng, sup, sleeps = _make_supervised(
        metrics, watchdog_s=300.0, join_timeout_s=0.05,
    )
    try:
        trips0 = counter_total(metrics, "app_tpu_watchdog_trips_total")
        ref = eng.generate_sync(
            "wedge me", max_new_tokens=24, temperature=0.0,
            stop_on_eos=False,
        )
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall(**kw):
            gate_in.set()
            gate_out.wait(timeout=120)
            # Returning (not raising) models a wedged call that finally
            # completes: the epoch check right after the seam must turn
            # it into a silent SchedulerSuperseded exit.

        # Hang the 4th device dispatch (mid-generation), exactly once.
        faults.arm("scheduler.device_step", action=stall, after=3, times=1)
        req = eng.submit_generate(
            "wedge me", max_new_tokens=24, temperature=0.0,
            stop_on_eos=False,
        )
        assert gate_in.wait(60)  # the "device step" is now hung
        old_sched = eng._sched
        # Deterministic trip: state a time past the bound.
        assert eng._watchdog.check(
            now=time.monotonic() + eng._watchdog.bound_s + 1
        )
        # Recovery completes WHILE the old thread is still wedged.
        rest = _drain_stream(req)
        result = req.future.result(timeout=120)
        assert rest == ref.token_ids
        assert result.token_ids == ref.token_ids
        assert counter_total(
            metrics, "app_tpu_watchdog_trips_total"
        ) == trips0 + 1
        assert _wait_until(lambda: eng.state == "SERVING")
        assert eng._sched is not old_sched

        # Release the zombie: it must exit via the epoch fence without
        # draining or flipping the restarted engine's flags.
        gate_out.set()
        assert _wait_until(lambda: not old_sched.is_alive())
        assert eng._running and eng._fatal is None
        assert eng.state == "SERVING"
        after = eng.generate_sync(
            "wedge me", max_new_tokens=24, temperature=0.0,
            stop_on_eos=False,
        )
        assert after.token_ids == ref.token_ids
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


def test_watchdog_rearms_on_restarted_engine(metrics):
    """Satellite: a tripped-then-reset Watchdog (the supervisor reuses
    ONE instance across restarts) must re-arm cleanly — monitor thread
    alive, latch clear, and able to trip again."""
    eng, sup, _ = _make_supervised(
        metrics, watchdog_s=300.0, join_timeout_s=0.05,
    )
    try:
        wd = eng._watchdog
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall(**kw):
            gate_in.set()
            gate_out.wait(timeout=120)

        faults.arm("scheduler.device_step", action=stall, after=1, times=1)
        req = eng.submit_generate(
            "arm, trip, re-arm", max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        assert gate_in.wait(60)
        assert wd.check(now=time.monotonic() + wd.bound_s + 1)
        assert wd.tripped
        _drain_stream(req)
        req.future.result(timeout=120)
        gate_out.set()
        assert _wait_until(lambda: eng.state == "SERVING")
        # Same instance, fresh latch, live monitor — re-armed on the
        # restarted engine (the unit test below proves the reset →
        # start → re-trip cycle on the class itself).
        assert eng._watchdog is wd
        assert not wd.tripped and wd.reason == ""
        assert wd._thread is not None and wd._thread.is_alive()
        # Fresh pet baseline: no stale-pet instant re-trip.
        assert not wd.check()
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


def test_watchdog_unit_reset_restarts_monitor():
    """Satellite (unit half): trip → monitor thread exits (latched);
    reset + start must give a live monitor and a clean latch, petting
    from zero — the exact sequence start_sync runs on the reused
    instance."""
    clock = [0.0]
    trips = []
    wd = Watchdog(
        5.0, clock=lambda: clock[0], on_trip=trips.append,
        check_interval_s=0.01,
    )
    wd.start()
    try:
        clock[0] = 100.0  # way past the bound: monitor trips and exits
        assert _wait_until(lambda: wd.tripped, timeout=10)
        assert _wait_until(
            lambda: wd._thread is None or not wd._thread.is_alive(),
            timeout=10,
        )
        assert len(trips) == 1
        # Engine-restart sequence: reset() then start().
        wd.reset()
        assert not wd.tripped and wd.reason == ""
        wd.start()
        assert wd._thread is not None and wd._thread.is_alive()
        assert not wd.check(now=clock[0] + 4.9)  # fresh pet baseline
        assert wd.check(now=clock[0] + 5.1)  # and it can trip AGAIN
        assert len(trips) == 2
    finally:
        wd.stop()


# ----------------------------------------------------------------------
# crash loop → DOWN after TPU_RESTART_MAX
# ----------------------------------------------------------------------


def test_crash_loop_lands_down_after_restart_max(metrics):
    eng, sup, sleeps = _make_supervised(metrics, max_restarts=3)
    try:
        restarts0 = counter_total(metrics, "app_tpu_engine_restarts_total")
        eng.generate_sync(
            "warm", max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        # Park the scheduler at the top of its loop so the submit lands
        # BEFORE the crash deterministically, then swap the stall for a
        # persistent raise: every scheduler — including each restarted
        # one — dies on its next loop iteration (times=None → forever).
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall(**kw):
            gate_in.set()
            gate_out.wait(timeout=120)

        faults.arm("scheduler.window", action=stall, times=1)
        assert gate_in.wait(30)
        req = eng.submit_generate(
            "doomed", max_new_tokens=8, temperature=0.0, stop_on_eos=False
        )
        faults.arm(
            "scheduler.window", raises=RuntimeError("persistent fault")
        )
        gate_out.set()
        assert _wait_until(lambda: eng.state == "DOWN", timeout=60)
        # Exactly max_restarts attempts — then it STOPPED retrying.
        assert sup.restarts == 3
        assert sup.consecutive_failures == 3
        assert counter_total(
            metrics, "app_tpu_engine_restarts_total"
        ) == restarts0 + 3
        assert len(sleeps) == 3
        # Exponential growth across attempts (jitter can't mask 2×:
        # max jittered delay of attempt n is the min of attempt n+2).
        assert sleeps[2][1] > sleeps[0][1]
        # The carried request fails with the crash-loop terminal error,
        # stream closed (sentinel delivered) — no hanging client.
        with pytest.raises(ErrorServiceUnavailable, match="DOWN after 3"):
            req.future.result(timeout=30)
        _drain_stream(req)  # terminates: the sentinel was delivered
        # Health surfaces it: status DOWN, state machine DOWN, gauge 3.
        health = eng.health_check()
        assert health["status"] == "DOWN"
        assert health["state"] == "DOWN"
        assert health["details"]["state"] == "DOWN"
        assert health["details"]["supervisor"]["consecutive_failures"] == 3
        assert gauge_value(metrics, "app_tpu_engine_state") == 3
        # New submissions are rejected, not queued into the void.
        with pytest.raises(Exception):
            eng.submit_generate("rejected", max_new_tokens=2)
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


def test_give_up_on_wedged_scheduler_fails_all_live_requests(metrics):
    """Budget exhausted by a watchdog trip whose scheduler is WEDGED:
    the thread never drains, so _give_up itself must tear down, salvage
    the queue/slot structures, and fail every live caller with the
    crash-loop error — DOWN may never strand a request."""
    eng, sup, _ = _make_supervised(
        metrics, max_restarts=1, watchdog_s=300.0, join_timeout_s=0.05,
    )
    try:
        eng.generate_sync(
            "warm", max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        # Failure 1 (fatal crash): consumes the whole budget of 1.
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall(**kw):
            gate_in.set()
            gate_out.wait(timeout=120)

        faults.arm("scheduler.window", action=stall, times=1)
        assert gate_in.wait(30)
        rider = eng.submit_generate(
            "first crash rider", max_new_tokens=6, temperature=0.0,
            stop_on_eos=False,
        )
        faults.arm(
            "scheduler.window", raises=RuntimeError("first crash"), times=1
        )
        gate_out.set()
        assert rider.future.result(timeout=120) is not None
        assert _wait_until(lambda: sup.restarts == 1)

        # Failure 2 (wedge + trip, inside the stability window): budget
        # is gone, and the wedged thread will never run its drain.
        gate_in2, gate_out2 = threading.Event(), threading.Event()

        def stall2(**kw):
            gate_in2.set()
            gate_out2.wait(timeout=120)

        faults.arm("scheduler.device_step", action=stall2, times=1)
        stranded = eng.submit_generate(
            "stranded unless give_up salvages", max_new_tokens=6,
            temperature=0.0, stop_on_eos=False,
        )
        assert gate_in2.wait(60)
        assert eng._watchdog.check(
            now=time.monotonic() + eng._watchdog.bound_s + 1
        )
        with pytest.raises(ErrorServiceUnavailable, match="DOWN after 1"):
            stranded.future.result(timeout=120)
        _drain_stream(stranded)  # sentinel delivered — no hanging client
        assert _wait_until(lambda: eng.state == "DOWN")
        gate_out2.set()  # release the zombie; the epoch fence absorbs it
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


def test_stop_mid_recovery_fails_parked_requests(metrics):
    """Shutdown while a recovery is parked in its backoff wait: the
    salvaged request must fail with the explicit shutdown error —
    nothing will ever requeue it, and a stopped supervisor must not
    leave a client hanging on an open stream/future."""
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
        metrics=metrics,
    )
    sleep_entered, sleep_release = threading.Event(), threading.Event()

    def blocking_sleep(seconds):
        sleep_entered.set()
        sleep_release.wait(timeout=60)

    sup = EngineSupervisor(
        eng, max_restarts=3, backoff_s=0.25, rng=random.Random(1),
        sleep=blocking_sleep, metrics=metrics,
    ).start()
    eng.start_sync()
    try:
        eng.generate_sync(
            "warm", max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall(**kw):
            gate_in.set()
            gate_out.wait(timeout=120)

        faults.arm("scheduler.window", action=stall, times=1)
        assert gate_in.wait(30)
        rider = eng.submit_generate(
            "parked by shutdown", max_new_tokens=6, temperature=0.0,
            stop_on_eos=False,
        )
        faults.arm(
            "scheduler.window", raises=RuntimeError("crash then stop"),
            times=1,
        )
        gate_out.set()
        # Recovery salvaged the rider and is parked in its backoff wait.
        assert sleep_entered.wait(30)
        stopper = threading.Thread(target=sup.stop)
        stopper.start()
        assert _wait_until(lambda: sup._stopping)
        sleep_release.set()
        stopper.join(timeout=30)
        assert not stopper.is_alive()
        with pytest.raises(ErrorServiceUnavailable, match="shutting down"):
            rider.future.result(timeout=30)
        _drain_stream(rider)  # sentinel delivered — no hanging client
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


def test_start_after_stop_resets_stopping_latch(metrics):
    """A supervisor restarted after stop() must supervise again: start()
    resets the ``_stopping`` latch (under ``_lock``, like every other
    write to it — a lock-free reset could interleave into a concurrent
    stop() between its flag write and its event set, resurrecting a
    supervisor the operator is tearing down; this is the write GL020
    caught). The observable contract: after start(), ``stopping`` is
    False, so the scheduler's death drain offers salvage again."""
    eng, sup, _ = _make_supervised(metrics)
    try:
        sup.stop()
        assert sup.stopping
        sup.start()
        assert not sup.stopping
        assert sup._thread is not None and sup._thread.is_alive()
    finally:
        sup.stop()
        eng.stop_sync()


def test_stable_period_resets_crash_loop_counter(metrics):
    """Two crashes separated by a stable period must each count from a
    fresh window (injectable clock states the stability, no sleeping)."""
    now = [1000.0]
    eng, sup, sleeps = _make_supervised(
        metrics, max_restarts=2, clock=lambda: now[0]
    )
    try:
        eng.generate_sync(
            "warm", max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )

        def crash_with_rider(prompt, exc):
            """Park the loop, submit a rider, swap the stall for a
            one-shot raise: the crash deterministically lands with the
            rider in flight, and the replay completes it."""
            gate_in, gate_out = threading.Event(), threading.Event()

            def stall(**kw):
                gate_in.set()
                gate_out.wait(timeout=120)

            faults.arm("scheduler.window", action=stall, times=1)
            assert gate_in.wait(30)
            req = eng.submit_generate(
                prompt, max_new_tokens=6, temperature=0.0, stop_on_eos=False
            )
            faults.arm("scheduler.window", raises=exc, times=1)
            gate_out.set()
            return req

        req = crash_with_rider("ride one", RuntimeError("crash one"))
        assert req.future.result(timeout=120) is not None
        assert _wait_until(lambda: sup.restarts == 1)
        assert sup.consecutive_failures == 1

        now[0] += 120.0  # > backoff_reset_s: the engine proved stable
        req2 = crash_with_rider("ride two", RuntimeError("crash two"))
        assert req2.future.result(timeout=120) is not None
        assert _wait_until(lambda: sup.restarts == 2)
        # Crash two was attempt 1 of a NEW window, not attempt 2: the
        # engine is nowhere near DOWN (max_restarts=2 would have been
        # exhausted without the reset).
        assert sup.consecutive_failures == 1
        assert eng.state == "SERVING"
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


# ----------------------------------------------------------------------
# non-retryable requests keep the existing terminal error path
# ----------------------------------------------------------------------


def test_non_retryable_requests_fail_while_retryable_replay(metrics):
    eng, sup, _ = _make_supervised(metrics)
    try:
        ref = eng.generate_sync(
            "retryable one", max_new_tokens=16, temperature=0.0,
            stop_on_eos=False,
        )
        # Park the scheduler at the top of its loop so both requests sit
        # in the queue when the crash hits.
        gate_in, gate_out = threading.Event(), threading.Event()

        def stall(**kw):
            gate_in.set()
            gate_out.wait(timeout=120)

        clock = [0.0]
        with faults.armed("scheduler.window", action=stall, times=1):
            assert gate_in.wait(30)
            live = eng.submit_generate(
                "retryable one", max_new_tokens=16, temperature=0.0,
                stop_on_eos=False,
            )
            dead = eng.submit_generate(
                "expired one", max_new_tokens=16, temperature=0.0,
                stop_on_eos=False,
                deadline=Deadline(3600.0, clock=lambda: clock[0]),
            )
            clock[0] = 7200.0  # 'dead' expires while queued
            # The next iteration crashes: the drain must salvage `live`
            # and fail `dead` through the existing terminal path.
            faults.arm(
                "scheduler.device_step",
                raises=RuntimeError("crash with mixed queue"), times=1,
            )
            gate_out.set()
        result = live.future.result(timeout=120)
        assert result.token_ids == ref.token_ids
        # The unconsumed stream carries the complete sequence too.
        assert _drain_stream(live) == ref.token_ids
        with pytest.raises(Exception) as excinfo:
            dead.future.result(timeout=120)
        # Existing terminal semantics: the expired request is NOT
        # replayed; it fails (deadline reap or the crash error,
        # whichever path got it first) and its stream closes.
        assert not isinstance(excinfo.value, ErrorServiceUnavailable)
        assert _drain_stream(dead) == []
        assert live.replays >= 1
        assert dead.replays == 0
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


# ----------------------------------------------------------------------
# SSE continuity across a restart
# ----------------------------------------------------------------------


class _RouteRecorder:
    """Just enough App surface for add_openai_routes."""

    def __init__(self):
        self.routes = {}

    def _verb(self, method, path):
        def deco(fn):
            self.routes[(method, path)] = fn
            return fn

        return deco

    def post(self, path):
        return self._verb("POST", path)

    def get(self, path):
        return self._verb("GET", path)


class _FakeCtx:
    def __init__(self, engine, body, deadline=None, cancel=None):
        import types

        self.container = types.SimpleNamespace(tpu=engine, tpu_embed=None)
        self.request = types.SimpleNamespace(
            raw=types.SimpleNamespace(body=json.dumps(body).encode())
        )
        self.deadline = deadline
        self.cancel_token = cancel


def test_sse_stream_resumes_across_restart(metrics):
    """The client-visible contract: one SSE stream, opened before the
    crash, carries the complete completion — the restart is invisible
    (no error event, text identical to a fault-free run)."""
    from gofr_tpu.serving.openai_compat import add_openai_routes

    eng, sup, _ = _make_supervised(metrics)
    try:
        ref = eng.generate_sync(
            "stream across the crash", max_new_tokens=32, temperature=0.0,
            stop_on_eos=False,
        )
        app = _RouteRecorder()
        add_openai_routes(app)
        handler = app.routes[("POST", "/v1/completions")]
        ctx = _FakeCtx(eng, {
            "prompt": "stream across the crash", "max_tokens": 32,
            "temperature": 0, "stream": True,
        })
        # The device dies mid-generation (4th dispatch), exactly once —
        # armed BEFORE the submit so the hit count, not wall clock,
        # decides where the crash lands.
        faults.arm(
            "scheduler.device_step",
            raises=RuntimeError("mid-SSE device loss"),
            after=3, times=1,
        )

        async def run():
            stream = await handler(ctx)
            events = []
            async for chunk in stream.chunks:
                events.append(chunk)
            return events

        events = asyncio.run(run())
        assert events[-1] == "data: [DONE]\n\n"
        payloads = [
            json.loads(e[len("data: "):])
            for e in events if e.startswith("data: {")
        ]
        assert not [p for p in payloads if "error" in p], (
            "a replayed stream must NOT surface an error event"
        )
        text = "".join(
            c.get("text", "")
            for p in payloads for c in p.get("choices", [])
        )
        finish = [
            c["finish_reason"]
            for p in payloads for c in p.get("choices", [])
            if c.get("finish_reason")
        ]
        assert text == ref.text
        assert finish == [ref.finish_reason]
        assert _wait_until(lambda: eng.state == "SERVING")
        assert sup.restarts == 1
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()


# ----------------------------------------------------------------------
# paged-KV engines recover too (allocator rebuilt from scratch)
# ----------------------------------------------------------------------


def test_paged_kv_engine_restart_rebuilds_pool(metrics):
    eng, sup, _ = _make_supervised(metrics, kv_block=16)
    try:
        ref = eng.generate_sync(
            "paged recovery", max_new_tokens=20, temperature=0.0,
            stop_on_eos=False,
        )
        total_blocks = eng.cache.n_blocks - 1
        assert len(eng._free_blocks) == total_blocks
        # Crash at the 3rd dispatch (2nd decode window) — blocks are
        # allocated and mid-use when the device dies.
        faults.arm(
            "scheduler.device_step",
            raises=RuntimeError("paged device loss"), after=2, times=1,
        )
        req = eng.submit_generate(
            "paged recovery", max_new_tokens=20, temperature=0.0,
            stop_on_eos=False,
        )
        result = req.future.result(timeout=120)
        assert result.token_ids == ref.token_ids
        _drain_stream(req)
        # The rebuilt pool is whole: nothing leaked across the crash.
        assert _wait_until(lambda: eng.state == "SERVING")
        assert _wait_until(
            lambda: len(eng._free_blocks) == eng.cache.n_blocks - 1
        )
    finally:
        faults.reset()
        sup.stop()
        eng.stop_sync()
