"""Engine supervision: self-healing restarts with crash-loop backoff.

PR 2's watchdog turned a wedged device step into a *detected* failure —
but a detected failure still latched the engine DOWN until an operator
restarted it. A production jax_graft system serving millions of users
must survive a hung device step or a crashed scheduler loop without a pager:
GoFr's capability surface implies the FRAMEWORK owns recovery, and the
north star's ICI-sharded multi-chip serving makes single-replica
self-healing the prerequisite for any replica-level failover story.

:class:`EngineSupervisor` owns the restart policy the engine itself
deliberately does not have:

* **Detection** — the watchdog's trip callback and the scheduler's
  fatal-exit path both notify the supervisor (``notify_trip`` /
  ``notify_crash``) instead of being terminal.
* **Salvage** — still-live *retryable* sequences (not cancelled, not
  past deadline, not prefix registrations) are snapshotted via
  ``_GenRequest.replay_state()`` — prompt, sampling params, and the
  tokens already streamed — and parked instead of failed. Their stream
  queues and futures stay open: the client never sees the crash.
* **Teardown + warm restart** — the engine's per-boot serving state
  (KV cache, paged allocator, queues, device slot planes) is rebuilt by
  ``engine.restart_sync()`` while the already-loaded params pytree and
  the compiled programs are reused — recovery costs a cache allocation,
  not a model load. A scheduler thread that never exits (truly wedged
  device call) is *abandoned*: the engine's scheduler epoch is bumped so
  every later touch from the zombie raises ``SchedulerSuperseded``
  instead of corrupting the fresh scheduler's state.
* **Backoff** — restarts are crash-loop aware: exponential, jittered
  (``TPU_RESTART_BACKOFF_S`` base, injectable clock/rng so tests state
  time instead of sleeping), with the consecutive-failure counter
  resetting after a stable period. ``TPU_RESTART_MAX`` consecutive
  failures land the engine in DOWN rather than restarting forever.
* **Replay** — after a successful restart the salvaged requests requeue
  (``engine.requeue_replay``): admission re-prefills prompt + the
  already-delivered tokens, so an SSE stream resumes at exactly the
  next token — no duplicates, no gaps. Requests that stopped being
  retryable during the restart get the existing terminal error event.

Health state machine, surfaced through ``engine.health_check`` (and so
``/.well-known/health`` and both gRPC Health RPCs) plus the
``app_tpu_engine_state`` gauge::

    SERVING ──trip/crash──▶ DEGRADED ──supervisor──▶ RESTARTING
       ▲                                                 │
       └───────── restart + replay succeeded ────────────┤
                                                         ▼
                DOWN ◀── TPU_RESTART_MAX consecutive failures

Observability: ``app_tpu_engine_restarts_total`` and
``app_tpu_requests_replayed_total`` count recoveries and carried
requests; every transition logs with its reason.
"""

from __future__ import annotations

import queue
import random
import threading

import time
from typing import Any, Callable, Optional

from gofr_tpu.analysis import lockcheck
from gofr_tpu.serving.types import _GenRequest

#: State-machine order mirrored into the ``app_tpu_engine_state`` gauge.
STATES = ("SERVING", "DEGRADED", "RESTARTING", "DOWN")


class EngineSupervisor:
    """Owns one engine's restart policy (attach via construction).

    All timing seams are injectable — ``clock`` for the stability
    window, ``rng`` for jitter, ``sleep`` for the backoff wait — so the
    chaos suite drives every recovery path deterministically: no real
    sleeps, no wall-clock races.
    """

    def __init__(
        self,
        engine: Any,
        *,
        max_restarts: int = 5,
        backoff_s: float = 0.5,
        backoff_cap_s: float = 30.0,
        backoff_reset_s: float = 60.0,
        join_timeout_s: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
        rng: Optional[random.Random] = None,
        sleep: Optional[Callable[[float], None]] = None,
        metrics: Any = None,
        logger: Any = None,
    ) -> None:
        self._engine = engine
        self.max_restarts = max(1, int(max_restarts))
        self.backoff_s = max(0.0, float(backoff_s))
        self.backoff_cap_s = max(self.backoff_s, float(backoff_cap_s))
        self.backoff_reset_s = float(backoff_reset_s)
        self.join_timeout_s = float(join_timeout_s)
        self._clock = clock
        self._rng = rng if rng is not None else random.Random()
        self._metrics = metrics
        self._logger = logger

        self._lock = lockcheck.make_lock("EngineSupervisor._lock")
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        # Default backoff wait doubles as the stop latch: a shutdown
        # mid-backoff returns immediately instead of finishing the wait.
        self._sleep: Callable[[float], None] = (
            sleep if sleep is not None else self._default_sleep
        )
        self._pending_reason: Optional[str] = None  # graftlint: guarded-by=_lock
        self._stopping = False  # graftlint: guarded-by=_lock
        self._thread: Optional[threading.Thread] = None

        # Policy bookkeeping (supervisor-thread-owned after start()).
        self.restarts = 0  # successful warm restarts performed
        self._consecutive = 0  # failures since the last stable period
        self._last_recovered_at: Optional[float] = None

        engine.attach_supervisor(self)

    def _default_sleep(self, seconds: float) -> None:
        self._stop_evt.wait(seconds)

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "EngineSupervisor":
        if self._thread is not None and self._thread.is_alive():
            return self
        # Under the lock like every other _stopping write: a lock-free
        # reset here could interleave into a concurrent stop() between
        # its flag write and its event set, resurrecting a supervisor
        # the operator is tearing down (GL020's first real catch).
        with self._lock:
            self._stopping = False
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._loop, name="tpu-supervisor", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop supervising (engine close / app shutdown). Does NOT stop
        the engine — by this point the caller owns its lifecycle again.
        Requests a recovery parked for replay are failed with an
        explicit shutdown error: nothing will ever requeue them, and a
        stopped supervisor must not leave clients hanging on open
        streams/futures."""
        with self._lock:
            self._stopping = True
        self._stop_evt.set()
        self._wake.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5)
        self.drain_parked()

    @property
    def stopping(self) -> bool:
        """True once stop() began: the scheduler's death drain consults
        this — a stopping supervisor accepts no salvage, because nothing
        would ever requeue it. Lock-free read: the flag only ever
        latches False→True while the reader cares, and the scheduler's
        death drain must not contend on the supervisor's lock."""
        return self._stopping  # graftlint: disable=GL020 — monotonic latch read; GIL-atomic bool, stale False only delays the drain one poll

    def drain_parked(self) -> None:
        """Pop-and-fail everything parked for replay (idempotent: pops
        under the submit lock, so stop(), a racing recovery's own
        stop-path, and engine.close()'s final sweep each fail a request
        at most once)."""
        from gofr_tpu.errors import ErrorServiceUnavailable

        eng = self._engine
        with eng._submit_lock:
            reqs, eng._replay = list(eng._replay), []
        if not reqs:
            return
        exc = ErrorServiceUnavailable(
            "engine shutting down mid-recovery; retry against another "
            "replica"
        )
        for req in reqs:
            self._fail_request(req, exc)

    # -- notifications (watchdog thread / dying scheduler thread) -------

    def notify_trip(self, reason: str) -> None:
        """Watchdog trip: the scheduler is WEDGED (it may never exit)."""
        self._request_recovery(f"watchdog: {reason}")

    def notify_crash(self, exc: BaseException) -> None:
        """Fatal scheduler exit: the thread drained (salvaging retryable
        requests into the engine's replay list) and died."""
        self._request_recovery(f"scheduler crash: {exc}")

    def notify_probe_failure(self, reason: str) -> None:
        """A synthetic health probe failed against a replica that still
        CLAIMS to be serving (replica pool's active prober): the serving
        dataplane is broken in a way no crash or watchdog trip caught —
        treat it as a detected failure and restart, instead of waiting
        for a real request to wedge. Degrades first so health endpoints
        and the pool's router stop sending traffic immediately."""
        self._engine._set_state("DEGRADED")
        self._request_recovery(f"probe: {reason}")

    def note_probe_success(self) -> None:
        """A synthetic probe PASSED (pool prober): the engine provably
        serves end to end, so the crash-loop window closes — the
        consecutive-failure counter resets and the next failure starts a
        fresh restart budget rather than landing straight in DOWN."""
        self._consecutive = 0
        self._last_recovered_at = self._clock()

    def revive(self) -> bool:
        """Bring a DOWN engine back for probation (probe-driven
        re-admission): restart it with a FRESH crash-loop budget. The
        caller (the pool's prober) must follow with a passing synthetic
        probe before routing traffic again — revive restores the
        machinery, the probe earns re-admission. Returns False when the
        supervisor is stopping or the restart itself fails (the engine
        stays DOWN)."""
        with self._lock:
            if self._stopping:
                return False
        try:
            self._engine.restart_sync()
        except Exception as exc:  # noqa: BLE001 — a failed revive must report, not raise
            if self._logger is not None:
                self._logger.errorf(
                    "supervisor: revive failed; engine stays DOWN: %s", exc
                )
            try:
                self._engine.stop_sync()
            except Exception:  # graftlint: disable=GL006 — best-effort rollback; the revive failure above is already logged
                pass
            return False
        self._consecutive = 0
        self._last_recovered_at = self._clock()
        if self._logger is not None:
            self._logger.infof(
                "supervisor: engine revived from DOWN (probe-driven); "
                "restart budget reset"
            )
        return True

    def _request_recovery(self, reason: str) -> None:
        with self._lock:
            if self._stopping:
                return
            # Coalesce: one recovery handles however many signals raced
            # in (a trip often precedes the wedged step's eventual
            # raise); keep the FIRST reason — it named the root cause.
            if self._pending_reason is None:
                self._pending_reason = reason
        self._wake.set()

    # -- introspection --------------------------------------------------

    @property
    def consecutive_failures(self) -> int:
        return self._consecutive

    def describe(self) -> dict:
        """Health-endpoint block (rides engine.health_check details)."""
        return {
            "restarts": self.restarts,
            "consecutive_failures": self._consecutive,
            "max_restarts": self.max_restarts,
            "backoff_s": self.backoff_s,
        }

    def backoff_delay(self, attempt: int) -> float:
        """Jittered exponential backoff for the ``attempt``-th
        consecutive restart (0-based): ``backoff_s * 2^attempt`` capped
        at ``backoff_cap_s``, scaled into [50%, 100%] so a fleet of
        replicas does not restart in lockstep."""
        base = min(self.backoff_cap_s, self.backoff_s * (2.0 ** attempt))
        return base * (0.5 + 0.5 * self._rng.random())

    # -- the supervision loop -------------------------------------------

    def _loop(self) -> None:
        while True:
            self._wake.wait()
            with self._lock:
                if self._stopping:
                    return
                reason = self._pending_reason
                self._pending_reason = None
                self._wake.clear()
            if reason is None:
                continue
            try:
                self._recover(reason)
            except Exception as exc:  # noqa: BLE001 — the loop must survive
                # A recovery step itself failing (cache realloc OOM on a
                # sick device, teardown error) must not kill this thread:
                # a dead supervisor strands every parked request forever.
                # Land in DOWN — the terminal state whose contract is
                # "every parked caller gets an explicit error".
                if self._logger is not None:
                    self._logger.errorf(
                        "supervisor: recovery itself failed (%s); "
                        "declaring the engine DOWN", exc,
                    )
                try:
                    self._give_up(f"recovery failed: {exc}")
                except Exception as exc2:  # noqa: BLE001 — last resort
                    if self._logger is not None:
                        self._logger.errorf(
                            "supervisor: give-up also failed: %s", exc2
                        )

    def _recover(self, reason: str) -> None:
        eng = self._engine
        now = self._clock()
        if (
            self._last_recovered_at is not None
            and now - self._last_recovered_at > self.backoff_reset_s
        ):
            # The previous recovery held long enough to count as stable:
            # this failure starts a fresh crash-loop window.
            self._consecutive = 0
        if self._consecutive >= self.max_restarts:
            self._give_up(reason)
            return
        attempt = self._consecutive
        self._consecutive += 1
        if self._logger is not None:
            self._logger.errorf(
                "supervisor: engine failure (%s); restart attempt %d/%d",
                reason, attempt + 1, self.max_restarts,
            )
        eng._set_state("RESTARTING")
        self._teardown()
        # Signals that raced in during teardown describe the SAME failure
        # being recovered (a trip's wedged step often raises moments
        # later; the old scheduler is dead and the new one not yet
        # started, so nothing else can be failing): absorb them so one
        # fault never burns two restart attempts.
        with self._lock:
            self._pending_reason = None
        # The three bail-out probes below read the stop latch lock-free
        # on purpose: each sits before/after a long blocking step
        # (backoff sleep, cache realloc) and a stale False merely means
        # stop()'s own drain_parked sweep — idempotent — cleans up.
        if self._stopping:  # graftlint: disable=GL020 — monotonic latch probe; stop() re-drains idempotently
            self.drain_parked()
            return
        self._sleep(self.backoff_delay(attempt))
        if self._stopping:  # graftlint: disable=GL020 — monotonic latch probe; stop() re-drains idempotently
            self.drain_parked()
            return
        eng.restart_sync()
        if self._stopping:  # graftlint: disable=GL020 — monotonic latch probe; stop() re-drains idempotently
            # close() raced the restart (its join timed out while the
            # cache realloc ran): undo the resurrection — the operator
            # asked for a stopped engine — and fail whatever was parked
            # (idempotent with stop()'s own drain).
            eng.stop_sync()
            self.drain_parked()
            return
        self.restarts += 1
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_engine_restarts_total",
                "model", eng.model_name,
            )
        replayed, dropped = self._requeue_salvaged()
        self._last_recovered_at = self._clock()
        if self._logger is not None:
            self._logger.infof(
                "supervisor: engine restarted (attempt %d); %d request(s) "
                "replayed, %d no longer retryable",
                attempt + 1, replayed, dropped,
            )

    def _teardown(self) -> None:
        """Stop the failed scheduler WITHOUT the engine's long join: mark
        a restart pending (the dying thread's drain then salvages
        retryable requests instead of failing them), give the thread a
        bounded join, and abandon it if it is truly wedged — bumping the
        scheduler epoch so any later touch from the zombie raises
        ``SchedulerSuperseded``, then salvaging the structures the dead
        drain never will."""
        eng = self._engine
        with eng._submit_lock:
            eng._running = False
            eng._draining = True
            eng._restart_pending = True
        eng._work.set()
        if eng._watchdog is not None:
            eng._watchdog.stop()
        old = eng._sched
        if old is not None:
            old.join(timeout=self.join_timeout_s)
            if old.is_alive():
                if self._logger is not None:
                    self._logger.errorf(
                        "supervisor: scheduler thread wedged past %.1fs "
                        "join; abandoning it (epoch fence)",
                        self.join_timeout_s,
                    )
                with eng._submit_lock:
                    eng._epoch += 1
                self._salvage_abandoned()
            eng._sched = None

    def _salvage_abandoned(self) -> None:
        """The wedged thread will never run its drain: collect every
        live request from the engine structures ourselves — retryable
        ones park for replay, the rest get their terminal error now."""
        eng = self._engine
        reqs: list[_GenRequest] = []
        with eng._submit_lock:
            while True:
                try:
                    reqs.append(eng._pending.get_nowait())
                except queue.Empty:
                    break
            for seq in eng._slots:
                if seq is not None:
                    reqs.append(seq.request)
            for st in eng._prefilling.values():
                reqs.append(st.request)
            reqs.extend(eng._wait_kv)
            eng._wait_kv.clear()
            eng._queued_tokens = 0
            eng._tenant_queued.clear()
            if eng._tenant_ledger is not None:
                # Live queue shares reset with the queues (replays
                # re-note on requeue); cumulative attribution survives
                # the restart like the flight recorder does.
                eng._tenant_ledger.reset_queued()
            # Partition ONCE: retryability can flip between evaluations
            # (a cancel racing in), and a request must land on exactly
            # one side.
            retry: list[_GenRequest] = []
            drop: list[_GenRequest] = []
            for req in reqs:
                (retry if req.retryable() else drop).append(req)
            eng._replay.extend(retry)
        for req in drop:
            self._fail_request(req)

    def _requeue_salvaged(self) -> tuple[int, int]:
        """Requeue every salvaged request on the restarted engine;
        returns (replayed, dropped). Drops — cancelled or expired during
        the outage, or a full fresh queue — fail through the existing
        terminal error path so streams end with an explicit error event,
        never a silent truncation."""
        eng = self._engine
        with eng._submit_lock:
            reqs, eng._replay = list(eng._replay), []
        replayed = dropped = 0
        for req in reqs:
            if eng.requeue_replay(req):
                replayed += 1
                continue
            if (
                req.retryable()
                and not eng._running
                and not self._stopping  # graftlint: disable=GL020 — monotonic latch probe; a stale False parks the request for a recovery stop() then fails itself
            ):
                # Still retryable, but the fresh engine already died
                # again (tight crash loop): park it back — the NEXT
                # recovery replays it, or _give_up fails it with the
                # crash-loop terminal error. (During shutdown there is
                # no next recovery: fall through to the terminal error.)
                with eng._submit_lock:
                    eng._replay.append(req)
                continue
            # A request the fresh queue could not take (full) may still
            # continue on a sibling replica before failing terminally.
            if eng.try_handoff(req):
                continue
            dropped += 1
            self._fail_request(req)
        return replayed, dropped

    def _fail_request(
        self, req: _GenRequest, exc: Optional[BaseException] = None
    ) -> None:
        """Terminal error + stream sentinel. The cancelled/deadline
        classification routes through ``scheduler._reap_reason`` — the
        ONE retirement predicate — so a retirement reason added there
        surfaces identically for requests failed across a restart."""
        from gofr_tpu.errors import (
            ErrorDeadlineExceeded,
            ErrorRequestCancelled,
            ErrorServiceUnavailable,
        )

        if exc is None:
            reason = self._engine._reap_reason(req)
            if reason == "cancelled":
                exc = ErrorRequestCancelled()
            elif reason == "deadline":
                exc = ErrorDeadlineExceeded(
                    f"after {len(req.token_ids)} generated token(s)"
                )
            else:
                exc = ErrorServiceUnavailable(
                    "engine restart could not carry this request; retry"
                )
        from concurrent.futures import InvalidStateError

        try:
            if not req.future.done():
                req.future.set_exception(exc)
        except InvalidStateError:  # caller cancelled concurrently
            pass
        req.stream.put(None)
        # Observability: a request failed across a restart still gets
        # exactly one flight-recorder entry/trace (latched — no double
        # summarization when this races a scheduler terminal path), and
        # the tenant ledger attributes it at the same seam (its own
        # latch) so attribution stays total across restarts too.
        if req.timeline is not None:
            req.timeline.finish(
                "error", type(exc).__name__,
                output_tokens=len(req.token_ids),
            )
        if self._engine._tenant_ledger is not None:
            self._engine._tenant_ledger.finish_request(req, "error")

    def _give_up(self, reason: str) -> None:
        """Crash loop: ``max_restarts`` consecutive failures — land in
        DOWN (health reports it, orchestrators reroute) and fail every
        live request instead of restarting forever. Runs a full
        teardown first: when the budget is exhausted by a watchdog trip
        the wedged scheduler never drained, so requests still sit in
        the queue/slots/prefill structures — _teardown salvages them
        into the replay list, and everything parked there fails with
        the explicit crash-loop error (no caller may hang on DOWN)."""
        eng = self._engine
        if self._logger is not None:
            self._logger.errorf(
                "supervisor: %d consecutive restart failures (%s); "
                "engine is DOWN until an operator intervenes",
                self._consecutive, reason,
            )
        self._teardown()
        eng._set_state("DOWN")
        from gofr_tpu.errors import ErrorServiceUnavailable

        exc = ErrorServiceUnavailable(
            f"engine DOWN after {self._consecutive} restart attempts "
            f"({reason}); retry against another replica"
        )
        with eng._submit_lock:
            reqs, eng._replay = list(eng._replay), []
        for req in reqs:
            # Replica-tier failover: a still-retryable request this
            # replica can no longer serve continues on a SIBLING replica
            # when a pool handoff is installed — the client's stream and
            # future carry over; only unplaceable requests get the
            # crash-loop terminal error.
            if eng.try_handoff(req):
                continue
            self._fail_request(req, exc)
