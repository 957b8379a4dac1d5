"""The device-trace capture singleton (ISSUE 15, satellite of the
scheduler-loop profiler).

``jax.profiler`` is a process-wide resource: exactly one trace may run
at a time, and every capture serializes its protobuf output to disk on
``stop_trace``. Two call sites share it — the operator's manual
``/debug/tpu-trace`` endpoint (``gofr_tpu/app.py``) and the
scheduler-loop profiler's anomaly auto-trigger
(``serving/loop_profiler.py``) — so the machinery lives here as ONE
process-wide :class:`ProfilerCapture`:

* **One trace dir, one lock, created at construction.** The previous
  endpoint minted ``self._trace_dir``/``self._trace_lock`` lazily via
  ``hasattr`` on the first request, so two concurrent first requests
  could each observe the attribute missing, mint two dirs/locks, and
  trace concurrently. :func:`get_capture` constructs the singleton once
  under a module lock; the dir is reused by every capture (each
  overwrites the last — an unauthenticated loop of trace requests must
  not fill the disk).
* **Cooldown for auto-triggers** (``TPU_LOOP_TRACE_COOLDOWN_S``): a
  stall *storm* would otherwise re-trigger a capture per anomaly and
  thrash the profiler — serializing trace output is itself host work
  that widens the stall. :meth:`trigger` suppresses anything inside the
  cooldown (counted, so ``/debug/loop`` shows what was skipped); the
  manual endpoint is never cooldown-gated (an operator asking is an
  operator asking) but does note its capture so the next auto-trigger
  backs off from it.
* **Non-blocking for the scheduler.** ``trigger`` hands the bounded
  capture to a daemon thread and returns immediately — the scheduler
  loop must never block for the capture window it is trying to
  diagnose.

Determinism: clock, sleep, the start/stop callables, and the thread
spawn are all injectable, so the cooldown and concurrency contracts are
tested with stated time and synchronous spawns.
"""

from __future__ import annotations

import tempfile
import threading

import time
from typing import Any, Callable, Optional

from gofr_tpu.analysis import lockcheck


class ProfilerCapture:
    """One process-wide ``jax.profiler`` capture slot: a reusable trace
    directory, a non-blocking busy lock, and an auto-trigger cooldown.
    Construct via :func:`get_capture` — a second instance would defeat
    the whole point."""

    def __init__(
        self,
        *,
        cooldown_s: float = 60.0,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        starter: Optional[Callable[[str], None]] = None,
        stopper: Optional[Callable[[], None]] = None,
        spawn: Optional[Callable[[Callable[[], None]], None]] = None,
        logger: Any = None,
    ) -> None:
        #: One reusable directory per process; every capture overwrites
        #: the last, so repeated captures cannot fill the disk.
        self.trace_dir = tempfile.mkdtemp(prefix="tpu-trace-")
        self.cooldown_s = max(0.0, float(cooldown_s))
        self._clock = clock
        self._sleep = sleep
        self._starter = starter
        self._stopper = stopper
        self._spawn = spawn
        self._logger = logger
        # The capture slot: held for the duration of one trace. A
        # threading (not asyncio) lock — the auto-trigger fires from
        # the scheduler thread; the async endpoint polls it
        # non-blocking and replies 409 instead of queueing.
        self._busy = lockcheck.make_lock("ProfilerCapture._busy")
        # Bookkeeping (counters + cooldown anchor) under its own lock
        # so trigger() stays race-free against note_manual_capture().
        self._state_lock = lockcheck.make_lock("ProfilerCapture._state_lock")
        self.captures = 0
        self.suppressed = 0
        self.last_capture_at: Optional[float] = None
        self.last_reason = ""
        self.last_error = ""

    # -- the capture slot ----------------------------------------------

    def try_acquire(self) -> bool:
        """Claim the capture slot without blocking (False = a capture
        is already running — the endpoint's 409)."""
        return self._busy.acquire(blocking=False)

    def release(self) -> None:
        self._busy.release()

    @property
    def busy(self) -> bool:
        return self._busy.locked()

    # -- profiler plumbing ---------------------------------------------

    def start_trace(self) -> None:
        """Start a device trace into the singleton dir (blocking disk /
        runtime work — callers keep it off their event loop)."""
        if self._starter is not None:
            self._starter(self.trace_dir)
            return
        import jax

        jax.profiler.start_trace(self.trace_dir)

    def stop_trace(self) -> None:
        if self._stopper is not None:
            self._stopper()
            return
        import jax

        jax.profiler.stop_trace()

    def note_manual_capture(self) -> None:
        """Record an endpoint-driven capture (counts, cooldown anchor):
        the next auto-trigger backs off from a trace the operator just
        took rather than stacking a second one onto the same incident."""
        with self._state_lock:
            self.captures += 1
            self.last_capture_at = self._clock()
            self.last_reason = "manual"

    # -- anomaly auto-trigger ------------------------------------------

    def trigger(self, ms: int, reason: str = "loop-stall") -> bool:
        """Fire-and-forget bounded capture for a loop anomaly: claims
        the slot and spawns the capture off-thread, or returns False
        when inside the cooldown / already busy (both counted as
        suppressed — a stall storm must not thrash the profiler).
        Never blocks the calling (scheduler) thread."""
        ms = max(1, int(ms))
        with self._state_lock:
            now = self._clock()
            if (
                self.last_capture_at is not None
                and now - self.last_capture_at < self.cooldown_s
            ):
                self.suppressed += 1
                return False
            if not self.try_acquire():
                self.suppressed += 1
                return False
            self.captures += 1
            self.last_capture_at = now
            self.last_reason = reason

        def run() -> None:
            try:
                self.start_trace()
                self._sleep(ms / 1e3)
                self.stop_trace()
                with self._state_lock:
                    self.last_error = ""
            except Exception as exc:  # noqa: BLE001 — a failed capture must never take the scheduler with it
                with self._state_lock:
                    self.last_error = f"{type(exc).__name__}: {exc}"
                if self._logger is not None:
                    self._logger.warnf(
                        "loop-anomaly trace capture failed: %s", exc
                    )
            finally:
                self.release()

        if self._spawn is not None:
            self._spawn(run)
        else:
            threading.Thread(
                target=run, name="tpu-trace-capture", daemon=True
            ).start()
        return True

    # -- rendering -----------------------------------------------------

    def snapshot(self) -> dict[str, Any]:
        with self._state_lock:
            return {
                "trace_dir": self.trace_dir,
                "busy": self.busy,
                "cooldown_s": self.cooldown_s,
                "captures": self.captures,
                "suppressed": self.suppressed,
                "last_reason": self.last_reason,
                "last_error": self.last_error,
            }


_capture: Optional[ProfilerCapture] = None
_capture_lock = lockcheck.make_lock("profiler_capture._capture_lock")


def capturing() -> bool:
    """A capture holds the slot now (without minting the singleton)."""
    cap = _capture
    return cap is not None and cap.busy


def get_capture(cooldown_s: Optional[float] = None) -> ProfilerCapture:
    """The process-wide singleton, constructed exactly once under a
    module lock (closing the lazy-``hasattr`` race the old endpoint
    had: two concurrent first requests can no longer mint two
    dirs/locks and trace concurrently). ``cooldown_s`` updates the
    auto-trigger cooldown when given — the engine passes its
    ``TPU_LOOP_TRACE_COOLDOWN_S`` through here at boot."""
    global _capture
    with _capture_lock:
        if _capture is None:
            _capture = ProfilerCapture()
        if cooldown_s is not None:
            _capture.cooldown_s = max(0.0, float(cooldown_s))
        return _capture
