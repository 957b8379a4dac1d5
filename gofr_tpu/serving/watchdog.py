"""Scheduler watchdog: declare the engine unhealthy when it stalls.

A hung device step (deadlocked collective, runaway compile, a device
that stopped answering) is indistinguishable from a slow one from inside the
scheduler thread — it is *blocked*. The watchdog watches from outside:
the scheduler **pets** it once per loop iteration (idle iterations pet
every ≤20 ms, busy ones once per window), and a monitor checks that
the gap since the last pet stays under a configurable wall-time bound.

On a trip the watchdog latches unhealthy, bumps
``app_tpu_watchdog_trips_total``, opens a tracing span so the stall is
visible in traces, and invokes ``on_trip`` — the engine's callback
flips it into draining (new submissions get 503) and the health
endpoint reports DOWN; with a supervisor attached
(``serving/supervisor.py``) the callback also requests an automatic
restart. The latch clears only on engine restart — manual or
supervisor-driven; either path runs ``reset()`` + ``start()`` on this
SAME instance, so the monitor thread (which exits once latched) is
respawned and the restarted engine is watched from a fresh pet
baseline.

Determinism: ``check(now=...)`` takes an explicit timestamp, so tests
trip the watchdog by *stating* a time, not by sleeping through the
bound. The background monitor thread (production) is just
``check()`` on an ``Event.wait`` cadence.
"""

from __future__ import annotations

import threading

import time
from typing import Any, Callable, Optional

from gofr_tpu.analysis import lockcheck


class Watchdog:
    """Wall-clock progress monitor for the scheduler thread."""

    def __init__(
        self,
        bound_s: float,
        *,
        clock: Callable[[], float] = time.monotonic,
        on_trip: Optional[Callable[[str], None]] = None,
        metrics: Any = None,
        logger: Any = None,
        model_name: str = "",
        check_interval_s: Optional[float] = None,
    ) -> None:
        self.bound_s = float(bound_s)
        self._clock = clock
        self._on_trip = on_trip
        self._metrics = metrics
        self._logger = logger
        self._model_name = model_name
        # Check often enough that a trip is reported well inside 2×bound
        # without burning a core.
        self._interval = (
            check_interval_s
            if check_interval_s is not None
            else max(0.05, min(self.bound_s / 4.0, 1.0))
        )
        self._lock = lockcheck.make_lock("Watchdog._lock")
        self._last_pet = self._clock()
        self._tripped = False
        self._reason = ""
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- scheduler side -------------------------------------------------

    def pet(self) -> None:
        """Progress heartbeat; called once per scheduler loop iteration."""
        # Single float store (GIL-atomic); the monitor tolerates a torn
        # read's staleness of one iteration.
        self._last_pet = self._clock()

    # -- monitor side ---------------------------------------------------

    @property
    def tripped(self) -> bool:
        return self._tripped

    @property
    def reason(self) -> str:
        return self._reason

    def check(self, now: Optional[float] = None) -> bool:
        """Evaluate the bound; returns the (possibly just-latched)
        tripped state. ``now`` overrides the clock for deterministic
        tests."""
        if self._tripped:
            return True
        t = self._clock() if now is None else now
        stalled_for = t - self._last_pet
        if stalled_for > self.bound_s:
            self._trip(
                f"scheduler made no progress for {stalled_for:.1f}s "
                f"(bound {self.bound_s:.1f}s)"
            )
        return self._tripped

    def _trip(self, reason: str) -> None:
        with self._lock:
            if self._tripped:
                return
            self._tripped = True
            self._reason = reason
        if self._logger is not None:
            self._logger.errorf("watchdog tripped: %s", reason)
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_watchdog_trips_total", "model", self._model_name
            )
        # Tracing: a zero-child span marks the trip instant so the stall
        # is findable next to the request spans it wedged.
        try:
            from gofr_tpu.tracing import get_tracer

            span = get_tracer().start_span("tpu-watchdog-trip")
            span.set_attribute("reason", reason)
            span.set_status("ERROR")
            span.end()
        except Exception as exc:  # noqa: BLE001 — tracing must not mask the trip
            if self._logger is not None:
                self._logger.debugf("watchdog trace span failed: %s", exc)
        if self._on_trip is not None:
            self._on_trip(reason)

    def reset(self) -> None:
        """Clear the latch (engine restart)."""
        with self._lock:
            self._tripped = False
            self._reason = ""
        self._last_pet = self._clock()

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._last_pet = self._clock()
        self._thread = threading.Thread(
            target=self._monitor_loop, name="tpu-watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _monitor_loop(self) -> None:
        while not self._stop.wait(self._interval):
            self.check()
            if self._tripped:
                # Latched; nothing more to observe until reset.
                return
