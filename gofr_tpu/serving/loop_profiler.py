"""Continuous scheduler-loop profiler (ISSUE 15).

The observability stack can say *what* happened to a request (PR 6
timelines), *what* the device holds (PR 10 HBM ledger + compile
tracker), and *who* consumed it (PR 11 tenants) — but nothing could say
where a scheduler *pass's* wall time goes. The loop in
``serving/scheduler.py:_scheduler_loop`` runs ~10 distinct phases per
pass (lifecycle reap, ledger tick, brownout tick, radix watermark
sweep, tier-import apply, prefill dispatch, emit flush, window
dispatch, the device-window fetch, idle waits), and "is the TPU idle
because of host bookkeeping?" had no permanent answer — only the manual
``/debug/tpu-trace`` endpoint, which requires an operator to already
know when to look. This module is that answer, always on:

* **Per-phase attribution, exact by construction.** The scheduler
  stamps ONE clock read at each phase boundary of every pass
  (window granularity — never per row; graftlint GL011's discipline,
  and GL019 is the new static twin for hidden device waits inside host
  phases). Each stamp closes the interval since the previous stamp into
  its phase; the residual between the last stamp and the next pass's
  first closes into ``other`` — so the per-phase durations of a pass
  sum to the pass's wall time *exactly* under any clock.
* **The two derived signals.** ``app_tpu_loop_utilization`` — the busy
  fraction of loop wall time over a rolling pass window (1 − idle
  share), and ``app_tpu_loop_host_overhead_ratio`` — the share of
  *busy* time spent outside the designated device-window seam
  (``_process_window``, where the loop legitimately blocks on the
  device). THE "is host bookkeeping starving the TPU" number: high
  utilization + high host ratio = the device waits on Python; every
  bench row now carries it.
* **Stall anomalies, hysteretic.** A pass exceeding ``TPU_LOOP_STALL_S``
  (absolute) or ``TPU_LOOP_STALL_FACTOR`` × the rolling p95 (relative,
  floored so micro-benches don't trip on noise) pins a loop-anomaly
  record — full phase breakdown plus the serving context at that
  instant (queue depth, occupancy, brownout level, HBM headroom) —
  into a bounded ring served on ``/debug/loop``. The detector latches:
  a stall *storm* produces one record per incident, not one per pass,
  and re-arms only after a clean pass (hysteresis in both directions).
  Optionally (``TPU_LOOP_TRACE_MS`` > 0) an anomaly auto-triggers a
  bounded ``jax.profiler`` capture through the
  :mod:`~gofr_tpu.serving.profiler_capture` singleton, cooldown-gated
  so the storm can't thrash the profiler.
* **A stalled pass explains itself.** Every pass also takes the
  scheduler thread's CPU clock, the process's, and the process's
  collector seconds (one ``gc.callbacks`` hook, module-wide), so an
  anomaly record says what the host did in a stall: ``cpu_s`` is the
  thread's CPU seconds in the pass (≈ ``total_s``: it computed; ≈ 0: it
  was off the CPU), ``proc_cpu_s`` every thread's (≈ 0 too: the whole
  process was off the CPU; more than the thread's: others ran, and one
  holding the GIL starves this one), ``gc_s`` the collector's.
  ``next_pass`` (the following pass's phases, filled in when it closes)
  then says on which side of the fetch the stall was: two windows are
  in flight behind the one being fetched, so a following
  ``device_window`` of ~0 means the device had run on through them —
  the result was there and the host did not pick it up — and a full
  window's length means the stalled window itself finished late: the
  device was busy with work queued ahead of it (``context`` says what
  was prefilling) or stood still.
* **Counters the window can difference.**
  ``app_tpu_loop_phase_seconds_total{phase}`` grows by each closed
  pass's phase seconds: the difference of two scrapes is the loop's
  time by phase over exactly the interval between them, which neither
  the last-pass gauge nor the rolling ratio gives.
* **The host's phases in the profiler's trace.** :meth:`LoopProfiler.
  phase` wraps a phase in ``jax.profiler.TraceAnnotation("loop/<phase>")``
  and laps on exit, so a ``/debug/tpu-trace`` capture carries the
  scheduler thread's phases on the same clock as the device's ops.
* **It measures itself.** Summarization/publication work per pass is
  accumulated into ``self_overhead_s`` and reported on ``/debug/loop``
  — the profiler's cost is a number, not a hope. The bench A/B
  (``TPU_LOOP_PROFILE=0``) pins the whole layer's cost.
* **The device's own timeline** (:class:`DeviceTimeline`, PR 37). The
  scheduler registers every program it dispatches (its name, the
  dispatch stamp, one small output no later program donates); one
  daemon watcher thread waits on them in dispatch order, as the device
  runs them, and stamps each ``ready`` on this profiler's clock. From
  the stamps alone: ``start = max(dispatch, previous ready)``,
  ``queued = start - dispatch``, ``device = ready - start``, and the gap
  ``dispatch - previous ready`` in which the device had nothing, put
  down to the loop's phase at that previous ready. A stalled pass's
  record says what the device did inside it.

Off is off: ``TPU_LOOP_PROFILE=0`` builds no profiler — every scheduler
phase runs in one shared no-op context (:func:`loop_phase`), no watcher
thread starts, and the loop is byte-identical to the pre-profiler
scheduler.

Determinism: every mutation takes the timestamp as an argument (the
caller reads the clock once per boundary), so tests drive exact phase
math, stall hysteresis, and ring bounds with stated clocks.
"""

from __future__ import annotations


import contextlib
import gc
import queue
import threading
import time
from collections import deque
from itertools import islice
from typing import Any, Callable, ContextManager, Iterator, Optional

import jax
from jax.profiler import TraceAnnotation

from gofr_tpu.analysis import lockcheck
from gofr_tpu.serving import profiler_capture

#: The bounded phase vocabulary (it appears in metric labels — GL016
#: discipline): the scheduler loop's boundaries, in pass order, plus
#: ``other`` for the residual between the last stamp and the pass end
#: (loop overhead, watchdog pet, fault seams).
PHASES = (
    "reap",           # lifecycle reap (cancel/deadline retirement)
    "ledger",         # tenant-ledger occupancy tick
    "brownout",       # brownout-controller evaluation
    "control",        # control-plane pass (signal sampling + loops)
    "sweep",          # radix-eviction watermark sweep
    "tier_import",    # disaggregated-tier payload apply
    "prefill",        # admission + chunked-prefill dispatch
    "emit_flush",     # prefill first-token emit flush
    "dispatch",       # decode-window dispatch (host-side enqueue)
    "device_window",  # window processing incl. the device fetch wait
    "idle",           # verifiably-idle wait for work
    "other",          # residual: loop overhead between stamps
)

#: The designated device-wait seam: the only phase whose time counts as
#: "the device is working / being waited on". Everything else busy is
#: host overhead. (graftlint GL019 statically pins that no OTHER phase
#: hides a device sync.)
DEVICE_PHASES = frozenset(("device_window",))

#: Phases that are waiting for work, not doing it.
IDLE_PHASES = frozenset(("idle",))

#: Relative (k × p95) stall detection floor: rolling p95s on an idle
#: CPU loop sit in the tens of microseconds, where a page fault would
#: "stall" by any multiplier. Below this absolute floor a pass is never
#: a relative anomaly.
REL_STALL_FLOOR_S = 0.05

#: Minimum rolling samples before the relative detector arms — a p95
#: over three passes is noise, not a baseline.
REL_STALL_MIN_SAMPLES = 16


# -- the collector's pauses, process-wide ------------------------------
#
# One ``gc.callbacks`` hook for the process (installed with the first
# profiler): each collection's wall seconds are added to its
# generation's total. A collection runs under the GIL on whichever
# thread tripped it and stops every Python thread, so the hook needs no
# lock of its own; publication to /metrics (below) does.
_GC_GENERATIONS = 3
_gc_total = [0.0] * _GC_GENERATIONS      # seconds collected, by generation
_gc_published = [0.0] * _GC_GENERATIONS  # ... already added to the counter
_gc_started = 0.0
_gc_lock = lockcheck.make_lock("loop_profiler._gc_lock")


def _on_gc(phase: str, info: dict[str, Any]) -> None:
    global _gc_started
    if phase == "start":
        _gc_started = time.perf_counter()
    elif _gc_started:
        _gc_total[info["generation"]] += time.perf_counter() - _gc_started
        _gc_started = 0.0


def gc_pause_seconds() -> float:
    """Wall seconds the collector has run in this process since the
    hook was installed."""
    return sum(_gc_total)


def _install_gc_hook() -> None:
    with _gc_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def _publish_gc(metrics: Any) -> None:
    """Add what the collector ran since the last publication to
    ``app_tpu_gc_pause_seconds_total``. The total is the process's, so
    whichever engine's loop publishes first takes the delta: replicas
    in one process count each second once."""
    for gen in range(_GC_GENERATIONS):
        if _gc_total[gen] <= _gc_published[gen]:
            continue
        with _gc_lock:
            delta = _gc_total[gen] - _gc_published[gen]
            _gc_published[gen] = _gc_total[gen]
        if delta > 0.0:
            metrics.add_counter(
                "app_tpu_gc_pause_seconds_total", delta,
                "generation", str(gen),
            )


_NO_PHASE: ContextManager[None] = contextlib.nullcontext()


def loop_phase(prof: "Optional[LoopProfiler]", name: str) -> ContextManager[None]:
    """``prof.phase(name)``, or a shared no-op when no profiler was
    built (``TPU_LOOP_PROFILE=0``): the scheduler's phases are ``with``
    blocks either way."""
    return _NO_PHASE if prof is None else prof.phase(name)


def _pctl(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted list (0.0 empty)."""
    if not sorted_vals:
        return 0.0
    idx = min(
        len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1)))
    )
    return sorted_vals[idx]


# -- the device's own timeline -------------------------------------------

#: How long the watcher waits on an empty queue before it looks whether
#: the scheduler thread it serves is still alive.
WATCH_POLL_S = 0.5

#: Device spans (busy or dry) kept for the stall records: thousands of
#: programs, minutes of serving.
DEVICE_SPANS = 4096


class _Watch:
    """One scheduler thread's watcher: its queue, and the timeline's state
    since that thread started (the previous program's ready stamp, the
    loop's phase and idle-wait count then, and the earliest dispatch of
    the programs folded into the next one)."""

    __slots__ = ("queue", "owner", "thread", "superseded", "ready",
                 "phase", "idle_waits", "fold")

    def __init__(self, owner: threading.Thread) -> None:
        self.queue: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        self.owner = owner
        self.thread: Optional[threading.Thread] = None  # the watcher
        self.superseded = False
        self.ready: Optional[float] = None
        self.phase = "other"
        self.idle_waits = 0
        self.fold: Optional[float] = None


class DeviceTimeline:
    """Each dispatched program's queue and device time, and the device's
    dry spells put down to the loop's phase (PR 37).

    The scheduler thread registers a program right after dispatching it
    (:meth:`register`: a queue put). One daemon thread a scheduler thread
    takes them in dispatch order, which is the order one device runs
    them, waits on each one's output (``block_until_ready`` releases the
    GIL) inside ``TraceAnnotation("device_wait/<program>")``, and stamps
    ``ready`` on the profiler's clock. :meth:`settle` derives the rest
    from the stamps alone, so busy plus dry time over a stretch is
    ``ready_last - ready_first`` exactly.

    A program whose outputs are all planes that the next program donates
    has nothing to wait on: it is registered with ``out=None`` and folded
    into the next program the watcher sees, whose interval then starts at
    the folded one's dispatch (the device is not dry while it runs); that
    record counts as busy and feeds no per-program histogram, nor does a
    program whose wait a profiler capture overlapped.

    The watcher lives with the scheduler thread that started it: a newer
    :meth:`start` (the supervisor's restart) supersedes it and it drops
    its queue, and it leaves on its own once its thread is gone. It takes
    no lock the restart takes; its own lock guards the sums it shares
    with ``/debug/loop``."""

    def __init__(
        self,
        model_name: str,
        *,
        metrics: Any = None,
        clock: Callable[[], float] = time.monotonic,
        loop: "Optional[LoopProfiler]" = None,
    ) -> None:
        self.model_name = model_name
        self._metrics = metrics
        self._clock = clock
        self._loop = loop
        self._watch: Optional[_Watch] = None
        self._lock = lockcheck.make_lock("DeviceTimeline._lock")
        # name -> [count, device seconds, queued seconds]
        self._programs: dict[str, list] = {}
        self._idle: dict[str, float] = {}   # dry seconds by loop phase
        self._folded: dict[str, int] = {}
        # (t0, t1, state, cause), oldest first; a stall record reads them.
        self._spans: deque[tuple[float, float, str, str]] = deque(
            maxlen=DEVICE_SPANS
        )
        # (record, start, end): stall records waiting for the device's side.
        self._stalls: list[tuple[dict[str, Any], float, float]] = []

    # -- the scheduler thread's side --------------------------------------

    def start(self) -> _Watch:
        """Start the calling scheduler thread's watcher, superseding any
        earlier one (whose queue is dropped)."""
        old = self._watch
        if old is not None:
            old.superseded = True
            old.queue.put(None)
        w = _Watch(threading.current_thread())
        self._watch = w
        w.thread = threading.Thread(
            target=self._run, args=(w,), name="tpu-device-watch", daemon=True
        )
        w.thread.start()
        return w

    def register(self, name: str, dispatch: float, out: Any) -> None:
        """A program just dispatched: its name, its dispatch stamp, and one
        small output no later program donates (``None``: fold it)."""
        w = self._watch
        if w is not None:
            w.queue.put((name, dispatch, out))

    # -- the watcher ---------------------------------------------------

    def _run(self, w: _Watch) -> None:
        loop = self._loop
        while True:
            try:
                item = w.queue.get(timeout=WATCH_POLL_S)
            except queue.Empty:
                if w.superseded or not w.owner.is_alive():
                    return
                continue
            if item is None or w.superseded:
                return
            name, dispatch, out = item
            # Did the loop wait for work since the previous ready? Then
            # a dry spell before this program was the loop's own idle.
            idled = loop is not None and loop.idle_waits != w.idle_waits
            # A capture's start and stop hold the runtime's completions
            # back (the stop of a 2 s capture froze the engine for 1.0 to
            # 3.2 s on the v5e, PR 37): a program it overlaps is not timed.
            traced = profiler_capture.capturing()
            if out is not None:
                with TraceAnnotation("device_wait/" + name):
                    try:
                        jax.block_until_ready(out)
                    except Exception:  # graftlint: disable=GL006 — a failed or abandoned program has no ready stamp; the scheduler reports the failure
                        out = None
            if out is None:
                self.fold(w, name, dispatch)
                continue
            ready = self._clock()
            if w.superseded:
                return
            phase = "other" if loop is None else loop.current_phase
            self.settle(w, name, dispatch, ready, phase, idled,
                        timed=not (traced or profiler_capture.capturing()))
            if loop is not None:
                w.idle_waits = loop.idle_waits

    def fold(self, w: _Watch, name: str, dispatch: float) -> None:
        """A program with nothing to wait on: the next one seen carries it."""
        if w.fold is None:
            w.fold = dispatch
        with self._lock:
            self._folded[name] = self._folded.get(name, 0) + 1

    def settle(
        self, w: _Watch, name: str, dispatch: float, ready: float,
        phase: str, idled: bool = False, timed: bool = True,
    ) -> None:
        """Program ``name`` dispatched at ``dispatch`` finished at
        ``ready``; the loop was in ``phase`` then, and ``idled`` says
        whether it waited for work since the previous program's ready.
        ``timed`` False (a capture overlapped the wait) keeps the record
        out of the per-program histograms."""
        prev = w.ready
        folded = w.fold is not None
        timed = timed and not folded
        first = w.fold if folded else dispatch
        start = first if prev is None else max(first, prev)
        gap = 0.0 if prev is None else max(0.0, first - prev)
        device = ready - start
        queued = start - dispatch
        cause = "idle" if idled else w.phase
        w.ready, w.phase, w.fold = ready, phase, None
        filled: list = []
        spans: list = []
        with self._lock:
            if timed:
                rec = self._programs.setdefault(name, [0, 0.0, 0.0])
                rec[0] += 1
                rec[1] += device
                rec[2] += queued
            if gap > 0.0:
                self._idle[cause] = self._idle.get(cause, 0.0) + gap
                self._spans.append((prev, start, "idle", cause))
            self._spans.append((start, ready, "busy", name))
            if self._stalls:
                filled = [s for s in self._stalls if s[2] <= ready]
                if filled:
                    self._stalls = [s for s in self._stalls if s[2] > ready]
                    spans = list(self._spans)
        for record, s0, s1 in filled:
            _fill_stall(record, spans, s0, s1)
        m = self._metrics
        if m is None:
            return
        model = self.model_name
        if timed:
            m.record_histogram(
                "app_tpu_program_device_seconds", device,
                "model", model, "program", name,
            )
            m.record_histogram(
                "app_tpu_program_queued_seconds", queued,
                "model", model, "program", name,
            )
        if device > 0.0:
            m.add_counter(
                "app_tpu_device_seconds_total", device,
                "model", model, "state", "busy", "cause", name,
            )
        if gap > 0.0:
            m.add_counter(
                "app_tpu_device_seconds_total", gap,
                "model", model, "state", "idle", "cause", cause,
            )

    # -- readers -------------------------------------------------------

    def note_stall(self, record: dict[str, Any], start: float,
                   end: float) -> None:
        """A stalled pass ``[start, end]``: its record's ``device_busy_s``,
        ``device_idle_s`` and ``device_idle_cause`` are filled once the
        device's timeline has passed ``end``."""
        with self._lock:
            self._stalls.append((record, start, end))

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "programs": {
                    name: {"count": n, "device_s": round(dev, 6),
                           "queued_s": round(q, 6)}
                    for name, (n, dev, q) in self._programs.items()
                },
                "idle_s": {k: round(v, 6) for k, v in self._idle.items()},
                "folded": dict(self._folded),
            }


def _fill_stall(record: dict[str, Any], spans: list, start: float,
                end: float) -> None:
    """What the device did inside ``[start, end]``, from its spans."""
    busy = 0.0
    idle: dict[str, float] = {}
    for t0, t1, state, cause in spans:
        overlap = min(t1, end) - max(t0, start)
        if overlap <= 0.0:
            continue
        if state == "busy":
            busy += overlap
        else:
            idle[cause] = idle.get(cause, 0.0) + overlap
    record["device_idle_cause"] = {k: round(v, 6) for k, v in idle.items()}
    record["device_idle_s"] = round(sum(idle.values()), 6)
    record["device_busy_s"] = round(busy, 6)


class LoopProfiler:
    """Per-phase time attribution + stall detection for one engine's
    scheduler loop. Written by the scheduler thread only (``begin_pass``
    / ``lap``); ``snapshot``/``describe`` read under a lock from ops
    threads. See the module docstring."""

    def __init__(
        self,
        model_name: str,
        *,
        stall_s: float = 1.0,
        stall_factor: float = 10.0,
        window: int = 256,
        anomaly_records: int = 64,
        trace_ms: int = 0,
        capture: Any = None,
        metrics: Any = None,
        logger: Any = None,
        perf: Callable[[], float] = time.perf_counter,
        clock: Callable[[], float] = time.monotonic,
        thread_time: Callable[[], float] = time.thread_time,
        process_time: Callable[[], float] = time.process_time,
        gc_seconds: Callable[[], float] = gc_pause_seconds,
    ) -> None:
        self.model_name = model_name
        #: Absolute stall bound (seconds; 0 disables the absolute arm).
        self.stall_s = max(0.0, float(stall_s))
        #: Relative stall bound: k × the rolling p95 of pass wall times
        #: (0 disables the relative arm).
        self.stall_factor = max(0.0, float(stall_factor))
        self.trace_ms = max(0, int(trace_ms))
        self._capture = capture
        self._metrics = metrics
        self._logger = logger
        self._perf = perf
        #: The clock :meth:`phase` laps with: the one whose readings
        #: the scheduler hands to ``begin_pass`` / ``lap``.
        self._clock = clock
        #: The calling (scheduler) thread's CPU seconds, the process's
        #: (every thread's), and the process's collector seconds: read
        #: once per pass.
        self._thread_time = thread_time
        self._process_time = process_time
        self._gc_seconds = gc_seconds
        if gc_seconds is gc_pause_seconds:
            _install_gc_hook()
        #: Serving-context callback for anomaly records (queue depth,
        #: occupancy, brownout level, HBM headroom) — installed by the
        #: engine, invoked on the scheduler thread at the stall instant.
        self.context: Optional[Callable[[], dict[str, Any]]] = None
        #: Compile-counter callback (the PR 10 tracker's ``total``):
        #: a pass during which XLA compiled is attributed by the
        #: compile tracker (warm-up compiles are expected; steady-state
        #: recompiles already warn and count) — it must not ALSO pin a
        #: loop-stall anomaly, or every boot would open with one.
        self.compiles: Optional[Callable[[], int]] = None
        self._last_compiles = 0
        self._lock = lockcheck.make_lock("LoopProfiler._lock")
        #: The phase the scheduler thread is in (``other`` between
        #: phases) and how many idle waits it began: the device
        #: timeline's watcher reads both when the device runs dry.
        self.current_phase = "other"
        self.idle_waits = 0
        #: The device's own timeline (its watcher starts with the
        #: scheduler thread: :meth:`DeviceTimeline.start`).
        self.device = DeviceTimeline(
            model_name, metrics=metrics, clock=clock, loop=self
        )
        # Current-pass accumulation (scheduler thread only — no lock).
        self._pass_start: Optional[float] = None
        self._last_stamp = 0.0
        self._acc: dict[str, float] = {}
        self._cpu_start = 0.0
        self._proc_start = 0.0
        self._gc_start = 0.0
        # The newest anomaly, until the pass after it closes and fills
        # in its ``next_pass``.
        self._awaits_next: Optional[dict[str, Any]] = None
        # Rolling state (under the lock).
        window = max(8, int(window))
        self.passes = 0
        self.stalls = 0
        self.self_overhead_s = 0.0
        self._phase_count: dict[str, int] = {p: 0 for p in PHASES}
        self._phase_total: dict[str, float] = {p: 0.0 for p in PHASES}
        self._phase_last: dict[str, float] = {p: 0.0 for p in PHASES}
        self._phase_window: dict[str, deque[float]] = {
            p: deque(maxlen=window) for p in PHASES
        }
        #: Rolling (total, idle, device) per pass — the utilization /
        #: host-overhead window and the relative detector's baseline.
        self._pass_window: deque[tuple[float, float, float]] = deque(
            maxlen=window
        )
        # Running window sums, maintained on append/evict so the
        # per-pass utilization/host-ratio reads are O(1) instead of
        # re-summing the window inside the lock on the hot loop; they
        # re-sync exactly from the deque once per window's worth of
        # passes to bound float drift.
        self._sum_total = 0.0
        self._sum_idle = 0.0
        self._sum_device = 0.0
        self._since_resync = 0
        # Anomaly rings: absolute-threshold stalls PIN (they survive a
        # burst of relative anomalies); relative ones ride the rolling
        # ring. Both bounded.
        anomaly_records = max(1, int(anomaly_records))
        self._anomalies: deque[dict[str, Any]] = deque(
            maxlen=anomaly_records
        )
        self._pinned: deque[dict[str, Any]] = deque(
            maxlen=max(1, anomaly_records // 4)
        )
        # Stall hysteresis latch: an incident records ONE anomaly; the
        # detector re-arms only after a pass below both thresholds, so
        # a storm of consecutive stalled passes cannot flood the ring
        # (the window/latch pair is this detector's hysteresis).
        self._stall_latched = False

    # -- scheduler-thread stamps (timestamps passed in) -----------------

    def begin_pass(self, now: float) -> None:
        """Start a pass — and close the previous one (its residual
        since the last stamp lands in ``other``, so per-phase durations
        sum to pass wall time exactly)."""
        cpu, proc = self._thread_time(), self._process_time()
        gc_s = self._gc_seconds()
        if self._pass_start is not None:
            self._close_pass(
                now, cpu - self._cpu_start, proc - self._proc_start,
                gc_s - self._gc_start,
            )
        self._pass_start = now
        self._last_stamp = now
        self._acc = {}
        self._cpu_start, self._proc_start, self._gc_start = cpu, proc, gc_s

    def lap(self, phase: str, now: float) -> None:
        """Attribute the interval since the previous stamp to
        ``phase``. One clock read per boundary, shared — never per row."""
        if self._pass_start is None:
            return
        self._acc[phase] = self._acc.get(phase, 0.0) + max(
            0.0, now - self._last_stamp
        )
        self._last_stamp = now

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Run a loop phase inside ``TraceAnnotation("loop/<name>")``
        on the calling thread and lap it on exit: one clock read per
        boundary, and the profiler's capture shows the phase on a host
        line beside the device's ops."""
        outer = self.current_phase
        self.current_phase = name
        if name == "idle":
            self.idle_waits += 1
        try:
            with TraceAnnotation("loop/" + name):
                yield
                # (Not reached when the body raises: a thread the
                # supervisor abandoned must not stamp the pass of the one
                # after it.)
                self.lap(name, self._clock())
        finally:
            self.current_phase = outer

    def dispatched(self, name: str, out: Any,
                   at: Optional[float] = None) -> None:
        """The scheduler dispatched program ``name`` (``out``: one small
        output no later program donates, or ``None`` to fold it into the
        next). ``at`` is its dispatch stamp; by default the last phase
        boundary's, which the ``dispatch`` phase stamps as the window's
        call returns."""
        self.device.register(name, self._last_stamp if at is None else at,
                             out)

    # -- pass summarization --------------------------------------------

    def _close_pass(
        self, now: float, cpu_s: float, proc_cpu_s: float, gc_s: float
    ) -> None:
        o0 = self._perf()
        start = self._pass_start
        assert start is not None
        total = max(0.0, now - start)
        residual = max(0.0, now - self._last_stamp)
        acc = self._acc
        if residual > 0.0:
            acc["other"] = acc.get("other", 0.0) + residual
        idle = acc.get("idle", 0.0)
        device = sum(acc.get(p, 0.0) for p in DEVICE_PHASES)
        anomaly: Optional[dict[str, Any]] = None
        kind = ""
        threshold = 0.0
        phases_s = {p: round(acc[p], 6) for p in PHASES if p in acc}
        with self._lock:
            self.passes += 1
            if self._awaits_next is not None:
                # This pass followed a stalled one: its phases say
                # what the device did during the stall.
                self._awaits_next["next_pass"] = phases_s
                self._awaits_next = None
            for p in PHASES:
                v = acc.get(p)
                if v is None:
                    self._phase_last[p] = 0.0
                    continue
                self._phase_count[p] += 1
                self._phase_total[p] += v
                self._phase_last[p] = v
                self._phase_window[p].append(v)
            # Maintain the running window sums across the append (and
            # the eviction it causes once the deque is full) — O(1).
            if len(self._pass_window) == self._pass_window.maxlen:
                ot, oi, od = self._pass_window[0]
                self._sum_total -= ot
                self._sum_idle -= oi
                self._sum_device -= od
            self._pass_window.append((total, idle, device))
            self._sum_total += total
            self._sum_idle += idle
            self._sum_device += device
            self._since_resync += 1
            if self._since_resync >= (self._pass_window.maxlen or 1):
                # Exact re-sync once per window of passes: amortized
                # O(1), bounds subtract-drift on the running sums.
                self._since_resync = 0
                self._sum_total = sum(t for t, _, _ in self._pass_window)
                self._sum_idle = sum(i for _, i, _ in self._pass_window)
                self._sum_device = sum(
                    d for _, _, d in self._pass_window
                )
            compiled = False
            if self.compiles is not None:
                n = int(self.compiles())
                compiled = n != self._last_compiles
                self._last_compiles = n
            if compiled:
                # XLA compiled during this pass: the time is the compile
                # tracker's to attribute (app_tpu_compile_seconds, the
                # steady-state recompile counter) — never a loop stall.
                pass
            elif self.stall_s > 0.0 and total >= self.stall_s:
                kind, threshold = "absolute", self.stall_s
            elif (
                self.stall_factor > 0.0
                and total >= REL_STALL_FLOOR_S
                and len(self._pass_window) - 1 >= REL_STALL_MIN_SAMPLES
            ):
                # The sort is the expensive part — it only runs for
                # passes already over the relative floor (no sub-floor
                # pass can be a relative stall), so sub-ms steady-state
                # passes never pay it. Baseline excludes this pass (the
                # deque's LAST entry): a stall is judged against the
                # passes that preceded it.
                baseline = sorted(
                    t for t, _, _ in islice(
                        self._pass_window, len(self._pass_window) - 1
                    )
                )
                rel = max(
                    self.stall_factor * _pctl(baseline, 0.95),
                    REL_STALL_FLOOR_S,
                )
                if total >= rel:
                    kind, threshold = "p95", rel
            if kind and not self._stall_latched:
                # New incident: latch (one record per incident — a
                # storm of stalled passes re-arms only after a clean
                # pass, the hysteresis window in the other direction).
                self._stall_latched = True
                self.stalls += 1
                anomaly = {
                    "pass": self.passes,
                    "kind": kind,
                    "total_s": round(total, 6),
                    "threshold_s": round(threshold, 6),
                    "phases": phases_s,
                    # CPU seconds in the pass: the scheduler thread's
                    # (≈ total_s: it computed; ≈ 0: it was off the
                    # CPU), every thread's of the process (≈ 0 too: the
                    # whole process was), and the collector's seconds.
                    "cpu_s": round(max(0.0, cpu_s), 6),
                    "proc_cpu_s": round(max(0.0, proc_cpu_s), 6),
                    "gc_s": round(max(0.0, gc_s), 6),
                    "next_pass": None,  # until the next pass closes
                    # What the device did inside the pass: busy and dry
                    # seconds, the dry ones by the loop's phase, filled
                    # in once the device's timeline has passed its end.
                    "device_busy_s": None,
                    "device_idle_s": None,
                    "device_idle_cause": None,
                }
                self._awaits_next = anomaly
            elif not kind:
                self._stall_latched = False
            util = self._utilization_locked()
            host = self._host_overhead_locked()
        if anomaly is not None:
            self.device.note_stall(anomaly, start, now)
            self._record_anomaly(anomaly)
        if self._metrics is not None:
            self._publish(acc, util, host)
        self.self_overhead_s += max(0.0, self._perf() - o0)

    def _record_anomaly(self, anomaly: dict[str, Any]) -> None:
        """Pin the record (context snapshot + optional device-trace
        trigger run outside the stats lock — the context callback reads
        engine state and the capture takes its own locks)."""
        if self.context is not None:
            try:
                anomaly["context"] = self.context()
            except Exception:  # noqa: BLE001  # graftlint: disable=GL006 — diagnostic enrichment; the record must land even when a context read races shutdown
                pass
        captured = False
        if self._capture is not None and self.trace_ms > 0:
            captured = bool(self._capture.trigger(
                self.trace_ms, reason=f"loop-stall:{anomaly['kind']}"
            ))
        anomaly["trace_captured"] = captured
        with self._lock:
            if anomaly["kind"] == "absolute":
                self._pinned.append(anomaly)
            else:
                self._anomalies.append(anomaly)
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_loop_stalls_total",
                "model", self.model_name, "kind", anomaly["kind"],
            )
        if self._logger is not None:
            self._logger.warnf(
                "scheduler-loop stall (%s): pass %d took %.3fs "
                "(threshold %.3fs); phases=%s trace_captured=%s",
                anomaly["kind"], anomaly["pass"], anomaly["total_s"],
                anomaly["threshold_s"], anomaly["phases"], captured,
            )

    def _publish(
        self, acc: dict[str, float], util: float, host: float
    ) -> None:
        """Refresh the loop gauges from the just-closed pass (every
        phase publishes, 0.0 when absent, so the exported set always
        sums to the pass wall time) and grow the counters by it."""
        m = self._metrics
        for p in PHASES:
            v = acc.get(p)
            m.set_gauge(
                "app_tpu_loop_phase_seconds", v or 0.0,
                "model", self.model_name, "phase", p,
            )
            if v is not None:
                # The counter twin: two scrapes' difference is the
                # loop's time by phase over exactly that interval.
                m.add_counter(
                    "app_tpu_loop_phase_seconds_total", v,
                    "model", self.model_name, "phase", p,
                )
        _publish_gc(m)
        m.set_gauge(
            "app_tpu_loop_utilization", util, "model", self.model_name
        )
        m.set_gauge(
            "app_tpu_loop_host_overhead_ratio", host,
            "model", self.model_name,
        )

    # -- derived signals ------------------------------------------------

    def _utilization_locked(self) -> float:
        if self._sum_total <= 0.0:
            return 0.0
        return max(
            0.0, min(1.0, 1.0 - self._sum_idle / self._sum_total)
        )

    def _host_overhead_locked(self) -> float:
        busy = self._sum_total - self._sum_idle
        if busy <= 0.0:
            return 0.0
        return max(
            0.0, min(1.0, (busy - self._sum_device) / busy)
        )

    def utilization(self) -> float:
        """Busy fraction of loop wall time over the rolling window."""
        with self._lock:
            return self._utilization_locked()

    def host_overhead_ratio(self) -> float:
        """Share of busy time outside the device-window seam — THE
        "is host bookkeeping starving the TPU" signal."""
        with self._lock:
            return self._host_overhead_locked()

    def phase_p50_ms(self) -> dict[str, float]:
        """Rolling per-phase p50 in ms (present phases only) — the
        bench JSON field."""
        with self._lock:
            out: dict[str, float] = {}
            for p in PHASES:
                win = self._phase_window[p]
                if win:
                    out[p] = round(_pctl(sorted(win), 0.50) * 1e3, 4)
            return out

    # -- rendering -----------------------------------------------------

    def describe(self) -> dict[str, Any]:
        """The compact advertisement (health details, capacity_report,
        the flight-record headline — the headroom idiom)."""
        with self._lock:
            return {
                "passes": self.passes,
                "stalls": self.stalls,
                "utilization": round(self._utilization_locked(), 6),
                "host_overhead_ratio": round(
                    self._host_overhead_locked(), 6
                ),
            }

    def snapshot(self) -> dict[str, Any]:
        """The full ``/debug/loop`` form: per-phase rolling stats,
        derived signals, stall thresholds, anomaly rings, the
        profiler's own measured overhead, and the capture singleton's
        state when auto-trace is armed."""
        with self._lock:
            phases: dict[str, Any] = {}
            for p in PHASES:
                if not self._phase_count[p]:
                    continue
                win = sorted(self._phase_window[p])
                phases[p] = {
                    "count": self._phase_count[p],
                    "total_s": round(self._phase_total[p], 6),
                    "last_s": round(self._phase_last[p], 6),
                    "p50_ms": round(_pctl(win, 0.50) * 1e3, 4),
                    "p95_ms": round(_pctl(win, 0.95) * 1e3, 4),
                }
            out: dict[str, Any] = {
                "enabled": True,
                "passes": self.passes,
                "stalls": self.stalls,
                "utilization": round(self._utilization_locked(), 6),
                "host_overhead_ratio": round(
                    self._host_overhead_locked(), 6
                ),
                "stall_s": self.stall_s,
                "stall_factor": self.stall_factor,
                "window": len(self._pass_window),
                "self_overhead_s": round(self.self_overhead_s, 6),
                "phases": phases,
                "anomalies": list(self._anomalies),
                "pinned_anomalies": list(self._pinned),
            }
        out["device"] = self.device.snapshot()
        if self._capture is not None and self.trace_ms > 0:
            out["trace"] = dict(self._capture.snapshot())
            out["trace_ms"] = self.trace_ms
        return out
