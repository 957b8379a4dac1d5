"""Scheduler-loop profiler suite (ISSUE 15 acceptance gate).

Deterministic throughout: the profiler takes every timestamp as an
argument (the scheduler's one-clock-read-per-boundary contract), so
phase math, stall hysteresis, and ring bounds are driven with stated
clocks; the capture singleton's cooldown runs with injected clock /
start / stop / spawn. Engine-level tests use one small module-scoped
engine on the CPU backend.

Covered:

* per-phase durations of a pass sum to its wall time EXACTLY under
  stated clocks (residual in ``other``), and the exported
  ``app_tpu_loop_phase_seconds{phase}`` gauges sum to it too;
* utilization (busy fraction) and host-overhead-ratio (busy share
  outside the device-window seam) arithmetic;
* stall detection: absolute bound, k×p95 relative bound (floored,
  armed only past the minimum sample count), hysteresis in BOTH
  directions — a storm of stalled passes pins exactly one record,
  re-arming only after a clean pass;
* compile-pass exemption: a pass during which the compile counter grew
  is the compile tracker's to attribute, never a loop stall;
* a stalled pass explains itself: its record carries the scheduler
  thread's CPU seconds, the process's, the collector's seconds inside
  the pass and, once the following pass closes, that pass's phases;
* ``app_tpu_loop_phase_seconds_total{phase}`` grows by each closed
  pass's phase seconds, so counter deltas over N passes are those
  passes' wall time exactly; ``app_tpu_gc_pause_seconds_total`` counts
  each collected second of the process once;
* ``phase()`` laps once per boundary on the profiler's own clock, skips
  the lap when its body raises, and is inert when no profiler is built;
* the anomaly ring is bounded and absolute-stall records are PINNED —
  they survive a burst of relative anomalies;
* trace-capture cooldown: a stall storm triggers at most one capture
  per cooldown (suppressions counted), the capture slot is exclusive,
  and :func:`get_capture` is a race-free singleton (the /debug/
  tpu-trace lazy-init fix);
* layer-off (``TPU_LOOP_PROFILE=0``): no profiler object, no hooks, a
  byte-identical greedy stream;
* advertisement: health details / capacity_report / flight_records
  headline / pool ``loop_report`` all carry the loop stats;
* the device's own timeline (PR 37): queued, device and dry seconds from
  stated dispatch and ready stamps, a dry spell put down to the loop's
  phase at the previous ready (or to its own wait for work), busy plus dry
  equal to the stretch between the first and the last ready exactly, a
  folded program, a stalled pass's device seconds, the watcher's thread
  bound to its scheduler thread (an abandoned one blocks no restart), and
  none of it with the layer off.
"""

from __future__ import annotations

import threading
import time

import pytest

from gofr_tpu.metrics import Manager
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving import loop_profiler
from gofr_tpu.serving.loop_profiler import (
    PHASES,
    REL_STALL_FLOOR_S,
    REL_STALL_MIN_SAMPLES,
    DeviceTimeline,
    LoopProfiler,
    _Watch,
    loop_phase,
)
from gofr_tpu.serving.profiler_capture import ProfilerCapture
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.replica_pool import EngineReplica, ReplicaPool


def loop_metrics() -> Manager:
    m = Manager()
    for name in (
        "app_tpu_loop_phase_seconds",
        "app_tpu_loop_utilization",
        "app_tpu_loop_host_overhead_ratio",
    ):
        m.new_gauge(name)
    for name in (
        "app_tpu_loop_stalls_total",
        "app_tpu_loop_phase_seconds_total",
        "app_tpu_gc_pause_seconds_total",
        "app_tpu_device_seconds_total",
    ):
        m.new_counter(name)
    for name in TIMELINE_HISTOGRAMS:
        m.new_histogram(name, "", (0.01, 0.1, 1.0))
    return m


#: The timeline's histograms; with ``app_tpu_device_seconds_total`` the
#: four series the layer mints.
TIMELINE_HISTOGRAMS = (
    "app_tpu_program_device_seconds",
    "app_tpu_program_queued_seconds",
    "app_tpu_token_handoff_seconds",
)


def hist(m: Manager, name: str, **labels: str) -> tuple[float, int]:
    """(sum, count) of a histogram over the series that carry ``labels``."""
    inst = [i for i in m.instruments() if i.name == name]
    total, count = 0.0, 0
    want = set(labels.items())
    for key, (_, (s, c)) in (inst[0].collect().items() if inst else ()):
        if want <= set(key):
            total, count = total + s, count + c
    return total, count


def gauge_values(m: Manager, name: str) -> dict:
    inst = [i for i in m.instruments() if i.name == name]
    return dict(inst[0].collect()) if inst else {}


def counter_value(m: Manager, name: str, **labels: str) -> float:
    inst = [i for i in m.instruments() if i.name == name]
    if not inst:
        return 0.0
    want = set(labels.items())
    return sum(
        v for k, v in inst[0].collect().items() if want <= set(k)
    )


def make_prof(**kw) -> LoopProfiler:
    defaults = dict(stall_s=1.0, stall_factor=0.0, anomaly_records=8)
    defaults.update(kw)
    return LoopProfiler("m", **defaults)


def drive_pass(
    prof: LoopProfiler, t0: float, laps: list[tuple[str, float]],
    t_end: float,
) -> None:
    """One full pass under stated clocks: lap each (phase, at) stamp
    and close the pass by beginning the next at ``t_end`` — exactly
    the scheduler's shape, where one ``begin_pass`` both closes pass N
    and opens pass N+1 (calling begin twice would interleave a
    zero-length pass and re-arm the stall latch)."""
    if prof._pass_start is None:
        prof.begin_pass(t0)
    else:
        assert prof._pass_start == pytest.approx(t0), (
            "non-contiguous stated clocks"
        )
    for phase, at in laps:
        prof.lap(phase, at)
    prof.begin_pass(t_end)


# ----------------------------------------------------------------------
# phase math
# ----------------------------------------------------------------------


def test_phase_durations_sum_to_pass_wall_exactly():
    m = loop_metrics()
    prof = make_prof(metrics=m)
    # Pass wall = 1.0s: reap 0.1, ledger 0.2, prefill 0.3,
    # device_window 0.25, residual 0.15 → "other".
    drive_pass(
        prof, 10.0,
        [("reap", 10.1), ("ledger", 10.3), ("prefill", 10.6),
         ("device_window", 10.85)],
        11.0,
    )
    snap = prof.snapshot()
    assert snap["passes"] == 1
    phases = snap["phases"]
    assert phases["reap"]["total_s"] == pytest.approx(0.1)
    assert phases["ledger"]["total_s"] == pytest.approx(0.2)
    assert phases["prefill"]["total_s"] == pytest.approx(0.3)
    assert phases["device_window"]["total_s"] == pytest.approx(0.25)
    assert phases["other"]["total_s"] == pytest.approx(0.15)
    assert sum(p["total_s"] for p in phases.values()) == pytest.approx(
        1.0
    )
    # The exported gauges carry the SAME per-pass attribution: the
    # phase gauges (absent phases publish 0.0) sum to pass wall time.
    vals = gauge_values(m, "app_tpu_loop_phase_seconds")
    assert len(vals) == len(PHASES)
    assert sum(vals.values()) == pytest.approx(1.0)


def test_phase_counter_deltas_are_the_passes_wall_time_exactly():
    """What a benchmark window does: scrape, wait, scrape, subtract."""
    m = loop_metrics()
    prof = make_prof(metrics=m, stall_s=0.0)
    name = "app_tpu_loop_phase_seconds_total"
    # Binary fractions: every sum below is exact.
    drive_pass(prof, 0.0, [("prefill", 0.25), ("idle", 1.0)], 1.0)
    scrape0 = {p: counter_value(m, name, phase=p) for p in PHASES}
    assert sum(scrape0.values()) == 1.0  # the pass before the window
    passes = 5
    for i in range(passes):
        t = 1.0 + 0.5 * i  # 0.5 s a pass: 0.125 host, 0.25 device, rest other
        drive_pass(
            prof, t,
            [("reap", t + 0.0625), ("dispatch", t + 0.125),
             ("device_window", t + 0.375)],
            t + 0.5,
        )
    delta = {p: counter_value(m, name, phase=p) - scrape0[p] for p in PHASES}
    assert sum(delta.values()) == passes * 0.5  # the window's wall time
    assert delta["device_window"] == passes * 0.25
    assert delta["idle"] == 0.0 and delta["prefill"] == 0.0
    # The window's host share, from counters alone: busy time outside
    # the device-window seam over busy time.
    busy = sum(v for p, v in delta.items() if p != "idle")
    assert (busy - delta["device_window"]) / busy == 0.5
    # A phase that never ran has no series: a reader sums what is there.
    inst = [i for i in m.instruments() if i.name == name][0]
    assert {dict(k)["phase"] for k in inst.collect()} == {
        "prefill", "idle", "reap", "dispatch", "device_window", "other",
    }


def test_multiple_laps_accumulate_within_a_pass():
    prof = make_prof()
    # tier_import laps twice in one pass (the wave-admission loop).
    drive_pass(
        prof, 0.0,
        [("tier_import", 0.1), ("prefill", 0.2), ("tier_import", 0.4)],
        0.5,
    )
    phases = prof.snapshot()["phases"]
    assert phases["tier_import"]["total_s"] == pytest.approx(0.3)
    assert phases["tier_import"]["count"] == 1  # one PASS touched it
    assert sum(p["total_s"] for p in phases.values()) == pytest.approx(
        0.5
    )


def test_lap_before_begin_is_a_noop():
    prof = make_prof()
    prof.lap("reap", 5.0)
    assert prof.snapshot()["passes"] == 0


# ----------------------------------------------------------------------
# utilization / host-overhead arithmetic
# ----------------------------------------------------------------------


def test_utilization_and_host_overhead_ratio_arithmetic():
    m = loop_metrics()
    prof = make_prof(metrics=m, stall_s=0.0)
    # Pass 1: 1.0s total, 0.4 idle → busy 0.6, of which 0.45 device.
    drive_pass(
        prof, 0.0,
        [("prefill", 0.15), ("device_window", 0.6), ("idle", 1.0)],
        1.0,
    )
    # Pass 2: 1.0s total, fully idle.
    drive_pass(prof, 1.0, [("idle", 2.0)], 2.0)
    # Window: total 2.0, idle 1.4 → utilization 0.3;
    # busy 0.6, device 0.45 → host overhead (0.6-0.45)/0.6 = 0.25.
    assert prof.utilization() == pytest.approx(0.3)
    assert prof.host_overhead_ratio() == pytest.approx(0.25)
    util = gauge_values(m, "app_tpu_loop_utilization")
    host = gauge_values(m, "app_tpu_loop_host_overhead_ratio")
    assert list(util.values())[0] == pytest.approx(0.3)
    assert list(host.values())[0] == pytest.approx(0.25)


def test_all_idle_window_reads_zero_utilization_and_host():
    prof = make_prof(stall_s=0.0)
    drive_pass(prof, 0.0, [("idle", 1.0)], 1.0)
    assert prof.utilization() == 0.0
    assert prof.host_overhead_ratio() == 0.0  # no busy time to blame


# ----------------------------------------------------------------------
# stall detection + hysteresis
# ----------------------------------------------------------------------


def test_absolute_stall_pins_exactly_one_record_per_incident():
    m = loop_metrics()
    prof = make_prof(stall_s=1.0, metrics=m)
    ctx_reads = []
    prof.context = lambda: (ctx_reads.append(1) or {"queue_depth": 7})
    # A fast pass, then THE deliberately-stalled pass.
    drive_pass(prof, 0.0, [("prefill", 0.01)], 0.01)
    drive_pass(prof, 0.01, [("prefill", 2.0)], 2.01)
    snap = prof.snapshot()
    assert snap["stalls"] == 1
    assert len(snap["pinned_anomalies"]) == 1
    rec = snap["pinned_anomalies"][0]
    assert rec["kind"] == "absolute"
    assert rec["total_s"] == pytest.approx(2.0)
    assert rec["phases"]["prefill"] == pytest.approx(1.99)
    assert rec["context"] == {"queue_depth": 7}
    assert ctx_reads == [1]
    assert counter_value(
        m, "app_tpu_loop_stalls_total", kind="absolute"
    ) == 1
    # Hysteresis: a STORM of stalled passes is one incident — the
    # detector stays latched until a clean pass re-arms it.
    drive_pass(prof, 2.01, [("prefill", 4.5)], 4.51)
    drive_pass(prof, 4.51, [("prefill", 7.0)], 7.01)
    assert prof.snapshot()["stalls"] == 1
    # Clean pass → re-armed → the next stall is a NEW incident.
    drive_pass(prof, 7.01, [("prefill", 7.02)], 7.02)
    drive_pass(prof, 7.02, [("prefill", 9.5)], 9.52)
    snap = prof.snapshot()
    assert snap["stalls"] == 2
    assert len(snap["pinned_anomalies"]) == 2


def test_stalled_pass_says_what_it_was_and_what_followed():
    cpu, proc, collected = [0.0], [0.0], [0.0]
    prof = make_prof(
        stall_s=1.0, thread_time=lambda: cpu[0], process_time=lambda: proc[0],
        gc_seconds=lambda: collected[0],
    )

    def one_pass(t0, laps, t_end, cpu_s, gc_s):
        if prof._pass_start is None:
            prof.begin_pass(t0)
        for phase, at in laps:
            prof.lap(phase, at)
        cpu[0] += cpu_s
        proc[0] += 4 * cpu_s  # three other threads as busy as this one
        collected[0] += gc_s
        prof.begin_pass(t_end)

    one_pass(0.0, [("device_window", 0.25)], 0.25, 0.03125, 0.0)
    # The stalled pass: 4 s waiting on a window's fetch, the thread
    # blocked (CPU 0.0625 s), the collector idle but for 0.125 s.
    one_pass(0.25, [("dispatch", 0.5), ("device_window", 4.25)], 4.25,
             0.0625, 0.125)
    rec = prof.snapshot()["pinned_anomalies"][0]
    assert rec["total_s"] == 4.0 and rec["phases"]["device_window"] == 3.75
    assert rec["cpu_s"] == 0.0625 and rec["gc_s"] == 0.125
    assert rec["proc_cpu_s"] == 0.25  # every thread's, this one's in it
    assert rec["next_pass"] is None  # the pass after it is still open
    # With two windows in flight the next one was dispatched before the
    # stalled fetch: its own fetch returning at once says the device
    # worked through the stall.
    one_pass(4.25, [("dispatch", 4.5), ("device_window", 4.5078125)],
             4.5078125, 0.25, 0.0)
    rec = prof.snapshot()["pinned_anomalies"][0]
    assert rec["next_pass"] == {"dispatch": 0.25, "device_window": 0.007812}
    # Only the pass right after the stall is recorded, and once.
    one_pass(4.5078125, [("device_window", 5.0)], 5.0, 0.0, 0.0)
    assert prof.snapshot()["pinned_anomalies"][0]["next_pass"] == rec["next_pass"]
    # The same record in a host-bound stall: the CPU clock ran all along.
    one_pass(5.0, [("prefill", 8.0)], 8.0, 2.875, 1.5)
    host_bound = prof.snapshot()["pinned_anomalies"][1]
    assert host_bound["cpu_s"] == 2.875 and host_bound["gc_s"] == 1.5


def test_collector_seconds_are_counted_once_for_the_process():
    import gc

    m = loop_metrics()
    a, b = make_prof(metrics=m, stall_s=0.0), make_prof(metrics=m, stall_s=0.0)
    assert gc.callbacks.count(loop_profiler._on_gc) == 1  # one hook, two profilers
    name = "app_tpu_gc_pause_seconds_total"
    drive_pass(a, 0.0, [("idle", 1.0)], 1.0)  # publishes what ran so far
    before = counter_value(m, name)
    collected0 = loop_profiler.gc_pause_seconds()
    junk = [[i] for i in range(20000)]
    junk.append(junk)
    del junk
    gc.collect()
    collected = loop_profiler.gc_pause_seconds() - collected0
    assert collected > 0.0
    drive_pass(a, 1.0, [("idle", 2.0)], 2.0)
    drive_pass(b, 0.0, [("idle", 1.0)], 1.0)  # the second engine's loop
    drive_pass(a, 2.0, [("idle", 3.0)], 3.0)
    # Both loops published; each collected second was added once. (More
    # may have run since: collections of the passes themselves.)
    grown = counter_value(m, name) - before
    assert collected <= grown + 1e-12
    assert grown <= loop_profiler.gc_pause_seconds() - collected0 + 1e-12
    assert counter_value(m, name, generation="2") > 0.0
    # ... and a pass sees what ran inside it, on this thread's CPU clock.
    c = make_prof(stall_s=0.0001, stall_factor=0.0)
    c.begin_pass(0.0)
    gc.collect()
    c.lap("prefill", 1.0)
    c.begin_pass(1.0)
    rec = c.snapshot()["pinned_anomalies"][0]
    # Two clocks, each read twice and rounded to the microsecond: the
    # process's may come out a tick under this thread's.
    assert rec["gc_s"] > 0.0 and rec["proc_cpu_s"] >= rec["cpu_s"] - 1e-5 > 0.0


def test_phase_context_laps_once_per_boundary_on_its_own_clock():
    t = [0.0]
    reads = []

    def clock():
        reads.append(t[0])
        return t[0]

    prof = make_prof(stall_s=0.0, clock=clock)
    prof.begin_pass(0.0)
    with prof.phase("reap"):
        t[0] = 0.125
    with prof.phase("prefill"):
        with prof.phase("tier_import"):  # nests: the inner laps first
            t[0] = 0.25
        t[0] = 0.5
    with pytest.raises(RuntimeError):
        with prof.phase("dispatch"):  # a body that raises does not lap
            t[0] = 0.75
            raise RuntimeError("superseded")
    with loop_phase(prof, "device_window"):
        t[0] = 1.0
    prof.begin_pass(1.0)
    assert reads == [0.125, 0.25, 0.5, 1.0]  # one read per closed boundary
    phases = {p: v["total_s"] for p, v in prof.snapshot()["phases"].items()}
    assert phases == {
        "reap": 0.125, "tier_import": 0.125, "prefill": 0.25,
        "device_window": 0.5,  # took the unlapped dispatch's time too
    }


def test_phase_context_is_inert_without_a_profiler():
    off = loop_phase(None, "reap")
    assert off is loop_phase(None, "device_window")  # one shared no-op
    with off:
        with loop_phase(None, "prefill"):
            pass


def test_relative_p95_stall_needs_samples_and_floor():
    prof = make_prof(stall_s=0.0, stall_factor=10.0)
    # Build a rolling baseline of 10ms passes (≥ the minimum samples).
    t = 0.0
    for _ in range(REL_STALL_MIN_SAMPLES):
        drive_pass(prof, t, [("prefill", t + 0.01)], t + 0.01)
        t += 0.01
    # 10× p95 = 0.1s but the floor is higher → 0.04s is NOT a stall...
    drive_pass(prof, t, [("prefill", t + 0.04)], t + 0.04)
    t += 0.04
    assert prof.snapshot()["stalls"] == 0
    assert REL_STALL_FLOOR_S > 0.01 * 10.0 / 10.0
    # ...while a pass over both k×p95 and the floor is.
    drive_pass(prof, t, [("prefill", t + 0.5)], t + 0.5)
    snap = prof.snapshot()
    assert snap["stalls"] == 1
    assert snap["anomalies"][0]["kind"] == "p95"
    assert snap["pinned_anomalies"] == []  # relative → rolling ring


def test_compile_pass_is_never_a_stall():
    prof = make_prof(stall_s=1.0)
    compiles = [0]
    prof.compiles = lambda: compiles[0]
    compiles[0] = 3  # XLA compiled during this (slow) pass
    drive_pass(prof, 0.0, [("prefill", 5.0)], 5.0)
    assert prof.snapshot()["stalls"] == 0
    # Counter stable + still slow → a genuine stall again.
    drive_pass(prof, 5.0, [("prefill", 10.0)], 10.0)
    assert prof.snapshot()["stalls"] == 1


def test_anomaly_ring_bounded_and_pins_survive_a_burst():
    # Rolling window just over the minimum sample count (the baseline
    # excludes the pass under judgment), so a full lap of clean passes
    # flushes each stall back out of the p95 baseline (a stall
    # inflating its own detection threshold is by design — the storm
    # path is the latch's job, not the ring's).
    prof = make_prof(
        stall_s=0.0, stall_factor=10.0, anomaly_records=4,
        window=REL_STALL_MIN_SAMPLES + 1,
    )
    t = 0.0

    def clean_laps(n: int) -> None:
        nonlocal t
        for _ in range(n):
            drive_pass(prof, t, [("prefill", t + 0.01)], t + 0.01)
            t += 0.01

    clean_laps(REL_STALL_MIN_SAMPLES)
    # One ABSOLUTE stall pins first.
    prof.stall_s = 1.0
    drive_pass(prof, t, [("prefill", t + 2.0)], t + 2.0)
    t += 2.0
    prof.stall_s = 0.0
    # A burst of relative anomalies (a clean window between incidents
    # re-arms the latch AND flushes the p95 baseline) overflows the
    # bounded rolling ring...
    for _ in range(6):
        clean_laps(REL_STALL_MIN_SAMPLES)
        drive_pass(prof, t, [("prefill", t + 0.5)], t + 0.5)
        t += 0.5
    snap = prof.snapshot()
    assert len(snap["anomalies"]) == 4  # bounded (maxlen) — 6 fired
    assert all(a["kind"] == "p95" for a in snap["anomalies"])
    # ...but the pinned absolute record SURVIVED the burst.
    assert len(snap["pinned_anomalies"]) == 1
    assert snap["pinned_anomalies"][0]["kind"] == "absolute"
    assert snap["stalls"] == 7


# ----------------------------------------------------------------------
# trace capture: cooldown + singleton
# ----------------------------------------------------------------------


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def make_capture(clock: FakeClock, cooldown_s: float = 60.0):
    events: list[str] = []
    cap = ProfilerCapture(
        cooldown_s=cooldown_s,
        clock=clock,
        sleep=lambda s: events.append(f"sleep:{s}"),
        starter=lambda d: events.append("start"),
        stopper=lambda: events.append("stop"),
        spawn=lambda fn: fn(),  # synchronous for determinism
    )
    return cap, events


def test_trace_capture_cooldown_bounds_a_stall_storm():
    clock = FakeClock(100.0)
    cap, events = make_capture(clock, cooldown_s=60.0)
    prof = make_prof(stall_s=1.0, trace_ms=50, capture=cap)
    # Stall → one capture; storm inside the cooldown → suppressed.
    drive_pass(prof, 0.0, [("prefill", 2.0)], 2.0)
    drive_pass(prof, 2.0, [("prefill", 2.01)], 2.01)  # re-arm
    clock.t = 130.0  # +30s: inside the cooldown
    drive_pass(prof, 2.01, [("prefill", 4.5)], 4.5)
    assert events == ["start", "sleep:0.05", "stop"]
    assert cap.captures == 1 and cap.suppressed == 1
    snap = prof.snapshot()
    assert snap["pinned_anomalies"][0]["trace_captured"] is True
    assert snap["pinned_anomalies"][1]["trace_captured"] is False
    assert snap["trace"]["suppressed"] == 1
    # Past the cooldown the next incident captures again.
    drive_pass(prof, 4.5, [("prefill", 4.51)], 4.51)  # re-arm
    clock.t = 200.0
    drive_pass(prof, 4.51, [("prefill", 7.0)], 7.0)
    assert cap.captures == 2


def test_capture_slot_is_exclusive_and_released_on_failure():
    clock = FakeClock(0.0)
    cap, _ = make_capture(clock, cooldown_s=0.0)
    assert cap.try_acquire()
    # Busy slot: a trigger is suppressed, never queued.
    assert cap.trigger(10) is False
    assert cap.suppressed == 1
    cap.release()
    # A failing capture still releases the slot.
    cap._starter = lambda d: (_ for _ in ()).throw(RuntimeError("boom"))
    assert cap.trigger(10) is True
    assert cap.busy is False
    assert "boom" in cap.snapshot()["last_error"]


def test_get_capture_is_a_race_free_singleton():
    """The /debug/tpu-trace lazy-init fix: concurrent first callers
    can no longer mint two dirs/locks and trace concurrently."""
    import gofr_tpu.serving.profiler_capture as pc

    old = pc._capture
    pc._capture = None
    try:
        got: list = []
        barrier = threading.Barrier(8)

        def grab():
            barrier.wait()
            got.append(pc.get_capture())

        threads = [threading.Thread(target=grab) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len({id(c) for c in got}) == 1
        assert len({c.trace_dir for c in got}) == 1
        # The engine's cooldown knob updates the shared instance.
        assert pc.get_capture(cooldown_s=7.5).cooldown_s == 7.5
    finally:
        pc._capture = old


# ----------------------------------------------------------------------
# the device's own timeline
# ----------------------------------------------------------------------


def test_device_timeline_arithmetic_on_stated_clocks():
    m = loop_metrics()
    tl = DeviceTimeline("m", metrics=m)
    w = _Watch(threading.current_thread())
    total = "app_tpu_device_seconds_total"
    # The thread's first program: it starts at its dispatch, no gap before.
    tl.settle(w, "decode_window", 1.0, 1.5, "dispatch")
    first = counter_value(m, total)
    # A prefill step dispatched behind it waits for it: starts at 1.5.
    tl.settle(w, "prefill_chunk", 1.25, 2.0, "prefill")
    # Registered while the device's queue is empty: it starts at its own
    # dispatch, and the dry half second goes to the phase the loop was in
    # when the device finished the program before it.
    tl.settle(w, "decode_window", 2.5, 3.0, "device_window")
    # The loop waited for work since that ready: the dry spell is its own.
    tl.settle(w, "prefill_chunk", 3.25, 3.5, "emit_flush", idled=True)
    assert hist(m, "app_tpu_program_device_seconds",
                program="prefill_chunk") == (0.75, 2)
    assert hist(m, "app_tpu_program_queued_seconds",
                program="prefill_chunk") == (0.25, 2)
    assert hist(m, "app_tpu_program_device_seconds",
                program="decode_window") == (1.0, 2)
    assert hist(m, "app_tpu_program_queued_seconds",
                program="decode_window") == (0.0, 2)
    assert counter_value(m, total, state="idle", cause="prefill") == 0.5
    assert counter_value(m, total, state="idle", cause="idle") == 0.25
    assert counter_value(m, total, state="busy",
                         cause="decode_window") == 1.0
    # Busy plus dry from the first ready on is the stretch between the
    # first and the last ready, exactly.
    assert counter_value(m, total) - first == 3.5 - 1.5
    snap = tl.snapshot()
    assert snap["programs"]["prefill_chunk"] == {
        "count": 2, "device_s": 0.75, "queued_s": 0.25,
    }
    assert snap["idle_s"] == {"prefill": 0.5, "idle": 0.25}


def test_a_program_with_nothing_to_wait_on_is_folded_into_the_next():
    m = loop_metrics()
    tl = DeviceTimeline("m", metrics=m)
    w = _Watch(threading.current_thread())
    total = "app_tpu_device_seconds_total"
    tl.settle(w, "decode_window", 0.0, 1.0, "prefill")
    # A block copy whose only output the prefill step donates: the device
    # runs it from its dispatch, so it is dry until then and busy after.
    tl.fold(w, "paged_copy_block", 1.5)
    tl.settle(w, "prefill_chunk", 1.75, 2.5, "prefill")
    assert counter_value(m, total, state="idle") == 0.5
    assert counter_value(m, total, state="busy",
                         cause="prefill_chunk") == 1.0
    # ... and no per-program record carries the copy's time.
    assert hist(m, "app_tpu_program_device_seconds",
                program="prefill_chunk") == (0.0, 0)
    assert tl.snapshot()["folded"] == {"paged_copy_block": 1}


def test_phase_context_names_the_current_phase_and_counts_idle_waits():
    prof = make_prof()
    prof.begin_pass(prof._clock())
    assert prof.current_phase == "other"
    with prof.phase("prefill"):
        with prof.phase("tier_import"):
            assert prof.current_phase == "tier_import"
        assert prof.current_phase == "prefill"
    assert prof.current_phase == "other"
    with prof.phase("idle"):
        assert prof.current_phase == "idle"
    with pytest.raises(RuntimeError):
        with prof.phase("idle"):
            raise RuntimeError("the body raised")
    assert prof.current_phase == "other" and prof.idle_waits == 2


def test_stalled_pass_says_what_the_device_did():
    prof = make_prof(stall_s=1.0)
    w = _Watch(threading.current_thread())
    drive_pass(prof, 0.0, [("device_window", 0.5)], 0.5)
    # The stalled pass, 0.5 to 4.5.
    drive_pass(prof, 0.5, [("prefill", 4.0)], 4.5)
    rec = prof.snapshot()["pinned_anomalies"][0]
    assert rec["device_busy_s"] is None  # the device's side is not seen yet
    prof.device.settle(w, "decode_window", 0.25, 1.0, "prefill")
    prof.device.settle(w, "prefill_chunk", 3.5, 4.25, "prefill")
    assert prof.snapshot()["pinned_anomalies"][0]["device_busy_s"] is None
    # Once the device's timeline passes the pass's end the record fills:
    # busy 0.5 to 1.0, then dry while the loop prefilled, busy from 3.5.
    prof.device.settle(w, "decode_window", 4.0, 5.0, "device_window")
    rec = prof.snapshot()["pinned_anomalies"][0]
    assert rec["device_busy_s"] == 1.5
    assert rec["device_idle_s"] == 2.5
    assert rec["device_idle_cause"] == {"prefill": 2.5}
    assert rec["total_s"] == 4.0


class _Blocked:
    """An output whose program does not finish until it is released."""

    def __init__(self) -> None:
        self.waiting = threading.Event()
        self.release = threading.Event()

    def block_until_ready(self):
        self.waiting.set()
        self.release.wait(30)
        return self


class _Done:
    def block_until_ready(self):
        return self


def _count(tl: DeviceTimeline, program: str) -> int:
    return tl.snapshot()["programs"].get(program, {}).get("count", 0)


def test_a_program_a_profiler_capture_overlaps_is_not_timed(monkeypatch):
    """A capture's start and stop hold the runtime's completions back: the
    device's timeline counts such a program busy and times it in no
    per-program histogram."""
    from gofr_tpu.serving import profiler_capture

    class Capture:
        busy = True

    monkeypatch.setattr(profiler_capture, "_capture", Capture())
    m = loop_metrics()
    prof = make_prof(metrics=m)
    tl = prof.device
    watch = tl.start()
    tl.register("decode_window", prof._clock(), _Done())
    deadline = time.monotonic() + 10
    while (counter_value(m, "app_tpu_device_seconds_total") == 0
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert counter_value(m, "app_tpu_device_seconds_total") > 0
    assert _count(tl, "decode_window") == 0
    Capture.busy = False
    tl.register("decode_window", prof._clock(), _Done())
    deadline = time.monotonic() + 10
    while _count(tl, "decode_window") == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert _count(tl, "decode_window") == 1
    assert hist(m, "app_tpu_program_device_seconds")[1] == 1
    tl.start()  # supersede the test's watcher
    watch.thread.join(5)
    assert not watch.thread.is_alive()


def test_an_abandoned_schedulers_watcher_does_not_block_a_restart(
    monkeypatch,
):
    monkeypatch.setattr(loop_profiler, "WATCH_POLL_S", 0.05)
    tl = make_prof().device
    wedged, hold, done = _Blocked(), threading.Event(), threading.Event()
    watches = {}

    def scheduler(name, out, until):
        watches[name] = tl.start()
        tl.register("decode_window", 0.0, out)
        until.wait(30)

    old = threading.Thread(target=scheduler, args=("old", wedged, hold),
                           daemon=True)
    old.start()
    assert wedged.waiting.wait(10)  # the device hangs on the old program
    # The supervisor abandons the old thread (it stays alive, wedged) and
    # starts a new one: its watcher starts at once and stamps programs.
    new = threading.Thread(target=scheduler, args=("new", _Done(), done),
                           daemon=True)
    new.start()
    new.join(0.5)
    deadline = time.monotonic() + 10
    while _count(tl, "decode_window") < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert _count(tl, "decode_window") == 1
    # Released at last, the old watcher drops what it held and leaves.
    tl.register("decode_window", 1.0, _Done())  # the new thread's queue
    wedged.release.set()
    watches["old"].thread.join(5)
    assert not watches["old"].thread.is_alive()
    # A watcher leaves once the scheduler thread it serves has ended.
    done.set()
    new.join(5)
    watches["new"].thread.join(5)
    assert not watches["new"].thread.is_alive()
    assert _count(tl, "decode_window") == 2  # the old program never counted
    hold.set()
    old.join(5)


# ----------------------------------------------------------------------
# engine integration: hooks, layer-off, advertisement
# ----------------------------------------------------------------------

ENG_KW = dict(
    n_slots=2, max_len=128, window_k=4, pipeline_depth=1,
    prefill_chunk=32, kv_block=32, auto_prefix=True,
    # A generous absolute stall bound: a loaded CI runner's scheduling
    # hiccup must not pin a flaky anomaly into the shared fixture.
    loop_stall_s=30.0,
)


@pytest.fixture(scope="module")
def eng():
    m = loop_metrics()
    e = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), metrics=m, **ENG_KW
    )
    e.start_sync()
    e.generate_sync(
        "warm the loop", max_new_tokens=4, temperature=0.0,
        stop_on_eos=False, timeout=120,
    )
    yield e, m
    e.stop_sync()


def _settled_report(e) -> dict:
    """The loop report once the post-generate passes have CLOSED: a
    pass's phases land when the next pass begins, and a result future
    resolves inside the device-window phase — an immediate read races
    it. Bounded poll, no fixed sleep."""
    import time as _time

    deadline = _time.monotonic() + 10.0
    while _time.monotonic() < deadline:
        rep = e.loop_report()
        if "device_window" in rep.get("phases", {}):
            return rep
        _time.sleep(0.005)
    return e.loop_report()


def test_engine_profiles_every_loop_phase(eng):
    e, m = eng
    rep = _settled_report(e)
    assert rep["enabled"] is True
    assert rep["passes"] >= 1 and rep["stalls"] == 0
    for phase in ("reap", "ledger", "sweep", "prefill", "emit_flush",
                  "dispatch", "device_window", "other"):
        assert phase in rep["phases"], sorted(rep["phases"])
    assert 0.0 <= rep["utilization"] <= 1.0
    assert 0.0 <= rep["host_overhead_ratio"] <= 1.0
    # The profiler measures itself.
    assert rep["self_overhead_s"] > 0.0
    # The exported phase gauges publish the full bounded label set
    # (GL016 discipline) — one value per phase, absent phases at 0.0.
    # (The sums-to-pass-wall contract is pinned exactly in the
    # stated-clock test above; the live gauges refresh per pass, so a
    # cross-read here would race the still-running loop.)
    vals = gauge_values(m, "app_tpu_loop_phase_seconds")
    assert len(vals) == len(PHASES)
    assert all(v >= 0.0 for v in vals.values())
    assert sum(vals.values()) > 0.0


def test_engine_advertises_loop_stats(eng):
    e, _ = eng
    compact = {"passes", "stalls", "utilization", "host_overhead_ratio"}
    assert set(e.health_check()["details"]["loop"]) == compact
    assert set(e.capacity_report()["loop"]) == compact
    assert set(e.flight_records()["loop"]) == compact


def test_pool_aggregates_loop_reports(eng):
    e, _ = eng
    pool = ReplicaPool([EngineReplica("r0", e)], probe_interval_s=0)
    try:
        rep = pool.loop_report()
        entry = rep["replicas"]["r0"]
        assert entry["enabled"] is True and entry["passes"] >= 1
        assert "state" in entry
    finally:
        # Detach without pool.close(): closing an EngineReplica stops
        # its engine, and this one is the shared module fixture.
        pool.stop_prober()
        for replica in pool._replicas:
            replica.set_handoff(None)
            replica.set_tier_exporter(None)


def _watchers() -> int:
    return sum(t.name == "tpu-device-watch" for t in threading.enumerate())


def test_layer_off_mints_nothing_and_streams_identically(eng):
    e, m_on = eng
    m_off = loop_metrics()
    watchers = _watchers()
    off = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), loop_profile=False,
        metrics=m_off, **ENG_KW,
    )
    off.start_sync()
    try:
        assert off._loop_prof is None
        assert off.loop_report() == {"enabled": False}
        assert "loop" not in off.health_check()["details"]
        assert "loop" not in off.capacity_report()
        assert "loop" not in off.flight_records()
        req_off = off.submit_generate(
            "loop ab prompt", max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        req_on = e.submit_generate(
            "loop ab prompt", max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        r_off = req_off.future.result(timeout=120)
        r_on = req_on.future.result(timeout=120)
        # TPU_LOOP_PROFILE=0 is byte-identical: same greedy stream.
        assert r_off.token_ids == r_on.token_ids
        # ... starts no watcher thread, stamps no stream's hand-off, and
        # mints none of the timeline's four series; the profiled engine
        # beside it does.
        assert _watchers() <= watchers
        assert req_off.stream.handed == 0.0 < req_on.stream.handed
        for name in TIMELINE_HISTOGRAMS:
            assert hist(m_off, name) == (0.0, 0), name
        assert counter_value(m_off, "app_tpu_device_seconds_total") == 0.0
        deadline = time.monotonic() + 10
        while (hist(m_on, "app_tpu_program_device_seconds",
                    program="decode_window")[1] == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert hist(m_on, "app_tpu_program_device_seconds",
                    program="decode_window")[1] > 0
        assert counter_value(m_on, "app_tpu_device_seconds_total",
                             state="busy", cause="prefill_chunk") > 0
    finally:
        off.stop_sync()


def test_tier_import_phase_attributes_on_apply(eng):
    """The tier-import apply stamps its own phase (it would otherwise
    hide inside prefill): the paged engine laps it every pass."""
    e, _ = eng
    rep = e.loop_report()
    assert "tier_import" in rep["phases"]
    assert rep["phases"]["tier_import"]["count"] >= 1
