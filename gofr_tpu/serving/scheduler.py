"""The LLM continuous-batching scheduler: admission, chunked prefill,
pipelined decode windows, paged-KV block accounting, and
retirement. Mixin methods on InferenceEngine — split from
``engine.py`` along its scheduler seams (r4 VERDICT weak #10)."""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import InvalidStateError

from typing import Any, Optional

import numpy as np

from gofr_tpu import faults
from gofr_tpu.analysis import lockcheck
from gofr_tpu.ops.attention import chunk_visit_ratio, decode_read_index
from gofr_tpu.serving.loop_profiler import loop_phase
from gofr_tpu.serving.types import (
    _ActiveSeq,
    _GenRequest,
    _PrefillState,
    GenerationResult,
)

# Thread attribute carrying the scheduler epoch the thread was started
# under (each thread brands only itself, so there is no cross-thread
# write to race).
_EPOCH_ATTR = "gofr_sched_epoch"

# Full prefill steps' worth of rows (x prefill_batch) that wave admission
# may dispatch between two decode windows (_scheduler_loop).
WAVE_STEPS = 2


class SchedulerSuperseded(BaseException):
    """The supervisor restarted the engine around this (previously
    wedged) scheduler thread: its epoch is stale, so it must exit
    WITHOUT touching engine state or draining — the supervisor already
    salvaged or failed every request it owned. BaseException on purpose:
    nothing between the dispatch seams and the loop may catch it."""


class SchedulerMixin:
    """The scheduler thread's entire dataplane-facing loop."""

    # -- the mixin contract (mypy strict scope) ------------------------
    # Everything below is provided by InferenceEngine.__init__ /
    # _init_llm_serving_state (state) or by the sibling mixins
    # (compiled-program callables). Declared here so the strict type
    # gate checks this module's OWN logic against a written-down
    # contract instead of guessing at the facade's shape.
    _running: bool
    _epoch: int
    _fatal: Optional[BaseException]
    _drained: bool
    _sched_idle: bool
    _restart_pending: bool
    _queued_tokens: int
    _prefix_lookups: int
    _prefix_hit_tokens: int
    _prefill_chunk_steps: int
    _table_dirty: bool
    _slot_state_dirty: bool
    _seeds_dirty: bool
    _lockstep: bool
    kv_block: int
    max_len: int
    tier_role: str
    prefix_evict_watermark: int
    effective_evict_watermark: int
    prefix_evict_hbm_frac: float
    _wm_fruitless: "Optional[tuple[int, int]]"
    n_slots: int
    pipeline_depth: int
    prefill_batch: int
    prefill_rungs: tuple[int, ...]
    prefill_attn_block: int
    moe_products: dict[tuple[str, int], str]
    decode_read_rungs: tuple[int, ...]
    decode_read_block: int
    prefill_chunk: int
    top_logprobs: int
    window_k: int
    enable_penalties: bool
    model_name: str
    _submit_lock: threading.Lock
    _idle_evt: threading.Event
    _work: threading.Event
    _pending: Any  # lifecycle.ClassPriorityQueue[_GenRequest]
    _wait_kv: Any  # deque[_GenRequest]
    _slots: "list[Optional[_ActiveSeq]]"
    _prefilling: "dict[int, _PrefillState]"
    _prefill_emits: list
    _moe_counts: Any  # deque of (device counts, rows, tokens)
    _replay: "list[_GenRequest]"
    _tenant_queued: "dict[str, int]"
    _slot_blocks: "list[list[int]]"
    _dispatched_tokens: "list[int]"
    _lora_gen: "list[int]"
    _allocator: Any  # ops.kv_cache.BlockAllocator
    _radix: Any  # Optional[serving.radix_cache.RadixPrefixIndex]
    _prefix_pool: Any  # Optional[serving.prefix_cache.PrefixPool]
    _supervisor: Any
    _handoff: Any
    _tier_exporter: Any
    _tier_imports: Any  # deque[ops.kv_cache.KVBlockPayload]
    _tier_import_done: Any  # dict[id(payload) -> threading.Event]
    _tier_exports: Any  # deque[(token_ids, result_box, threading.Event)]
    _watchdog: Any
    _metrics: Any
    _obs: Any  # serving.observability.RequestObservability
    _loop_prof: Any  # Optional[serving.loop_profiler.LoopProfiler]
    _tenant_ledger: Any  # Optional[serving.tenant_ledger.TenantLedger]
    _ledger: Any  # Optional[serving.device_telemetry.HBMLedger]
    _slo: Any  # Optional[serving.slo.SLOEngine]
    _brownout: Any  # Optional[serving.brownout.BrownoutController]
    _control: Any  # Optional[serving.control_plane.ControlPlane]
    _compiles: Any  # serving.device_telemetry.CompileTracker
    _logger: Any
    _tput: Any  # lifecycle.AggregateThroughput
    tokenizer: Any
    cache: Any
    params: Any
    _jax: Any
    _jnp: Any
    devices: Any  # list of jax devices this engine lives on
    _up: Any  # host→device placement callable
    _table_host: Any  # np.ndarray [S, max_blocks] mirror
    _seeds_host: Any
    _noff_host: Any
    _aids_host: Any
    _bidx_host: Any
    _bval_host: Any
    # Device-resident slot planes (jax arrays).
    _tokens_dev: Any
    _logps_dev: Any
    _nsteps_dev: Any
    _seeds_dev: Any
    _noff_dev: Any
    _aids_dev: Any
    _active_dev: Any
    _temps_dev: Any
    _topp_dev: Any
    _greedy_dev: Any
    _fpen_dev: Any
    _ppen_dev: Any
    _pcounts_dev: Any
    _bidx_dev: Any
    _bval_dev: Any
    _topi_dev: Any
    _topl_dev: Any
    # Compiled-program callables (LLMProgramsMixin) and engine methods
    # this loop calls across the facade.
    _prefill_step: Any  # (rows, use_bias) -> the compiled rung
    _prefill_operands: Any  # the nine per-row arrays -> its operands
    _decode_window: Any
    # Compile-tracked paged-pool jits (engine._init_llm_serving_state
    # wraps ops.kv_cache.paged_{copy,insert,extract,move}_block per
    # engine; extract/move are the device-leg tier-transfer pair).
    _paged_copy_block: Any
    _paged_insert_block: Any
    _paged_extract_block: Any
    _paged_move_block: Any
    _block_sharding: Any  # Optional[NamedSharding] for inbound planes
    _note_dequeued: Any
    _set_state: Any
    hbm_headroom_ratio: Any
    _kv_pool_counts: Any
    try_handoff: Any

    def _check_superseded(self) -> None:
        """Raise :class:`SchedulerSuperseded` when this thread's branded
        epoch no longer matches the engine's — i.e. the supervisor
        abandoned this thread mid-wedge and a new scheduler owns the
        state. Called at the seams where a wedged step would resume."""
        epoch = getattr(threading.current_thread(), _EPOCH_ATTR, None)
        if epoch is not None and epoch != self._epoch:
            raise SchedulerSuperseded

    def _scheduler_loop(self) -> None:
        error: BaseException | None = None
        # Brand this thread with the epoch it was started under: if the
        # supervisor abandons it (wedged device step) and restarts the
        # engine, the bumped engine epoch makes every later touch from
        # this thread raise SchedulerSuperseded instead of corrupting
        # the new scheduler's state.
        epoch = self._epoch
        setattr(threading.current_thread(), _EPOCH_ATTR, epoch)
        # Windows are PIPELINED `pipeline_depth` deep: dispatch window n+D
        # before fetching window n's tokens. The host↔device round trip is
        # latency, not bandwidth — overlapping D fetches with compute makes
        # the floor device step time.
        from collections import deque

        inflight: deque = deque()  # _dispatch_window return tuples
        # Loop profiler (serving/loop_profiler.py): each phase of a pass
        # is a `with` block that enters TraceAnnotation("loop/<phase>")
        # and, on exit, reads the clock ONCE and attributes the time
        # since the previous stamp to the phase (window granularity —
        # GL011's discipline). Off (TPU_LOOP_PROFILE=0) = one shared
        # no-op context per boundary.
        prof = self._loop_prof
        if prof is not None:
            # The device's own timeline: a watcher thread that stamps
            # each program this thread dispatches as the device finishes
            # it (serving/loop_profiler.py DeviceTimeline).
            prof.device.start()
        try:
            while self._running and self._epoch == epoch:
                # begin_pass also CLOSES the previous pass: residual
                # time since its last stamp lands in "other", so the
                # per-phase durations sum to pass wall time exactly.
                if prof is not None:
                    prof.begin_pass(self._obs.now())
                with loop_phase(prof, "reap"):
                    # Progress heartbeat: the watchdog trips when this
                    # loop stalls (a hung device step) for longer than
                    # its wall-time bound. Idle iterations pet every
                    # ≤20 ms.
                    if self._watchdog is not None:
                        self._watchdog.pet()
                    # Fault seam: a test's armed action here can stall
                    # the whole loop (watchdog coverage) or fail one
                    # iteration.
                    faults.fire("scheduler.window", engine=self)
                    self._check_superseded()
                    # Lifecycle reap: cancelled/disconnected/deadline-
                    # expired sequences retire HERE, once per loop
                    # iteration, so a dead stream's KV blocks free
                    # within one decode window.
                    self._reap_lifecycle()
                # Tenant attribution (serving/tenant_ledger.py): one
                # KV-occupancy integration pass per loop iteration —
                # one clock read shared by every live slot, never per
                # token. Off (TPU_TENANT_LEDGER=0) = this one check.
                if self._tenant_ledger is not None:
                    with loop_phase(prof, "ledger"):
                        self._ledger_tick()
                # Brownout control loop (serving/brownout.py): ONE
                # evaluation per scheduler pass — the GL011-disciplined
                # cadence the ladder's sustain windows assume. Off
                # (TPU_BROWNOUT=0) = this one check.
                if self._brownout is not None:
                    with loop_phase(prof, "brownout"):
                        self._brownout_tick()
                # Control plane (serving/control_plane.py): ONE guarded
                # pass over every registered signal + the three closed
                # loops, right after the sensors it consumes ticked.
                # Off (TPU_CONTROL_PLANE=0) = this one check; evaluate
                # never raises (a lying sensor degrades its loop to
                # observe-only instead of wedging this pass).
                if self._control is not None:
                    with loop_phase(prof, "control"):
                        self._control.evaluate(self._obs.now())
                if self.kv_block:
                    # Proactive prefix-eviction sweep: keep the free
                    # list above the watermark so admission finds free
                    # blocks instead of pre-evicting synchronously.
                    with loop_phase(prof, "sweep"):
                        self._radix_watermark_sweep()
                with loop_phase(prof, "prefill"):
                    # One chunk step per iteration, interleaved 1:1 with
                    # decode windows: a long prompt's prefill proceeds in
                    # bounded slices and never freezes active token
                    # streams (VERDICT r1 #9).
                    rows = self._dispatch_prefill_chunk(lap_import=True)
                    progressed = rows > 0
                    # Wave admission: on a cold start or a retirement
                    # wave the 1:1 interleave would refill capacity one
                    # chunk per window — at 64 slots that is ~15 windows
                    # of a mostly-idle device (measured: the 64-slot
                    # bench lost ~2 s per wave to it). While live streams
                    # fill under a quarter of the slots, keep draining;
                    # past that, protect the live streams' latency (1:1
                    # again). The drain holds every live stream still,
                    # so it ends at WAVE_STEPS full steps' rows between
                    # two windows: short prompts (a few rows a step) are
                    # drained whole as before, while prompts of dozens of
                    # chunks, whose steps are full and cost more than a
                    # window each, cannot freeze the streams for seconds
                    # until a quarter of the slots is live again (PR 33:
                    # 32 slots of ~18-chunk prompts hovered at that
                    # quarter, drains of 5 to 25 steps).
                    while (
                        0 < rows < WAVE_STEPS * self.prefill_batch
                        and sum(1 for s in self._slots if s is not None) * 4
                        < self.n_slots
                    ):
                        more = self._dispatch_prefill_chunk()
                        if not more:
                            break
                        rows += more
                with loop_phase(prof, "emit_flush"):
                    self._flush_prefill_emits()
                any_active = any(s is not None for s in self._slots)
                if not any_active and not inflight:
                    if not progressed and not self._prefill_emits:
                        with loop_phase(prof, "idle"):
                            # Publish "verifiably idle" under the submit
                            # lock: the graceful drain trusts this flag,
                            # and the lock means no submission can race
                            # past it.
                            with self._submit_lock:
                                if (
                                    self._pending.empty()
                                    and not self._wait_kv
                                ):
                                    self._sched_idle = True
                                    self._idle_evt.set()
                            self._work.wait(timeout=0.02)
                            self._work.clear()
                    continue
                with self._submit_lock:
                    self._sched_idle = False
                # Dispatch only while some active slot still has budget
                # beyond what in-flight windows already cover — a wave of
                # same-length requests otherwise ends with `depth` pure-
                # overshoot windows whose tokens are all discarded.
                # (tokens_in_flight counts the k emissions per window +
                # the prefill token; emitted = in_flight - 1, so dispatch
                # while in_flight <= budget. eos/stop retirements end
                # earlier via processing.)
                wants_more = any_active and any(
                    s is not None
                    and s.tokens_in_flight <= s.request.remaining_new_tokens
                    for s in self._slots
                )
                if wants_more:
                    with loop_phase(prof, "dispatch"):
                        inflight.append(self._dispatch_window())
                    if prof is not None:
                        # Dispatched as the phase's closing stamp read.
                        prof.dispatched("decode_window", inflight[-1][0])
                keep = self.pipeline_depth if wants_more else 0
                if len(inflight) > keep:
                    # The designated device-wait seam: the fetch block
                    # inside _process_window is where the loop
                    # legitimately waits on the device — everything
                    # else busy counts as host overhead (GL019 is the
                    # static twin of this attribution).
                    with loop_phase(prof, "device_window"):
                        while len(inflight) > keep:
                            self._process_window(*inflight.popleft())
        except SchedulerSuperseded:
            # The supervisor restarted the engine around this wedged
            # thread: a new scheduler owns every structure, and the
            # supervisor already salvaged/failed this thread's requests.
            # Exit with NO drain — failing futures here would double-
            # resolve requests the new scheduler is replaying.
            return
        except BaseException as exc:  # noqa: BLE001 — must not strand futures
            # A scheduler crash (e.g. a kernel that fails to compile on this
            # hardware) must fail every caller, not hang them until timeout.
            # The flag writes hold the submit lock like every other writer:
            # _enqueue's fatal/running checks must never see a half-
            # published death.
            error = exc
            with self._submit_lock:
                if self._epoch != epoch:
                    # An abandoned (wedged) thread whose stuck call
                    # finally RAISED: the engine was restarted around it,
                    # so these flags belong to the new scheduler — exit
                    # without touching anything.
                    return
                self._fatal = exc
                self._running = False
            self._set_state("DEGRADED")
            if self._logger is not None:
                self._logger.errorf("engine scheduler died: %s", exc)
        # Drain: fail queued requests AND active slots so no awaiting caller
        # hangs on an unresolved future / unterminated stream. The submit
        # lock closes the race where a submitter enqueues between the
        # scheduler's exit and this drain.
        reason: BaseException = error or RuntimeError("engine stopped")
        # With a supervisor attached and a restart coming (fatal exit, or
        # a watchdog-trip teardown marked by _restart_pending), RETRYABLE
        # requests are salvaged for replay instead of failed: their
        # futures/streams stay open and the supervisor requeues them on
        # the restarted engine. Non-retryable ones (cancelled, expired,
        # prefix registrations) fail through the existing terminal path.
        # A STOPPING supervisor accepts no salvage — nothing would ever
        # requeue it (a crash racing engine.close() must fail its
        # requests, not park them forever).
        sup = self._supervisor
        salvaging = (
            sup is not None
            and not sup.stopping
            and (error is not None or self._restart_pending)
        )
        salvaged: list[_GenRequest] = []

        handoff_after: list[_GenRequest] = []

        def _terminal(req: _GenRequest) -> None:
            # done() + InvalidStateError guard: an async caller may have
            # cancelled the future already.
            try:
                if not req.future.done():
                    req.future.set_exception(reason)
            except InvalidStateError:  # cancelled concurrently
                pass
            req.stream.put(None)
            self._obs_finish(req, "error", "engine_stopped")

        def _fail(req: _GenRequest) -> None:
            if salvaging and req.retryable():
                salvaged.append(req)
                return
            # Replica-pool handoff: with no supervisor to replay locally
            # (or a stopping one), a still-retryable request can instead
            # continue on a SIBLING replica — the pool requeues it with
            # its stream/future intact. Deferred past the submit-lock
            # release below: adoption takes the SIBLING engine's submit
            # lock, and two replicas draining into each other under
            # their own locks would deadlock. Only unplaceable requests
            # get the terminal error.
            if (
                not salvaging
                and self._handoff is not None
                and not req.aid
                and not req.pin_replica
                and req.retryable()
            ):
                handoff_after.append(req)
                return
            _terminal(req)

        # Block on in-flight windows first: returning from stop with device
        # computations + async host copies still outstanding races
        # interpreter teardown (observed as a runtime-client thread panic
        # at exit). This barrier is also where a WEDGED device leaves the
        # thread parked — everything after it runs under ONE submit-lock
        # hold with one epoch check, so the supervisor's abandonment
        # (epoch bump + salvage, also under the lock) strictly either
        # precedes this drain (it returns untouched) or follows it (the
        # salvage sees emptied structures and the already-parked replay
        # list) — never interleaves into double-salvage or stranding.
        while inflight:
            emitted = inflight.popleft()[0]
            try:
                np.asarray(emitted)  # graftlint: disable=GL001 — shutdown barrier, not a hot-path sync
            except Exception:  # graftlint: disable=GL006 — device may already be down; any failure here means the fetch is moot
                pass
        with self._submit_lock:
            if self._epoch != epoch:
                # Superseded at (or while parked in) the barrier: the
                # supervisor owns every request now.
                return
            self._drained = True
            self._queued_tokens = 0
            self._tenant_queued.clear()
            if self._tenant_ledger is not None:
                self._tenant_ledger.reset_queued()
            while not self._pending.empty():
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                _fail(req)
            for i, seq in enumerate(self._slots):
                if seq is None:
                    continue
                _fail(seq.request)
                self._release_slot(i)
            for slot, st in list(self._prefilling.items()):
                _fail(st.request)
                del self._prefilling[slot]
            while self._wait_kv:
                _fail(self._wait_kv.popleft())
            self._prefill_emits.clear()
            self._moe_counts.clear()
            if salvaged:
                self._replay.extend(salvaged)
        # Handoffs run with the submit lock RELEASED (see _fail above).
        for req in handoff_after:
            if not self.try_handoff(req):
                _terminal(req)
        # Wake any graceful drain blocked on the idle event: whether this
        # exit was clean or fatal, there is nothing left to wait for.
        self._idle_evt.set()
        # Crash notification LAST: the supervisor may start restarting the
        # moment it hears, and the salvage above must already be parked.
        if error is not None and self._supervisor is not None:
            self._supervisor.notify_crash(error)

    # ------------------------------------------------------------------
    # observability (serving/observability.py)
    # ------------------------------------------------------------------

    def _obs_finish(
        self, req: _GenRequest, outcome: str, reason: str = ""
    ) -> None:
        """Close a request's timeline from a terminal path. Latched by
        the timeline itself, so racing terminal paths (reap vs drain vs
        supervisor fail) summarize exactly once; no-op when the
        observability layer is off. The tenant ledger's exactly-once
        attribution rides the same seam (its own latch on the request)."""
        tl = req.timeline
        if tl is not None:
            tl.finish(outcome, reason, output_tokens=len(req.token_ids))
        if self._tenant_ledger is not None:
            self._tenant_ledger.finish_request(req, outcome)

    def _ledger_tick(self) -> None:
        """Snapshot (tenant, blocks held) for every slot with a live
        block table — decoding AND mid-prefill — and hand it to the
        tenant ledger's occupancy integrator with ONE clock read.
        Unpaged engines still tick (token/outcome attribution needs a
        clock base), just with no rows."""
        led = self._tenant_ledger
        rows: list[tuple[str, int]] = []
        if self.kv_block:
            for i, seq in enumerate(self._slots):
                if seq is not None and self._slot_blocks[i]:
                    rows.append(
                        (seq.request.tenant, len(self._slot_blocks[i]))
                    )
            for slot, st in self._prefilling.items():
                if self._slot_blocks[slot]:
                    rows.append(
                        (st.request.tenant, len(self._slot_blocks[slot]))
                    )
        led.tick(self._obs.now(), rows)

    def _brownout_tick(self) -> None:
        """Feed the controller its two inputs — the worst 5m burn rate
        and the HBM headroom ratio — once per scheduler pass. Both are
        host arithmetic already in hand (one locked ring read, one
        allocator-count division); the controller reads its own clock
        once inside ``evaluate``."""
        slo = self._slo
        burn = slo.worst_burn("5m") if slo is not None else 0.0
        headroom = (
            self.hbm_headroom_ratio() if self._ledger is not None else None
        )
        self._brownout.evaluate(burn, headroom)

    # ------------------------------------------------------------------
    # request-lifecycle reap (cancellation + deadlines)
    # ------------------------------------------------------------------

    @staticmethod
    def _reap_reason(req: _GenRequest) -> Optional[str]:
        """The ONE retirement predicate ("cancelled" | "deadline" |
        None) — every reap site must route through this so a new
        retirement reason can never be missed by one of them."""
        if req.cancel.cancelled or req.future.cancelled():
            return "cancelled"
        if req.deadline is not None and req.deadline.expired():
            return "deadline"
        return None

    def _reap_request(self, req: _GenRequest, slot: int = -1) -> bool:
        """Retire ``req`` if its cancel token tripped (client gone) or
        its deadline expired. Returns True when retired: the future gets
        its terminal error, the stream its sentinel, and ``slot`` (when
        ≥0) is released — paged mode returns its KV blocks to the pool.
        """
        reason = self._reap_reason(req)
        if reason is None:
            return False
        try:
            if not req.future.done():
                if reason == "deadline":
                    from gofr_tpu.errors import ErrorDeadlineExceeded

                    req.future.set_exception(ErrorDeadlineExceeded(
                        f"after {len(req.token_ids)} generated token(s)"
                    ))
                else:
                    from gofr_tpu.errors import ErrorRequestCancelled

                    req.future.set_exception(ErrorRequestCancelled())
        except InvalidStateError:  # caller cancelled concurrently
            pass
        req.stream.put(None)
        self._obs_finish(req, reason)
        if slot >= 0:
            self._release_slot(slot)
        if self._metrics is not None:
            name = (
                "app_tpu_deadline_exceeded_total" if reason == "deadline"
                else "app_tpu_requests_cancelled_total"
            )
            self._metrics.increment_counter(
                name, "model", self.model_name
            )
        if self._logger is not None:
            self._logger.debugf(
                "retired request (%s) after %d token(s)",
                reason, len(req.token_ids),
            )
        return True

    def _reap_lifecycle(self) -> None:
        """One pass over every live request the outside world may have
        abandoned: active decode slots, slots mid-prefill, and requests
        parked for KV blocks. Queued requests are checked at admission
        (``_dispatch_prefill_chunk``) where they are popped anyway."""
        for i, seq in enumerate(self._slots):
            if seq is not None:
                self._reap_request(seq.request, slot=i)
        for slot, st in list(self._prefilling.items()):
            if self._reap_request(st.request, slot=slot):
                del self._prefilling[slot]
        if self._wait_kv and any(
            self._reap_reason(r) is not None for r in self._wait_kv
        ):
            kept = [r for r in self._wait_kv if not self._reap_request(r)]
            self._wait_kv.clear()
            self._wait_kv.extend(kept)

    # ------------------------------------------------------------------
    # paged-KV block allocator (host side; kv_block > 0 only)
    # ------------------------------------------------------------------

    def _publish_prefix_gauge(self) -> None:
        """Refresh ``app_tpu_prefix_cached_blocks`` — call after ANY
        path that shrinks or grows the radix index (retire-insert,
        pressure eviction, adapter purge), or dashboards report a
        stale count until some unrelated request retires."""
        if self._metrics is not None and self._radix is not None:
            self._metrics.set_gauge(
                "app_tpu_prefix_cached_blocks",
                self._radix.n_cached_blocks,
                "model", self.model_name,
            )

    def _alloc_block(self) -> Optional[int]:
        """One free pool block, evicting unreferenced radix-cached
        blocks (LRU) when the free list is dry — cached prefixes are a
        best-effort optimization and must never starve live requests."""
        bid = self._allocator.alloc()
        if bid is None and self._radix is not None and self._radix.evict(1):
            bid = self._allocator.alloc()
            self._publish_prefix_gauge()
        return bid

    def _ensure_blocks(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s allocation to cover ``tokens`` logical tokens.
        Returns False when the pool is exhausted (caller defers or fails)
        — rolling back any partial grab, so a waiting request can never
        strand blocks on an idle slot while live streams starve."""
        B = self.kv_block
        target = min(
            (min(tokens, self.max_len) + B - 1) // B,
            self._table_host.shape[1],
        )
        row = self._slot_blocks[slot]
        start_len = len(row)
        shortfall = (target - start_len) - self._allocator.n_free
        if shortfall > 0 and self._radix is not None:
            # Batch the pressure eviction: one LRU sweep for the whole
            # grow instead of a full-trie scan per allocated block (the
            # per-alloc evict(1) in _alloc_block stays as the fallback).
            if self._radix.evict(shortfall):
                self._publish_prefix_gauge()
        while len(row) < target:
            blk = self._alloc_block()
            if blk is None:
                while len(row) > start_len:  # rollback the partial grab
                    rb = row.pop()
                    self._table_host[slot, len(row)] = 0
                    self._allocator.decref(rb)
                return False
            self._table_host[slot, len(row)] = blk
            row.append(blk)
            self._table_dirty = True
        if self._metrics is not None and len(row) != start_len:
            self._metrics.set_gauge(
                "app_tpu_kv_blocks_free", self._allocator.n_free,
                "model", self.model_name,
            )
        return True

    def _release_blocks(
        self, slot: int, adopted: "frozenset[int] | set[int]" = frozenset()
    ) -> None:
        """Drop ``slot``'s references on its table row (skipping blocks
        whose reference the radix index just ADOPTED) and clear the row.
        Refcount-0 blocks return to the free list; blocks still aliased
        by other slots or cached in the index survive."""
        row = self._slot_blocks[slot]
        if row:
            for blk in row:
                if blk not in adopted:
                    self._allocator.decref(blk)
            self._slot_blocks[slot] = []
            self._table_host[slot, :] = 0
            self._table_dirty = True
        self._dispatched_tokens[slot] = 0

    def _cache_prompt_blocks(self, req: _GenRequest, slot: int) -> set[int]:
        """Insert a retiring request's now-immutable FULL prompt blocks
        into the radix index instead of freeing them (the automatic
        prefix cache's write path). Only blocks wholly covered by the
        prompt qualify — the boundary partial block and decode blocks
        carry generated tokens; and only a COMPLETED prefill is indexed
        (``effective_prompt_len`` is set at finalize). Returns the block
        ids whose reference the index adopted."""
        if req.prefix_store or req.effective_prompt_len <= 0:
            return set()
        if req.aid and req.lora_gen != self._lora_gen[req.aid]:
            # The adapter slot was reloaded since admission: these blocks
            # hold K/V from superseded weights — never index them.
            return set()
        row = self._slot_blocks[slot]
        n_full = min(len(req.prompt_ids) // self.kv_block, len(row))
        if n_full <= 0:
            return set()
        flags = self._radix.insert(
            req.prompt_ids, row[:n_full], req.aid
        )
        if req.aid and req.lora_gen != self._lora_gen[req.aid]:
            # load/unload_lora raced retirement: its generation bump
            # landed after the staleness check above, and its purge may
            # have run BEFORE our insert — leaving just-indexed blocks
            # that hold the superseded weights' K/V. The bump always
            # precedes the purge, so re-checking after the insert
            # catches every interleaving: purge the aid again ourselves.
            # Refcount accounting stays exact either way — the purge
            # consumes the index's reference for adopted blocks (so the
            # caller must still skip them) and the incumbent's for
            # duplicates (the caller still drops its own).
            self._radix.purge_aid(req.aid)
        return {row[j] for j, f in enumerate(flags) if f}

    def _alias_prefix_blocks(
        self, slot: int, req: _GenRequest, pids: list[int]
    ) -> int:
        """Admission-time zero-copy prefix hit: walk the radix index for
        the longest cached full-block prefix of ``pids``, alias those
        physical blocks into ``slot``'s table (refcount bump, no device
        copy), and return the token count the chunked prefill may skip.

        Boundary copy-on-write: when the cached prefix covers the ENTIRE
        prompt, the finalize chunk still re-writes the last prompt
        position (it samples the first token there), so the final
        aliased block is duplicated via ``paged_copy_block`` and the
        table points at the private copy — a slot never writes a block
        with refcount > 1. If no block is free for the copy, the last
        aliased block is simply surrendered and prefilled fresh."""
        radix = self._radix
        if radix is None or req.prefix_store:
            return 0
        # lookup returns with one allocator reference HELD per block
        # (taken under the radix lock, so a racing purge_aid cannot free
        # a block before we reference it); each reference transfers to
        # the slot's table below — blocks we end up not aliasing must be
        # decref'd here.
        blocks, matched = radix.lookup(pids, req.aid)
        self._prefix_lookups += 1
        hit = bool(blocks)
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_prefix_lookup_total",
                "model", self.model_name,
                "result", "hit" if hit else "miss",
            )
        if not hit:
            return 0
        B = self.kv_block
        for bid in blocks[self._table_host.shape[1]:]:
            self._allocator.decref(bid)  # beyond the slot table's width
        blocks = blocks[: self._table_host.shape[1]]
        matched = len(blocks) * B
        done = min(matched, len(pids) - 1)
        row = self._slot_blocks[slot]  # free slot → empty row
        for j, bid in enumerate(blocks):
            self._table_host[slot, j] = bid
            row.append(bid)
        self._table_dirty = True
        if done < matched:
            # Whole prompt cached: COW the boundary block the finalize
            # chunk will write into.
            src = row[-1]
            dst = self._alloc_block()
            if dst is None:
                row.pop()
                self._table_host[slot, len(row)] = 0
                self._allocator.decref(src)
                done = min(len(row) * B, len(pids) - 1)
            else:
                # Table upload can ride the next _push_table — the copy
                # only touches pool planes, not the table (compile-
                # tracked: the COW jit is one program per geometry).
                self.cache = self._paged_copy_block(
                    self.cache,
                    self._up(np.int32(src)),
                    self._up(np.int32(dst)),
                )
                self._cache_program("paged_copy_block")
                row[-1] = dst
                self._table_host[slot, len(row) - 1] = dst
                self._allocator.decref(src)
        return done

    def _release_slot(self, slot: int) -> None:
        """Free a slot and (paged mode) drop its block references —
        indexing finished prompts' full blocks in the radix cache first,
        so repeated prefixes admission-alias instead of re-prefilling."""
        seq = self._slots[slot]
        self._slots[slot] = None
        self._slot_state_dirty = True
        if self.kv_block:
            adopted: set[int] = set()
            if self._radix is not None and seq is not None:
                adopted = self._cache_prompt_blocks(seq.request, slot)
            self._release_blocks(slot, adopted)
        if self._metrics is not None and self.kv_block:
            self._metrics.set_gauge(
                "app_tpu_kv_blocks_free", self._allocator.n_free,
                "model", self.model_name,
            )
            self._publish_prefix_gauge()

    def _cache_program(self, name: str, out: Any = None) -> None:
        """A program on the cache just dispatched, for the device's
        timeline: ``out`` an output no later program donates, or ``None``
        (every output is a plane the next program donates) to fold it into
        the next one the timeline sees."""
        if self._loop_prof is not None:
            self._loop_prof.dispatched(name, out, self._obs.now())

    def _push_table(self) -> None:
        """Upload the block-table mirror if admission/top-up dirtied it."""
        if self.kv_block and self._table_dirty:
            self.cache = self.cache._replace(
                block_table=self._up(self._table_host)
            )
            self._table_dirty = False

    # ------------------------------------------------------------------
    # disaggregated prefill/decode tier (service/replica_pool.py)
    # ------------------------------------------------------------------

    def _apply_tier_imports(self) -> None:
        """Apply queued tier-transfer payloads (decode tier): write each
        shipped block into a freshly allocated pool block and insert it
        into the radix index under its content key — the transferred
        request (already requeued by ``handoff_prefilled``) then
        admission-aliases them zero-copy like any prefix hit. Runs on
        the scheduler thread only: the cache planes are donated to
        in-flight dispatches, so no other thread may touch them.
        Anything that cannot apply (no radix, geometry drift after a
        warm restart, pool dry) is dropped and the request simply
        re-prefills — the fused fallback, never a wrong answer."""
        while self._tier_imports:
            try:
                payload = self._tier_imports.popleft()
            except IndexError:  # raced handoff_prefilled's un-stash
                return
            self._import_payload(payload)
            # Release an import_payload(wait_s=...) caller parked on
            # this payload's apply (the pool's remote-source pull): the
            # latch is set AFTER the radix insert, so a submit that
            # follows the wait deterministically alias-hits.
            done = self._tier_import_done.pop(id(payload), None)
            if done is not None:
                done.set()
        self._apply_tier_exports()

    def _apply_tier_exports(self) -> None:
        """Service queued prefill-source export requests
        (``engine.export_cached``): walk the radix index for each asked
        token chain and lift the longest cached prefix to host as a
        shippable payload. Runs on the scheduler thread only — the
        lookup references stay held across the block extraction so
        pressure eviction cannot free the blocks mid-export, then every
        reference is surrendered (export copies bytes, it never adopts
        blocks). Any failure resolves the caller's latch with a miss —
        the asking pod re-prefills, never sees an error."""
        while self._tier_exports:
            try:
                ids_t, box, done = self._tier_exports.popleft()
            except IndexError:
                return
            try:
                payload = self._export_cached_now(list(ids_t))
            except Exception as exc:  # noqa: BLE001 — an export failure is a source miss, never a scheduler crash
                payload = None
                if self._logger is not None:
                    self._logger.warnf(
                        "tier-source export failed (%s: %s); answering "
                        "miss", type(exc).__name__, exc,
                    )
            if payload is not None:
                box.append(payload)
            done.set()

    def _export_cached_now(self, ids: "list[int]") -> Any:
        """The scheduler-thread half of ``export_cached``: radix lookup
        (references held), host-bounce the matched whole blocks, then
        surrender every lookup reference. None on a miss."""
        radix = self._radix
        if radix is None or not self.kv_block:
            return None
        B = self.kv_block
        chain, matched = radix.lookup(ids, 0)
        n = matched // B
        if n <= 0:
            for bid in chain:
                self._allocator.decref(bid)
            return None
        from gofr_tpu.ops.kv_cache import export_blocks

        try:
            return export_blocks(
                self.cache, chain[:n], ids[: n * B], src=self.model_name
            )
        finally:
            # Lookup references surrendered in full: the export shipped
            # COPIES, so the index alone decides how long the source
            # blocks stay cached.
            for bid in chain:
                self._allocator.decref(bid)

    def _import_payload(self, payload: Any) -> int:
        """One payload → pool blocks + radix entries; returns blocks
        actually imported (possibly a prefix of the payload: content
        already cached here is skipped, and a dry pool truncates the
        tail)."""
        radix = self._radix
        if radix is None or not self.kv_block:
            return 0
        if not payload.compatible_with(self.cache) or len(
            payload.token_ids
        ) != payload.n_blocks * payload.block:
            # Re-validated on the applying engine: a supervisor restart
            # between handoff and apply rebuilds the cache, and a
            # payload from a different model/quant geometry must never
            # alias into it. (The byte checksum was already verified at
            # handoff admission; in-proc payload memory cannot rot in
            # between, so only the geometry can go stale here.)
            if self._logger is not None:
                self._logger.warnf(
                    "tier import from %s rejected: stale or corrupt "
                    "payload (%d block(s)); request will re-prefill",
                    payload.src, payload.n_blocks,
                )
            return 0
        B = self.kv_block
        ids = list(payload.token_ids)
        # Chunks already cached here need no copy: walk the longest
        # cached prefix and import only the tail. The lookup references
        # stay HELD until after the insert below — surrendering them
        # first would let _alloc_block's pressure eviction free exactly
        # these nodes mid-import, and insert would then rebuild the
        # chain around stale (reused) block ids.
        chain, matched = radix.lookup(ids, 0)
        start = matched // B
        imported = 0
        from gofr_tpu.ops.kv_cache import DeviceKVPayload

        device_leg = isinstance(payload, DeviceKVPayload)
        for j in range(start, payload.n_blocks):
            bid = self._alloc_block()
            if bid is None:
                break  # pool dry: the un-imported tail re-prefills
            if device_leg:
                try:
                    self._write_block_device_leg(bid, payload, j)
                except Exception as exc:  # noqa: BLE001 — a failed write degrades to re-prefill, never kills the loop
                    # The write runs HERE, on the importing scheduler
                    # thread, after the transfer already returned — a
                    # cross-mesh device_put against a rebuilt mesh (or
                    # any placement failure) must degrade exactly like
                    # a rejected payload: surrender the fresh block,
                    # keep what already imported, and let the tail
                    # re-prefill. Escaping would crash the scheduler
                    # loop over a cache warm.
                    self._allocator.decref(bid)
                    if self._logger is not None:
                        self._logger.warnf(
                            "device-leg block write failed (%s: %s); "
                            "%d/%d block(s) imported, tail will "
                            "re-prefill",
                            type(exc).__name__, exc, imported,
                            payload.n_blocks,
                        )
                    break
            else:
                args = [
                    self.cache,
                    self._up(np.int32(bid)),
                    self._up(payload.k[:, j]),
                    self._up(payload.v[:, j]),
                ]
                if self.cache.k_s is not None and payload.k_s is not None:
                    args += [
                        self._up(payload.k_s[:, j]),
                        self._up(payload.v_s[:, j]),
                    ]
                self.cache = self._paged_insert_block(*args)
                self._cache_program("paged_insert_block")
            chain.append(bid)
            imported += 1
        n = start + imported
        if n:
            # insert() walks the existing prefix nodes (flag False —
            # the index keeps its own reference, OURS is surrendered
            # below) and ADOPTS the fresh tail blocks' references.
            # Nothing mutates the trie between the lookup above and
            # this insert — both run on the scheduler thread, and
            # purge_aid only ever targets LoRA slots, never aid 0.
            flags = radix.insert(ids[: n * B], chain[:n], 0)
            for j, adopted in enumerate(flags):
                if not adopted:
                    # j < start: drop the reference lookup handed us.
                    # j >= start (duplicate raced in): drop our fresh
                    # block — the incumbent wins.
                    self._allocator.decref(chain[j])
            self._publish_prefix_gauge()
        if self._metrics is not None:
            self._metrics.set_gauge(
                "app_tpu_kv_blocks_free", self._allocator.n_free,
                "model", self.model_name,
            )
        if self._logger is not None:
            self._logger.debugf(
                "tier import from %s: %d/%d block(s) imported (%d "
                "already cached)",
                payload.src, imported, payload.n_blocks, start,
            )
        return imported

    def _write_block_device_leg(self, bid: int, payload: Any, j: int) -> None:
        """Device-leg import of ONE shipped block: place the inbound
        device planes onto this pool's sharding (an explicit
        ``device_put`` — shard-to-shard over ICI/DMA when the meshes
        differ, a no-op when the exporting engine shares them) and
        write them in with the donated fixed-shape ``paged_move_block``.
        Never touches host memory — graftlint GL018 pins that (no
        ``device_get``/``np.asarray`` of cache planes in
        ``*_device_leg``/``paged_move*`` code)."""
        jax = self._jax
        k_blk = payload.k_blocks[j]
        v_blk = payload.v_blocks[j]
        if self._block_sharding is not None:
            k_blk = jax.device_put(k_blk, self._block_sharding)
            v_blk = jax.device_put(v_blk, self._block_sharding)
        args = [self.cache, self._up(np.int32(bid)), k_blk, v_blk]
        if self.cache.k_s is not None and payload.k_s_blocks is not None:
            k_s_blk = payload.k_s_blocks[j]
            v_s_blk = payload.v_s_blocks[j]
            if self._block_sharding is not None:
                k_s_blk = jax.device_put(k_s_blk, self._block_sharding)
                v_s_blk = jax.device_put(v_s_blk, self._block_sharding)
            args += [k_s_blk, v_s_blk]
        self.cache = self._paged_move_block(*args)
        self._cache_program("paged_move_block")

    def _export_payload_device_leg(
        self, block_ids: "list[int]", token_ids: "list[int]"
    ) -> Any:
        """Device-leg extraction: lift each finished block's planes out
        of this pool as fresh DEVICE arrays (one fixed-shape jitted
        gather per block — one compile per cache geometry, GSPMD-aware
        so a tp-sharded pool extracts shard-local slices) and wrap them
        with the same content keys / geometry fingerprint the
        host-bounce payload carries. The planes never visit host memory
        (GL018); everything host-side — keys, fingerprint, radix
        bookkeeping — is identical to the host leg."""
        from gofr_tpu.ops.kv_cache import DeviceKVPayload, cache_geometry

        ks: "list[Any]" = []
        vs: "list[Any]" = []
        kss: "list[Any]" = []
        vss: "list[Any]" = []
        for bid in block_ids:
            k_blk, v_blk, k_s_blk, v_s_blk = self._paged_extract_block(
                self.cache, self._up(np.int32(bid))
            )
            self._cache_program("paged_extract_block", k_blk)
            ks.append(k_blk)
            vs.append(v_blk)
            if k_s_blk is not None:
                kss.append(k_s_blk)
                vss.append(v_s_blk)
        return DeviceKVPayload(
            block=self.kv_block,
            token_ids=tuple(int(t) for t in token_ids),
            k_blocks=tuple(ks),
            v_blocks=tuple(vs),
            k_s_blocks=tuple(kss) if kss else None,
            v_s_blocks=tuple(vss) if vss else None,
            src=self.model_name,
            geometry=cache_geometry(self.cache),
        )

    def _export_prefilled(self, slot: int, req: _GenRequest) -> bool:
        """Prefill-tier export: offer a just-finalized prefill to the
        pool's transfer exporter instead of decoding locally. True →
        the pool placed the request on a decode replica; the slot's
        blocks are indexed into the LOCAL radix (the next request with
        this prefix aliases instead of re-prefilling) and released.
        False → the caller decodes locally, the fused fallback — a
        collapsed decode tier degrades to today's serving, never drops
        a request. Probe requests (``pin_replica``) and LoRA requests
        always decode locally (a probe must measure THIS replica;
        adapter weights live per-engine)."""
        if (
            self.tier_role != "prefill"
            or self._tier_exporter is None
            or req.pin_replica
            or req.prefix_store
            or req.aid
            # Requests carrying already-delivered tokens (failover
            # continuations that landed here) decode locally: tier
            # export ships FRESH prefills.
            or req.token_ids
        ):
            return False

        def make_payload(leg: str = "host") -> Any:
            # Called by the pool AFTER its cheap gates (hop cap, tier
            # mode, deadline) with the transfer leg it selected: the
            # extraction is the expensive part, and a collapsed decode
            # tier must not pay it per request. Runs synchronously on
            # this thread while the slot's blocks are still held.
            # ``leg="device"`` extracts device-resident block planes
            # (zero host copies); anything else is the deliberate host
            # bounce the wire and host legs ship.
            if not self.kv_block:
                return None
            B = self.kv_block
            row = self._slot_blocks[slot]
            n_full = min(len(req.prompt_ids) // B, len(row))
            if n_full <= 0:
                return None
            if leg == "device":
                return self._export_payload_device_leg(
                    row[:n_full], req.prompt_ids[: n_full * B]
                )
            from gofr_tpu.ops.kv_cache import export_blocks

            return export_blocks(
                self.cache, row[:n_full],
                req.prompt_ids[: n_full * B],
                src=self.model_name,
            )

        try:
            # Fault seam: the prefill replica failing at the prefill→
            # transfer boundary (extraction crash, device loss right
            # after finalize).
            faults.fire("tier.prefill_done", engine=self, request=req)
            placed = bool(self._tier_exporter(req, make_payload))
        except Exception as exc:  # noqa: BLE001 — every export failure has a local fallback
            if self._logger is not None:
                self._logger.errorf(
                    "tier export failed (%s: %s); decoding locally",
                    type(exc).__name__, exc,
                )
            placed = False
        if not placed:
            return False
        if self.kv_block:
            # Warm the local radix with the full prompt blocks before
            # releasing the slot (reads only immutable request fields —
            # the decode replica owns the mutable ones by now), so the
            # prefill tier's repeated-prefix traffic aliases instead of
            # re-prefilling.
            adopted: set[int] = set()
            if self._radix is not None:
                row = self._slot_blocks[slot]
                n_full = min(len(req.prompt_ids) // self.kv_block, len(row))
                if n_full > 0:
                    flags = self._radix.insert(
                        req.prompt_ids, row[:n_full], 0
                    )
                    adopted = {
                        row[j] for j, f in enumerate(flags) if f
                    }
            self._release_blocks(slot, adopted)
            if self._metrics is not None:
                self._metrics.set_gauge(
                    "app_tpu_kv_blocks_free", self._allocator.n_free,
                    "model", self.model_name,
                )
                self._publish_prefix_gauge()
        return True

    def _radix_watermark_sweep(self) -> None:
        """Proactive prefix-cache eviction (``TPU_PREFIX_EVICT_WM``):
        keep at least the watermark's worth of pool blocks FREE by
        sweeping LRU radix entries once per loop iteration, so
        admission under pressure finds free blocks waiting instead of
        paying a synchronous pre-evict scan inside its own grow. 0
        (default) = off: eviction happens only on allocation shortfall,
        exactly the pre-watermark behavior.

        The EFFECTIVE watermark is resolved at boot: the explicit
        block-count knob when set, else derived from the HBM ledger's
        headroom target (``TPU_PREFIX_EVICT_HBM_FRAC`` — keep
        frac×budget of device HBM free, converted to blocks via the
        pool's bytes-per-block)."""
        wm = self.effective_evict_watermark
        if not wm or self._radix is None:
            return
        short = wm - self._allocator.n_free
        if short <= 0:
            return
        # Fruitless-sweep latch: when nothing was evictable (every
        # cached leaf still aliased by live slots), re-scanning the
        # whole trie every loop iteration is pure hot-path overhead —
        # skip until the free count or the cache composition changes.
        sig = (self._allocator.n_free, self._radix.n_cached_blocks)
        if sig == self._wm_fruitless:
            return
        if self._radix.evict(short):
            self._wm_fruitless = None
            self._publish_prefix_gauge()
            if self._metrics is not None:
                self._metrics.set_gauge(
                    "app_tpu_kv_blocks_free", self._allocator.n_free,
                    "model", self.model_name,
                )
        else:
            self._wm_fruitless = sig

    def _dispatch_prefill_chunk(self, lap_import: bool = False) -> int:
        """Admit pending requests into free slots and dispatch ONE
        [rows, prefill_chunk] chunk step, ``rows`` the smallest rung of
        ``prefill_rungs`` that holds the rows that wait (at most
        ``prefill_batch``).
        ``lap_import`` is True only on the scheduler pass's first
        (seam) call: the loop profiler's tier_import stamp belongs to
        that one — see the lap site below.

        Each row advances one slot's prompt by up to ``prefill_chunk``
        tokens; rows whose prompt completes sample their first token and
        merge it into the decode token vector ON DEVICE (no host roundtrip
        between prefill and decode). Returns the prompt rows of the step
        it dispatched, 0 if there was none to dispatch.
        """
        # Disaggregated-tier imports (shipped KV blocks → radix index)
        # apply HERE, immediately ahead of the admission pops, so a
        # just-transferred request's alias walk hits its own shipped
        # blocks instead of re-prefilling them (a payload landing after
        # its request was popped still applies next call — the request
        # just pays a redundant prefill, never a wrong answer).
        if self.kv_block:
            # Tier-import apply is its own loop phase: shipped-block
            # writes are device work that would otherwise hide inside
            # "prefill" (one stamp per apply, not per block). Only the
            # PASS-SEAM call laps — re-entries from the wave-admission
            # loop would otherwise attribute prefill work to tier_import
            # and invert the host-overhead diagnosis.
            with loop_phase(
                self._loop_prof if lap_import else None, "tier_import"
            ):
                self._apply_tier_imports()
        # Admission is host bookkeeping only — the device work is the
        # chunk steps that follow.
        free = [
            i for i, s in enumerate(self._slots)
            if s is None and i not in self._prefilling
        ]
        while free and (self._wait_kv or not self._pending.empty()):
            if self._wait_kv:
                req = self._wait_kv.popleft()
            else:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                self._note_dequeued(req)
            # Admission-time lifecycle check: a request that was
            # cancelled or whose deadline expired while queued must not
            # occupy a KV slot at all.
            if self._reap_request(req):
                continue
            if req.aid and req.lora_gen != self._lora_gen[req.aid]:
                # The adapter slot was reloaded/unloaded while this
                # request sat in the queue — its stamp no longer matches,
                # so admitting it would run under weights the caller
                # never asked for. Prefix registrations resolve -1 (their
                # documented stale-store outcome); generate requests fail
                # loudly.
                if not req.future.done():
                    if req.prefix_store:
                        req.future.set_result(-1)
                    else:
                        req.future.set_exception(RuntimeError(
                            f"LoRA adapter slot {req.aid} was reloaded or "
                            "unloaded while this request was queued; "
                            "resubmit against the current adapter set"
                        ))
                req.stream.put(None)
                self._obs_finish(req, "error", "lora_reloaded")
                continue
            # Replay-aware admission: a request the supervisor carried
            # across a restart re-prefills prompt + already-delivered
            # tokens (prefill_ids), so decode resumes at exactly the
            # next token. Fresh requests: prefill_ids IS the prompt.
            pids = req.prefill_ids()
            # Clamp generation budget so pipelined-window overshoot can't
            # overrun the cache (admission-time guard; see
            # _dispatch_window). Done BEFORE any block allocation so the
            # replay-complete early-retire below cannot strand pool
            # blocks on a slot it never occupies.
            room = (
                self.max_len - 1 - len(req.prompt_ids)
                - (self.pipeline_depth + 1) * self.window_k
            )
            req.max_new_tokens = max(1, min(req.max_new_tokens, room))
            if req.replayed_tokens >= req.max_new_tokens:
                # The clamp (or the original budget) is already covered
                # by the tokens delivered before the restart: the request
                # is complete — retire it with the full result instead of
                # prefilling a slot to generate nothing.
                seq = _ActiveSeq(request=req, last_token=req.token_ids[-1])
                self._retire(-1, seq)
                continue
            cached_done = 0
            if self.kv_block:
                # A request bigger than the ENTIRE pool can never be
                # admitted — fail it now instead of deadlocking the
                # admission queue behind it forever.
                B = self.kv_block
                need = (min(len(pids) + 1, self.max_len) + B - 1) // B
                if need > self.cache.n_blocks - 1:
                    if not req.future.done():
                        req.future.set_exception(RuntimeError(
                            f"prompt needs {need} KV blocks but the pool "
                            f"has {self.cache.n_blocks - 1}; raise "
                            f"TPU_KV_POOL_BLOCKS"
                        ))
                    req.stream.put(None)
                    self._obs_finish(req, "error", "kv_pool_too_small")
                    continue
                # Automatic prefix cache (TPU_AUTO_PREFIX): alias the
                # longest cached full-block prefix into the slot's table
                # — zero-copy — and chunk-prefill only the remainder.
                cached_done = self._alias_prefix_blocks(free[0], req, pids)
                # Cover the prompt + the first decode token now; windows
                # top up ahead of dispatch. Pool dry → hold the request
                # back (retirements will refill the free list), dropping
                # any aliased references so cached blocks never strand
                # on a slot the request does not occupy.
                if not self._ensure_blocks(
                    free[0], len(pids) + 1
                ):
                    # Unconditional: aliasing may have seeded the row
                    # (even a COW'd block on a zero-length hit), and a
                    # deferred request must leave the slot's row empty.
                    self._release_blocks(free[0])
                    self._wait_kv.appendleft(req)
                    break
                self._dispatched_tokens[free[0]] = 0
            slot = free.pop(0)
            self._seeds_host[slot] = req.seed
            # Sampling-counter offset: a replayed request resumes its
            # counter-based sample path at the delivered-token count
            # (fresh requests start at 0), so non-greedy streams carried
            # across a restart continue byte-identically.
            self._noff_host[slot] = req.replayed_tokens
            self._aids_host[slot] = req.aid
            self._bidx_host[slot, :] = -1
            self._bval_host[slot, :] = 0.0
            for j, (tok, bv) in enumerate(req.logit_bias.items()):
                self._bidx_host[slot, j] = tok
                self._bval_host[slot, j] = bv
            self._seeds_dirty = True
            state = _PrefillState(request=req, ids=pids)
            if cached_done:
                # Aliased blocks already hold these positions' K/V;
                # done < len(pids) always (the clamp in
                # _alias_prefix_blocks), so the finalize chunk still
                # runs and samples the first token — re-writing the
                # boundary position lands in a COW'd or fresh block.
                state.done = cached_done
            if self._prefix_pool is not None and not req.prefix_store:
                # Per-adapter pools: pooled K/V is a function of the
                # weights that prefilled it, so a request only reuses a
                # prefix registered under its OWN adapter.
                idx, plen = self._prefix_pool.lookup(pids, req.aid)
                if idx >= 0:
                    # Copy pooled KV rows in; prefill only the remainder.
                    # done < len(prompt) always, so the final chunk still
                    # runs and samples the first token (re-writing the
                    # boundary token's K/V is idempotent).
                    self.cache = self._prefix_pool.load(
                        self.cache, idx, slot, plen
                    )
                    self._cache_program("prefix_load")
                    state.done = min(plen, len(pids) - 1)
                    if self._metrics is not None:
                        self._metrics.increment_counter(
                            "app_tpu_prefix_hits", "model", self.model_name
                        )
            self._prefilling[slot] = state
            if req.aid and req.lora_gen != self._lora_gen[req.aid]:
                # load/unload_lora raced this admission: the generation
                # bump landed after the queue-pop staleness check above,
                # and its in-flight failure snapshot may have run before
                # this request became visible in _prefilling. Now that
                # it IS visible, one of the two sides must catch it —
                # re-validate here so aliased blocks holding the OLD
                # weights' K/V are surrendered instead of decoded
                # against, failing the request exactly like the
                # queue-pop path.
                del self._prefilling[slot]
                if self.kv_block:
                    self._release_blocks(slot)
                free.insert(0, slot)
                if not req.future.done():
                    if req.prefix_store:
                        req.future.set_result(-1)
                    else:
                        req.future.set_exception(RuntimeError(
                            f"LoRA adapter slot {req.aid} was reloaded "
                            "or unloaded while this request was being "
                            "admitted; resubmit against the current "
                            "adapter set"
                        ))
                req.stream.put(None)
                self._obs_finish(req, "error", "lora_reloaded")
                continue
            # Observability: admission is now CERTAIN (every reject path
            # above `continue`d) — stamp the queue-wait end. One clock
            # read per admitted request, admission-rate not token-rate,
            # shared by the timeline and the tenant ledger.
            tl = req.timeline
            led = self._tenant_ledger
            if tl is not None or led is not None:
                now_adm = self._obs.now()
                if tl is not None:
                    tl.mark_admitted(now_adm)
                if led is not None:
                    led.note_admitted(req, now_adm)
            if cached_done:
                # Count hit tokens only once admission is CERTAIN —
                # a pool-dry deferral re-runs the alias walk on
                # re-admission (double-counting the same hit), and the
                # staleness re-check above can still reject outright.
                self._prefix_hit_tokens += cached_done
                if tl is not None:
                    tl.note_prefix_hit(cached_done)
                if self._metrics is not None:
                    self._metrics.add_counter(
                        "app_tpu_prefix_hit_tokens_total", cached_done,
                        "model", self.model_name,
                    )
        if not self._prefilling:
            return 0
        # Fault seam: a raise here is a device failure at prefill
        # dispatch — the scheduler's death drain must fail every caller.
        faults.fire("scheduler.device_step", engine=self, kind="prefill")
        self._check_superseded()
        # Host-side dispatch count (exactly one chunk step leaves this
        # method per True return): the prefix-cache tests assert a warm
        # request takes strictly fewer steps.
        self._prefill_chunk_steps += 1
        if self._seeds_dirty:
            # Upload the admission-scoped planes BEFORE the dispatch:
            # the step reads _aids_dev, and a stale plane would prefill
            # with the slot's PREVIOUS occupant's adapter.
            self._seeds_dev = self._up(self._seeds_host)
            self._noff_dev = self._up(self._noff_host)
            self._bidx_dev = self._up(self._bidx_host)
            self._bval_dev = self._up(self._bval_host)
            self._aids_dev = self._up(self._aids_host)
            self._seeds_dirty = False

        P, c = self.prefill_batch, self.prefill_chunk
        rows = list(self._prefilling.items())[:P]

        # Every compiled row is computed in full, so the step runs at
        # the smallest rung that holds the rows that wait.
        R = next(r for r in self.prefill_rungs if r >= len(rows))
        tokens = np.zeros((R, c), dtype=np.int32)
        slots = np.zeros((R,), dtype=np.int32)
        starts = np.zeros((R,), dtype=np.int32)
        lens = np.zeros((R,), dtype=np.int32)
        finalize = np.zeros((R,), dtype=bool)
        row_valid = np.zeros((R,), dtype=bool)
        temps = np.ones((R,), dtype=np.float32)
        topps = np.ones((R,), dtype=np.float32)
        greedy = np.ones((R,), dtype=bool)
        for i, (slot, st) in enumerate(rows):
            ids = st.ids
            chunk = ids[st.done : st.done + c]
            tokens[i, : len(chunk)] = chunk
            slots[i] = slot
            starts[i] = st.done
            lens[i] = len(chunk)
            finalize[i] = st.done + len(chunk) >= len(ids)
            row_valid[i] = True
            temps[i] = max(st.request.temperature, 0.0)
            topps[i] = st.request.top_p
            greedy[i] = st.request.temperature <= 0
        for i in range(len(rows), R):
            # Padding rows duplicate row 0: identical K/V writes to the
            # same cache positions are idempotent, and row_valid=False
            # keeps them out of the finalize merge.
            tokens[i] = tokens[0]
            slots[i], starts[i], lens[i] = slots[0], starts[0], lens[0]
            temps[i], greedy[i], topps[i] = temps[0], greedy[0], topps[0]

        # A blocked prefill attention runs each row over its own blocks of
        # positions: the block-steps this step runs, over what running every
        # row to the longest row's block would (padding rows cost row 0's).
        visit_ratio = (
            chunk_visit_ratio(starts, lens, self.prefill_attn_block)
            if self.prefill_attn_block else None
        )
        jnp = self._jnp
        t0m = self._obs.now()
        self._push_table()
        args = self._prefill_operands(
            tokens, slots, starts, lens, finalize, row_valid,
            temps, greedy, topps,
        )
        # Static compile choice: the no-bias program has no bias scatter
        # at all (each variant compiles once, then caches).
        use_bias = any(
            st.request.logit_bias for _, st in rows
        )
        # Locals-then-commit around the dispatch (zombie fence; see
        # _dispatch_window).
        (ccache, ctoks, clps, first_dev,
         first_lp_dev, cpc, cnst,
         cti, ctl, ftopi_dev, ftopl_dev, moe_dev) = self._prefill_step(
            R, use_bias
        )(*args)
        self._check_superseded()
        if moe_dev is not None:
            # The step's route counts ride back beside its first tokens:
            # an async copy started here, read when it has landed.
            moe_dev.copy_to_host_async()
            self._moe_counts.append(
                (moe_dev, len(rows), int(lens[: len(rows)].sum()))
            )
        self.cache, self._tokens_dev, self._logps_dev = ccache, ctoks, clps
        self._pcounts_dev, self._nsteps_dev = cpc, cnst
        self._topi_dev, self._topl_dev = cti, ctl
        if self._lockstep:
            self._jax.block_until_ready(first_dev)  # graftlint: disable=GL019 — multi-process CPU lockstep barrier (gloo collective ordering), a deliberate device wait
        if self._metrics is not None:
            self._metrics.record_histogram(
                "app_tpu_batch_size", len(rows), "batcher", "prefill"
            )
            self._metrics.increment_counter(
                "app_tpu_prefill_steps_total",
                "model", self.model_name, "rows", str(R),
            )
            self._count_moe_product("prefill_chunk", R)
            if self.cfg.is_hybrid and not starts[: len(rows)].all():
                # A prompt's first chunk reads zeros for its slot's state.
                self._metrics.add_counter(
                    "app_tpu_state_resets_total",
                    int((starts[: len(rows)] == 0).sum()),
                    "model", self.model_name,
                )
            # How much of the [R, c] step that ran was prompt: the rest
            # of its R x c token rows is padding the device computes
            # anyway.
            self._metrics.record_histogram(
                "app_tpu_prefill_fill_ratio",
                float(lens[: len(rows)].sum()) / (R * c),
                "model", self.model_name,
            )
            if visit_ratio is not None:
                self._metrics.record_histogram(
                    "app_tpu_prefill_attn_visit_ratio", visit_ratio,
                    "model", self.model_name,
                )

        emits_started = False
        # One clock read per chunk DISPATCH (window granularity); the
        # per-row loop below only copies it into timelines.
        t1m = self._obs.now()
        if self._loop_prof is not None:
            self._loop_prof.dispatched("prefill_chunk", first_dev, t1m)
        for i, (slot, st) in enumerate(rows):
            st.done += int(lens[i])
            tl = st.request.timeline
            if tl is not None:
                tl.note_chunk(t0m, t1m, int(lens[i]), R, visit_ratio)
            if finalize[i]:
                if tl is not None:
                    tl.mark_prefill_done(t1m)
                st.request.effective_prompt_len = st.done
                del self._prefilling[slot]
                if st.request.prefix_store:
                    # Park the rows in the pool instead of decoding; the
                    # slot goes straight back to the free list. A prefix
                    # whose adapter was reloaded/unloaded while this
                    # prefill was in flight prefilled under the WRONG
                    # weights — drop it (resolve -1) instead of
                    # registering stale K/V under a reusable slot id.
                    r_aid = st.request.aid
                    if r_aid and st.request.lora_gen != self._lora_gen[r_aid]:
                        if not st.request.future.done():
                            st.request.future.set_result(-1)
                    else:
                        idx = self._prefix_pool.store(
                            st.request.prompt_ids, self.cache, slot,
                            r_aid,
                        )
                        self._cache_program("prefix_store")
                        if not st.request.future.done():
                            st.request.future.set_result(idx)
                    st.request.stream.put(None)
                elif (
                    st.request.aid
                    and st.request.lora_gen
                    != self._lora_gen[st.request.aid]
                ):
                    # Generate request whose adapter slot was reloaded
                    # after admission (the admission stamp check and
                    # load_lora's in-flight snapshot bracket a tiny
                    # check-then-insert window on the scheduler thread;
                    # this finalize-time re-check closes it). It must
                    # not start decoding under weights the caller never
                    # asked for.
                    if not st.request.future.done():
                        st.request.future.set_exception(RuntimeError(
                            f"LoRA adapter slot {st.request.aid} was "
                            "reloaded while this request was prefilling; "
                            "resubmit against the current adapter set"
                        ))
                    st.request.stream.put(None)
                    self._release_slot(slot)
                else:
                    if self._export_prefilled(slot, st.request):
                        # Disaggregated tier: the pool placed this
                        # request's decode phase on a decode replica
                        # (KV blocks shipped or re-prefilling there);
                        # the slot is free again for the next prefill.
                        continue
                    seq = _ActiveSeq(request=st.request, last_token=-1)
                    self._slots[slot] = seq
                    self._slot_state_dirty = True
                    # Early first-token emission: the chunk step SAMPLED this
                    # row's first token on device — fetch it asynchronously
                    # and emit the moment it lands (~prefill + one-way RTT)
                    # instead of after the first decode window drains through
                    # the pipeline (~3 windows).
                    if not emits_started:
                        emits_started = True
                        fetches = [first_dev, first_lp_dev]
                        if self.top_logprobs:
                            fetches += [ftopi_dev, ftopl_dev]
                        for arr in fetches:
                            arr.copy_to_host_async()
                    self._prefill_emits.append(
                        (first_dev, first_lp_dev, ftopi_dev, ftopl_dev, i,
                         slot, seq)
                    )
        self._update_slot_gauges()
        return len(rows)

    def _flush_prefill_emits(self) -> None:
        """Emit first tokens whose async prefill fetch has landed.

        Non-blocking (``is_ready`` poll); each entry emits at most once —
        if a decode window's processing got there first (the loaded case),
        the entry is dropped.
        """
        self._flush_moe_counts()
        if not self._prefill_emits:
            return
        self._check_superseded()
        # One host materialization per DEVICE ARRAY per flush: entries
        # from the same chunk dispatch share their fetched arrays, and
        # np.asarray inside the per-entry loop re-copied the full array
        # once per emitting row per window. Keyed by id() — the arrays
        # are alive for the duration of this pass (held by `entries`).
        host_cache: dict[int, np.ndarray] = {}

        def pull(arr: Any) -> np.ndarray:
            h = host_cache.get(id(arr))
            if h is None:
                # Landed (is_ready) + started async at dispatch: a copy,
                # not a sync.
                h = np.asarray(arr)  # graftlint: disable=GL001
                host_cache[id(arr)] = h
            return h

        keep = []
        # One timestamp pair per FLUSH, shared by every entry that emits
        # in it (per-row clock reads in this loop were exactly the host
        # overhead graftlint GL011 exists to flag; entries in one flush
        # landed together, so a shared stamp loses nothing).
        now = time.time()
        now_m = self._obs.now()
        handoff = self._loop_prof is not None
        for entry in self._prefill_emits:
            first_dev, lp_dev, ftopi_dev, ftopl_dev, row, slot, seq = entry
            req = seq.request
            # The window emission path won the race (token already out),
            # or the request is gone — nothing to do.
            if req.future.done() or req.token_ids or seq.first_emitted:
                continue
            # Cancelled/expired between finalize and this flush: retire
            # NOW instead of emitting a first token to a caller that
            # already gave up (the reap releases the slot too).
            if self._reap_request(
                req, slot=slot if self._slots[slot] is seq else -1
            ):
                continue
            try:
                if not first_dev.is_ready():
                    keep.append(entry)
                    continue
            except AttributeError:  # fake/CPU backends: always ready
                pass
            tok = int(pull(first_dev)[row])
            lp = float(pull(lp_dev)[row])
            top = None
            if self.top_logprobs and req.top_logprobs:
                ti = pull(ftopi_dev)[row]
                tl = pull(ftopl_dev)[row]
                top = [
                    (int(ti[j]), float(tl[j]))
                    for j in range(req.top_logprobs)
                ]
            req.ttft_s = now - req.enqueued_at
            seq.first_token_at = now
            seq.first_emitted = True
            if req.timeline is not None:
                req.timeline.mark_first_token(now_m)
            if handoff:
                req.stream.handed = now_m
            seq.last_token = tok
            seq.n_generated += 1
            self._emit_token(seq, tok, lp, top)
            if self._finished(seq):
                self._retire(slot, seq)
                if self._slots[slot] is seq:
                    self._release_slot(slot)
        self._prefill_emits = keep

    def _count_routes(self, held: float, tokens: int) -> None:
        """``tokens`` computed tokens' routes, ``held`` of them on experts
        that live here, into ``app_tpu_moe_routes_total{where}``."""
        routes = (
            tokens * self.cfg.n_moe_layers * self.cfg.n_experts_active
        )
        for where, n in (("held", held), ("absent", routes - held)):
            self._metrics.add_counter(
                "app_tpu_moe_routes_total", n,
                "model", self.model_name, "where", where,
            )

    def _count_sparse_queries(
        self, program: str, selected: float, tokens: int
    ) -> None:
        """``tokens`` computed tokens of a hybrid stack, ``selected`` of
        them past the dense length, into
        ``app_tpu_sparse_attn_queries_total{branch, program}``: a query is
        a computed token x a sparse layer."""
        layers = self.cfg.n_sparse_layers
        for branch, n in (("selected", selected), ("dense", tokens - selected)):
            self._metrics.add_counter(
                "app_tpu_sparse_attn_queries_total", n * layers,
                "model", self.model_name, "branch", branch,
                "program", program,
            )

    def _count_moe_product(self, program: str, rows: int) -> None:
        """One dispatched step of an expert model, under the product its
        expert layers ran (``programs.moe_products``): the share of steps in
        which the grouped product engaged."""
        product = self.moe_products.get((program, rows))
        if product is not None and self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_moe_product_steps_total", "model", self.model_name,
                "product", product, "program", program,
            )

    def _flush_moe_counts(self) -> None:
        """Record the route counts of the prefill steps whose async copy
        has landed (steps whose expert layers ran grouped): one record of
        the step's expert load ratio and, where the engine may hold a
        share of the experts, the routes that landed on held experts
        against all the step's routes."""
        while self._moe_counts:
            counts, n_rows, tokens = self._moe_counts[0]
            try:
                if not counts.is_ready():
                    return
            except AttributeError:  # fake/CPU backends: always ready
                pass
            self._moe_counts.popleft()
            if self._metrics is None:
                continue
            host = np.asarray(counts)  # graftlint: disable=GL001 — landed (is_ready): a copy, not a sync
            if self.cfg.is_hybrid:  # [rows]: queries past the dense length
                self._count_sparse_queries(
                    "prefill_chunk", float(host[:n_rows].sum()), tokens
                )
                continue
            if self.cfg.counts_routes:
                self._count_routes(float(host[:n_rows].sum()), tokens)
            self._metrics.record_histogram(
                "app_tpu_moe_expert_load_ratio", float(host[-1]),
                "model", self.model_name,
            )

    def _dispatch_window(self) -> tuple:
        """Dispatch one k-step device window (non-blocking) and start the
        async device→host copy of its emitted [2, k, S] block. Returns
        ``(emitted_dev, slots_snapshot, etops_dev_or_None,
        held, longest)`` for _process_window — the snapshot matters
        because by processing time a retired slot may already hold a NEW
        request admitted in between."""
        # Fault seam: a raise models the device failing a decode window;
        # an armed action that blocks models a hung step (watchdog).
        faults.fire("scheduler.device_step", engine=self, kind="decode")
        self._check_superseded()
        jnp = self._jnp
        if self._slot_state_dirty:
            # Slot composition changed since the last window: re-upload the
            # [n_slots] state vectors once. Steady-state windows skip this —
            # dispatch is then pure device work, no H2D copies at all.
            active = np.zeros((self.n_slots,), dtype=bool)
            temps = np.ones((self.n_slots,), dtype=np.float32)
            topps = np.ones((self.n_slots,), dtype=np.float32)
            greedy = np.ones((self.n_slots,), dtype=bool)
            fpen = np.zeros((self.n_slots,), dtype=np.float32)
            ppen = np.zeros((self.n_slots,), dtype=np.float32)
            for i, seq in enumerate(self._slots):
                if seq is not None:
                    active[i] = True
                    temps[i] = max(seq.request.temperature, 0.0)
                    topps[i] = seq.request.top_p
                    greedy[i] = seq.request.temperature <= 0
                    fpen[i] = seq.request.frequency_penalty
                    ppen[i] = seq.request.presence_penalty
            self._active_dev = self._up(active)
            self._temps_dev = self._up(temps)
            self._topp_dev = self._up(topps)
            self._greedy_dev = self._up(greedy)
            if self.enable_penalties:
                self._fpen_dev = self._up(fpen)
                self._ppen_dev = self._up(ppen)
            self._slot_state_dirty = False

        use_bias = any(
            seq is not None and seq.request.logit_bias
            for seq in self._slots
        )

        if self.kv_block:
            # Allocation must stay AHEAD of the window about to be
            # dispatched (its writes land before the host sees the
            # tokens). A dry pool mid-stream fails the request — the
            # honest outcome of an oversubscribed pool.
            for i, seq in enumerate(self._slots):
                if seq is None:
                    continue
                req = seq.request
                base = req.effective_prompt_len or len(req.prompt_ids)
                need = (
                    base + self._dispatched_tokens[i] + self.window_k + 1
                )
                if self._ensure_blocks(i, need):
                    self._dispatched_tokens[i] += self.window_k
                    continue
                if not req.future.done():
                    req.future.set_exception(RuntimeError(
                        "KV block pool exhausted mid-generation "
                        "(raise TPU_KV_POOL_BLOCKS or lower concurrency)"
                    ))
                req.stream.put(None)
                self._obs_finish(req, "error", "kv_pool_exhausted")
                self._release_slot(i)
            self._push_table()

        # Cache positions that are context when this window starts, a live
        # slot each: its prompt plus what earlier windows already cover; and
        # the longest of them, which decides how much of every slot the
        # window's dense attention reads.
        held: list[int] = []
        longest = 0
        for i, seq in enumerate(self._slots):
            if seq is not None:
                req = seq.request
                held.append(
                    (req.effective_prompt_len or len(req.prompt_ids))
                    + seq.tokens_in_flight - 1
                )
                # A hybrid cache's dense read serves the slots under the
                # dense length only; the others gather their chosen blocks.
                if not (self.cfg.is_hybrid
                        and held[-1] >= self.cfg.sparse_dense_len):
                    longest = max(longest, held[-1])
                seq.tokens_in_flight += self.window_k
        # Results land in LOCALS first and commit to self only after a
        # superseded check: a dispatch that BLOCKED here (a hung device
        # step — the exact case the supervisor abandons threads over) must not
        # overwrite the restarted engine's live cache/planes when its
        # stuck call finally returns.
        (emitted, etops, toks, lps, cache, nst, pc, ti, tl) = (
            self._decode_window(
                self.params, self._tokens_dev, self._logps_dev,
                self.cache, self._active_dev, self._nsteps_dev,
                self._temps_dev, self._greedy_dev, self._topp_dev,
                self._fpen_dev, self._ppen_dev, self._pcounts_dev,
                self._seeds_dev, self._bidx_dev, self._bval_dev,
                self._topi_dev, self._topl_dev, self._aids_dev,
                k=self.window_k, use_bias=use_bias,
            )
        )
        self._check_superseded()
        self._tokens_dev, self._logps_dev = toks, lps
        self.cache, self._nsteps_dev = cache, nst
        self._pcounts_dev, self._topi_dev, self._topl_dev = pc, ti, tl
        self._count_moe_product("decode_window", self.n_slots)
        if etops is not None and not any(
            seq is not None and seq.request.top_logprobs
            for seq in self._slots
        ):
            # Nobody asked for alternatives: skip the [2, k, S, K]
            # device→host block entirely (the program computes it either
            # way; the fetch is what costs on the dispatch path).
            etops = None
        emitted.copy_to_host_async()
        if etops is not None:
            etops.copy_to_host_async()
        if self._lockstep:
            lockcheck.note_device_sync("lockstep_block_until_ready")
            self._jax.block_until_ready(emitted)
        return emitted, list(self._slots), etops, held, longest

    def _process_window(
        self,
        emitted: Any,
        snapshot: "list[Optional[_ActiveSeq]]",
        etops: Any,
        held: "list[int]",
        longest: int,
    ) -> None:
        # Interruptible wait: while this window's block is in flight, flush
        # any prefill first-token fetches that land first (unloaded TTFT
        # would otherwise be gated on the window fetch).
        if self._prefill_emits and hasattr(emitted, "is_ready"):
            while not emitted.is_ready():
                self._flush_prefill_emits()
                # Device-readiness poll: there is no host-side event to
                # wait on for an in-flight device computation, and the
                # 1 ms granularity is what lets prefill emits interleave
                # with the window fetch. Not a latency-adding sleep.
                time.sleep(0.001)  # graftlint: disable=GL004
        lockcheck.note_device_sync("decode_window_fetch")
        # Named in the profiler's trace: the one place this thread
        # blocks on the device, beside the device's own ops.
        with self._jax.profiler.TraceAnnotation("window_fetch"):
            emitted_host = np.asarray(emitted)
        # The fetch above is this loop's other blocking point (a hung
        # device step stalls HERE, not only at dispatch): if the supervisor
        # abandoned this thread while it was stuck, the token block in
        # hand belongs to the OLD engine — emitting it would duplicate
        # tokens on replayed streams and release slots/blocks of the
        # restarted scheduler's allocator.
        self._check_superseded()
        etops_host = np.asarray(etops) if etops is not None else None

        now = time.time()
        mono_now = self._obs.now()  # shared by every row in this window
        # The entry layer times each stream's hand-off from this stamp:
        # one attribute write a row, before the row's puts.
        handoff = self._loop_prof is not None
        for i, seq in enumerate(snapshot):
            if seq is None:
                continue
            if seq.request.future.done():
                # Retired by an earlier window's processing (overshoot
                # tokens — drop), or cancelled by the caller mid-flight:
                # free the slot or it would stay active forever.
                if self._slots[i] is seq:
                    seq.request.stream.put(None)
                    # Overshoot after a normal retirement is already
                    # summarized (the timeline latch makes this a
                    # no-op); a caller-cancelled live generation gets
                    # its terminal record here.
                    self._obs_finish(
                        seq.request,
                        "cancelled" if seq.request.future.cancelled()
                        else "ok",
                    )
                    self._release_slot(i)
                    # A future in CANCELLED state (not resolved) means the
                    # caller abandoned a live generation — count it here
                    # because this release races the lifecycle reap and
                    # whichever runs first frees the slot. (cancel() on a
                    # completed future is a no-op, so normal retirements
                    # whose token trips afterwards never miscount.)
                    if (
                        seq.request.future.cancelled()
                        and self._metrics is not None
                    ):
                        self._metrics.increment_counter(
                            "app_tpu_requests_cancelled_total",
                            "model", self.model_name,
                        )
                continue
            if seq.request.ttft_s == 0.0:
                seq.request.ttft_s = now - seq.request.enqueued_at
                seq.first_token_at = now
                if seq.request.timeline is not None:
                    seq.request.timeline.mark_first_token(mono_now)
            want_top = (
                etops_host is not None and seq.request.top_logprobs
            )
            if handoff:
                seq.request.stream.handed = mono_now
            for step in range(self.window_k):
                if seq.first_emitted and not seq.first_skip_done:
                    # This position repeats the prefill-sampled token
                    # that _flush_prefill_emits already emitted.
                    seq.first_skip_done = True
                    continue
                tok = int(emitted_host[0, step, i])
                top = None
                if want_top:
                    top = [
                        (int(etops_host[0, step, i, j]),
                         float(etops_host[1, step, i, j]))
                        for j in range(seq.request.top_logprobs)
                    ]
                seq.last_token = tok
                seq.n_generated += 1
                self._emit_token(
                    seq, tok, float(emitted_host[1, step, i]), top
                )
                if self._finished(seq):
                    self._retire(i, seq)
                    if self._slots[i] is seq:
                        self._release_slot(i)
                    break
        if self._metrics is not None:
            # Per-WINDOW observability (one record each per processed
            # window, from host values already in hand — no per-token
            # work, no device pulls).
            dispatched_live = sum(1 for s in snapshot if s is not None)
            if emitted_host.shape[0] > 2 and dispatched_live:
                # A grouped expert layer's third plane: each slot's routes
                # on held experts at each step. Every live slot computed
                # all window_k steps, whatever was emitted of them.
                live = [i for i, s in enumerate(snapshot) if s is not None]
                if self.cfg.is_hybrid:
                    # Two planes: the positions each slot's query attended
                    # through the choice of blocks at each step (0 where it
                    # took the dense read), and its context there.
                    attended = emitted_host[2][:, live]
                    self._count_sparse_queries(
                        "decode_window", float((attended > 0).sum()),
                        dispatched_live * self.window_k,
                    )
                    if attended.any():
                        self._metrics.record_histogram(
                            "app_tpu_sparse_attn_read_ratio",
                            float(attended.sum())
                            / float(emitted_host[3][:, live].sum()),
                            "model", self.model_name,
                        )
                else:
                    self._count_routes(
                        float(emitted_host[2][:, live].sum()),
                        dispatched_live * self.window_k,
                    )
            # How full the batch is now (the gauge), and how full this
            # window ran — the slots live when it was dispatched — as a
            # histogram whose sum over count between two scrapes is the
            # mean over exactly the windows in between.
            in_use = sum(1 for s in self._slots if s is not None)
            self._metrics.set_gauge(
                "app_tpu_batch_occupancy",
                in_use / max(1, self.n_slots),
                "model", self.model_name,
            )
            self._metrics.record_histogram(
                "app_tpu_window_occupancy",
                dispatched_live / max(1, self.n_slots),
                "model", self.model_name,
            )
            # The share of the cache the dense decode path reads
            # (every position of every slot) that was context and
            # not reserve when this window was dispatched.
            self._metrics.record_histogram(
                "app_tpu_kv_live_ratio",
                sum(held) / max(1, self.n_slots * self.max_len),
                "model", self.model_name,
            )
            # The share of the cache that the window's last step read,
            # where each slot stands window_k - 1 positions further on. A
            # latent cache's kernel: each live slot's own blocks
            # (ops/attention.latent_decode_block), an inactive slot none.
            # Otherwise the device's own rule
            # (ops/attention.decode_read_index) over the longest live
            # slot, the same rung of every slot. 1.0: the whole cache.
            block = self.decode_read_block
            if block:
                read = sum(
                    -(-(n + self.window_k - 1) // block) * block
                    for n in held
                ) / (self.n_slots * self.max_len)
            else:
                rungs = self.decode_read_rungs
                read = rungs[
                    decode_read_index(rungs, longest + self.window_k - 1)
                ] / rungs[-1]
            self._metrics.record_histogram(
                "app_tpu_decode_read_ratio", read, "model", self.model_name,
            )
        self._update_slot_gauges()

    def _emit_token(
        self,
        seq: _ActiveSeq,
        tok: int,
        logprob: float,
        top: "Optional[list[tuple[int, float]]]" = None,
    ) -> None:
        req = seq.request
        if req.replay_skip > 0:
            # Exact-replay regeneration phase: this token was already
            # delivered to the client before the restart — swallow the
            # re-generated copy instead of duplicating it on the
            # stream. The walk is deterministic (counter-based
            # sampling), so a mismatch means the replay landed on a
            # different engine seed/params — log it, the stream stays
            # consistent with what was already delivered.
            idx = len(req.token_ids) - req.replay_skip
            if (
                self._logger is not None
                and 0 <= idx < len(req.token_ids)
                and req.token_ids[idx] != tok
            ):
                self._logger.warnf(
                    "exact replay diverged at position %d (%d != %d); "
                    "do the pool's replicas share TPU_SEED?",
                    idx, tok, req.token_ids[idx],
                )
            req.replay_skip -= 1
            return
        if seq.request.top_logprobs:
            seq.request.token_top_logprobs.append(top)
        seq.request.token_ids.append(tok)
        seq.request.token_logprobs.append(logprob)
        seq.request.stream.put(tok)
        # Aggregate-throughput sample feeding projected-wait shedding
        # (engine._throughput_tps): every emission across every slot
        # counts, so the estimate is the batch's rate, not one stream's.
        self._tput.note(1)
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_tokens_generated", "model", self.model_name
            )

    def _finished(self, seq: _ActiveSeq) -> bool:
        req = seq.request
        eos = self.tokenizer.eos_id if self.tokenizer is not None else -1
        if req.stop_on_eos and req.token_ids and req.token_ids[-1] == eos:
            return True
        if req.stop_texts and self.tokenizer is not None:
            text = self.tokenizer.decode(req.token_ids)
            at = min(
                (p for p in (text.find(s) for s in req.stop_texts) if p != -1),
                default=-1,
            )
            if at != -1:
                req.stop_cut = at
                return True
        if len(req.token_ids) >= req.max_new_tokens:
            return True
        prompt_len = req.effective_prompt_len or len(req.prompt_ids)
        # Context-length guard. After a replay, effective_prompt_len
        # already covers the pre-restart tokens (they were re-prefilled),
        # so subtract them from the generated count or the sum would
        # double-count and retire the stream early.
        return (
            prompt_len + len(req.token_ids) - req.replayed_tokens
            >= self.max_len - 1
        )

    def _retire(self, slot: int, seq: _ActiveSeq) -> None:
        req = seq.request
        text = self.tokenizer.decode(req.token_ids) if self.tokenizer else ""
        ids, lps = list(req.token_ids), list(req.token_logprobs)
        tops = list(req.token_top_logprobs) if req.top_logprobs else None
        eos = self.tokenizer.eos_id if self.tokenizer is not None else -1
        if req.stop_cut >= 0:
            # Stop sequence: trim the text at the match and the token/
            # logprob lists to the longest prefix whose decode fits the
            # kept text, so text and logprobs stay aligned.
            text = text[: req.stop_cut]
            keep = 0
            for i in range(1, len(ids) + 1):
                if len(self.tokenizer.decode(ids[:i])) <= req.stop_cut:
                    keep = i
                else:
                    break
            ids, lps = ids[:keep], lps[:keep]
            if tops is not None:
                tops = tops[:keep]
            reason = "stop"
        elif req.stop_on_eos and ids and ids[-1] == eos:
            reason = "stop"
        else:
            reason = "length"  # token budget or context window exhausted
        result = GenerationResult(
            text=text,
            token_ids=ids,
            prompt_tokens=len(req.prompt_ids),
            ttft_s=req.ttft_s,
            duration_s=time.time() - req.enqueued_at,
            truncated=req.truncated,
            token_logprobs=lps,
            finish_reason=reason,
            token_top_logprobs=tops,
            # Deliberate brownout truncation: advertised ONLY when the
            # clamp actually cut the answer short (finish_reason
            # "length") — a stream that hit EOS inside the clamped
            # budget was not truncated by policy.
            brownout=req.brownout_clamped and reason == "length",
        )
        # Summarize BEFORE resolving: a caller that sees the result is
        # guaranteed the flight-recorder entry, histogram records, and
        # spans already exist (the deterministic-test contract; the work
        # is host-side bookkeeping plus a non-blocking exporter enqueue).
        if req.timeline is not None:
            req.timeline.finish("ok", reason, output_tokens=len(ids))
        if self._tenant_ledger is not None:
            self._tenant_ledger.finish_request(req, "ok")
        if not req.future.done():
            req.future.set_result(result)
        req.stream.put(None)  # stream sentinel (after the result resolves)

    def _update_slot_gauges(self) -> None:
        if self._metrics is None:
            return
        in_use = sum(1 for s in self._slots if s is not None)
        self._metrics.set_gauge("app_tpu_kv_slots_in_use", in_use, "model", self.model_name)
        self._metrics.set_gauge(
            "app_tpu_queue_depth", self._pending.qsize(), "batcher", "generate"
        )
        # Saturation signals (device_telemetry): headroom is O(1)
        # arithmetic over the allocator's free count; occupancy and
        # fragmentation are two divisions. All host values already in
        # hand — no device pulls, window granularity.
        self._metrics.set_gauge(
            "app_tpu_hbm_headroom_ratio", self.hbm_headroom_ratio(),
            "model", self.model_name,
        )
        if self.kv_block:
            total, used, cached = self._kv_pool_counts()
            self._metrics.set_gauge(
                "app_tpu_kv_pool_occupancy_ratio", used / max(1, total),
                "model", self.model_name,
            )
            # The used pool's radix-cached (reclaimable-under-pressure)
            # share: high occupancy + high fragmentation = pressure the
            # eviction watermark can relieve; high occupancy + LOW
            # fragmentation = live streams genuinely need the blocks.
            self._metrics.set_gauge(
                "app_tpu_kv_pool_fragmentation_ratio",
                (cached / used) if used else 0.0,
                "model", self.model_name,
            )
        try:
            # This engine's own device(s): replica i of a pool is not
            # on chip 0.
            for dev in self.devices:
                stats = dev.memory_stats() or {}
                if "bytes_in_use" in stats:
                    self._metrics.set_gauge(
                        "app_tpu_hbm_used_bytes", stats["bytes_in_use"],
                        "chip", str(dev.id),
                    )
        except Exception:  # graftlint: disable=GL006 — gauge-only path; memory_stats support varies by backend and must never touch token flow
            pass

