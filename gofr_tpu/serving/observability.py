"""Request-lifecycle observability: tracing, phase metrics, flight recorder.

Five layers of machinery now sit between a socket and a token
(admission → queue → chunked prefill → radix-cache alias → decode →
emit, with supervisor replay and replica failover underneath), and until
this module the only latency number a request ever exported was a single
``ttft_s`` field. This is the layer every later perf PR is measured
through; it owns three things:

* **Tracing** — every generation carries a :class:`RequestTimeline`
  whose trace id is adopted from the incoming W3C ``traceparent``
  (HTTP header / gRPC metadata) or minted at submit. Child spans for
  queue-wait, admission (with shed outcome), each prefill chunk,
  emit-flush, and decode — plus instant spans for supervisor replays
  and replica-pool failover/hedge hops — are emitted **once, at
  retirement**, from the timeline's already-collected host timestamps,
  so tracing adds zero work to the scheduler's dispatch path and the
  spans stitch into one trace across replicas (``HTTPReplica``
  propagates ``traceparent`` downstream).
* **Phase metrics** — histograms ``app_tpu_queue_wait_seconds``,
  ``app_tpu_prefill_seconds``, ``app_tpu_ttft_seconds``,
  ``app_tpu_inter_token_seconds``, ``app_tpu_e2e_seconds``: exactly ONE
  ``record`` per request per phase, computed at retirement from
  host-side timestamps already in hand. Never per token, never a new
  host↔device pull (graftlint GL006/GL010/GL011 stay clean).
  Time to first token is also split where it is spent (see
  :meth:`RequestTimeline.phases`): ``entry`` → ``queue_wait`` →
  ``prefill_wait`` → ``prefill_dispatch`` → ``first_token_wait`` →
  ``delivery``, six phases that sum to ``first_written − received``
  exactly, each its own histogram.
* **Flight recorder** — a fixed-size ring of per-request timelines
  (phase durations, token counts, prefix-cache hit tokens,
  shed/cancel/replay/failover annotations, trace id) served at
  ``/debug/flight`` on the ops port. Slow and errored requests are
  **pinned** into a separate bounded ring so a burst of healthy traffic
  cannot evict the interesting ones.

Overhead contract: with the layer off (``TPU_FLIGHT_RECORDER=0``, no
metrics manager, no active trace exporter) ``begin`` returns ``None``
and every scheduler hook is a single ``is not None`` check. With it on,
the per-request cost is one small object, a handful of monotonic clock
reads at *window* granularity, and one deferred summarization at
retirement — measured <2% tok/s on the CPU-fallback bench A/B.

Determinism: the clock is injectable (this package's standing contract —
tests state time instead of sleeping) and the flight recorder assigns
monotonic request ids, so eviction/pinning tests are exact.
"""

from __future__ import annotations


import time
from collections import deque
from typing import Any, Callable, Optional

from gofr_tpu.analysis import lockcheck
from gofr_tpu.tracing import get_tracer
from gofr_tpu.tracing.tracer import Tracer, _rand_hex, current_span


def parse_traceparent(tp: str) -> tuple[Optional[str], Optional[str]]:
    """W3C ``traceparent`` string → (trace_id, span_id), (None, None)
    when malformed — same validation as ``tracing.extract_traceparent``
    but for a bare value instead of a header dict."""
    parts = (tp or "").split("-")
    if len(parts) == 4 and len(parts[1]) == 32 and len(parts[2]) == 16:
        return parts[1], parts[2]
    return None, None


def tracer_active(tracer: Optional[Tracer] = None) -> bool:
    """True when completed spans actually go somewhere (an exporter that
    is not the no-op) — span construction is skipped entirely
    otherwise."""
    t = tracer or get_tracer()
    exporter = getattr(t, "_exporter", None)
    return exporter is not None and not getattr(exporter, "is_noop", False)


def emit_instant_span(
    name: str,
    traceparent: Optional[str],
    attributes: Optional[dict[str, Any]] = None,
) -> None:
    """Emit a zero-duration span (a trace *annotation*: hedge hops and
    similar events that are not tied to a request timeline). No-op
    without an active exporter or a parseable ``traceparent``."""
    tracer = get_tracer()
    if not tracer_active(tracer):
        return
    trace_id, parent_id = (
        parse_traceparent(traceparent) if traceparent else (None, None)
    )
    if trace_id is None:
        span = current_span()
        if span is None:
            return
        trace_id, parent_id = span.trace_id, span.span_id
    now_ns = time.time_ns()
    tracer.emit_span(
        name,
        trace_id=trace_id,
        parent_span_id=parent_id,
        start_ns=now_ns,
        end_ns=now_ns,
        attributes=attributes,
    )


def emit_boot_span(
    name: str,
    start_ns: int,
    end_ns: int,
    attributes: Optional[dict[str, Any]] = None,
) -> None:
    """Emit a completed boot-phase span (``tpu.shard_init`` and kin):
    engine construction has no request to ride, so the span joins the
    ambient trace when one is active (an app booting under a traced
    startup hook) and otherwise mints its own trace id — an operator
    asking "why did boot take 40s" still finds the mesh-build/param-
    sharding window. No-op without an active exporter."""
    tracer = get_tracer()
    if not tracer_active(tracer):
        return
    span = current_span()
    trace_id = span.trace_id if span is not None else _rand_hex(16)
    parent_id = span.span_id if span is not None else None
    tracer.emit_span(
        name,
        trace_id=trace_id,
        parent_span_id=parent_id,
        start_ns=start_ns,
        end_ns=end_ns,
        attributes=attributes,
    )


class RequestTimeline:
    """One request's host-side lifecycle record.

    Written by the scheduler thread at window granularity (every method
    takes the timestamp as an argument — the caller reads the clock once
    per window/chunk, never per row; graftlint GL011). Annotations
    (replay, failover) may arrive from supervisor/pool threads;
    ``finish`` is latched under a lock so exactly one summarization
    happens no matter which terminal path wins a race.
    """

    __slots__ = (
        "hub", "rid", "trace_id", "parent_span_id", "received", "enqueued",
        "wall_ns_base", "mono_base", "admitted", "admissions",
        "prefill_done", "first_token", "first_written", "done", "outcome",
        "finish_reason",
        "chunks", "annotations", "transfers", "prompt_tokens",
        "output_tokens", "prefix_hit_tokens", "replays", "tenant",
        "_lock", "_finished",
    )

    def __init__(
        self,
        hub: "RequestObservability",
        rid: int,
        trace_id: str,
        parent_span_id: Optional[str],
        enqueued: float,
        wall_ns_base: int,
        prompt_tokens: int,
        tenant: str = "",
        received: Optional[float] = None,
    ) -> None:
        self.hub = hub
        self.rid = rid
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        # When the HTTP handler took the request up, before tokenisation
        # (the hub's clock). None for a request that came in by gRPC,
        # pubsub or /v1/batches: it has no entry phase.
        self.received = received
        self.enqueued = enqueued
        # Wall↔monotonic anchor pair: phases are measured monotonic (NTP
        # steps must not skew durations), spans need wall-clock ns.
        self.wall_ns_base = wall_ns_base
        self.mono_base = enqueued
        self.admitted: Optional[float] = None
        self.admissions = 0
        self.prefill_done: Optional[float] = None
        self.first_token: Optional[float] = None
        # After the SSE handler's first token chunk went out (set by
        # the handler's task, not the scheduler thread).
        self.first_written: Optional[float] = None
        self.done: Optional[float] = None
        self.outcome = ""
        self.finish_reason = ""
        # (start, end, tokens, rows, attn_visit_ratio) per dispatched
        # prefill chunk step: this request's tokens in it, the row count
        # it ran at, and ``note_chunk``'s share of block-steps (or None).
        self.chunks: list[
            tuple[float, float, int, int, Optional[float]]
        ] = []
        # (name, t, attrs) — shed/replay/failover events.
        self.annotations: list[tuple[str, float, dict[str, Any]]] = []
        # (source, target, start, end, result) — disaggregated-tier KV
        # transfers between the prefill and decode phases; rendered as
        # a `tpu.transfer` span with real duration, unlike the instant
        # annotations above.
        self.transfers: list[tuple[str, str, float, float, str, str]] = []
        self.prompt_tokens = prompt_tokens
        self.output_tokens = 0
        self.prefix_hit_tokens = 0
        self.replays = 0
        # The submitting tenant (X-Tenant-Id), carried so finalize can
        # feed per-tenant SLO overrides (serving/slo.py) without a
        # second measurement path.
        self.tenant = tenant
        self._lock = lockcheck.make_lock("RequestTimeline._lock")
        self._finished = False

    # -- scheduler-thread marks (timestamps passed in; see class doc) --

    def mark_admitted(self, now: float) -> None:
        if self.admitted is None:
            self.admitted = now
        self.admissions += 1

    def note_prefix_hit(self, tokens: int) -> None:
        self.prefix_hit_tokens += tokens

    def note_chunk(
        self, start: float, end: float, tokens: int, rows: int,
        attn_visit_ratio: Optional[float] = None,
    ) -> None:
        """One prefill chunk step this request rode: its dispatch times,
        the request's tokens in it, the step's row count and, where the
        prefill attention is blocked, the share of rows x the longest
        row's blocks that the step's rows visited."""
        self.chunks.append((start, end, tokens, rows, attn_visit_ratio))

    def mark_prefill_done(self, now: float) -> None:
        if self.prefill_done is None:
            self.prefill_done = now

    def mark_first_token(self, now: float) -> None:
        if self.first_token is None:
            self.first_token = now

    def mark_first_written(self) -> None:
        """The streaming handler wrote the first token's chunk. Called
        from the handler's task once per token; only the first call
        reads the clock."""
        if self.first_written is None:
            self.first_written = self.hub.now()

    # -- cross-thread annotations --------------------------------------

    def annotate(
        self, name: str, now: float, **attrs: Any
    ) -> None:
        self.annotations.append((name, now, attrs))

    def note_replay(self, mode: str, now: float) -> None:
        self.replays += 1
        self.annotate("tpu.replay", now, mode=mode)

    def note_failover(self, src: str, dst: str, now: float) -> None:
        self.annotate("tpu.failover", now, source=src, target=dst)

    def note_transfer(
        self,
        src: str,
        dst: str,
        start: float,
        end: float,
        result: str,
        leg: str = "host",
    ) -> None:
        """One disaggregated-tier KV transfer hop (prefill replica →
        decode replica), recorded from the pool's transfer thread —
        shows up in /debug/flight and as a `tpu.transfer` child span
        between the prefill and decode phases of the request's ONE
        trace. ``leg`` names the rung that carried the blocks (dma /
        device / wire / host; "none" for hops that shipped nothing,
        e.g. a failover fallback). Remote prefill-SOURCE pulls record
        here too — result ``source_hit`` / ``source_miss`` /
        ``source_rejected`` / ``source_error`` with ``leg`` naming the
        pull rung (dma / wire) — so the whole pull descent shows on the
        same trace as the request it warmed."""
        self.transfers.append((src, dst, start, end, result, leg))

    def traceparent(self) -> str:
        """The W3C header a downstream hop (wire-leg tier transfer,
        remote adoption) forwards so its spans join THIS request's
        trace. The span-id field names the caller's parent span when
        one was adopted, else a fresh id — trace-id continuity is the
        contract; the parent link is best-effort, exactly like any
        cross-host hop."""
        return (
            f"00-{self.trace_id}-"
            f"{self.parent_span_id or _rand_hex(8)}-01"
        )

    # -- terminal ------------------------------------------------------

    def finish(
        self,
        outcome: str,
        finish_reason: str = "",
        output_tokens: Optional[int] = None,
    ) -> None:
        """Latched terminal summarization: histograms (one record per
        phase), deferred span emission, flight-recorder entry. Safe to
        call from any terminal path — retire, lifecycle reap, drain,
        supervisor fail — exactly the first call wins."""
        with self._lock:
            if self._finished:
                return
            self._finished = True
        self.done = self.hub.now()
        self.outcome = outcome
        self.finish_reason = finish_reason
        if output_tokens is not None:
            self.output_tokens = output_tokens
        self.hub.finalize(self)

    @property
    def finished(self) -> bool:
        return self._finished

    # -- rendering -----------------------------------------------------

    def wall_ns(self, t: float) -> int:
        return self.wall_ns_base + int((t - self.mono_base) * 1e9)

    def phases(self) -> dict[str, float]:
        """Durations (seconds) of the completed phases; a phase the
        request never reached is simply absent.

        ``entry_s``, ``queue_wait_s``, ``prefill_wait_s``,
        ``prefill_dispatch_s``, ``first_token_wait_s`` and
        ``delivery_s`` are consecutive differences of the marks
        received → enqueued → admitted → first chunk's dispatch start →
        prefill_done → first_token → first_written, so together they
        are ``first_written − received`` exactly, under any clock."""
        out: dict[str, float] = {}
        if self.received is not None:
            out["entry_s"] = self.enqueued - self.received
        if self.admitted is not None:
            out["queue_wait_s"] = self.admitted - self.enqueued
            if self.chunks:
                first_chunk = self.chunks[0][0]
                out["prefill_wait_s"] = first_chunk - self.admitted
                if self.prefill_done is not None:
                    # One chunk step a pass, a window fetch between
                    # them: the first step's start to the last one's
                    # dispatch.
                    out["prefill_dispatch_s"] = (
                        self.prefill_done - first_chunk
                    )
        if self.prefill_done is not None and self.admitted is not None:
            out["prefill_s"] = self.prefill_done - self.admitted
            if self.first_token is not None:
                # The last chunk queued behind the in-flight windows,
                # its own compute, and the wait for the next
                # _flush_prefill_emits poll.
                out["first_token_wait_s"] = (
                    self.first_token - self.prefill_done
                )
        if self.first_token is not None:
            out["ttft_s"] = self.first_token - self.enqueued
            if self.first_written is not None:
                out["delivery_s"] = self.first_written - self.first_token
        if self.done is not None and self.first_token is not None:
            decode_s = self.done - self.first_token
            out["decode_s"] = decode_s
            if self.output_tokens >= 2:
                out["inter_token_s"] = decode_s / (self.output_tokens - 1)
        if self.done is not None:
            out["e2e_s"] = self.done - self.enqueued
        return out

    def to_dict(self) -> dict[str, Any]:
        """The flight-recorder / ``/debug/flight`` entry."""
        return {
            "rid": self.rid,
            "trace_id": self.trace_id,
            "outcome": self.outcome,
            "finish_reason": self.finish_reason,
            "enqueued_unix": self.wall_ns_base / 1e9,
            "phases": {
                k: round(v, 6) for k, v in self.phases().items()
            },
            "prompt_tokens": self.prompt_tokens,
            "output_tokens": self.output_tokens,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefill_chunks": len(self.chunks),
            "replays": self.replays,
            "transfers": [
                {
                    "source": src,
                    "target": dst,
                    "duration_s": round(end - start, 6),
                    "result": result,
                    "leg": leg,
                }
                for src, dst, start, end, result, leg in self.transfers
            ],
            "annotations": [
                {
                    "name": name,
                    "t_offset_s": round(t - self.enqueued, 6),
                    **{k: str(v) for k, v in attrs.items()},
                }
                for name, t, attrs in self.annotations
            ],
        }


class FlightRecorder:
    """Fixed-size ring of retired request timelines, with slow/errored
    ones pinned into their own bounded ring so a burst of healthy
    traffic cannot evict the requests worth looking at."""

    def __init__(
        self,
        capacity: int = 256,
        pin_capacity: int = 64,
        slow_s: float = 5.0,
    ) -> None:
        self.capacity = max(1, int(capacity))
        self.pin_capacity = max(1, int(pin_capacity))
        self.slow_s = float(slow_s)
        self._lock = lockcheck.make_lock("FlightRecorder._lock")
        self._ring: deque[dict[str, Any]] = deque(maxlen=self.capacity)
        self._pinned: deque[dict[str, Any]] = deque(
            maxlen=self.pin_capacity
        )

    def record(self, entry: dict[str, Any], pin: bool) -> None:
        with self._lock:
            if pin:
                self._pinned.append(entry)
            else:
                self._ring.append(entry)

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "capacity": self.capacity,
                "pin_capacity": self.pin_capacity,
                "slow_s": self.slow_s,
                "records": list(self._ring),
                "pinned": list(self._pinned),
            }


#: Histogram names, registered in ``container.register_framework_metrics``.
PHASE_HISTOGRAMS = {
    "entry_s": "app_tpu_entry_seconds",
    "queue_wait_s": "app_tpu_queue_wait_seconds",
    "prefill_wait_s": "app_tpu_prefill_wait_seconds",
    "prefill_dispatch_s": "app_tpu_prefill_dispatch_seconds",
    "first_token_wait_s": "app_tpu_first_token_wait_seconds",
    "delivery_s": "app_tpu_delivery_seconds",
    "prefill_s": "app_tpu_prefill_seconds",
    "ttft_s": "app_tpu_ttft_seconds",
    "inter_token_s": "app_tpu_inter_token_seconds",
    "e2e_s": "app_tpu_e2e_seconds",
}


class RequestObservability:
    """Per-engine observability hub: mints timelines at submit, owns the
    flight recorder, and turns finished timelines into histogram records
    and spans. A timeline keeps a reference to the hub that minted it,
    so a request adopted by a sibling replica (failover) still lands in
    its origin's recorder exactly once."""

    def __init__(
        self,
        model_name: str,
        *,
        metrics: Any = None,
        recorder: Optional[FlightRecorder] = None,
        clock: Callable[[], float] = time.monotonic,
        wall_ns: Callable[[], int] = time.time_ns,
        passes: int = 1,
        model_attrs: Optional[dict] = None,
    ) -> None:
        self.model_name = model_name
        # Times the layer stack runs a forward (a looped model's n_passes):
        # an attribute of the spans whose device time scales with it.
        self.passes = passes
        # Further attributes of the same two spans, from the model's config
        # (an expert share's experts_held and router_width, a latent
        # cache's cache_row); none for a model without them.
        self.model_attrs = dict(model_attrs or {})
        self._metrics = metrics
        self.recorder = recorder
        self._clock = clock
        self._wall_ns = wall_ns
        self._seq_lock = lockcheck.make_lock("RequestObservability._seq_lock")
        self._seq = 0
        # SLO evaluation (serving/slo.py): when the engine configures
        # objectives, finalize feeds every retired timeline's outcome
        # and phases into the burn-rate engine — the PR 6 phase records
        # ARE the SLO input, no second measurement path.
        self.slo: Any = None

    def now(self) -> float:
        return self._clock()

    def note_handoff(self, handed: float) -> None:
        """A stream's chunk went out; ``handed`` is when the scheduler
        had that window's tokens in hand (``TokenStream.handed``): the
        executor or loop read, the tokenizer's decode and the write, one
        record a window a stream."""
        if self._metrics is not None:
            self._metrics.record_histogram(
                "app_tpu_token_handoff_seconds", self._clock() - handed,
                "model", self.model_name,
            )

    def begin(
        self,
        prompt_tokens: int,
        traceparent: Optional[str] = None,
        tenant: str = "",
        received: Optional[float] = None,
    ) -> Optional[RequestTimeline]:
        """Mint a timeline for a submitting request, adopting the trace
        context from ``traceparent``, then from the calling task's
        current span, then minting a fresh trace id. Returns None when
        the whole layer is off (no recorder, no metrics, no active
        exporter) so the scheduler hooks cost one ``is not None``."""
        if (
            self.recorder is None
            and self._metrics is None
            and self.slo is None
            and not tracer_active()
        ):
            return None
        trace_id: Optional[str] = None
        parent_id: Optional[str] = None
        if traceparent:
            trace_id, parent_id = parse_traceparent(traceparent)
        if trace_id is None:
            span = current_span()
            if span is not None:
                trace_id, parent_id = span.trace_id, span.span_id
        if trace_id is None:
            trace_id = _rand_hex(16)
        with self._seq_lock:
            self._seq += 1
            rid = self._seq
        return RequestTimeline(
            self, rid, trace_id, parent_id,
            enqueued=self._clock(),
            wall_ns_base=self._wall_ns(),
            prompt_tokens=prompt_tokens,
            tenant=tenant,
            received=received,
        )

    def note_shed(
        self, timeline: Optional[RequestTimeline], reason: str
    ) -> None:
        """Admission rejected the request (429/503/504 before a slot):
        close its timeline with the shed outcome — the recorder pins it,
        and the trace shows an admission span with the outcome."""
        if timeline is None:
            return
        timeline.annotate("tpu.shed", self.now(), reason=reason)
        timeline.finish("shed", finish_reason=reason)

    # -- terminal summarization ---------------------------------------

    def finalize(self, timeline: RequestTimeline) -> None:
        """Called exactly once per timeline (from ``finish``): histogram
        records, deferred span emission, flight-recorder entry."""
        phases = timeline.phases()
        if self._metrics is not None:
            for key, metric in PHASE_HISTOGRAMS.items():
                if key in phases:
                    self._metrics.record_histogram(
                        metric, phases[key], "model", self.model_name
                    )
        if self.slo is not None:
            # Burn-rate input (serving/slo.py): the retired request's
            # outcome + phases, judged at request granularity — with
            # the tenant so per-tenant overrides see it too.
            self.slo.observe(
                timeline.outcome, phases, tenant=timeline.tenant
            )
        tracer = get_tracer()
        if tracer_active(tracer):
            self._emit_spans(tracer, timeline, phases)
        if self.recorder is not None:
            e2e = phases.get("e2e_s", 0.0)
            pin = (
                timeline.outcome not in ("ok",)
                or e2e > self.recorder.slow_s
            )
            self.recorder.record(timeline.to_dict(), pin)

    def _emit_spans(
        self,
        tracer: Tracer,
        tl: RequestTimeline,
        phases: dict[str, float],
    ) -> None:
        """One ``tpu.request`` span (child of the transport span when a
        traceparent came in) with phase children — all from timestamps
        already collected, nothing touched the dispatch path."""
        done = tl.done if tl.done is not None else tl.enqueued
        root = tracer.emit_span(
            "tpu.request",
            trace_id=tl.trace_id,
            parent_span_id=tl.parent_span_id,
            start_ns=tl.wall_ns(tl.enqueued),
            end_ns=tl.wall_ns(done),
            attributes={
                "tpu.model": self.model_name,
                "tpu.outcome": tl.outcome,
                "tpu.prompt_tokens": tl.prompt_tokens,
                "tpu.output_tokens": tl.output_tokens,
                "tpu.replays": tl.replays,
            },
            status="OK" if tl.outcome == "ok" else "ERROR",
        )
        pid = root.span_id

        def child(
            name: str, start: float, end: float, **attrs: Any
        ) -> None:
            tracer.emit_span(
                name,
                trace_id=tl.trace_id,
                parent_span_id=pid,
                start_ns=tl.wall_ns(start),
                end_ns=tl.wall_ns(end),
                attributes=attrs,
            )

        if tl.received is not None:
            child("tpu.entry", tl.received, tl.enqueued)
        if tl.admitted is not None:
            child("tpu.queue_wait", tl.enqueued, tl.admitted)
            child(
                "tpu.admission", tl.admitted, tl.admitted,
                outcome="admitted",
                prefix_hit_tokens=tl.prefix_hit_tokens,
            )
            if tl.chunks:
                child("tpu.prefill_wait", tl.admitted, tl.chunks[0][0])
        for i, (start, end, tokens, rows, visit) in enumerate(tl.chunks):
            child(
                "tpu.prefill.chunk", start, end,
                index=i, tokens=tokens, rows=rows, passes=self.passes,
                **({} if visit is None else {"attn_visit_ratio": visit}),
                **self.model_attrs,
            )
        if tl.prefill_done is not None and tl.first_token is not None:
            child("tpu.emit_flush", tl.prefill_done, tl.first_token)
        for src, dst, start, end, result, leg in tl.transfers:
            # The disaggregated-tier hop: a real-duration span between
            # the prefill phase (on `src`) and the decode phase (on
            # `dst`), in the SAME trace, tagged with the leg that
            # carried the blocks (device / wire / host).
            child(
                "tpu.transfer", start, end,
                source=src, target=dst, result=result, leg=leg,
            )
        if tl.first_token is not None and tl.first_written is not None:
            child("tpu.delivery", tl.first_token, tl.first_written)
        if tl.first_token is not None:
            child(
                "tpu.decode", tl.first_token, done,
                tokens=tl.output_tokens, passes=self.passes,
                **self.model_attrs,
            )
        for name, t, attrs in tl.annotations:
            child(name, t, t, **attrs)
