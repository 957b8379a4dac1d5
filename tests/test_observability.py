"""Request-lifecycle observability suite (ISSUE 6 acceptance gate).

Deterministic throughout: injectable clocks (no sleeps-as-
synchronization), an in-memory span collector instead of a wire
exporter, faults driven through ``gofr_tpu/faults``, and the prober/
supervisor seams the chaos suites already use.

Covered:

* timeline phase math and flight-recorder entries (injected clock);
* flight-recorder ring eviction with slow/errored requests PINNED so a
  burst cannot evict them;
* phase histograms record EXACTLY once per request per phase, from
  host-side values only;
* one trace per request: ``tpu.request`` is a child of the caller's
  ``traceparent`` and every phase span (queue-wait, admission, prefill
  chunks, emit-flush, decode) shares its trace id;
* THE acceptance path: a request served through a ``ReplicaPool`` whose
  replica dies mid-stream produces ONE trace whose spans — phases on
  replica A, the replay and failover annotations, phases on replica B —
  all share the request's trace id, and ``/debug/flight`` (the pool's
  ``flight_records``) shows the same timeline with the failover
  annotation;
* ``traceparent`` round-trips through ``HTTPReplica`` so cross-replica
  traces stitch;
* time to first token split where it is spent: the six phases entry →
  queue_wait → prefill_wait → prefill_dispatch → first_token_wait →
  delivery sum EXACTLY to ``first_written − received`` under a stated
  clock, a request stopped before a mark lacks the later phases, each
  phase records one histogram sample and one span, and a completion
  streamed over HTTP carries all six in ``/debug/flight``'s record;
* shed requests land PINNED in the recorder with the shed outcome;
* the layer costs nothing when off: ``TPU_FLIGHT_RECORDER=0`` with no
  metrics and no active exporter mints no timeline at all.
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.observability import (
    FlightRecorder,
    RequestObservability,
    parse_traceparent,
)
from gofr_tpu.serving.supervisor import EngineSupervisor
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.replica_pool import (
    EngineReplica,
    HTTPReplica,
    ReplicaPool,
)
from gofr_tpu.tracing import Tracer, get_tracer, set_tracer

TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


class _CaptureExporter:
    """In-memory span sink; ``is_noop`` absent → the tracer is ACTIVE."""

    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def export(self, span, service_name):
        with self._lock:
            self.spans.append(span)

    def by_name(self, name):
        with self._lock:
            return [s for s in self.spans if s.name == name]

    def clear(self):
        with self._lock:
            self.spans.clear()


@pytest.fixture()
def capture():
    """Install a capturing tracer for the test, restore after."""
    old = get_tracer()
    cap = _CaptureExporter()
    set_tracer(Tracer(service_name="obs-test", exporter=cap))
    yield cap
    set_tracer(old)


@pytest.fixture(scope="module")
def metrics():
    # Container registration is the real instrument set (histograms
    # with buckets, gauges) — the one production records into.
    return Container.create(MockConfig({"APP_NAME": "obs-test"})).metrics


@pytest.fixture(scope="module")
def engine(metrics):
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
        metrics=metrics,
    )
    eng.start_sync()
    yield eng
    eng.stop_sync()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def _hist_sum_count(metrics, name, model="llama-tiny"):
    """(count, sum) of a histogram's samples for ``model``."""
    inst = {i.name: i for i in metrics.instruments()}[name]
    for labels, (_counts, (total, n)) in inst.collect().items():
        if ("model", model) in labels:
            return n, total
    return 0, 0.0


def _hist_count(metrics, name, model="llama-tiny"):
    return _hist_sum_count(metrics, name, model)[0]


def _gauge(metrics, name):
    inst = {i.name: i for i in metrics.instruments()}[name]
    values = inst.collect()
    return next(iter(values.values())) if values else None


PHASES = (
    "app_tpu_queue_wait_seconds",
    "app_tpu_prefill_seconds",
    "app_tpu_ttft_seconds",
    "app_tpu_inter_token_seconds",
    "app_tpu_e2e_seconds",
)


# ----------------------------------------------------------------------
# timeline + recorder units (injected clock, no engine)
# ----------------------------------------------------------------------


def test_timeline_phase_math_with_injected_clock():
    t = [100.0]
    hub = RequestObservability(
        "m", recorder=FlightRecorder(), clock=lambda: t[0],
        wall_ns=lambda: 1_000_000_000,
    )
    tl = hub.begin(prompt_tokens=7, traceparent=TRACEPARENT)
    assert tl is not None
    assert tl.trace_id == "ab" * 16 and tl.parent_span_id == "cd" * 8
    t[0] = 100.5
    tl.mark_admitted(t[0])
    t[0] = 101.0
    tl.note_chunk(100.5, 101.0, 7, 1)
    tl.mark_prefill_done(t[0])
    t[0] = 101.25
    tl.mark_first_token(t[0])
    t[0] = 103.25
    tl.finish("ok", "stop", output_tokens=5)
    phases = tl.phases()
    assert phases["queue_wait_s"] == pytest.approx(0.5)
    assert phases["prefill_s"] == pytest.approx(0.5)
    assert phases["ttft_s"] == pytest.approx(1.25)
    assert phases["decode_s"] == pytest.approx(2.0)
    assert phases["inter_token_s"] == pytest.approx(0.5)  # 2.0 / (5-1)
    assert phases["e2e_s"] == pytest.approx(3.25)
    snap = hub.recorder.snapshot()
    assert len(snap["records"]) == 1 and not snap["pinned"]
    entry = snap["records"][0]
    assert entry["outcome"] == "ok" and entry["prompt_tokens"] == 7
    assert entry["prefill_chunks"] == 1
    # finish() is latched: a racing second terminal path is a no-op.
    tl.finish("error", "late")
    assert tl.outcome == "ok"
    assert len(hub.recorder.snapshot()["records"]) == 1


TTFT_SPLIT = (
    "entry_s", "queue_wait_s", "prefill_wait_s", "prefill_dispatch_s",
    "first_token_wait_s", "delivery_s",
)
# The marks of one streamed request, in order, on a clock of binary
# fractions so that every difference and their sum are exact.
MARKS = dict(
    received=100.0, enqueued=100.125, admitted=100.5, chunk0=100.75,
    chunk1=101.0, prefill_done=101.5, first_token=101.625,
    first_written=101.6875,
)


def _timeline_up_to(hub, t, last_mark):
    """Drive a timeline through MARKS up to and including ``last_mark``
    (None: every mark), then retire it."""
    order = list(MARKS)
    upto = len(order) if last_mark is None else order.index(last_mark) + 1
    reached = set(order[:upto])
    t[0] = MARKS["enqueued"]
    tl = hub.begin(prompt_tokens=300, received=MARKS["received"])
    if "admitted" in reached:
        tl.mark_admitted(MARKS["admitted"])
    if "chunk0" in reached:
        tl.note_chunk(MARKS["chunk0"], MARKS["chunk0"] + 0.0625, 256, 2, 0.75)
    if "prefill_done" in reached:
        tl.note_chunk(MARKS["chunk1"], MARKS["prefill_done"], 44, 1)
        tl.mark_prefill_done(MARKS["prefill_done"])
    if "first_token" in reached:
        tl.mark_first_token(MARKS["first_token"])
    if "first_written" in reached:
        t[0] = MARKS["first_written"]
        tl.mark_first_written()
        t[0] += 0.5
        tl.mark_first_written()  # later tokens: the first mark stands
    t[0] = 103.0
    tl.finish("ok" if last_mark is None else "cancelled", "stop",
              output_tokens=9)
    return tl


def test_ttft_split_sums_exactly_to_first_written_minus_received():
    t = [0.0]
    hub = RequestObservability(
        "m", recorder=FlightRecorder(), clock=lambda: t[0], wall_ns=lambda: 0,
    )
    tl = _timeline_up_to(hub, t, None)
    phases = tl.phases()
    assert [phases[k] for k in TTFT_SPLIT] == [
        0.125, 0.375, 0.25, 0.75, 0.125, 0.0625,
    ]
    # Exactly, not approximately: consecutive differences of one clock.
    assert sum(phases[k] for k in TTFT_SPLIT) == (
        tl.first_written - tl.received
    ) == 1.6875
    # What was there keeps its meaning beside the split.
    assert phases["prefill_s"] == (
        phases["prefill_wait_s"] + phases["prefill_dispatch_s"]
    )
    assert phases["ttft_s"] == sum(phases[k] for k in TTFT_SPLIT[1:5])
    # /debug/flight carries the split through to_dict with no code of
    # its own.
    entry = hub.recorder.snapshot()["records"][-1]
    assert set(TTFT_SPLIT) <= set(entry["phases"])


@pytest.mark.parametrize("last_mark,present", [
    ("enqueued", ["entry_s"]),
    ("admitted", ["entry_s", "queue_wait_s"]),
    ("chunk0", ["entry_s", "queue_wait_s", "prefill_wait_s"]),
    ("prefill_done", list(TTFT_SPLIT[:4])),
    ("first_token", list(TTFT_SPLIT[:5])),
])
def test_request_stopped_before_a_mark_lacks_the_later_phases(
    last_mark, present,
):
    t = [0.0]
    hub = RequestObservability(
        "m", recorder=FlightRecorder(), clock=lambda: t[0], wall_ns=lambda: 0,
    )
    phases = _timeline_up_to(hub, t, last_mark).phases()
    assert [k for k in TTFT_SPLIT if k in phases] == present


def test_request_without_an_http_handler_lacks_entry_and_delivery():
    """gRPC, pubsub and /v1/batches submit with no ``received`` and write
    no SSE chunk: the four phases in between are all there is."""
    t = [50.0]
    hub = RequestObservability(
        "m", recorder=FlightRecorder(), clock=lambda: t[0], wall_ns=lambda: 0,
    )
    tl = hub.begin(prompt_tokens=4)
    tl.mark_admitted(50.5)
    tl.note_chunk(50.75, 51.0, 4, 1)
    tl.mark_prefill_done(51.0)
    tl.mark_first_token(51.5)
    t[0] = 52.0
    tl.finish("ok", "length", output_tokens=3)
    phases = tl.phases()
    assert [k for k in TTFT_SPLIT if k in phases] == list(TTFT_SPLIT[1:5])
    assert sum(phases[k] for k in TTFT_SPLIT[1:5]) == phases["ttft_s"] == 1.5


def test_ttft_split_records_one_histogram_sample_and_one_span_per_phase(
    metrics, capture,
):
    from gofr_tpu.serving.observability import PHASE_HISTOGRAMS

    split = [PHASE_HISTOGRAMS[k] for k in TTFT_SPLIT]
    assert split == [
        "app_tpu_entry_seconds", "app_tpu_queue_wait_seconds",
        "app_tpu_prefill_wait_seconds", "app_tpu_prefill_dispatch_seconds",
        "app_tpu_first_token_wait_seconds", "app_tpu_delivery_seconds",
    ]
    t = [0.0]
    hub = RequestObservability(
        "split-model", metrics=metrics, clock=lambda: t[0], wall_ns=lambda: 0,
    )
    before = {n: _hist_count(metrics, n, "split-model") for n in split}
    tl = _timeline_up_to(hub, t, None)
    _timeline_up_to(hub, t, "admitted")  # records the first two only
    after = {n: _hist_count(metrics, n, "split-model") for n in split}
    assert [after[n] - before[n] for n in split] == [2, 2, 1, 1, 1, 1]
    # One child span per phase, under the request's one trace and parent.
    root = [s for s in capture.by_name("tpu.request")
            if s.trace_id == tl.trace_id][0]
    for name, seconds in (
        ("tpu.entry", 0.125), ("tpu.queue_wait", 0.375),
        ("tpu.prefill_wait", 0.25), ("tpu.emit_flush", 0.125),
        ("tpu.delivery", 0.0625),
    ):
        spans = [s for s in capture.by_name(name)
                 if s.trace_id == tl.trace_id]
        assert len(spans) == 1, name
        assert spans[0].parent_id == root.span_id
        assert spans[0].end_ns - spans[0].start_ns == int(seconds * 1e9)
    # prefill_dispatch is the chunk spans: first one's start to the last
    # one's end.
    chunks = [s for s in capture.by_name("tpu.prefill.chunk")
              if s.trace_id == tl.trace_id]
    assert chunks[-1].end_ns - chunks[0].start_ns == int(0.75 * 1e9)
    # Each carries the row count of the step it rode in.
    assert [(c.attributes["tokens"], c.attributes["rows"]) for c in chunks] \
        == [(256, 2), (44, 1)]
    # ... and, where the step's attention ran in blocks a row, the share of
    # rows x the longest row's blocks it visited; absent where none was given.
    assert [c.attributes.get("attn_visit_ratio") for c in chunks] == [0.75, None]


def test_flight_recorder_evicts_ring_but_pins_survive_burst():
    t = [0.0]
    hub = RequestObservability(
        "m", recorder=FlightRecorder(capacity=4, pin_capacity=2, slow_s=5.0),
        clock=lambda: t[0], wall_ns=lambda: 0,
    )

    def run_one(outcome, e2e):
        tl = hub.begin(prompt_tokens=1)
        start = t[0]
        t[0] += e2e
        tl.finish(outcome, "x", output_tokens=1)
        return start

    run_one("error", 0.1)   # pinned (errored)
    run_one("ok", 9.0)      # pinned (slow: e2e > slow_s)
    for _ in range(10):     # healthy burst far beyond the ring
        run_one("ok", 0.1)
    snap = hub.recorder.snapshot()
    assert len(snap["records"]) == 4  # ring capacity: burst evicted
    assert len(snap["pinned"]) == 2   # the interesting ones survived
    assert {e["outcome"] for e in snap["pinned"]} == {"error", "ok"}
    assert snap["pinned"][1]["phases"]["e2e_s"] == pytest.approx(9.0)


def test_layer_off_mints_no_timeline():
    hub = RequestObservability("m", metrics=None, recorder=None)
    assert hub.begin(prompt_tokens=1) is None  # noop tracer, nothing on


# ----------------------------------------------------------------------
# engine integration: histograms, spans, recorder
# ----------------------------------------------------------------------


def _windows_spied(engine):
    """Wrap ``_process_window`` so the test sees each processed window's
    ``longest`` (the longest live slot when it was dispatched)."""
    seen: list[int] = []
    process = engine._process_window

    def spy(emitted, snapshot, etops, live_positions, longest):
        seen.append(longest)
        return process(emitted, snapshot, etops, live_positions, longest)

    engine._process_window = spy
    return seen, lambda: engine.__dict__.pop("_process_window")


def test_decode_read_ratio_records_the_host_rules_rung_once_a_window(
    metrics, engine,
):
    """One record a processed window, beside the occupancy: the rung the
    window's LAST step read (the device's rule, asked on the host about
    the longest live slot window_k - 1 positions on) over max_len. The
    prompt is chosen so that the context crosses the 128 rung of this
    256-position cache while it decodes."""
    from gofr_tpu.ops.attention import decode_read_index, decode_read_rungs

    name = "app_tpu_decode_read_ratio"
    rungs = decode_read_rungs(engine.max_len)
    assert engine.decode_read_rungs == rungs == (128, 256)
    seen, unspy = _windows_spied(engine)
    n0, sum0 = _hist_sum_count(metrics, name)
    w0, _ = _hist_sum_count(metrics, "app_tpu_window_occupancy")
    try:
        r = engine.generate_sync(
            "x" * 100, max_new_tokens=40, temperature=0.0, stop_on_eos=False,
        )
    finally:
        unspy()
    assert len(r.token_ids) == 40
    n1, sum1 = _hist_sum_count(metrics, name)
    w1, _ = _hist_sum_count(metrics, "app_tpu_window_occupancy")
    assert n1 - n0 == w1 - w0 == len(seen) >= 5
    want = [
        rungs[decode_read_index(rungs, longest + engine.window_k - 1)]
        / engine.max_len
        for longest in seen
    ]
    assert set(want) == {0.5, 1.0}  # both rungs were read
    assert sum1 - sum0 == pytest.approx(sum(want))


def test_decode_read_ratio_is_one_for_a_cache_with_one_rung(metrics):
    eng = InferenceEngine(
        "llama-tiny-f32", n_slots=2, max_len=64, tokenizer=ByteTokenizer(),
        metrics=metrics,
    )
    assert eng.decode_read_rungs == (64,)
    eng.start_sync()
    try:
        eng.generate_sync(
            "one rung", max_new_tokens=12, temperature=0.0, stop_on_eos=False,
        )
    finally:
        eng.stop_sync()
    n, total = _hist_sum_count(
        metrics, "app_tpu_decode_read_ratio", model="llama-tiny-f32"
    )
    assert n >= 2 and total == pytest.approx(n)


def test_phase_histograms_record_exactly_once_per_request(metrics, engine):
    ratios = ("app_tpu_window_occupancy", "app_tpu_prefill_fill_ratio")
    before = {name: _hist_count(metrics, name) for name in PHASES}
    before_ratio = {name: _hist_sum_count(metrics, name) for name in ratios}
    for _ in range(2):
        r = engine.generate_sync(
            "histogram once per phase", max_new_tokens=8,
            temperature=0.0, stop_on_eos=False,
        )
        assert len(r.token_ids) == 8
    after = {name: _hist_count(metrics, name) for name in PHASES}
    for name in PHASES:
        assert after[name] - before[name] == 2, name
    # Per-window and per-step utilization rode along (host values
    # only): the occupancy gauge, and the two ratios as histograms whose
    # sum over count between two reads is the mean over exactly the
    # windows (steps) in between.
    assert _gauge(metrics, "app_tpu_batch_occupancy") is not None
    mean = {}
    for name in ratios:
        (n0, sum0), (n1, sum1) = before_ratio[name], _hist_sum_count(metrics, name)
        assert n1 - n0 >= 2, name  # at least one a request
        mean[name] = (sum1 - sum0) / (n1 - n0)
    # One of the four slots was live when each window was dispatched ...
    assert mean["app_tpu_window_occupancy"] == pytest.approx(0.25)
    # ... and one row waited at each prefill step, so the step ran at
    # the lowest rung: the prompt over 1 x prefill_chunk token rows.
    assert engine.prefill_rungs == (1, 4)
    assert mean["app_tpu_prefill_fill_ratio"] == pytest.approx(
        r.prompt_tokens / engine.prefill_chunk
    )


def test_one_trace_per_request_with_phase_parentage(capture, engine):
    r = engine.generate_sync(
        "trace me end to end", max_new_tokens=6, temperature=0.0,
        stop_on_eos=False, traceparent=TRACEPARENT,
    )
    assert len(r.token_ids) == 6
    roots = capture.by_name("tpu.request")
    assert len(roots) == 1
    root = roots[0]
    # The engine's request span is a CHILD of the caller's traceparent.
    assert root.trace_id == "ab" * 16
    assert root.parent_id == "cd" * 8
    assert root.attributes["tpu.outcome"] == "ok"
    for name in (
        "tpu.queue_wait", "tpu.admission", "tpu.prefill.chunk",
        "tpu.emit_flush", "tpu.decode",
    ):
        spans = capture.by_name(name)
        assert spans, f"missing {name} span"
        assert all(s.trace_id == root.trace_id for s in spans), name
        assert all(s.parent_id == root.span_id for s in spans), name
    decode = capture.by_name("tpu.decode")[0]
    assert decode.attributes["tokens"] == 6
    # Spans carry real wall-clock extents (start <= end, all inside the
    # request span).
    assert root.start_ns <= decode.start_ns <= decode.end_ns <= root.end_ns


def test_trace_adopted_from_current_span_without_explicit_header(
    capture, engine
):
    # The HTTP middleware / gRPC interceptor set a context-var span; an
    # in-task submit with NO explicit traceparent still joins its trace.
    span = get_tracer().start_span("GET /v1/completions")
    try:
        engine.generate_sync(
            "adopt ambient span", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False,
        )
    finally:
        span.end()
    root = capture.by_name("tpu.request")[0]
    assert root.trace_id == span.trace_id
    assert root.parent_id == span.span_id


def test_shed_request_is_pinned_with_outcome(engine):
    from gofr_tpu.errors import ErrorDeadlineExceeded

    with pytest.raises(ErrorDeadlineExceeded):
        engine.submit_generate(
            "shed me", max_new_tokens=4, temperature=0.0,
            deadline_s=-1.0,
        )
    pinned = engine.flight_records()["pinned"]
    assert pinned, "shed request must be pinned"
    entry = pinned[-1]
    assert entry["outcome"] == "shed"
    assert any(a["name"] == "tpu.shed" for a in entry["annotations"])


def test_flight_recorder_off_disables_layer(metrics):
    eng = InferenceEngine(
        "llama-tiny", n_slots=2, max_len=128, tokenizer=ByteTokenizer(),
        flight_recorder=False,
    )
    eng.start_sync()
    try:
        req = eng.submit_generate(
            "no timeline", max_new_tokens=2, temperature=0.0,
            stop_on_eos=False,
        )
        assert req.timeline is None  # no metrics, noop tracer, ring off
        req.future.result(timeout=120)
        assert eng.flight_records() == {"enabled": False}
    finally:
        eng.stop_sync()


# ----------------------------------------------------------------------
# traceparent round-trip through HTTPReplica
# ----------------------------------------------------------------------


class _FakeResp:
    status_code = 200
    body = b""

    def json(self):
        return {
            "choices": [{"text": "ok", "finish_reason": "stop"}],
            "usage": {"prompt_tokens": 1},
        }


class _CaptureService:
    def __init__(self):
        self.headers = None

    def post(self, path, json=None, headers=None):
        self.headers = dict(headers or {})
        return _FakeResp()


def test_traceparent_round_trips_through_http_replica():
    service = _CaptureService()
    replica = HTTPReplica("remote", service)
    req = replica.submit(
        "stitch me", max_new_tokens=4, traceparent=TRACEPARENT
    )
    result = req.future.result(timeout=30)
    assert result.text == "ok"
    # Propagated downstream verbatim...
    assert service.headers.get("traceparent") == TRACEPARENT
    # ...and the receiving server's middleware would adopt exactly the
    # caller's trace id (the round trip: one trace across replicas).
    trace_id, span_id = parse_traceparent(service.headers["traceparent"])
    assert trace_id == "ab" * 16 and span_id == "cd" * 8


# ----------------------------------------------------------------------
# THE acceptance path: replay + failover keep one trace
# ----------------------------------------------------------------------


def _make_supervised(metrics, **eng_kw):
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
        metrics=metrics, **eng_kw,
    )
    sup = EngineSupervisor(
        eng, max_restarts=1, backoff_s=0.25, backoff_reset_s=60.0,
        rng=random.Random(99), sleep=lambda s: None, metrics=metrics,
    ).start()
    eng.start_sync()
    return eng, sup


@pytest.fixture(scope="module")
def engine_pair(metrics):
    a = _make_supervised(metrics)
    b = _make_supervised(metrics)
    yield a, b
    faults.reset()
    for eng, sup in (a, b):
        sup.stop()
        eng.stop_sync()


def _drain(req, timeout=180.0):
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


def test_failover_mid_stream_keeps_one_trace_and_flight_timeline(
    capture, metrics, engine_pair
):
    """A request served through a ReplicaPool whose replica dies
    mid-stream produces ONE trace — queue/admission/prefill on A,
    decode on A, the replay + failover annotations, decode on B — all
    under the request's trace id, and the pool's flight view shows the
    same timeline with the failover annotation."""
    (eng_a, sup_a), (eng_b, sup_b) = engine_pair
    pool = ReplicaPool(
        [EngineReplica("a", eng_a), EngineReplica("b", eng_b)],
        probe_interval_s=0, probe_timeout_s=60.0,
        rng=random.Random(7), metrics=metrics,
    )
    params = dict(max_new_tokens=24, temperature=0.0, stop_on_eos=False)
    try:
        ref = eng_b.generate_sync("observed failover stream", **params)
        capture.clear()

        # A's device dies from its 4th dispatch on — persistent and
        # targeted, so crash 1 lands mid-stream, the recovery replay's
        # prefill is crash 2, max_restarts=1 exhausts, A goes DOWN and
        # hands the live request to B.
        hits = {"n": 0}

        def crash_a(engine=None, **kw):
            if engine is eng_a:
                hits["n"] += 1
                if hits["n"] >= 4:
                    raise RuntimeError("injected: replica A device loss")

        faults.arm("scheduler.device_step", action=crash_a)
        req = pool.submit_generate(
            "observed failover stream", traceparent=TRACEPARENT, **params
        )
        toks = _drain(req)
        result = req.future.result(timeout=180)
        assert toks == ref.token_ids
        assert result.token_ids == ref.token_ids

        # ONE trace: every span shares the request's trace id.
        root = capture.by_name("tpu.request")[0]
        assert root.trace_id == "ab" * 16
        span_names = {s.name for s in capture.spans}
        for needed in (
            "tpu.queue_wait", "tpu.admission", "tpu.prefill.chunk",
            "tpu.decode", "tpu.replay", "tpu.failover",
        ):
            assert needed in span_names, needed
        # tpu.compile spans are the one deliberate exception: a warm-up
        # compile belongs to the ENGINE's boot trace (or its own), not
        # to whichever request happened to trigger it — the request's
        # trace must still be complete without them.
        assert all(
            s.trace_id == root.trace_id
            for s in capture.spans
            if s.name.startswith("tpu.") and s.name != "tpu.compile"
        )
        failover_span = capture.by_name("tpu.failover")[0]
        assert failover_span.attributes["source"] == "a"
        assert failover_span.attributes["target"] == "b"

        # /debug/flight view: the SAME timeline, once, in the origin
        # replica's recorder, carrying the failover annotation.
        flights = pool.flight_records()["replicas"]
        entries = [
            e
            for snap in flights.values()
            for e in snap.get("records", []) + snap.get("pinned", [])
            if e["trace_id"] == root.trace_id
            and any(
                a["name"] == "tpu.failover" for a in e["annotations"]
            )
        ]
        assert len(entries) == 1
        entry = entries[0]
        assert entry["outcome"] == "ok"
        assert entry["replays"] >= 1
        names = [a["name"] for a in entry["annotations"]]
        assert "tpu.replay" in names and "tpu.failover" in names
        assert entry["output_tokens"] == len(ref.token_ids)
    finally:
        faults.reset()
        pool.stop_prober()
        for replica in pool.replicas:
            replica.engine.set_replica_handoff(None)
        # The wounded replica must be healthy again for later tests.
        assert eng_b.state == "SERVING"
        if eng_a.state != "SERVING":
            sup_a.revive()
        assert eng_a.state == "SERVING"
