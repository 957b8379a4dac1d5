"""Network-chaos suite for the multi-host replica data plane
(ISSUE 7 acceptance gate).

Everything network-shaped is driven deterministically through the
``gofr_tpu/faults`` HTTP transport points (``http.request``,
``http.stream.open``, ``http.stream.event``) — no real sockets except
where the test IS about socket behavior (the slow-loris stall and the
real-upstream integration test, both bounded by sub-second read
timeouts).

Covered:

* transport fault points: canned 5xx bursts and connect-refused on the
  unary path, fault-served SSE streams, truncation, mid-body reset;
* connect-vs-read budget separation (satellite: a loaded-but-alive
  remote is classified BUSY by the probe, never demoted; a dead one
  fails fast at the handshake);
* streaming through ``HTTPReplica``: SSE consumption with the
  ``include_tokens`` wire, upstream error events propagating
  un-rerouted, caller cancellation ending consumption without failover;
* THE acceptance paths: a remote replica killed mid-SSE (truncated
  stream), resetting mid-body, or stalling past the idle timeout
  (slow-loris, real socket) hands its live request to an in-proc
  sibling — the client stream is byte-identical to a fault-free run,
  zero 5xx, ONE trace id spans both replicas, and the pool's flight
  view shows the failover annotation; a LoRA-adapter request passes the
  same check with the adapter lazily reconciled onto the sibling;
* connect-reset during a hedged unary retry: the sibling answers, the
  client never sees the loss;
* streaming through a REAL remote gofr_tpu app (full OpenAI SSE +
  ``stream_options.include_tokens`` over a live socket) matches the
  remote engine's own generation;
* ``PoolScaler``: sustained pressure spawns through the injectable
  factory, idle drains retire with zero dropped in-flight requests,
  bounds ``TPU_POOL_{MIN,MAX}_REPLICAS`` are never violated, and a
  drain that cannot empty its replica aborts and re-admits it.
"""

from __future__ import annotations

import json
import random
import socket
import threading
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.errors import ErrorServiceUnavailable
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.client import HTTPService, classify_transport_error
from gofr_tpu.service.pool_scaler import PoolScaler
from gofr_tpu.service.replica_pool import (
    EngineReplica,
    HTTPReplica,
    Replica,
    ReplicaPool,
)
from gofr_tpu.tracing import Tracer, get_tracer, set_tracer

TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


# ----------------------------------------------------------------------
# shared fixtures / helpers
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def metrics():
    # Container registration is the real instrument set — including the
    # pool gauges and scale/remote-failover counters this PR adds.
    return Container.create(MockConfig({"APP_NAME": "chaos-test"})).metrics


@pytest.fixture(scope="module")
def sibling(metrics):
    """The in-proc sibling every remote fails over TO. LoRA slots armed
    for the adapter-reconciliation acceptance test."""
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, tokenizer=ByteTokenizer(),
        metrics=metrics, lora_slots=2, lora_rank=4,
    )
    eng.start_sync()
    yield eng
    eng.stop_sync()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


class _CaptureExporter:
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
    old = get_tracer()
    cap = _CaptureExporter()
    set_tracer(Tracer(service_name="chaos-test", exporter=cap))
    yield cap
    set_tracer(old)


def counter_total(metrics, name: str) -> float:
    inst = {i.name: i for i in metrics.instruments()}[name]
    return sum(inst.collect().values())


def _drain(req, timeout=180.0) -> list[int]:
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


def _tagged(kind: str, msg: str = "injected transport loss") -> Exception:
    """The typed 503 the transport layer raises, pre-classified — what
    ``client._unavailable`` would build from the matching httpx error."""
    exc = ErrorServiceUnavailable(msg)
    exc.kind = kind
    return exc


def _sse(tokens, text="", finish=None, prompt_tokens=None) -> str:
    choice = {"index": 0, "token_ids": list(tokens), "text": text}
    if finish is not None:
        choice["finish_reason"] = finish
    if prompt_tokens is not None:
        choice["prompt_tokens"] = prompt_tokens
    return "data: " + json.dumps({"choices": [choice]})


def _sse_lines(token_ids, *, chunk=3, finish="stop", done=True,
               prompt_tokens=0) -> list[str]:
    """A well-formed (or deliberately truncated: ``finish=None`` /
    ``done=False``) SSE stream carrying the given token ids."""
    lines = []
    for i in range(0, len(token_ids), chunk):
        lines.append(_sse(token_ids[i:i + chunk]))
    if finish is not None:
        lines.append(_sse([], finish=finish, prompt_tokens=prompt_tokens))
    if done:
        lines.append("data: [DONE]")
    return lines


def _pool(replicas, metrics=None, **kw):
    kw.setdefault("probe_interval_s", 0)
    kw.setdefault("probe_timeout_s", 60.0)
    kw.setdefault("rng", random.Random(7))
    # Raw least-loaded routing: deterministic first pick (list order on
    # ties) regardless of what throughput the shared engine measured in
    # earlier tests.
    kw.setdefault("weighted", False)
    return ReplicaPool(replicas, metrics=metrics, **kw)


def _release(pool):
    pool.stop_prober()
    for replica in pool.replicas:
        replica.set_handoff(None)


# ----------------------------------------------------------------------
# transport fault points (no engine, no socket)
# ----------------------------------------------------------------------


def test_http_request_fault_point_cans_5xx_and_raises_transport_loss():
    from gofr_tpu.service.client import Response

    svc = HTTPService("http://127.0.0.1:9")  # never dialed: fault serves
    faults.arm(
        "http.request",
        action=lambda **ctx: Response(b'{"err":"burst"}', 503, {}),
    )
    resp = svc.post("v1/completions", json={"prompt": "x"})
    assert resp.status_code == 503  # canned 5xx, no socket involved
    faults.arm("http.request", raises=_tagged("connect", "refused"))
    with pytest.raises(ErrorServiceUnavailable) as exc_info:
        svc.get("v1/models")
    assert exc_info.value.kind == "connect"


def test_stream_fault_points_serve_truncate_and_reset():
    svc = HTTPService("http://127.0.0.1:9")
    lines = _sse_lines([1, 2, 3, 4], chunk=2)
    faults.arm("http.stream.open", action=lambda **ctx: list(lines))
    with svc.stream_lines("POST", "v1/completions", json={}) as got:
        assert list(got) == lines
    # Per-event verdict "truncate" = upstream vanished without EOF
    # framing: the stream ends early, no error at the transport level
    # (the CONSUMER detects the missing terminal framing).
    faults.arm("http.stream.open", action=lambda **ctx: list(lines))
    faults.arm("http.stream.event", action=lambda **ctx: "truncate", after=1)
    with svc.stream_lines("POST", "v1/completions", json={}) as got:
        assert list(got) == lines[:1]
    # Per-event raise = mid-body connection reset.
    faults.arm("http.stream.open", action=lambda **ctx: list(lines))
    faults.arm(
        "http.stream.event", raises=_tagged("read", "reset mid-body"),
        after=2,
    )
    with svc.stream_lines("POST", "v1/completions", json={}) as got:
        received = []
        with pytest.raises(ErrorServiceUnavailable):
            for line in got:
                received.append(line)
        assert received == lines[:2]


def test_classify_transport_error_separates_connect_from_read():
    import httpx

    assert classify_transport_error(httpx.ConnectError("refused")) == "connect"
    assert classify_transport_error(httpx.ConnectTimeout("syn")) == "connect"
    assert classify_transport_error(httpx.ReadTimeout("stall")) == "read"
    assert classify_transport_error(httpx.ReadError("reset")) == "read"
    assert classify_transport_error(RuntimeError("other")) == "transport"


def test_connect_budget_is_separate_from_and_shorter_than_read_budget():
    svc = HTTPService("http://127.0.0.1:9", timeout=30.0)
    # Default: the handshake budget never inherits a long read budget —
    # a dead upstream must fail in ~RTT time, not after 30s.
    assert svc.connect_timeout_s == 5.0
    assert svc.timeout == 30.0
    svc2 = HTTPService("http://127.0.0.1:9", timeout=2.0)
    assert svc2.connect_timeout_s == 2.0  # never above the total budget
    svc3 = HTTPService(
        "http://127.0.0.1:9", timeout=30.0, connect_timeout_s=1.5
    )
    assert svc3.connect_timeout_s == 1.5
    for s in (svc, svc2, svc3):
        s.close()


# ----------------------------------------------------------------------
# dead-vs-busy probe classification (satellite 1)
# ----------------------------------------------------------------------


class _ErrService:
    """Health endpoint that raises a pre-classified transport error."""

    def __init__(self, exc):
        self.exc = exc

    def get(self, path, **kw):
        raise self.exc

    def health_check(self):
        raise self.exc


def test_probe_classifies_read_timeout_behind_load_as_busy():
    replica = HTTPReplica(
        "loaded", _ErrService(_tagged("read", "slow behind queue")),
    )
    with replica._lock:
        replica._inflight = 3  # live upstream, busy serving queued work
    verdict, detail = replica.probe(timeout_s=5.0)
    assert verdict == "busy"
    assert "3 in-flight" in detail
    # Busy is never a demotion: the replica keeps routing (restarting a
    # merely-loaded replica would cascade its queue onto the siblings).
    assert replica.state() == "SERVING"


def test_probe_classifies_connect_failure_as_dead_even_under_load():
    replica = HTTPReplica(
        "dead", _ErrService(_tagged("connect", "nothing listening")),
    )
    with replica._lock:
        replica._inflight = 3
    verdict, _ = replica.probe(timeout_s=5.0)
    assert verdict == "fail"  # the HANDSHAKE failed: nobody is home
    assert replica.state() == "DOWN"


def test_probe_classifies_idle_read_timeout_as_dead():
    replica = HTTPReplica(
        "quiet", _ErrService(_tagged("read", "no answer")),
    )
    verdict, _ = replica.probe(timeout_s=5.0)  # zero in-flight: not busy
    assert verdict == "fail"
    assert replica.state() == "DOWN"


def test_probe_refreshes_advertised_adapter_set_from_health_payload():
    class _HealthService:
        def get(self, path, **kw):
            class _Resp:
                status_code = 200

                @staticmethod
                def json():
                    return {
                        "data": {
                            "status": "UP",
                            "details": {
                                "tpu": {
                                    "status": "UP",
                                    "details": {
                                        "lora_adapters": ["tuned", "fr"],
                                    },
                                },
                            },
                        },
                    }

            return _Resp()

    replica = HTTPReplica("remote", _HealthService())
    assert replica.adapters() == frozenset()
    verdict, _ = replica.probe(timeout_s=5.0)
    assert verdict == "pass"
    assert replica.adapters() == frozenset({"tuned", "fr"})


# ----------------------------------------------------------------------
# streaming HTTPReplica (fault-served SSE, no engine)
# ----------------------------------------------------------------------


def _stream_replica(name="remote", **kw):
    kw.setdefault("tokenizer", ByteTokenizer())
    return HTTPReplica(name, HTTPService("http://127.0.0.1:9"), **kw)


def test_http_replica_consumes_sse_stream_into_local_handle():
    ids = [72, 105, 33, 10, 65]
    faults.arm(
        "http.stream.open",
        action=lambda **ctx: _sse_lines(ids, prompt_tokens=4),
    )
    replica = _stream_replica()
    assert replica.supports_stream
    req = replica.submit("Hi!", max_new_tokens=8, temperature=0.0)
    toks = _drain(req)
    result = req.future.result(timeout=30)
    assert toks == ids
    assert result.token_ids == ids
    assert result.finish_reason == "stop"
    assert result.prompt_tokens == 4  # carried on the finish chunk
    assert result.text == ByteTokenizer().decode(ids)
    assert replica.load() == 0  # in-flight accounting drained


def test_truncated_stream_without_handoff_fails_with_tagged_503():
    ids = [1, 2, 3, 4, 5, 6]
    faults.arm(
        "http.stream.open",
        action=lambda **ctx: _sse_lines(ids, finish=None, done=False)[:1],
    )
    replica = _stream_replica()
    req = replica.submit("x", max_new_tokens=8, temperature=0.0)
    with pytest.raises(ErrorServiceUnavailable) as exc_info:
        req.future.result(timeout=30)
    assert exc_info.value.kind == "read"
    assert "truncated" in str(exc_info.value)
    assert _drain(req) == ids[:3]  # delivered prefix, then the sentinel


def test_upstream_4xx_error_event_propagates_without_failover():
    offered = []
    faults.arm(
        "http.stream.open",
        action=lambda **ctx: [
            "data: " + json.dumps({
                "error": {"message": "prompt too long", "code": 413},
            }),
        ],
    )
    replica = _stream_replica()
    replica.set_handoff(lambda req: offered.append(req) or True)
    req = replica.submit("x" * 64, max_new_tokens=8, temperature=0.0)
    with pytest.raises(Exception) as exc_info:
        req.future.result(timeout=30)
    assert getattr(exc_info.value, "status_code", 0) == 413
    # Request-shaped errors fail identically on every replica: a
    # failover would just re-fail elsewhere (and double-bill the work).
    assert offered == []


def test_cancelled_caller_stops_stream_consumption_without_failover():
    from gofr_tpu.errors import ErrorRequestCancelled

    replica = _stream_replica()
    offered = []
    replica.set_handoff(lambda req: offered.append(req) or True)
    holder = {}
    handed_over = threading.Event()

    def lines(**ctx):
        # Trip the CANCEL TOKEN (not the future) mid-delivery — the
        # transport-agnostic cancellation path: the consumer must
        # notice at the next event, walk away quietly, and resolve the
        # future with the same typed error the in-proc reap uses.
        yield _sse([9, 8])
        # submit() starts this consumer before it returns the request:
        # under load the generator gets here first, and a KeyError from
        # it would read as a transport fault and fail over.
        assert handed_over.wait(10)
        holder["req"].cancel.cancel()
        yield _sse([7, 6])
        yield from _sse_lines([5], done=True)

    faults.arm("http.stream.open", action=lines)
    req = replica.submit("x", max_new_tokens=8, temperature=0.0)
    holder["req"] = req
    handed_over.set()
    assert _drain(req) == [9, 8]
    with pytest.raises(ErrorRequestCancelled):
        req.future.result(timeout=10)
    assert offered == []  # nobody wants this stream: no failover
    assert replica.load() == 0


def test_sampling_body_forwards_explicit_seed_zero():
    # seed=0 is a valid explicit seed; dropping it from the wire while
    # remote_seeded marks the request resumable would let a sibling
    # re-walk a sampled prefix on a different sample path.
    body = HTTPReplica._sampling_body(
        "p", {"seed": 0, "temperature": 0.8}, stream=True
    )
    assert body["seed"] == 0
    assert "seed" not in HTTPReplica._sampling_body("p", {}, stream=True)


# ----------------------------------------------------------------------
# acceptance: remote dies mid-SSE → in-proc sibling, byte-identical,
# one trace
# ----------------------------------------------------------------------

PARAMS = dict(max_new_tokens=24, temperature=0.0, stop_on_eos=False)


def _flight_entries_with_failover(pool, trace_id):
    return [
        e
        for snap in pool.flight_records()["replicas"].values()
        for e in snap.get("records", []) + snap.get("pinned", [])
        if e["trace_id"] == trace_id
        and any(a["name"] == "tpu.failover" for a in e["annotations"])
    ]


def test_remote_truncated_sse_fails_over_byte_identical_one_trace(
    capture, metrics, sibling
):
    """THE acceptance path: a remote replica killed mid-SSE (truncated
    stream, no terminal framing) hands its live request to the in-proc
    sibling, which resumes from the delivered-token prefix — the client
    stream is byte-identical to a fault-free run, zero 5xx, one trace
    id spans both replicas, and /debug/flight shows the failover."""
    prompt = "multi-host failover stream"
    ref = sibling.generate_sync(prompt, **PARAMS)
    capture.clear()
    # The remote delivers the first 8 tokens of the (shared-weights)
    # greedy path, then vanishes without [DONE].
    faults.arm(
        "http.stream.open",
        action=lambda **ctx: _sse_lines(
            ref.token_ids[:8], chunk=3, finish=None, done=False
        ),
    )
    remote = _stream_replica("remote-a")
    pool = _pool([remote, EngineReplica("b", sibling)], metrics=metrics)
    before = counter_total(metrics, "app_tpu_remote_stream_failovers_total")
    try:
        req = pool.submit_generate(prompt, traceparent=TRACEPARENT, **PARAMS)
        toks = _drain(req)
        result = req.future.result(timeout=180)  # zero 5xx: resolves ok
        assert faults.fired("http.stream.open") == 1  # remote served first
        assert toks == ref.token_ids
        assert result.token_ids == ref.token_ids
        assert result.finish_reason == ref.finish_reason
        after = counter_total(
            metrics, "app_tpu_remote_stream_failovers_total"
        )
        assert after == before + 1

        # ONE trace: the timeline minted on the adopting replica joined
        # the caller's traceparent, so every span — including the
        # failover annotation — shares the request's trace id.
        root = capture.by_name("tpu.request")[0]
        assert root.trace_id == "ab" * 16
        span_names = {s.name for s in capture.spans}
        assert "tpu.failover" in span_names
        assert all(
            s.trace_id == root.trace_id
            for s in capture.spans if s.name.startswith("tpu.")
        )
        failover_span = capture.by_name("tpu.failover")[0]
        assert failover_span.attributes["source"] == "remote-a"
        assert failover_span.attributes["target"] == "b"

        # /debug/flight: the SAME timeline once, in the adopting
        # replica's recorder, with the failover annotation and the
        # replica-descriptor detail this PR adds.
        entries = _flight_entries_with_failover(pool, root.trace_id)
        assert len(entries) == 1
        assert entries[0]["outcome"] == "ok"
        flights = pool.flight_records()["replicas"]
        assert flights["remote-a"]["remote"] is True
        assert flights["remote-a"]["state"] == "SERVING"
        assert "adapters" in flights["b"]
    finally:
        faults.reset()
        _release(pool)


def test_remote_mid_body_reset_fails_over_byte_identical(metrics, sibling):
    """Same acceptance contract, different wound: the connection resets
    MID-BODY (tagged read loss between SSE events) instead of ending
    quietly."""
    prompt = "reset mid body"
    ref = sibling.generate_sync(prompt, **PARAMS)
    faults.arm(
        "http.stream.open",
        action=lambda **ctx: _sse_lines(ref.token_ids[:9], chunk=3),
    )
    # Three events (9 tokens) delivered, then the wire dies.
    faults.arm(
        "http.stream.event", raises=_tagged("read", "connection reset"),
        after=3,
    )
    remote = _stream_replica("remote-a")
    pool = _pool([remote, EngineReplica("b", sibling)], metrics=metrics)
    try:
        req = pool.submit_generate(prompt, **PARAMS)
        toks = _drain(req)
        result = req.future.result(timeout=180)
        assert toks == ref.token_ids
        assert result.token_ids == ref.token_ids
    finally:
        faults.reset()
        _release(pool)


class _StallServer(threading.Thread):
    """A real socket that answers one streaming request with valid SSE
    headers + the given events, then holds the connection open without
    ever sending another byte — the slow-loris upstream."""

    def __init__(self, payload: bytes):
        super().__init__(daemon=True)
        self.payload = payload
        self._sock = socket.socket()
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(1)
        self.port = self._sock.getsockname()[1]
        self._conns = []

    def run(self):
        try:
            conn, _ = self._sock.accept()
        except OSError:
            return
        self._conns.append(conn)
        try:
            conn.recv(65536)  # the POST; no need to parse it
            conn.sendall(
                b"HTTP/1.1 200 OK\r\n"
                b"Content-Type: text/event-stream\r\n"
                b"Connection: close\r\n\r\n" + self.payload
            )
        except OSError:
            pass
        # ... and then silence: never more bytes, never EOF.

    def close(self):
        for conn in self._conns:
            try:
                conn.close()
            except OSError:
                pass
        try:
            self._sock.close()
        except OSError:
            pass


def test_remote_slow_loris_stall_fails_over_past_idle_timeout(
    metrics, sibling
):
    """A remote that keeps the connection open but stops sending bytes
    (slow-loris) trips the per-read idle budget — classified as a read
    stall, the live request resumes on the sibling byte-identically."""
    prompt = "slow loris stall"
    ref = sibling.generate_sync(prompt, **PARAMS)
    payload = "".join(
        line + "\n" for line in _sse_lines(
            ref.token_ids[:4], chunk=2, finish=None, done=False
        )
    ).encode()
    server = _StallServer(payload)
    server.start()
    svc = HTTPService(f"http://127.0.0.1:{server.port}", timeout=10.0)
    remote = HTTPReplica(
        "stalled", svc, tokenizer=ByteTokenizer(), idle_timeout_s=0.3,
    )
    pool = _pool([remote, EngineReplica("b", sibling)], metrics=metrics)
    try:
        req = pool.submit_generate(prompt, **PARAMS)
        toks = _drain(req)
        result = req.future.result(timeout=180)
        assert toks == ref.token_ids
        assert result.token_ids == ref.token_ids
    finally:
        _release(pool)
        server.close()
        svc.close()


def test_lora_request_fails_over_with_lazy_adapter_reconciliation(
    metrics, sibling
):
    """Acceptance: a LoRA-adapter request has the same failover rights
    as a base-model one. The remote advertised (and was serving) the
    adapter; at failover NO routable sibling has it loaded, so the pool
    lazily reconciles — loading the registered source onto the sibling
    — and the stream completes byte-identically under the adapter's
    weights."""
    import jax

    from gofr_tpu.models.transformer import lora_dims

    rank, cfg = 4, sibling.cfg
    key = jax.random.PRNGKey(23)
    leaves = {}
    for target in ("wq", "wk", "wv", "wo"):
        d_in, d_out = lora_dims(cfg, target)
        key, k1, k2 = jax.random.split(key, 3)
        leaves[target] = (
            0.5 * jax.random.normal(k1, (cfg.n_layers, d_in, rank)),
            0.5 * jax.random.normal(k2, (cfg.n_layers, rank, d_out)),
        )
    prompt = "adapter failover"
    params = dict(PARAMS, adapter="tuned")
    # The oracle: generate WITH the adapter, then unload it — the
    # reconciliation below must reproduce this exactly from the
    # registered source.
    sibling.load_lora("tuned", leaves)
    try:
        ref = sibling.generate_sync(prompt, **params)
        base = sibling.generate_sync(prompt, **PARAMS)
        assert ref.token_ids != base.token_ids  # the adapter matters
    finally:
        sibling.unload_lora("tuned")

    faults.arm(
        "http.stream.open",
        action=lambda **ctx: _sse_lines(
            ref.token_ids[:6], chunk=3, finish=None, done=False
        ),
    )
    remote = _stream_replica("remote-lora")
    remote._adapters = frozenset({"tuned"})  # advertised via last probe
    pool = _pool([remote, EngineReplica("b", sibling)], metrics=metrics)
    pool.register_adapter_source("tuned", leaves)
    try:
        assert "tuned" not in pool.replicas[1].adapters()
        req = pool.submit_generate(prompt, **params)
        toks = _drain(req)
        result = req.future.result(timeout=180)
        assert faults.fired("http.stream.open") == 1  # routed to the
        # advertising remote, not the adapterless sibling
        assert toks == ref.token_ids
        assert result.token_ids == ref.token_ids
        # The sibling now advertises the adapter it lazily loaded.
        assert "tuned" in pool.replicas[1].adapters()
        assert "tuned" in pool.lora_names()
    finally:
        faults.reset()
        _release(pool)
        try:
            sibling.unload_lora("tuned")
        except KeyError:
            pass


def test_connect_reset_during_hedge_retries_on_sibling(metrics, sibling):
    """Unary path: the routed remote connect-resets; the budgeted
    fast-fail retry lands on the sibling and the caller never sees the
    loss. The remote is NOT demoted — that is the prober's decision."""
    prompt = "hedged connect reset"
    ref = sibling.generate_sync(prompt, **PARAMS)
    faults.arm("http.request", raises=_tagged("connect", "reset by peer"))
    remote = HTTPReplica(
        "flaky", HTTPService("http://127.0.0.1:9"), stream=False,
    )
    pool = _pool([remote, EngineReplica("b", sibling)], metrics=metrics)
    before = counter_total(metrics, "app_tpu_hedged_requests_total")
    try:
        result = pool.generate_sync(prompt, timeout=120, **PARAMS)
        assert faults.fired("http.request") == 1  # remote was tried first
        assert result.token_ids == ref.token_ids
        assert counter_total(
            metrics, "app_tpu_hedged_requests_total"
        ) == before + 1
        assert not remote.probe_failed
    finally:
        faults.reset()
        _release(pool)


# ----------------------------------------------------------------------
# streaming through a REAL remote gofr_tpu app (live socket)
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
        ).result(120)
        return self

    def __exit__(self, *exc):
        import asyncio

        asyncio.run_coroutine_threadsafe(
            self.app.stop(), self._loop
        ).result(30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        self._loop.close()

    @property
    def address(self):
        return f"http://127.0.0.1:{self.app.http_port}"


def test_streaming_through_real_remote_app_matches_remote_engine():
    """Integration proof for the whole wire: a pool fronting a REAL
    remote gofr_tpu app consumes its OpenAI SSE with
    ``stream_options.include_tokens`` over a live socket; the streamed
    token ids match the remote engine's own generation, and the remote
    pod's flight recorder shows the request under the CALLER's trace id
    (one trace across hosts)."""
    from gofr_tpu import App
    from gofr_tpu.serving.openai_compat import add_openai_routes
    from gofr_tpu.service import new_http_service

    app = App(config=MockConfig({
        "APP_NAME": "remote-pod", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2",
        "TPU_MAX_LEN": "128",
    }))
    add_openai_routes(app)
    prompt_ids = [72, 101, 108, 108, 111]  # id-array prompt: no
    # tokenizer coupling between the pool and the remote pod
    with _Harness(app) as harness:
        direct = app.container.tpu.generate_sync(
            prompt_ids, max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        svc = new_http_service(harness.address)
        replica = HTTPReplica("pod-0", svc)
        pool = _pool([replica])
        try:
            assert replica.supports_stream  # streaming remotes default on
            req = pool.submit_generate(
                prompt_ids, max_new_tokens=8, temperature=0.0,
                stop_on_eos=False, traceparent=TRACEPARENT,
            )
            toks = _drain(req)
            result = req.future.result(timeout=120)
            assert toks == direct.token_ids
            assert result.token_ids == direct.token_ids
            assert result.prompt_tokens == len(prompt_ids)
            assert replica.load() == 0
            # The remote pod adopted the caller's traceparent from the
            # forwarded header: its OWN flight recorder shows the
            # request under the SAME trace id — cross-host stitching,
            # observed end to end on the receiving side.
            flights = app.container.tpu.flight_records()
            assert any(
                e["trace_id"] == "ab" * 16
                for e in flights.get("records", [])
                + flights.get("pinned", [])
            )
            # Probe over the live wire refreshes health + capabilities.
            assert pool.probe_once() == {"pod-0": "pass"}
        finally:
            _release(pool)


# ----------------------------------------------------------------------
# PoolScaler: load-adaptive spawn/drain (stub replicas, injected clocks)
# ----------------------------------------------------------------------


class _ScalerStub(Replica):
    supports_stream = True

    def __init__(self, name, load=0):
        super().__init__(name)
        self.load_value = load
        self.closed = False
        self.handoff = None

    def state(self):
        return "SERVING"

    def load(self):
        return self.load_value

    def set_handoff(self, handoff):
        self.handoff = handoff

    def close(self):
        self.closed = True


def _scaler(pool, spawn, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("up_load_per_replica", 4.0)
    kw.setdefault("down_load_per_replica", 0.5)
    kw.setdefault("scale_up_wait_s", 10.0)
    kw.setdefault("scale_down_wait_s", 60.0)
    kw.setdefault("interval_s", 0)  # no thread: tests drive evaluate()
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("metrics", pool._metrics)
    return PoolScaler(pool, spawn, **kw)


def test_scaler_spawns_under_sustained_pressure_never_past_max(metrics):
    spawned = []

    def spawn():
        replica = _ScalerStub(f"scaled-{len(spawned)}", load=9)
        spawned.append(replica)
        return replica

    a = _ScalerStub("a", load=9)
    pool = _pool([a], metrics=metrics)
    scaler = _scaler(pool, spawn)
    before = counter_total(metrics, "app_tpu_scale_events_total")
    # Pressure must SUSTAIN for scale_up_wait_s: a single bursty sweep
    # never spawns (cold engines take seconds to become useful).
    assert scaler.evaluate(now=0.0) == "steady"
    assert scaler.evaluate(now=9.9) == "steady"
    assert spawned == []
    assert scaler.evaluate(now=10.0) == "up"
    assert len(pool.replicas) == 2
    assert spawned[0].handoff is not None  # failover wiring on join
    # Still saturated: the window re-anchors, then the ceiling holds.
    assert scaler.evaluate(now=20.0) == "steady"
    assert scaler.evaluate(now=30.0) == "up"
    assert len(pool.replicas) == 3
    for t in (40.0, 50.0, 60.0, 70.0):
        assert scaler.evaluate(now=t) == "steady"  # at TPU_POOL_MAX
    assert len(pool.replicas) == 3
    assert len(spawned) == 2
    assert counter_total(
        metrics, "app_tpu_scale_events_total"
    ) == before + 2


def test_scaler_drains_idle_spawned_replica_and_respects_min(metrics):
    spawned = []

    def spawn():
        replica = _ScalerStub(f"scaled-{len(spawned)}", load=0)
        spawned.append(replica)
        return replica

    a = _ScalerStub("a", load=9)
    pool = _pool([a], metrics=metrics)
    # down threshold 0.6: a pool with ONE lingering in-flight request
    # across two replicas (0.5/replica) still counts as idle enough.
    scaler = _scaler(pool, spawn, down_load_per_replica=0.6)
    assert scaler.evaluate(now=0.0) == "steady"
    assert scaler.evaluate(now=10.0) == "up"
    victim = spawned[0]
    victim.load_value = 1  # one request still in flight
    a.load_value = 0  # the burst passed

    picked_during_drain = []

    def drain_sleep(_s):
        # While draining, routing already skips the victim — and the
        # in-flight request finishes before retirement (zero dropped).
        picked_during_drain.append(pool.pick().name)
        victim.load_value = 0

    scaler._sleep = drain_sleep
    # Idleness must sustain for scale_down_wait_s.
    assert scaler.evaluate(now=20.0) == "steady"
    assert scaler.evaluate(now=79.9) == "steady"
    assert scaler.evaluate(now=80.0) == "down"
    assert picked_during_drain == ["a"]  # never the draining victim
    assert victim.closed
    assert victim.handoff is None  # detached before retirement
    assert [r.name for r in pool.replicas] == ["a"]
    # At the floor now: idleness forever never drains below min.
    for t in (150.0, 220.0, 290.0):
        assert scaler.evaluate(now=t) == "steady"
    assert len(pool.replicas) == 1


def test_drain_aborts_and_readmits_when_inflight_never_completes(metrics):
    clock = [0.0]
    a = _ScalerStub("a")
    b = _ScalerStub("b", load=2)  # stuck in-flight work
    pool = _pool([a, b], metrics=metrics, clock=lambda: clock[0])

    def stuck_sleep(_s):
        clock[0] += 1.0  # time passes; the work never completes

    assert pool.drain_replica(b, timeout_s=5.0, sleep=stuck_sleep) is False
    # Nothing dropped, nothing closed: the replica re-entered routing.
    assert not b.closed
    assert not b.draining
    assert b in pool.replicas
    assert b.handoff is not None


def test_scaler_repairs_floor_immediately_when_capacity_dies(metrics):
    spawned = []

    def spawn():
        replica = _ScalerStub(f"scaled-{len(spawned)}")
        spawned.append(replica)
        return replica

    a, b = _ScalerStub("a"), _ScalerStub("b")
    pool = _pool([a, b], metrics=metrics)
    scaler = _scaler(pool, spawn, min_replicas=2, max_replicas=3)
    assert scaler.evaluate(now=0.0) == "steady"
    b.probe_failed = True  # demoted: no longer counts as capacity
    # Below min is a violation NOW — no sustain window.
    assert scaler.evaluate(now=0.1) == "up"
    assert len(pool.replicas) == 3
    # Another death: capacity is 2 == min again... then a third dies.
    spawned[0].probe_failed = True
    # MEMBERSHIP is at max_replicas: never exceeded, even to repair the
    # floor — recovering the demoted replicas is the prober's job.
    assert scaler.evaluate(now=0.2) == "steady"
    assert len(pool.replicas) == 3
    assert len(spawned) == 1


def test_pool_gauges_report_composition_by_state(metrics):
    a = _ScalerStub("a")
    b = _ScalerStub("b")
    c = _ScalerStub("c")
    pool = _pool([a, b, c], metrics=metrics)
    b.draining = True
    c.probe_failed = True
    pool.publish_pool_gauges()
    inst = {i.name: i for i in metrics.instruments()}["app_tpu_pool_replicas"]
    values = {
        dict(labels)["state"]: v for labels, v in inst.collect().items()
    }
    assert values["serving"] == 1.0
    assert values["draining"] == 1.0
    assert values["down"] == 1.0
    assert values["restarting"] == 0.0
