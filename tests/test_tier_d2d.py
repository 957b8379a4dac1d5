"""Device-to-device / over-the-wire tier-transfer suite (ISSUE 14
acceptance gate).

The PR 8 disaggregated tiers shipped every finished prefill HOST-BOUNCE;
this suite pins the leg-aware ladder that replaces it:

* **device leg** (shared JAX runtime): per-block jitted extraction +
  sharding-aware ``device_put`` + donated jitted ``paged_move_block`` —
  zero host copies, pinned byte-identical to the fused reference for
  greedy AND seeded-sampled streams, at tp=1 and across DISJOINT tp=2
  meshes (the 8-virtual-device conftest), with zero steady-state
  recompiles across repeated transfers after the warm-up fence;
* **wire leg** (remote decode replica): the exported payload rides a
  length-prefixed binary POST to the remote's ops-port import endpoint
  (a REAL gofr_tpu app over a live socket), then the request streams
  there over the ordinary OpenAI SSE — byte-identical, one trace id;
* **the failure matrix, per leg**: mid-POST death, corrupt body, and a
  stale geometry fingerprint all degrade to ``"fused"`` (re-prefill on
  the adopter) with zero 5xx and one trace id; a dead ops port excludes
  the target; a device-leg exception bans the leg and the SAME target
  retries one rung down (device → host) — any leg failure degrades to
  the next rung, terminally fused;
* **leg selection**: the automatic ladder picks device for in-proc
  targets and wire for remotes; ``TPU_TRANSFER_LEG`` pins exactly one;
* **per-SLO-class priority dequeue** (rode along): deterministic
  ordering under stated clocks — interactive jumps queued batch work,
  stable FIFO within a class, max-wait promotion as the starvation
  bound — and the engine wires it from ``TPU_QUEUE_CLASS_PROMOTE_S``.

Everything is deterministic: faults fire on exact hit counts, the
backoff sleeps record instead of sleeping, and the wire chaos rides the
``http.request`` fault point so no real packet is harmed.
"""

from __future__ import annotations

import asyncio
import http.client
import queue as queue_mod
import random
import threading
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.errors import ErrorServiceUnavailable
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.ops.kv_cache import (
    KVBlockPayload,
    export_blocks,
    payload_from_wire,
    payload_to_wire,
)
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.lifecycle import ClassPriorityQueue
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.replica_pool import (
    EngineReplica,
    HTTPReplica,
    ReplicaPool,
)

TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"

COUNTERS = (
    "app_tpu_tier_transfers_total",
    "app_tpu_tier_transfer_bytes_total",
    "app_tpu_failovers_total",
    "app_tpu_requests_replayed_total",
    "app_tpu_tokens_generated",
    "app_tpu_prefix_lookup_total",
    "app_tpu_prefix_hit_tokens_total",
)
GAUGES = (
    "app_tpu_tier_mode",
    "app_tpu_engine_state",
    "app_tpu_replica_state",
    "app_tpu_pool_replicas",
    "app_tpu_queue_depth",
    "app_tpu_kv_slots_in_use",
    "app_tpu_kv_blocks_free",
    "app_tpu_prefix_cached_blocks",
    "app_tpu_hbm_used_bytes",
)
HISTOGRAMS = (
    "app_tpu_tier_transfer_seconds",
    "app_tpu_infer_latency",
    "app_tpu_batch_size",
)


def _metrics_manager():
    m = new_metrics_manager()
    for name in COUNTERS:
        m.new_counter(name)
    for name in GAUGES:
        m.new_gauge(name)
    for name in HISTOGRAMS:
        m.new_histogram(name)
    return m


def counter_total(metrics, name, **labels):
    inst = {i.name: i for i in metrics.instruments()}[name]
    total = 0.0
    for key, value in inst.collect().items():
        if all((k, str(v)) in key for k, v in labels.items()):
            total += value
    return total


def _prompt(tag: int):
    """96 tokens = exactly 3 full 32-token blocks, distinct per tag so
    every test's transfer ships COLD content (a collision would dedupe
    against the shared decode engine's radix and skip the leg under
    test)."""
    return [2 + (i * 7 + tag * 13) % 200 for i in range(95)] + [tag % 200]


@pytest.fixture(scope="module")
def metrics():
    return _metrics_manager()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def _make_engine(metrics, **kw):
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, window_k=4,
        pipeline_depth=1, prefill_chunk=32, kv_block=32, auto_prefix=True,
        tokenizer=ByteTokenizer(), metrics=metrics, **kw,
    )
    eng.start_sync()
    return eng


@pytest.fixture(scope="module")
def engines(metrics):
    """One prefill + one decode engine shared by the suite (compile
    cost), plus a fused single-engine reference for byte-identity."""
    pf = _make_engine(metrics)
    dc = _make_engine(metrics)
    ref = _make_engine(metrics)
    yield pf, dc, ref
    faults.reset()
    for eng in (pf, dc, ref):
        eng.close()


def _pool(replicas, metrics, **kw):
    sleeps: list = []
    kw.setdefault("probe_interval_s", 0)
    kw.setdefault("probe_timeout_s", 60.0)
    kw.setdefault("hedge_delay_s", 300.0)
    kw.setdefault("transfer_retries", 2)
    kw.setdefault("transfer_backoff_s", 0.01)
    kw.setdefault("sleep", sleeps.append)
    kw.setdefault("rng", random.Random(7))
    pool = ReplicaPool(replicas, metrics=metrics, **kw)
    pool._test_sleeps = sleeps
    return pool


@pytest.fixture()
def tier_pool(metrics, engines):
    pf, dc, _ = engines
    pool = _pool(
        [
            EngineReplica("pf", pf, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        metrics,
    )
    yield pool
    pool.stop_prober()
    for replica in pool.replicas:
        replica.set_handoff(None)
        replica.set_tier_exporter(None)


def _drain(req, timeout=120.0):
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


def _legs(req):
    tl = req.timeline
    assert tl is not None
    return [(result, leg) for _, _, _, _, result, leg in tl.transfers]


# ----------------------------------------------------------------------
# device leg: byte-identity, observability, zero recompiles
# ----------------------------------------------------------------------


def test_device_leg_greedy_byte_identical(metrics, engines, tier_pool):
    """The automatic ladder picks the device leg for in-proc targets;
    the stream is byte-identical to the fused reference, the transfer
    is tagged leg="device" end to end (counter, bytes counter,
    timeline), and the decode replica's radix holds the blocks."""
    pf, dc, ref = engines
    prompt = _prompt(1)
    want = ref.generate_sync(prompt, max_new_tokens=10, temperature=0.0,
                             timeout=120.0)
    ok0 = counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok", leg="device"
    )
    bytes0 = counter_total(
        metrics, "app_tpu_tier_transfer_bytes_total", leg="device"
    )
    req = tier_pool.submit_generate(prompt, max_new_tokens=10,
                                    temperature=0.0)
    toks = _drain(req)
    result = req.future.result(timeout=5)  # zero 5xx
    assert toks == result.token_ids == want.token_ids
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok", leg="device"
    ) == ok0 + 1
    assert counter_total(
        metrics, "app_tpu_tier_transfer_bytes_total", leg="device"
    ) > bytes0
    assert _legs(req) == [("ok", "device")]
    tl = req.timeline
    assert len(tl.trace_id) == 32  # one trace end to end
    assert dc._radix.n_cached_blocks >= 3


def test_device_leg_seeded_sampled_byte_identical(engines, tier_pool):
    _, _, ref = engines
    prompt = _prompt(2)
    want = ref.generate_sync(
        prompt, max_new_tokens=10, temperature=0.8, seed=42, timeout=120.0
    )
    req = tier_pool.submit_generate(
        prompt, max_new_tokens=10, temperature=0.8, seed=42
    )
    toks = _drain(req)
    assert toks == want.token_ids
    assert req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("ok", "device")]


def test_host_pin_byte_identical(metrics, engines):
    """TPU_TRANSFER_LEG=host pins the PR 8 host bounce; same bytes,
    same stream, leg="host" in every signal."""
    pf, dc, ref = engines
    prompt = _prompt(3)
    pool = _pool(
        [
            EngineReplica("pf", pf, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        metrics, transfer_leg="host",
    )
    try:
        want = ref.generate_sync(prompt, max_new_tokens=10,
                                 temperature=0.0, timeout=120.0)
        req = pool.submit_generate(prompt, max_new_tokens=10,
                                   temperature=0.0)
        toks = _drain(req)
        assert toks == want.token_ids
        assert _legs(req) == [("ok", "host")]
    finally:
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)
            replica.set_tier_exporter(None)


def test_zero_steady_state_recompiles_repeated_device_transfers(
    metrics, engines, tier_pool
):
    """Repeated device-leg transfers after the PR 10 warm-up fence
    compile nothing: extract/move are one fixed-shape program per cache
    geometry. The first transfer here is the warm-up: under xdist's load
    distribution the suite's earlier transfers may have run on another
    worker's engines, and this test alone would then meet the first
    compile of extract/move and of the decode window after its fence."""
    pf, dc, _ = engines
    _drain(tier_pool.submit_generate(
        _prompt(9), max_new_tokens=6, temperature=0.0
    ))
    pf.mark_steady_state()
    dc.mark_steady_state()
    for tag in (4, 5, 6):
        req = tier_pool.submit_generate(
            _prompt(tag), max_new_tokens=6, temperature=0.0
        )
        _drain(req)
        assert _legs(req) == [("ok", "device")]
    for eng in (pf, dc):
        assert eng.compile_stats()["steady_state_recompiles"] == 0


def test_device_leg_failure_degrades_to_host_rung(metrics, engines,
                                                  tier_pool):
    """A device-leg import blowing up bans the leg for that transfer
    and the SAME target retries one rung down (host bounce) — the
    ladder's any-leg-failure contract, still byte-identical, still one
    transfer counted (result=ok, leg=host)."""
    pf, dc, ref = engines
    prompt = _prompt(7)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    with faults.armed(
        "tier.import", raises=RuntimeError("device import died"), times=1
    ):
        req = tier_pool.submit_generate(prompt, max_new_tokens=8,
                                        temperature=0.0)
        toks = _drain(req)
    assert toks == want.token_ids
    assert req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("ok", "host")]


def test_tp2_device_leg_across_disjoint_meshes_byte_identical(metrics,
                                                              engines):
    """Prefill pod on devices[0:2], decode pod on devices[2:4]: the
    device leg reshards each block shard-to-shard with an explicit
    ``device_put`` — no host gather (GL018's lived contract) — and the
    stream stays byte-identical to the unsharded fused reference."""
    import jax

    _, _, ref = engines
    devs = list(jax.devices())
    if len(devs) < 4:
        pytest.skip("needs the conftest's 8 virtual devices")
    prompt = _prompt(8)
    pf2 = _make_engine(metrics, devices=devs[0:2], tp=2)
    dc2 = _make_engine(metrics, devices=devs[2:4], tp=2)
    pool = _pool(
        [
            EngineReplica("pf2", pf2, role="prefill"),
            EngineReplica("dc2", dc2, role="decode"),
        ],
        metrics,
    )
    try:
        want = ref.generate_sync(prompt, max_new_tokens=10,
                                 temperature=0.0, timeout=240.0)
        req = pool.submit_generate(prompt, max_new_tokens=10,
                                   temperature=0.0)
        toks = _drain(req, timeout=240.0)
        assert toks == want.token_ids
        assert _legs(req) == [("ok", "device")]
        assert dc2._radix.n_cached_blocks >= 3
    finally:
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)
            replica.set_tier_exporter(None)
        pf2.close()
        dc2.close()


# ----------------------------------------------------------------------
# wire leg: a real remote decode replica over a live socket
# ----------------------------------------------------------------------


class _Harness:
    """Boot a gofr_tpu App on ephemeral ports (httptest.Server role)."""

    def __init__(self, app):
        self.app = app
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, daemon=True
        )

    def __enter__(self):
        self._thread.start()
        asyncio.run_coroutine_threadsafe(
            self.app.start(), self._loop
        ).result(120)
        return self

    def __exit__(self, *exc):
        asyncio.run_coroutine_threadsafe(
            self.app.stop(), self._loop
        ).result(30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        self._loop.close()

    @property
    def address(self):
        return f"http://127.0.0.1:{self.app.http_port}"

    @property
    def ops_address(self):
        return f"http://127.0.0.1:{self.app.metrics_port}"


@pytest.fixture(scope="module")
def remote_app():
    """A REAL decode-replica app: OpenAI SSE on the HTTP port, the
    tier-import endpoint on the ops port. Same model/seed as the
    in-proc engines, so tiered streams are byte-identical."""
    from gofr_tpu import App
    from gofr_tpu.serving.openai_compat import add_openai_routes

    app = App(config=MockConfig({
        "APP_NAME": "remote-decode", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "4",
        "TPU_MAX_LEN": "256", "TPU_KV_BLOCK": "32",
        "TPU_AUTO_PREFIX": "true", "TPU_PREFILL_CHUNK": "32",
    }))
    add_openai_routes(app)
    with _Harness(app) as harness:
        yield app, harness


@pytest.fixture()
def wire_pool(metrics, engines, remote_app):
    """1 in-proc prefill + 1 REMOTE decode replica (wire-leg import
    service at the remote's ops port)."""
    from gofr_tpu.service import new_http_service

    pf, _, _ = engines
    app, harness = remote_app
    remote = HTTPReplica(
        "dc-remote",
        new_http_service(harness.address),
        tokenizer=pf.tokenizer,
        role="decode",
        import_service=new_http_service(harness.ops_address),
        metrics=metrics,
    )
    assert remote.supports_tier_import
    pool = _pool(
        [EngineReplica("pf", pf, role="prefill"), remote], metrics,
    )
    yield pool
    pool.stop_prober()
    for replica in pool.replicas:
        replica.set_handoff(None)
        replica.set_tier_exporter(None)
    remote.close()


def test_wire_leg_greedy_byte_identical_one_trace(metrics, engines,
                                                  remote_app, wire_pool):
    """THE wire acceptance path: blocks POSTed to the remote ops port,
    the request streamed over OpenAI SSE — byte-identical to the fused
    reference, result=ok leg=wire, the remote's radix warmed, and the
    remote's flight recorder shows the request under the CALLER's
    trace id (one trace across hosts)."""
    _, _, ref = engines
    app, _ = remote_app
    prompt = _prompt(20)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    cached0 = app.container.tpu._radix.n_cached_blocks
    ok0 = counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok", leg="wire"
    )
    req = wire_pool.submit_generate(
        prompt, max_new_tokens=8, temperature=0.0, traceparent=TRACEPARENT,
    )
    toks = _drain(req)
    result = req.future.result(timeout=5)  # zero 5xx
    assert toks == result.token_ids == want.token_ids
    assert counter_total(
        metrics, "app_tpu_tier_transfers_total", result="ok", leg="wire"
    ) == ok0 + 1
    assert _legs(req) == [("ok", "wire")]
    assert app.container.tpu._radix.n_cached_blocks >= cached0 + 3
    flights = app.container.tpu.flight_records()
    assert any(
        e["trace_id"] == "ab" * 16
        for e in flights.get("records", []) + flights.get("pinned", [])
    )


def test_wire_leg_seeded_sampled_byte_identical(engines, wire_pool):
    _, _, ref = engines
    prompt = _prompt(21)
    want = ref.generate_sync(
        prompt, max_new_tokens=8, temperature=0.8, seed=7, timeout=120.0
    )
    req = wire_pool.submit_generate(
        prompt, max_new_tokens=8, temperature=0.8, seed=7
    )
    toks = _drain(req)
    assert toks == want.token_ids
    assert _legs(req) == [("ok", "wire")]


def test_wire_mid_post_death_degrades_fused_zero_5xx(metrics, engines,
                                                     wire_pool):
    """The import POST dying mid-wire (read loss after the connection
    opened) degrades to fused adoption: the request still streams on
    the remote and re-prefills there — byte-identical, zero 5xx, one
    trace id, result=fused leg=wire."""
    _, _, ref = engines
    prompt = _prompt(22)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    exc = ErrorServiceUnavailable("mid-POST reset")
    exc.kind = "read"
    with faults.armed("http.request", raises=exc, times=1):
        req = wire_pool.submit_generate(prompt, max_new_tokens=8,
                                        temperature=0.0)
        toks = _drain(req)
    assert toks == want.token_ids
    assert req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("fused", "wire")]


def test_wire_corrupt_body_rejected_then_fused(metrics, engines,
                                               remote_app, wire_pool):
    """A corrupt wire body is rejected by the remote (400, CRC/framing)
    and the transfer degrades to fused — never a wrong answer. Both
    halves pinned: the endpoint's verdict on actually-corrupt bytes,
    and the exporter's ladder on a canned rejection."""
    from gofr_tpu.service.client import Response

    _, _, ref = engines
    app, harness = remote_app
    # Half 1: real corrupt bytes at the real endpoint.
    pf_cache_engine = ref
    payload = export_blocks(
        pf_cache_engine.cache, [1], list(range(32)), src="test"
    )
    body = bytearray(payload_to_wire(payload))
    body[-3] ^= 0xFF  # flip one plane byte: CRC must catch it
    conn = http.client.HTTPConnection(
        "127.0.0.1", app.metrics_port, timeout=60
    )
    conn.request("POST", "/ops/tier-import", body=bytes(body),
                 headers={"Content-Type": "application/octet-stream"})
    resp = conn.getresponse()
    verdict = resp.read()
    conn.close()
    assert resp.status == 200  # framing parsed; CRC fails at validation
    assert b'"fused"' in verdict
    # Short/garbage framing is a 400 "rejected", never a 5xx.
    conn = http.client.HTTPConnection(
        "127.0.0.1", app.metrics_port, timeout=60
    )
    conn.request("POST", "/ops/tier-import", body=b"garbage")
    resp = conn.getresponse()
    verdict = resp.read()
    conn.close()
    assert resp.status == 400
    assert b'"rejected"' in verdict
    # Half 2: the exporter sees a rejection → fused adoption,
    # byte-identical stream.
    prompt = _prompt(23)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    with faults.armed(
        "http.request",
        action=lambda **ctx: Response(b'{"result":"rejected"}', 400, {}),
        times=1,
    ):
        req = wire_pool.submit_generate(prompt, max_new_tokens=8,
                                        temperature=0.0)
        toks = _drain(req)
    assert toks == want.token_ids
    assert _legs(req) == [("fused", "wire")]


def test_wire_stale_fingerprint_fused(remote_app):
    """A payload from a different cache geometry must never alias into
    the remote pool: the endpoint accepts the bytes, validation fails
    the fingerprint, the reply is "fused" (the request re-prefills)."""
    import numpy as np

    app, _ = remote_app
    k = np.zeros((2, 1, 2, 16, 4), dtype=np.float32)  # wrong geometry
    from gofr_tpu.ops.kv_cache import payload_checksum

    stale = KVBlockPayload(
        block=16, token_ids=tuple(range(16)), k=k, v=k,
        src="old-pod", checksum=payload_checksum(k, k),
        geometry=(2, 2, 16, 4, "float32", False),
    )
    conn = http.client.HTTPConnection(
        "127.0.0.1", app.metrics_port, timeout=60
    )
    conn.request("POST", "/ops/tier-import", body=payload_to_wire(stale))
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    assert resp.status == 200
    assert b'"fused"' in body


def test_wire_dead_ops_port_excludes_target(metrics, engines):
    """Nothing listening at the ops port (connect-refused) → the remote
    is excluded; with no other decode target the request decodes
    locally on the prefill replica (local_fused) — served either way."""
    from gofr_tpu.service import new_http_service

    pf, _, ref = engines
    prompt = _prompt(24)
    remote = HTTPReplica(
        "dc-dead",
        new_http_service("http://127.0.0.1:9"),
        tokenizer=pf.tokenizer, role="decode",
        import_service=new_http_service("http://127.0.0.1:9"),
    )
    pool = _pool(
        [EngineReplica("pf", pf, role="prefill"), remote], metrics,
    )
    try:
        exc = ErrorServiceUnavailable("refused")
        exc.kind = "connect"
        want = ref.generate_sync(prompt, max_new_tokens=6,
                                 temperature=0.0, timeout=120.0)
        lf0 = counter_total(
            metrics, "app_tpu_tier_transfers_total", result="local_fused"
        )
        with faults.armed("http.request", raises=exc):
            req = pool.submit_generate(prompt, max_new_tokens=6,
                                       temperature=0.0)
            toks = _drain(req)
        assert toks == want.token_ids
        assert counter_total(
            metrics, "app_tpu_tier_transfers_total", result="local_fused"
        ) == lf0 + 1
    finally:
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)
            replica.set_tier_exporter(None)
        remote.close()


# ----------------------------------------------------------------------
# leg selection
# ----------------------------------------------------------------------


def test_leg_selection_matrix(metrics, engines):
    """The ladder's static half: automatic selection prefers device
    for in-proc targets; pins restrict to exactly one leg; a pin no
    target can serve degrades to local fused serving (never a 5xx)."""
    pf, dc, ref = engines
    cases = [
        ("", "device"),      # auto → device for an in-proc sibling
        ("device", "device"),
        ("host", "host"),
    ]
    for tag, (pin, expected) in enumerate(cases, start=30):
        prompt = _prompt(tag)
        pool = _pool(
            [
                EngineReplica("pf", pf, role="prefill"),
                EngineReplica("dc", dc, role="decode"),
            ],
            metrics, transfer_leg=pin,
        )
        try:
            req = pool.submit_generate(prompt, max_new_tokens=4,
                                       temperature=0.0)
            _drain(req)
            assert _legs(req) == [("ok", expected)], (pin,)
        finally:
            pool.stop_prober()
            for replica in pool.replicas:
                replica.set_handoff(None)
                replica.set_tier_exporter(None)
    # A wire pin with only in-proc decode targets: no reachable
    # target, the prefill replica decodes locally — still served.
    prompt = _prompt(39)
    want = ref.generate_sync(prompt, max_new_tokens=4, temperature=0.0,
                             timeout=120.0)
    pool = _pool(
        [
            EngineReplica("pf", pf, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        metrics, transfer_leg="wire",
    )
    try:
        lf0 = counter_total(
            metrics, "app_tpu_tier_transfers_total", result="local_fused"
        )
        req = pool.submit_generate(prompt, max_new_tokens=4,
                                   temperature=0.0)
        toks = _drain(req)
        assert toks == want.token_ids
        assert counter_total(
            metrics, "app_tpu_tier_transfers_total", result="local_fused"
        ) == lf0 + 1
    finally:
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)
            replica.set_tier_exporter(None)


def test_pool_import_facade_prefers_decode_and_tries_siblings():
    """The wire endpoint's pool facade must land blocks where the
    companion request will DECODE: decode-role replicas first, and a
    rejecting (unpaged/stale) replica must not stop a sibling from
    importing."""
    calls: list = []

    class _Eng(_StubEngine):
        def __init__(self, name, verdict):
            self._name, self._verdict = name, verdict

        def import_payload(self, payload):
            calls.append(self._name)
            return self._verdict

    pf = EngineReplica("pf", _Eng("pf", "imported"), role="prefill")
    dc = EngineReplica("dc", _Eng("dc", "imported"), role="decode")
    pool = ReplicaPool([pf, dc])
    assert pool.import_payload(object()) == "imported"
    assert calls == ["dc"]  # decode tier first, prefill never touched
    # A fused-replying (unpaged) decode replica falls through to the
    # next importer instead of wasting the shipped bytes.
    calls.clear()
    dc_unpaged = EngineReplica("dc0", _Eng("dc0", "fused"), role="decode")
    dc_paged = EngineReplica("dc1", _Eng("dc1", "imported"), role="decode")
    pool2 = ReplicaPool([pf, dc_unpaged, dc_paged])
    assert pool2.import_payload(object()) == "imported"
    assert calls == ["dc0", "dc1"]


def test_transfer_leg_validation():
    with pytest.raises(ValueError):
        ReplicaPool(
            [EngineReplica("x", _StubEngine())], transfer_leg="carrier-pigeon"
        )


class _StubEngine:
    family = "llm"
    tier_role = "fused"
    model_name = "stub"
    kv_block = 0

    def set_replica_handoff(self, h):
        pass

    def set_tier_exporter(self, e):
        pass

    @property
    def state(self):
        return "SERVING"


# ----------------------------------------------------------------------
# wire codec units
# ----------------------------------------------------------------------


def test_wire_codec_roundtrip_and_framing_rejections(engines):
    _, _, ref = engines
    import numpy as np

    payload = export_blocks(ref.cache, [1, 2], list(range(64)), src="me")
    wire = payload_to_wire(payload)
    back = payload_from_wire(wire)
    assert back.verify()
    assert back.compatible_with(ref.cache)
    assert back.token_ids == payload.token_ids
    assert back.checksum == payload.checksum
    assert np.array_equal(back.k, payload.k)
    assert back.nbytes() == payload.nbytes()
    # Framing violations raise ValueError (the endpoint's 400 rung).
    for bad in (b"", b"NOPE", wire[:10], wire[:-5]):
        with pytest.raises(ValueError):
            payload_from_wire(bad)
    # Byte corruption inside a plane survives framing but fails the
    # re-computed CRC.
    corrupt = bytearray(wire)
    corrupt[-3] ^= 0xFF
    assert not payload_from_wire(bytes(corrupt)).verify()


# ----------------------------------------------------------------------
# per-SLO-class priority dequeue (satellite)
# ----------------------------------------------------------------------


class _Req:
    def __init__(self, name, slo_class):
        self.name = name
        self.slo_class = slo_class


def test_class_dequeue_deterministic_ordering():
    """Stated clocks: interactive jumps queued standard/batch work at
    pop time, stable FIFO within a class."""
    now = [0.0]
    q = ClassPriorityQueue(promote_after_s=10.0, clock=lambda: now[0])
    for name, cls in (
        ("b0", "batch"), ("s0", "standard"), ("i0", "interactive"),
        ("b1", "batch"), ("i1", "interactive"), ("s1", "standard"),
    ):
        q.put_nowait(_Req(name, cls))
        now[0] += 1.0
    order = [q.get_nowait().name for _ in range(q.qsize())]
    assert order == ["i0", "i1", "s0", "s1", "b0", "b1"]
    with pytest.raises(queue_mod.Empty):
        q.get_nowait()


def test_class_dequeue_starvation_bound_promotes_oldest():
    """A lower-class head past the promotion window pops first — among
    over-age heads the OLDEST wins regardless of class, so batch work
    is delayed by at most the window, never forever."""
    now = [0.0]
    q = ClassPriorityQueue(promote_after_s=5.0, clock=lambda: now[0])
    q.put_nowait(_Req("b0", "batch"))
    now[0] = 2.0
    q.put_nowait(_Req("s0", "standard"))
    now[0] = 8.0
    q.put_nowait(_Req("i0", "interactive"))
    # b0 waited 8s > 5s, s0 6s > 5s: oldest over-age head (b0) first,
    # then s0, then the interactive arrival.
    assert [q.get_nowait().name for _ in range(3)] == ["b0", "s0", "i0"]


def test_class_dequeue_off_is_strict_fifo_and_unknown_is_standard():
    q = ClassPriorityQueue(promote_after_s=0.0)
    q.put_nowait(_Req("b", "batch"))
    q.put_nowait(_Req("i", "interactive"))
    assert [q.get_nowait().name, q.get_nowait().name] == ["b", "i"]
    q2 = ClassPriorityQueue(promote_after_s=10.0)
    q2.put_nowait(_Req("w", "weird-class"))
    q2.put_nowait(_Req("i", "interactive"))
    # Unknown classes rank standard (never 400, never starved-first).
    assert [q2.get_nowait().name, q2.get_nowait().name] == ["i", "w"]


def test_class_dequeue_maxsize_and_queue_api():
    q = ClassPriorityQueue(maxsize=2)
    q.put_nowait(_Req("a", "standard"))
    q.put_nowait(_Req("b", "standard"))
    with pytest.raises(queue_mod.Full):
        q.put_nowait(_Req("c", "standard"))
    assert q.qsize() == 2 and not q.empty()
    assert q.maxsize == 2


def test_engine_wires_class_dequeue_from_config(engines):
    """The engine's admission queue IS the class queue, wired from
    TPU_QUEUE_CLASS_PROMOTE_S (default 5s, 0 = strict FIFO)."""
    pf, _, _ = engines
    assert isinstance(pf._pending, ClassPriorityQueue)
    assert pf._pending.promote_after_s == 5.0
    eng = InferenceEngine.from_config(
        MockConfig({
            "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2",
            "TPU_MAX_LEN": "64", "TPU_QUEUE_CLASS_PROMOTE_S": "12.5",
        })
    )
    assert eng._pending.promote_after_s == 12.5
