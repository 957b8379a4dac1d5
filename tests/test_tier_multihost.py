"""True multi-host disaggregation suite (ISSUE 19 acceptance gate).

PR 14's transfer ladder stopped at device/wire/host inside one failure
domain. This suite pins the two planes that make the tiers genuinely
multi-host:

* **the dma leg** (new top rung): the exporter stages wire bytes on its
  process-local transfer server and ships only a ``KVH1`` claim ticket;
  the importer redeems it over a raw TCP fetch with layered budgets and
  post-fetch checksum/geometry/token verification. On CI jax (no
  ``jax.experimental.transfer``) the loopback emulation IS the backend,
  which is exactly what makes the matrix runnable without a pod;
* **streaming prefill sources** (the pull plane): a prefill-role remote
  advertising ``tier_source`` in health is asked for blocks it already
  computed (``POST /ops/tier-export`` — the tier-import codec run in
  reverse), dma ticket first, inline wire body one rung down, local
  prefill as the terminal rung;
* **the failure matrix on the new rungs** — each cell falls exactly ONE
  rung, byte-identical to the fused reference, zero 5xx, one trace id:
  stale/replayed/expired handles and checksum-geometry drift read as
  ``stale`` (never aliased as garbage), a dead transfer server is
  ``connect`` (next source, not next rung), slow-loris trips the read
  budget inside the request's own deadline, an armed ``offer`` bans the
  dma rung and the SAME target retries one rung down, and — the
  acceptance path — a REAL subprocess pod ``kill -9``'d mid-DMA (serve
  thread parked via the ``transfer.dma.serve`` seam) degrades
  dma → wire → local with zero leaked staged bodies or pool blocks on
  the surviving side.

The subprocess half (``@pytest.mark.slow``) boots
``tests/multihost_child.py`` pods on live ephemeral ports; everything
else is deterministic — faults fire on exact hit counts, TTL clocks are
injected, and no test sleeps as synchronization.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

from gofr_tpu import faults
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.ops.kv_cache import (
    HANDLE_MAGIC,
    WIRE_MAGIC,
    KVHandlePayload,
    handle_from_wire,
    handle_to_wire,
)
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.dma import (
    DmaError,
    DmaTransferServer,
    dma_fetch,
    get_transfer_server,
    reset_transfer_server,
)
from gofr_tpu.service.replica_pool import (
    EngineReplica,
    HTTPReplica,
    ReplicaPool,
)

TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"

COUNTERS = (
    "app_tpu_tier_transfers_total",
    "app_tpu_tier_transfer_bytes_total",
    "app_tpu_tier_sources_total",
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
    every test pulls/ships COLD content (a collision would alias
    against an earlier test's import and skip the rung under test)."""
    return [2 + (i * 7 + tag * 13) % 200 for i in range(95)] + [tag % 200]


@pytest.fixture(scope="module")
def metrics():
    return _metrics_manager()


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _dma_hygiene():
    """Every test that touched the process-global transfer server
    leaves the NEXT test a fresh one (new ephemeral port, empty staging
    dict) — a leaked staged body here would mask the zero-leak
    assertions of whichever test runs after."""
    yield
    reset_transfer_server()


def _make_engine(metrics, **kw):
    kw.setdefault("kv_block", 32)
    eng = InferenceEngine(
        "llama-tiny", n_slots=4, max_len=256, window_k=4,
        pipeline_depth=1, prefill_chunk=32, auto_prefix=True,
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


def _close_pool(pool):
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


def _export_payload(engine, tag, *, new_tokens=1):
    """A REAL host-bounce payload off ``engine``'s radix: generate to
    cache the prompt's blocks, then export the cached prefix — the
    exact production staging path, not a hand-built fixture."""
    ids = _prompt(tag)
    engine.generate_sync(ids, max_new_tokens=new_tokens, temperature=0.0,
                         timeout=120.0)
    payload = engine.export_cached(ids, timeout_s=10.0)
    assert payload is not None
    return ids, payload


# ----------------------------------------------------------------------
# KVH1 claim-ticket codec units
# ----------------------------------------------------------------------


def test_handle_codec_roundtrip():
    handle = KVHandlePayload(
        address="127.0.0.1:4321", key="a" * 32, block=32,
        token_ids=tuple(range(64)), src="pf", checksum=0xDEADBEEF,
        geometry=(4, 2, 32, 8), nbytes_hint=4096,
    )
    wire = handle_to_wire(handle)
    assert wire[:4] == HANDLE_MAGIC
    back = handle_from_wire(wire)
    assert back == handle
    assert back.n_blocks == 2
    assert back.nbytes() == 4096
    assert back.verify()


def test_handle_codec_rejects_malformed():
    handle = KVHandlePayload(
        address="127.0.0.1:1", key="k", block=32,
        token_ids=tuple(range(32)),
    )
    wire = handle_to_wire(handle)
    for bad in (b"", b"KVH", b"XXXX" + wire[4:], wire[:7], wire[:-3],
                HANDLE_MAGIC + b"\x00\x00\x00\x05notjs"):
        with pytest.raises(ValueError):
            handle_from_wire(bad)
    # First-4-byte dispatch: a handle is never confusable with an
    # inline body (the import endpoint branches on exactly this).
    assert wire[:4] != WIRE_MAGIC


# ----------------------------------------------------------------------
# loopback transfer-server units: staging, single-use, TTL, budgets
# ----------------------------------------------------------------------


def test_offer_fetch_roundtrip_and_single_use(metrics, engines):
    pf, _, _ = engines
    _, payload = _export_payload(pf, 30)
    server = DmaTransferServer(ttl_s=30.0).start()
    try:
        handle = server.offer(payload, src="pf")
        assert handle.address == server.address
        assert handle.checksum == payload.checksum
        assert server.staged_count() == 1
        fetched = dma_fetch(handle)
        assert fetched.token_ids == payload.token_ids
        assert fetched.checksum == payload.checksum
        assert fetched.verify()
        assert server.staged_count() == 0  # zero leaked staged bodies
        # Single-use: a replayed claim is STALE, never a re-ship of
        # blocks whose radix entries may since have been evicted.
        with pytest.raises(DmaError) as err:
            dma_fetch(handle)
        assert err.value.kind == "stale"
    finally:
        server.stop()


def test_ttl_expiry_reads_as_stale(metrics, engines):
    pf, _, _ = engines
    _, payload = _export_payload(pf, 31)
    now = [100.0]
    server = DmaTransferServer(ttl_s=5.0, clock=lambda: now[0]).start()
    try:
        handle = server.offer(payload)
        now[0] += 6.0  # past the TTL: the staged body is gone
        with pytest.raises(DmaError) as err:
            dma_fetch(handle)
        assert err.value.kind == "stale"
        server.offer(payload)  # the sweep on offer reaps the corpse
        assert server.staged_count() == 1
    finally:
        server.stop()


def test_fetch_failure_kinds(metrics, engines):
    """Every transport failure is typed so the ladder can tell "the
    source is GONE" (connect → next source) from "this rung broke"
    (read/stale/proto → one rung down)."""
    pf, _, _ = engines
    _, payload = _export_payload(pf, 32)
    server = DmaTransferServer(ttl_s=30.0).start()
    handle = server.offer(payload)
    server.stop()
    # connect: nothing listening on the advertised port.
    with pytest.raises(DmaError) as err:
        dma_fetch(handle, connect_timeout_s=0.5)
    assert err.value.kind == "connect"
    # proto: an address that is not host:port at all.
    bogus = dataclasses.replace(handle, address="not-an-address")
    with pytest.raises(DmaError) as err:
        dma_fetch(bogus)
    assert err.value.kind == "proto"


def test_checksum_and_geometry_drift_read_as_stale(metrics, engines):
    """The fetched bytes must be the bytes the handle promised — a
    transfer server restarted into a new staging namespace (or drifted
    pod geometry) is caught BEFORE the importer touches its pool."""
    pf, _, _ = engines
    _, payload = _export_payload(pf, 33)
    server = DmaTransferServer(ttl_s=30.0).start()
    try:
        for drift in (
            {"checksum": payload.checksum ^ 1},
            {"geometry": tuple([*payload.geometry[:-1],
                                payload.geometry[-1] + 1])},
            {"token_ids": tuple([*payload.token_ids[:-1], 0])},
        ):
            handle = dataclasses.replace(server.offer(payload), **drift)
            with pytest.raises(DmaError) as err:
                dma_fetch(handle)
            assert err.value.kind == "stale"
    finally:
        server.stop()


def test_slow_loris_trips_the_read_budget(metrics, engines):
    """A stalled exporter (the ``transfer.dma.serve`` seam parked mid-
    transfer) cannot pin the importer: EVERY socket read carries the
    budget, so the fetch dies ``read`` inside it."""
    pf, _, _ = engines
    _, payload = _export_payload(pf, 34)
    server = DmaTransferServer(ttl_s=30.0).start()
    gate = threading.Event()
    try:
        handle = server.offer(payload)
        t0 = time.monotonic()
        with faults.armed("transfer.dma.serve",
                          action=lambda **_kw: gate.wait(30.0)):
            with pytest.raises(DmaError) as err:
                dma_fetch(handle, read_timeout_s=0.3)
        assert err.value.kind == "read"
        assert time.monotonic() - t0 < 5.0  # the budget cut it, not TTL
    finally:
        gate.set()
        server.stop()


# ----------------------------------------------------------------------
# the dma rung in the push ladder (in-proc, pinned)
# ----------------------------------------------------------------------


def test_pinned_dma_leg_byte_identical_greedy_and_seeded(metrics, engines):
    """``TPU_TRANSFER_LEG=dma`` pins the new top rung even in-process:
    the finished prefill stages on the loopback server and the decode
    replica redeems the ticket over a real TCP fetch — byte-identical
    to the fused reference for greedy AND seeded-sampled streams,
    result=ok leg=dma, zero staged bodies left behind."""
    pf, dc, ref = engines
    pool = _pool(
        [EngineReplica("pf", pf, role="prefill"),
         EngineReplica("dc", dc, role="decode")],
        metrics, transfer_leg="dma",
    )
    try:
        ok0 = counter_total(metrics, "app_tpu_tier_transfers_total",
                            result="ok", leg="dma")
        bytes0 = counter_total(metrics, "app_tpu_tier_transfer_bytes_total",
                               leg="dma")
        for tag, params in ((35, {"temperature": 0.0}),
                            (36, {"temperature": 0.8, "seed": 7})):
            prompt = _prompt(tag)
            want = ref.generate_sync(prompt, max_new_tokens=8,
                                     timeout=120.0, **params)
            req = pool.submit_generate(prompt, max_new_tokens=8, **params)
            toks = _drain(req)
            assert toks == want.token_ids
            assert req.future.result(timeout=5).token_ids == want.token_ids
            assert _legs(req) == [("ok", "dma")]
        assert counter_total(metrics, "app_tpu_tier_transfers_total",
                             result="ok", leg="dma") == ok0 + 2
        assert counter_total(metrics, "app_tpu_tier_transfer_bytes_total",
                             leg="dma") > bytes0
        assert get_transfer_server().staged_count() == 0
    finally:
        _close_pool(pool)


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


def test_transfer_leg_validation_accepts_dma():
    with pytest.raises(ValueError):
        ReplicaPool(
            [EngineReplica("x", _StubEngine())], transfer_leg="rdma"
        )
    pool = ReplicaPool(
        [EngineReplica("x", _StubEngine())], transfer_leg="dma",
        probe_interval_s=0,
    )
    try:
        assert pool.transfer_leg == "dma"
    finally:
        pool.stop_prober()


# ----------------------------------------------------------------------
# the dma rung against a REAL remote app (live sockets) + its ladder
# ----------------------------------------------------------------------


class _Harness:
    """Boot a gofr_tpu App on ephemeral ports (httptest.Server role)."""

    def __init__(self, app):
        import asyncio

        self.app = app
        self._asyncio = asyncio
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, daemon=True
        )

    def __enter__(self):
        self._thread.start()
        self._asyncio.run_coroutine_threadsafe(
            self.app.start(), self._loop
        ).result(120)
        return self

    def __exit__(self, *exc):
        self._asyncio.run_coroutine_threadsafe(
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
    """A REAL remote pod in-process: OpenAI SSE on the HTTP port, the
    tier-import AND tier-export endpoints on the ops port. It plays
    decode target for the push tests and prefill SOURCE for the pull
    tests — one pod, both directions of the same ops-port seam."""
    from gofr_tpu import App
    from gofr_tpu.config import MockConfig
    from gofr_tpu.serving.openai_compat import add_openai_routes

    app = App(config=MockConfig({
        "APP_NAME": "mh-remote", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "4",
        "TPU_MAX_LEN": "256", "TPU_KV_BLOCK": "32",
        "TPU_AUTO_PREFIX": "true", "TPU_PREFILL_CHUNK": "32",
    }))
    add_openai_routes(app)
    with _Harness(app) as harness:
        yield app, harness


def _remote_replica(name, harness, tokenizer, metrics, *, role,
                    ops_address=None):
    from gofr_tpu.service import new_http_service

    return HTTPReplica(
        name,
        new_http_service(harness.address),
        tokenizer=tokenizer,
        role=role,
        import_service=new_http_service(ops_address or harness.ops_address),
        metrics=metrics,
    )


@pytest.fixture()
def dma_push_pool(metrics, engines, remote_app):
    """1 in-proc prefill + 1 REMOTE decode replica whose probe saw the
    ``tier_source.dma`` advertisement — the automatic ladder's top rung
    for this target is dma."""
    pf, _, _ = engines
    _, harness = remote_app
    remote = _remote_replica("dc-remote", harness, pf.tokenizer, metrics,
                             role="decode")
    pool = _pool(
        [EngineReplica("pf", pf, role="prefill"), remote], metrics,
    )
    pool.probe_once()
    assert remote.supports_dma_import  # probe-fed capability
    yield pool
    _close_pool(pool)
    remote.close()


def test_remote_dma_leg_byte_identical_one_trace(metrics, engines,
                                                 remote_app, dma_push_pool):
    """THE remote dma path: a KVH1 ticket POSTed to the remote ops
    port, the remote redeeming it back over a live TCP fetch, the
    request streamed over OpenAI SSE — byte-identical to the fused
    reference, result=ok leg=dma, the remote's flight recorder showing
    the request under the CALLER's trace id."""
    _, _, ref = engines
    app, _ = remote_app
    prompt = _prompt(40)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    ok0 = counter_total(metrics, "app_tpu_tier_transfers_total",
                        result="ok", leg="dma")
    req = dma_push_pool.submit_generate(
        prompt, max_new_tokens=8, temperature=0.0, traceparent=TRACEPARENT,
    )
    toks = _drain(req)
    assert toks == req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("ok", "dma")]
    assert counter_total(metrics, "app_tpu_tier_transfers_total",
                         result="ok", leg="dma") == ok0 + 1
    assert get_transfer_server().staged_count() == 0
    flights = app.container.tpu.flight_records()
    assert any(
        e["trace_id"] == "ab" * 16
        for e in flights.get("records", []) + flights.get("pinned", [])
    )


def test_remote_dma_offer_failure_falls_one_rung_to_wire(
        metrics, engines, dma_push_pool):
    """An armed staging failure bans the dma rung and the SAME target
    retries one rung down (dma → wire) — byte-identical, zero 5xx."""
    _, _, ref = engines
    prompt = _prompt(41)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    with faults.armed("transfer.dma.offer",
                      raises=RuntimeError("staging plane down"), times=1):
        req = dma_push_pool.submit_generate(prompt, max_new_tokens=8,
                                            temperature=0.0)
        toks = _drain(req)
    assert toks == want.token_ids
    assert req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("ok", "wire")]


def test_remote_dma_fetch_failure_falls_one_rung_to_wire(
        metrics, engines, dma_push_pool):
    """The remote failing to redeem the ticket (fetch dies mid-DMA) is
    a LEG failure, not an adoption: the pool re-ships the SAME blocks
    over the inline wire body — never a silent fused re-prefill."""
    _, _, ref = engines
    prompt = _prompt(42)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)
    with faults.armed("transfer.dma.fetch",
                      raises=DmaError("reset mid-DMA", kind="read"),
                      times=1):
        req = dma_push_pool.submit_generate(prompt, max_new_tokens=8,
                                            temperature=0.0)
        toks = _drain(req)
    assert toks == want.token_ids
    assert req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("ok", "wire")]


# ----------------------------------------------------------------------
# streaming prefill sources: the pull plane (live sockets)
# ----------------------------------------------------------------------


@pytest.fixture()
def source_pool(metrics, engines, remote_app):
    """1 LOCAL decode replica + the remote pod as a prefill SOURCE:
    before admitting a fresh request locally, the pool pulls the
    remote's cached blocks through /ops/tier-export."""
    _, dc, _ = engines
    app, harness = remote_app
    source = _remote_replica("pf-source", harness, dc.tokenizer, metrics,
                             role="prefill")
    pool = _pool(
        [EngineReplica("dc", dc, role="decode"), source], metrics,
        source_timeout_s=5.0,
    )
    pool.probe_once()
    assert source.supports_tier_source  # probe-fed advertisement
    assert pool.tier_sources() == [source]
    yield app, pool
    _close_pool(pool)
    source.close()


def test_source_warm_hit_fewer_chunks_one_trace(metrics, engines,
                                                source_pool):
    """THE pull acceptance path: the remote already prefilled the
    prompt; the local decode replica pulls its blocks (dma ticket +
    TCP fetch), admission-aliases them, and dispatches STRICTLY fewer
    prefill chunk steps than a cold run — byte-identical, source_hit
    on the dma rung, ONE trace id across the pull and the stream."""
    _, dc, ref = engines
    app, pool = source_pool
    # Cold yardstick: a prompt NOBODY cached costs the full chunk walk
    # (and records an authoritative source_miss — re-asking via wire
    # cannot hit, so the descent stops at one note).
    cold_prompt = _prompt(50)
    s0 = dc._prefill_chunk_steps
    req = pool.submit_generate(cold_prompt, max_new_tokens=4,
                               temperature=0.0)
    cold_toks = _drain(req)
    cold_steps = dc._prefill_chunk_steps - s0
    assert cold_steps >= 3
    assert _legs(req) == [("source_miss", "dma")]
    assert cold_toks == ref.generate_sync(
        cold_prompt, max_new_tokens=4, temperature=0.0, timeout=120.0
    ).token_ids
    # Warm the SOURCE (not the local engine), then pull.
    warm_prompt = _prompt(51)
    app.container.tpu.generate_sync(warm_prompt, max_new_tokens=1,
                                    temperature=0.0, timeout=120.0)
    want = ref.generate_sync(warm_prompt, max_new_tokens=8,
                             temperature=0.0, timeout=120.0)
    hit0 = counter_total(metrics, "app_tpu_tier_sources_total", kind="hit")
    s1 = dc._prefill_chunk_steps
    req = pool.submit_generate(warm_prompt, max_new_tokens=8,
                               temperature=0.0, traceparent=TRACEPARENT)
    toks = _drain(req)
    warm_steps = dc._prefill_chunk_steps - s1
    assert toks == req.future.result(timeout=5).token_ids == want.token_ids
    assert warm_steps < cold_steps
    assert _legs(req) == [("source_hit", "dma")]
    assert req.timeline.trace_id == "ab" * 16
    assert counter_total(metrics, "app_tpu_tier_sources_total",
                         kind="hit") == hit0 + 1
    assert counter_total(metrics, "app_tpu_tier_transfer_bytes_total",
                         leg="dma") > 0
    assert get_transfer_server().staged_count() == 0


def test_source_seeded_sampled_byte_identical(metrics, engines,
                                              source_pool):
    _, _, ref = engines
    app, pool = source_pool
    prompt = _prompt(52)
    app.container.tpu.generate_sync(prompt, max_new_tokens=1,
                                    temperature=0.0, timeout=120.0)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.8,
                             seed=7, timeout=120.0)
    req = pool.submit_generate(prompt, max_new_tokens=8, temperature=0.8,
                               seed=7)
    toks = _drain(req)
    assert toks == want.token_ids
    assert _legs(req) == [("source_hit", "dma")]


def test_source_stale_handle_descends_to_wire(metrics, engines,
                                              source_pool):
    """A genuinely stale ticket (redeemed out from under the importer —
    the transfer server replies length 0) falls ONE rung: the same
    source re-asked for the inline wire body, which hits."""
    _, _, ref = engines
    app, pool = source_pool
    prompt = _prompt(53)
    app.container.tpu.generate_sync(prompt, max_new_tokens=1,
                                    temperature=0.0, timeout=120.0)
    want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                             timeout=120.0)

    def _poach(key="", **_kw):
        get_transfer_server().redeem(key)  # the claim is now stale

    with faults.armed("transfer.dma.fetch", action=_poach, times=1):
        req = pool.submit_generate(prompt, max_new_tokens=8,
                                   temperature=0.0)
        toks = _drain(req)
    assert toks == req.future.result(timeout=5).token_ids == want.token_ids
    assert _legs(req) == [("source_error", "dma"), ("source_hit", "wire")]


def test_source_connect_refused_skips_the_source(metrics, engines,
                                                 remote_app, free_port):
    """A dead export port is ``connect``-kind: the source is GONE, so
    the pull breaks to the next source (none here) — local prefill,
    byte-identical, zero 5xx, one error note."""
    _, dc, ref = engines
    app, harness = remote_app
    source = _remote_replica(
        "pf-dead-ops", harness, dc.tokenizer, metrics, role="prefill",
        ops_address=f"http://127.0.0.1:{free_port()}",
    )
    pool = _pool(
        [EngineReplica("dc", dc, role="decode"), source], metrics,
        source_timeout_s=5.0,
    )
    try:
        pool.probe_once()  # health (live) advertises; the ops port lies dead
        assert pool.tier_sources() == [source]
        prompt = _prompt(54)
        want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                                 timeout=120.0)
        err0 = counter_total(metrics, "app_tpu_tier_sources_total",
                             kind="error")
        req = pool.submit_generate(prompt, max_new_tokens=8,
                                   temperature=0.0)
        toks = _drain(req)
        assert toks == want.token_ids
        assert req.future.result(timeout=5).token_ids == want.token_ids
        assert _legs(req) == [("source_error", "dma")]
        assert counter_total(metrics, "app_tpu_tier_sources_total",
                             kind="error") == err0 + 1
    finally:
        _close_pool(pool)
        source.close()


def test_source_slow_loris_expires_inside_the_budget(metrics, engines,
                                                     source_pool):
    """Partition/stall mid-pull (the serve thread parked) trips the
    read budget, and the EXPIRED pull budget then stops the descent —
    the terminal rung is local prefill, inside TPU_SOURCE_TIMEOUT_S,
    with the stream byte-identical and zero 5xx."""
    _, dc, ref = engines
    app, pool = source_pool
    # A tighter budget than the fixture's: the stall must cut inside it.
    pool.source_timeout_s = 1.2
    gate = threading.Event()
    try:
        prompt = _prompt(55)
        app.container.tpu.generate_sync(prompt, max_new_tokens=1,
                                        temperature=0.0, timeout=120.0)
        want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                                 timeout=120.0)
        exp0 = counter_total(metrics, "app_tpu_tier_sources_total",
                             kind="expired")
        t0 = time.monotonic()
        with faults.armed("transfer.dma.serve",
                          action=lambda **_kw: gate.wait(30.0)):
            req = pool.submit_generate(prompt, max_new_tokens=8,
                                       temperature=0.0)
            toks = _drain(req)
        assert time.monotonic() - t0 < 10.0
        assert toks == want.token_ids
        assert req.future.result(timeout=5).token_ids == want.token_ids
        assert _legs(req) == [("source_error", "dma")]
        assert counter_total(metrics, "app_tpu_tier_sources_total",
                             kind="expired") == exp0 + 1
    finally:
        gate.set()
        pool.source_timeout_s = 5.0


def test_source_geometry_drift_rejected_locally(metrics, engines,
                                                remote_app):
    """A source whose pod geometry drifted (kv_block 32 vs a local 16)
    survives the fetch — the bytes match the ticket — but the IMPORT
    rejects before touching the pool: source_rejected, no wire retry
    (it would reject identically), local prefill byte-identical."""
    _, _, ref = engines
    app, harness = remote_app
    dc16 = _make_engine(metrics, kv_block=16)
    source = _remote_replica("pf-drift", harness, dc16.tokenizer, metrics,
                             role="prefill")
    pool = _pool(
        [EngineReplica("dc16", dc16, role="decode"), source], metrics,
        source_timeout_s=5.0,
    )
    try:
        pool.probe_once()
        prompt = _prompt(56)
        app.container.tpu.generate_sync(prompt, max_new_tokens=1,
                                        temperature=0.0, timeout=120.0)
        want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                                 timeout=120.0)
        rej0 = counter_total(metrics, "app_tpu_tier_sources_total",
                             kind="rejected")
        req = pool.submit_generate(prompt, max_new_tokens=8,
                                   temperature=0.0)
        toks = _drain(req)
        assert toks == want.token_ids
        assert req.future.result(timeout=5).token_ids == want.token_ids
        assert _legs(req) == [("source_rejected", "dma")]
        assert counter_total(metrics, "app_tpu_tier_sources_total",
                             kind="rejected") == rej0 + 1
    finally:
        _close_pool(pool)
        source.close()
        dc16.close()


def test_source_pull_never_fires_when_locally_warm(metrics, engines,
                                                   source_pool):
    """The ``radix.peek`` gate: content already warm locally skips the
    pull entirely — no socket, no note, no counter."""
    _, dc, ref = engines
    app, pool = source_pool
    prompt = _prompt(57)
    app.container.tpu.generate_sync(prompt, max_new_tokens=1,
                                    temperature=0.0, timeout=120.0)
    dc.generate_sync(prompt, max_new_tokens=1, temperature=0.0,
                     timeout=120.0)  # locally warm
    total0 = counter_total(metrics, "app_tpu_tier_sources_total")
    req = pool.submit_generate(prompt, max_new_tokens=4, temperature=0.0)
    toks = _drain(req)
    assert toks == ref.generate_sync(
        prompt, max_new_tokens=4, temperature=0.0, timeout=120.0
    ).token_ids
    assert _legs(req) == []
    assert counter_total(metrics, "app_tpu_tier_sources_total") == total0


# ----------------------------------------------------------------------
# subprocess pods: kill -9 mid-DMA, warm hit across real processes
# ----------------------------------------------------------------------


class _ChildPod:
    """A REAL separate-process pod (tests/multihost_child.py)."""

    def __init__(self, *, stall=False):
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # The child runs by script path, so ITS sys.path gets tests/,
        # not the repo root — gofr_tpu must come in via PYTHONPATH.
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        if stall:
            env["MULTIHOST_CHILD_STALL"] = "1"
        self.proc = subprocess.Popen(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "multihost_child.py")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            cwd=repo_root, env=env, text=True,
        )
        self.lines: list[str] = []
        self.ready = threading.Event()
        self.stalled = threading.Event()
        self.http_port = 0
        self.ops_port = 0
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        assert self.proc.stdout is not None
        for line in self.proc.stdout:
            line = line.strip()
            self.lines.append(line)
            if line.startswith("READY "):
                parts = dict(p.split("=") for p in line.split()[1:])
                self.http_port = int(parts["http"])
                self.ops_port = int(parts["ops"])
                self.ready.set()
            elif line == "DMA-SERVE-STALLED":
                self.stalled.set()

    def wait_ready(self, timeout=240.0):
        assert self.ready.wait(timeout), (
            f"child pod never came up:\n" + "\n".join(self.lines[-30:])
        )

    def warm(self, token_ids, *, timeout=120.0):
        """Prefill+cache ``token_ids`` on the child via its OpenAI
        endpoint (prompt-as-token-ids is in the API)."""
        conn = http.client.HTTPConnection("127.0.0.1", self.http_port,
                                          timeout=timeout)
        try:
            conn.request(
                "POST", "/v1/completions",
                body=json.dumps({
                    "model": "llama-tiny", "prompt": list(token_ids),
                    "max_tokens": 1, "temperature": 0,
                }),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 200, body[:300]
        finally:
            conn.close()

    def metric(self, name):
        conn = http.client.HTTPConnection("127.0.0.1", self.ops_port,
                                          timeout=10.0)
        try:
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
        finally:
            conn.close()
        total = 0.0
        seen = False
        for line in text.splitlines():
            if line.startswith(name) and not line.startswith("#"):
                total += float(line.rsplit(None, 1)[-1])
                seen = True
        return total if seen else None

    def kill9(self):
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)

    def close(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


def _stable_metric(child, name, *, timeout=30.0):
    """A gauge read only after it stops moving (two consecutive equal
    samples): slot retirement on the child lags the HTTP reply by a
    scheduler tick, and a mid-retirement sample would fake a leak."""
    deadline = time.monotonic() + timeout
    prev = child.metric(name)
    while time.monotonic() < deadline:
        time.sleep(0.2)
        cur = child.metric(name)
        if cur == prev and cur is not None:
            return cur
        prev = cur
    return prev


def _child_source_pool(child, dc, metrics, *, source_timeout_s):
    from gofr_tpu.service import new_http_service

    source = HTTPReplica(
        "pf-pod",
        new_http_service(f"http://127.0.0.1:{child.http_port}"),
        tokenizer=dc.tokenizer,
        role="prefill",
        import_service=new_http_service(
            f"http://127.0.0.1:{child.ops_port}"
        ),
        metrics=metrics,
    )
    pool = _pool(
        [EngineReplica("dc", dc, role="decode"), source], metrics,
        source_timeout_s=source_timeout_s,
    )
    pool.probe_once()
    return pool, source


@pytest.mark.slow
def test_subprocess_source_warm_hit_zero_leak_both_sides(metrics, engines):
    """Cross-PROCESS pull: a real child pod (own interpreter, own JAX
    runtime, own transfer server) prefills a prompt; this process pulls
    its blocks over live sockets and admission-aliases them — fewer
    chunk dispatches, byte-identical, one trace id, and ZERO leaked
    blocks on EITHER side (the child's free-block gauge returns to its
    pre-export value; our staging dict is empty)."""
    _, dc, ref = engines
    child = _ChildPod()
    pool = source = None
    try:
        child.wait_ready()
        prompt = _prompt(60)
        child.warm(prompt)
        free_before = _stable_metric(child, "app_tpu_kv_blocks_free")
        pool, source = _child_source_pool(child, dc, metrics,
                                          source_timeout_s=10.0)
        assert pool.tier_sources() == [source]
        want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                                 timeout=120.0)
        s0 = dc._prefill_chunk_steps
        req = pool.submit_generate(prompt, max_new_tokens=8,
                                   temperature=0.0,
                                   traceparent=TRACEPARENT)
        toks = _drain(req)
        assert toks == req.future.result(timeout=5).token_ids
        assert toks == want.token_ids
        assert dc._prefill_chunk_steps - s0 < 3  # aliased, not re-prefilled
        assert _legs(req) == [("source_hit", "dma")]
        assert req.timeline.trace_id == "ab" * 16
        # Zero leak, both sides: the child exported COPIES (its pool is
        # untouched), and its transfer server redeemed the single-use
        # staging entry, so nothing is pinned on either host.
        free_after = _stable_metric(child, "app_tpu_kv_blocks_free")
        assert free_after == free_before
        assert get_transfer_server().staged_count() == 0
    finally:
        if pool is not None:
            _close_pool(pool)
        if source is not None:
            source.close()
        child.close()


@pytest.mark.slow
def test_subprocess_kill9_mid_dma_degrades_one_rung_at_a_time(metrics,
                                                              engines):
    """THE acceptance path: the child pod is ``kill -9``'d while its
    serve thread is parked MID-DMA (our fetch blocked inside its read
    budget). The pull degrades exactly one rung at a time — dma dies
    ``read``, the wire re-ask dies ``connect`` (the pod is gone), the
    terminal rung is local prefill — and the request completes
    byte-identically (greedy AND seeded-sampled on the follow-up
    request against the corpse), zero 5xx, one trace id, zero leaked
    staged bodies or slots on the surviving side."""
    _, dc, ref = engines
    child = _ChildPod(stall=True)
    pool = source = None
    try:
        child.wait_ready()
        prompt = _prompt(61)
        child.warm(prompt)
        pool, source = _child_source_pool(child, dc, metrics,
                                          source_timeout_s=30.0)
        assert pool.tier_sources() == [source]
        want = ref.generate_sync(prompt, max_new_tokens=8, temperature=0.0,
                                 timeout=120.0)
        box: dict = {}

        def _submit():
            box["req"] = pool.submit_generate(
                prompt, max_new_tokens=8, temperature=0.0,
                traceparent=TRACEPARENT,
            )
            box["toks"] = _drain(box["req"])

        worker = threading.Thread(target=_submit, daemon=True)
        worker.start()
        # The child prints the marker the instant our fetch lands on
        # its parked serve thread: the transfer is now mid-flight.
        assert child.stalled.wait(60.0), "\n".join(child.lines[-30:])
        child.kill9()
        worker.join(timeout=120.0)
        assert not worker.is_alive()
        req, toks = box["req"], box["toks"]
        assert toks == req.future.result(timeout=5).token_ids  # zero 5xx
        assert toks == want.token_ids
        assert _legs(req) == [
            ("source_error", "dma"),   # the fetch died mid-read
            ("source_error", "wire"),  # the re-ask found nobody listening
        ]
        assert req.timeline.trace_id == "ab" * 16
        # Seeded follow-up against the corpse: the connect-refused pull
        # degrades straight to local prefill, still byte-identical.
        prompt2 = _prompt(62)
        want2 = ref.generate_sync(prompt2, max_new_tokens=8,
                                  temperature=0.8, seed=7, timeout=120.0)
        req2 = pool.submit_generate(prompt2, max_new_tokens=8,
                                    temperature=0.8, seed=7)
        toks2 = _drain(req2)
        assert toks2 == want2.token_ids
        assert _legs(req2) == [("source_error", "dma")]
        # Surviving side leaks nothing: no staged bodies, no pinned
        # slots once the streams retired.
        assert get_transfer_server().staged_count() == 0
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if all(s is None for s in dc._slots):
                break
            time.sleep(0.05)
        assert all(s is None for s in dc._slots)
    finally:
        if pool is not None:
            _close_pool(pool)
        if source is not None:
            source.close()
        child.close()
