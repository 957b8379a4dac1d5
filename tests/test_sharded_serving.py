"""GSPMD-sharded serving data-plane suite (ISSUE 9 acceptance gate).

The tp-invariance contract, pinned: on the 8 virtual CPU devices the
conftest forces, a ``tp=2`` engine (params Megatron-sharded, the paged
KV pool's head axis sharded over the mesh) produces BYTE-IDENTICAL
greedy (and seeded-sampled) streams to an unsharded ``tp=1`` engine —
including prefix-cache hits, disaggregated-tier KV-block transfers
between two differently-placed sharded pods, and a mid-stream replica
failover. This is the trimmed tp-serving subset of the multichip dryrun
(``__graft_entry__.dryrun_virtual_mesh`` step 5), wired as a named CI step
so sharded-serving token-identity regresses loudly.

Also covered: the pod layout (dp across replicas, tp within — the
backend carves DISJOINT device slices per in-proc replica), mesh
topology advertising (health probes, replica descriptors,
``/debug/flight``, the ``app_tpu_mesh_devices`` gauge), and the
``tpu.shard_init`` boot span.

Determinism: engines share the default seed; faults fire on exact hit
counts through ``gofr_tpu/faults``; supervisor backoff sleeps are
recorded, not slept.
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
from gofr_tpu.serving.supervisor import EngineSupervisor
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.service.replica_pool import EngineReplica, ReplicaPool
from gofr_tpu.tracing import Tracer, get_tracer, set_tracer

#: 96 tokens = exactly 3 full 32-token KV blocks, so prefix hits,
#: tier transfers, and the COW boundary all engage.
PROMPT = list(range(2, 200, 3)) + [7] * 30
assert len(PROMPT) == 96

#: Every engine in this suite uses the same serving geometry, so the
#: jitted programs compile once per (mesh placement) and are shared.
ENG_KW = dict(
    n_slots=4, max_len=256, window_k=4, pipeline_depth=1,
    prefill_chunk=32, kv_block=32, auto_prefix=True,
)


def _device_slices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 4, "suite needs the conftest's 8 virtual devices"
    return devs[:2], devs[2:4]


@pytest.fixture(scope="module")
def metrics():
    # The container's registered instrument set — what production
    # records into (includes app_tpu_mesh_devices).
    return Container.create(MockConfig({"APP_NAME": "shard-test"})).metrics


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    faults.reset()


def _make_engine(metrics, devices=None, tp=0, **kw):
    eng = InferenceEngine(
        "llama-tiny", tokenizer=ByteTokenizer(), metrics=metrics,
        tp=tp, devices=devices, **{**ENG_KW, **kw},
    )
    eng.start_sync()
    return eng


@pytest.fixture(scope="module")
def engines(metrics):
    """The shared pair: an unsharded tp=1 reference and a tp=2 engine
    on the first device slice. Module-scoped — construction and
    first-dispatch GSPMD compiles dominate this suite's wall clock."""
    slice0, _ = _device_slices()
    ref = _make_engine(metrics)
    tp2 = _make_engine(metrics, devices=slice0, tp=2)
    yield ref, tp2
    faults.reset()
    for eng in (ref, tp2):
        eng.close()


def _drain_stream(req, timeout=120.0):
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok = req.stream.get(timeout=max(deadline - time.monotonic(), 0.1))
        if tok is None:
            return toks
        toks.append(tok)


def _counter_total(metrics, name, **labels):
    inst = {i.name: i for i in metrics.instruments()}[name]
    total = 0.0
    for key, value in inst.collect().items():
        if all((k, str(v)) in key for k, v in labels.items()):
            total += value
    return total


def _gauge(metrics, name, **labels):
    inst = {i.name: i for i in metrics.instruments()}[name]
    for key, value in inst.collect().items():
        if all((k, str(v)) in key for k, v in labels.items()):
            return value
    return None


# ----------------------------------------------------------------------
# the sharded engine IS sharded (not silently replicated)
# ----------------------------------------------------------------------


def test_tp2_engine_shards_params_and_paged_pool(engines):
    _, tp2 = engines
    assert tp2.tp == 2
    topo = tp2.mesh_topology()
    assert topo["axes"] == {"tp": 2}
    assert topo["n_devices"] == 2
    # The paged KV pool's planes actually SPAN both chips (the head
    # axis shards over tp) — a silently-replicated cache would defeat
    # the HBM-scaling point of the tentpole.
    assert len(tp2.cache.k.sharding.device_set) == 2
    assert len(tp2.cache.v.sharding.device_set) == 2
    # Megatron-sharded params: a column-parallel projection spans both
    # chips too.
    wq = tp2.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 2
    # Host logic stays device-count-agnostic: the block table is
    # per-LOGICAL-block, identical in shape to the unsharded engine's.
    ref, _ = engines
    assert tp2.cache.block_table.shape == ref.cache.block_table.shape
    assert tp2.cache.n_blocks == ref.cache.n_blocks


# ----------------------------------------------------------------------
# tp-invariance: byte-identical streams, cold and prefix-cache-warm
# ----------------------------------------------------------------------


def test_tp2_greedy_streams_byte_identical_including_prefix_hits(engines):
    ref, tp2 = engines
    params = dict(max_new_tokens=16, temperature=0.0, stop_on_eos=False)

    # COLD: first sight of this prompt on both engines.
    want = ref.generate_sync(PROMPT, timeout=240, **params)
    req = tp2.submit_generate(PROMPT, **params)
    toks = _drain_stream(req)
    got = req.future.result(timeout=5)
    assert toks == got.token_ids == want.token_ids
    assert got.finish_reason == want.finish_reason

    # WARM: the retired prompt's full blocks are radix-indexed; the
    # repeat admission-aliases them zero-copy — on the SHARDED pool
    # exactly as on the unsharded one — with strictly fewer prefill
    # chunk dispatches and a byte-identical stream.
    hits0, chunks0 = tp2._prefix_hit_tokens, tp2._prefill_chunk_steps
    ref_hits0 = ref._prefix_hit_tokens
    want_warm = ref.generate_sync(PROMPT, timeout=240, **params)
    got_warm = tp2.generate_sync(PROMPT, timeout=240, **params)
    assert got_warm.token_ids == want_warm.token_ids == want.token_ids
    assert tp2._prefix_hit_tokens > hits0
    assert tp2._prefix_hit_tokens - hits0 == ref._prefix_hit_tokens - ref_hits0
    assert tp2._prefill_chunk_steps - chunks0 < chunks0


def test_tp2_seeded_sampled_stream_byte_identical(engines):
    ref, tp2 = engines
    params = dict(
        max_new_tokens=24, temperature=0.9, seed=4242, stop_on_eos=False,
    )
    want = ref.generate_sync("sharded sampling", timeout=240, **params)
    got = tp2.generate_sync("sharded sampling", timeout=240, **params)
    assert got.token_ids == want.token_ids
    assert len(want.token_ids) == 24


# ----------------------------------------------------------------------
# disaggregated tiers over sharded pods: the export/import seam at tp=2
# ----------------------------------------------------------------------


def test_tier_transfer_between_sharded_pods_byte_identical(
    metrics, engines
):
    """Prefill pod on devices[0:2] ships its finished KV blocks to a
    decode pod on devices[2:4] — the payload leaves one mesh and lands
    on ANOTHER (different device placement), through the same
    per-logical-block host-bounce seam as tp=1. Stream byte-identical
    to the unsharded reference, transfer result "ok"."""
    ref, tp2 = engines
    slice0, slice1 = _device_slices()
    dc = _make_engine(metrics, devices=slice1, tp=2)
    pool = ReplicaPool(
        [
            EngineReplica("pf", tp2, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        probe_interval_s=0,
        probe_timeout_s=60.0,
        hedge_delay_s=300.0,
        transfer_retries=2,
        transfer_backoff_s=0.01,
        sleep=lambda s: None,
        rng=random.Random(7),
        metrics=metrics,
    )
    try:
        params = dict(max_new_tokens=12, temperature=0.0, stop_on_eos=False)
        want = ref.generate_sync(PROMPT, timeout=240, **params)
        ok0 = _counter_total(
            metrics, "app_tpu_tier_transfers_total", result="ok"
        )
        req = pool.submit_generate(PROMPT, **params)
        toks = _drain_stream(req)
        result = req.future.result(timeout=5)
        assert toks == result.token_ids == want.token_ids
        assert _counter_total(
            metrics, "app_tpu_tier_transfers_total", result="ok"
        ) == ok0 + 1
        # The decode pod imported the blocks into ITS sharded pool and
        # admission aliased them (zero-copy radix hit, tp>1; the whole
        # prompt is cached, so the COW boundary re-writes the final
        # position — 95 of 96 prompt tokens count as hit).
        assert dc._prefix_hit_tokens >= 3 * 32 - 1
    finally:
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)
            replica.set_tier_exporter(None)
        tp2.tier_role = "fused"
        dc.close()


# ----------------------------------------------------------------------
# mid-stream failover between sharded pods stays byte-identical
# ----------------------------------------------------------------------


def test_mid_stream_failover_between_sharded_pods_byte_identical(metrics):
    """Two tp=2 pods on disjoint device slices behind a pool; pod A's
    device dies mid-stream and exhausts its restart budget — the pool
    hands the live request to pod B, and the client's GREEDY stream is
    byte-identical to a fault-free run (the dryrun contract, now
    surviving a replica loss)."""
    slice0, slice1 = _device_slices()

    def supervised(devices):
        eng = InferenceEngine(
            "llama-tiny", tokenizer=ByteTokenizer(), metrics=metrics,
            tp=2, devices=devices, **ENG_KW,
        )
        sup = EngineSupervisor(
            eng, max_restarts=1, backoff_s=0.25, backoff_reset_s=60.0,
            rng=random.Random(1234), sleep=lambda s: None, metrics=metrics,
        ).start()
        eng.start_sync()
        return eng, sup

    eng_a, sup_a = supervised(slice0)
    eng_b, sup_b = supervised(slice1)
    pool = ReplicaPool(
        [EngineReplica("a", eng_a), EngineReplica("b", eng_b)],
        probe_interval_s=0, probe_timeout_s=60.0,
        rng=random.Random(7), metrics=metrics,
    )
    params = dict(max_new_tokens=24, temperature=0.0, stop_on_eos=False)
    try:
        failovers0 = _counter_total(metrics, "app_tpu_failovers_total")
        ref_b = eng_b.generate_sync(PROMPT, timeout=240, **params)
        ref_a = eng_a.generate_sync(PROMPT, timeout=240, **params)
        assert ref_a.token_ids == ref_b.token_ids
        assert len(ref_b.token_ids) == 24

        a_hits = {"n": 0}

        def crash_a(engine=None, **kw):
            if engine is eng_a:
                a_hits["n"] += 1
                if a_hits["n"] >= 5:
                    raise RuntimeError("injected: sharded pod A device loss")

        faults.arm("scheduler.device_step", action=crash_a)
        req = pool.submit_generate(PROMPT, **params)
        pre = [req.stream.get(timeout=120) for _ in range(3)]
        assert all(t is not None for t in pre)
        rest = _drain_stream(req)
        result = req.future.result(timeout=120)
        assert pre + rest == ref_b.token_ids
        assert result.token_ids == ref_b.token_ids
        assert _counter_total(
            metrics, "app_tpu_failovers_total"
        ) == failovers0 + 1
    finally:
        faults.reset()
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)
        sup_a.stop()
        sup_b.stop()
        eng_a.stop_sync()
        eng_b.stop_sync()


# ----------------------------------------------------------------------
# the pod layout: dp across replicas, tp within (config seam)
# ----------------------------------------------------------------------


def test_pool_carves_disjoint_tp_pods_and_serves_token_identical(engines):
    """TPU_TP=2 × TPU_REPLICAS=2 through the container seam: each
    in-proc replica is one sharded pod on its OWN device slice (the
    dryrun's dp=2 × tp=2 pod-serving topology, production-shaped), and
    pool-served greedy output is token-identical to unsharded."""
    from gofr_tpu.serving.backend import new_tpu_from_config

    ref, _ = engines
    pool = new_tpu_from_config(MockConfig({
        "TPU_MODEL": "llama-tiny",
        "TPU_TP": "2",
        "TPU_REPLICAS": "2",
        "TPU_POOL_MAX_REPLICAS": "3",
        "TPU_KV_SLOTS": "4",
        "TPU_MAX_LEN": "256",
        "TPU_DECODE_WINDOW": "4",
        "TPU_PIPELINE_DEPTH": "1",
        "TPU_PREFILL_CHUNK": "32",
        "TPU_KV_BLOCK": "32",
        "TPU_AUTO_PREFIX": "true",
    }))
    assert isinstance(pool, ReplicaPool)
    try:
        sets = [
            frozenset(r.mesh_topology()["devices"]) for r in pool.replicas
        ]
        assert len(sets) == 2
        assert sets[0].isdisjoint(sets[1])
        for replica in pool.replicas:
            replica.engine.start_sync()
        params = dict(max_new_tokens=12, temperature=0.0, stop_on_eos=False)
        want = ref.generate_sync(PROMPT, timeout=240, **params)
        got = pool.generate_sync(PROMPT, timeout=240, **params)
        assert got.token_ids == want.token_ids
        # A scaled-up pod lands on a FREE device slice, not on top of a
        # live replica's (the scaler's spawn factory scans held slices,
        # it does not count spawns).
        assert pool.scaler is not None
        scaled = pool.scaler.spawn()
        try:
            scaled_set = frozenset(scaled.mesh_topology()["devices"])
            assert scaled_set.isdisjoint(sets[0] | sets[1])
        finally:
            scaled.engine.close()
    finally:
        pool.close()


# ----------------------------------------------------------------------
# observability: topology advertised, shard-init span emitted
# ----------------------------------------------------------------------


def test_mesh_topology_advertised_everywhere(metrics, engines):
    ref, tp2 = engines
    # Health probes carry the pod shape; unsharded engines carry none.
    assert tp2.health_check()["details"]["mesh"]["axes"] == {"tp": 2}
    assert "mesh" not in ref.health_check()["details"]
    assert ref.mesh_topology() is None
    # The per-axis device gauge: 2 for the sharded engine's tp axis,
    # 1 advertised by the unsharded one.
    assert _gauge(metrics, "app_tpu_mesh_devices", axis="tp") == 2.0
    # Replica descriptors and /debug/flight records stamp the mesh.
    pool = ReplicaPool(
        [EngineReplica("sharded", tp2), EngineReplica("plain", ref)],
        probe_interval_s=0, metrics=metrics,
    )
    try:
        desc = pool.health_check()["details"]["replicas"]
        assert desc["sharded"]["mesh"]["axes"] == {"tp": 2}
        assert desc["plain"]["mesh"] is None
        records = pool.flight_records()["replicas"]
        assert records["sharded"]["mesh"]["n_devices"] == 2
        assert records["plain"]["mesh"] is None
    finally:
        pool.stop_prober()
        for replica in pool.replicas:
            replica.set_handoff(None)


def test_partition_devices_layout_and_undersized_error():
    from gofr_tpu.parallel.mesh import partition_devices

    devs = list(range(8))
    assert partition_devices(devs, 2, 3) == [[0, 1], [2, 3], [4, 5]]
    # Overflow groups past the last full slice share slice 0.
    assert partition_devices(devs, 4, 3) == [
        [0, 1, 2, 3], [4, 5, 6, 7], [0, 1, 2, 3],
    ]
    # Fewer devices than ONE group fails loudly here, not inside
    # make_mesh with misleading context.
    with pytest.raises(ValueError):
        partition_devices(devs[:1], 2, 1)


def test_remote_replica_mesh_cache_clears_when_pod_unshards():
    """A remote pod that restarts UNSHARDED must stop advertising its
    old tp topology — the probe assigns the cached mesh
    unconditionally from the health payload."""
    from gofr_tpu.service.replica_pool import HTTPReplica

    class _Resp:
        status_code = 200

        def __init__(self, details):
            self._details = details

        def json(self):
            return {"status": "UP", "details": self._details}

    class _Svc:
        def __init__(self):
            self.details = {"mesh": {"axes": {"tp": 2}, "n_devices": 2,
                                     "devices": ["a", "b"]}}

        def get(self, path):
            return _Resp(self.details)

    svc = _Svc()
    replica = HTTPReplica("remote", svc, stream=False)
    assert replica.probe(timeout_s=1.0)[0] == "pass"
    assert replica.mesh_topology()["axes"] == {"tp": 2}
    svc.details = {}  # pod restarted unsharded: no mesh key at all
    assert replica.probe(timeout_s=1.0)[0] == "pass"
    assert replica.mesh_topology() is None


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


def test_shard_init_span_covers_mesh_build_and_param_sharding():
    old = get_tracer()
    cap = _CaptureExporter()
    set_tracer(Tracer(service_name="shard-test", exporter=cap))
    try:
        slice0, _ = _device_slices()
        InferenceEngine(
            "llama-tiny", tokenizer=ByteTokenizer(),
            tp=2, devices=slice0, **ENG_KW,
        )
        spans = cap.by_name("tpu.shard_init")
        assert len(spans) == 1
        span = spans[0]
        assert span.attributes["tpu.mesh_axes"] == "tp=2"
        assert span.attributes["tpu.mesh_devices"] == 2
        assert span.end_ns > span.start_ns  # real duration, not instant
    finally:
        set_tracer(old)
