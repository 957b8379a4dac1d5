"""Tenant attribution, SLO burn rates, and fairness-aware shedding
(serving/tenant_ledger.py + serving/slo.py; docs/advanced-guide/
observability.md "Tenant attribution & SLOs").

Deterministic throughout: ledger/SLO clocks are injectable (tests state
time instead of sleeping), greedy streams are byte-compared, and the
conservation invariants are exact under stated clocks."""

from __future__ import annotations

import pytest

from gofr_tpu.errors import ErrorTooManyRequests
from gofr_tpu.metrics.manager import Manager
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.slo import SLOEngine
from gofr_tpu.serving.tenant_ledger import TenantLedger
from gofr_tpu.serving.tokenizer import ByteTokenizer


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def tenant_metrics() -> Manager:
    m = Manager()
    for name in (
        "app_tpu_tenant_tokens_total",
        "app_tpu_tenant_kv_block_seconds_total",
        "app_tpu_tenant_requests_total",
        "app_tpu_tokens_generated",
        "app_tpu_requests_shed_total",
    ):
        m.new_counter(name)
    for name in ("app_tpu_slo_burn_rate", "app_tpu_slo_compliant"):
        m.new_gauge(name)
    return m


def counter_value(m: Manager, name: str, **labels: str) -> float:
    inst = [i for i in m.instruments() if i.name == name]
    if not inst:
        return 0.0
    want = set(labels.items())
    return sum(
        v for k, v in inst[0].collect().items() if want <= set(k)
    )


def make_engine(**kw):
    defaults = dict(
        n_slots=2, max_len=128, kv_block=16,
        tokenizer=ByteTokenizer(), tenant_ledger=True, seed=0,
    )
    defaults.update(kw)
    eng = InferenceEngine("llama-tiny", **defaults)
    eng.start_sync()
    return eng


# ----------------------------------------------------------------------
# TenantLedger units
# ----------------------------------------------------------------------


def test_ledger_kv_block_second_conservation_exact():
    """Σ per-tenant block·seconds == the pool-wide integral, EXACTLY,
    under a stated clock — the invariant is by-construction (same dt,
    same call), so any drift is a bug."""
    led = TenantLedger("m", clock=FakeClock())
    led.tick(0.0, [("a", 4), ("b", 2)])      # baseline (dt undefined)
    led.tick(1.0, [("a", 4), ("b", 2)])      # 1s: a+4, b+2
    led.tick(3.0, [("a", 1), ("c", 5)])      # 2s: a+2, c+10
    led.tick(3.0, [("a", 9)])                # dt=0: nothing accrues
    snap = led.snapshot()
    t = snap["tenants"]
    assert t["a"]["kv_block_seconds"] == 6.0
    assert t["b"]["kv_block_seconds"] == 2.0
    assert t["c"]["kv_block_seconds"] == 10.0
    assert snap["pool_kv_block_seconds"] == 18.0
    assert sum(
        s["kv_block_seconds"] for s in t.values()
    ) == snap["pool_kv_block_seconds"]
    # The dt=0 tick still refreshed the live held-block snapshot.
    assert t["a"]["held_blocks"] == 9


def test_ledger_label_clamp_overflow_folds_into_other():
    """Metric labels clamp to the first label_max distinct tenants;
    later tenants fold into tenant="_other" (bounded cardinality,
    monotonic series) while the /debug/tenants table stays unclamped."""
    m = tenant_metrics()
    led = TenantLedger("m", metrics=m, label_max=2, clock=FakeClock())
    led.tick(0.0, [])
    for i, tenant in enumerate(("a", "b", "c", "d")):
        led.tick(float(i + 1), [(tenant, 2)])
    inst = [
        i for i in m.instruments()
        if i.name == "app_tpu_tenant_kv_block_seconds_total"
    ][0]
    labels = {
        dict(k)["tenant"] for k in inst.collect()
    }
    assert labels == {"a", "b", "_other"}
    # The full table names everyone; the fold list names the clamped.
    snap = led.snapshot()
    assert set(snap["tenants"]) == {"a", "b", "c", "d"}
    assert snap["folded_tenants"] == ["c", "d"]
    assert snap["tenants"]["c"]["kv_block_seconds"] == 2.0


def test_ledger_table_bound_under_tenant_churn():
    """Tenant ids are request-controlled: a client minting a fresh id
    per request must not grow ledger memory without bound. Past
    table_max, new tenants account into the OVERFLOW row wholesale —
    attribution stays total, conservation still holds."""
    led = TenantLedger("m", label_max=2, table_max=3, clock=FakeClock())
    led.tick(0.0, [])
    for i in range(10):
        led.tick(float(i + 1), [(f"churn-{i}", 2)])
    snap = led.snapshot()
    assert len(snap["tenants"]) <= 4  # 3 rows + _other
    assert "_other" in snap["tenants"]
    assert sum(
        s["kv_block_seconds"] for s in snap["tenants"].values()
    ) == snap["pool_kv_block_seconds"] == 20.0

    class Req:
        prompt_ids = [1] * 10
        max_new_tokens = 10
        tenant = "churn-9"  # folded: no own row
        ledger_t0 = 0.0
        ledger_admitted = 0.0
        ledger_done = False

    # Folded tenants' queue accounting balances through OVERFLOW...
    led.note_enqueued(Req())
    assert led.snapshot()["tenants"]["_other"]["queued_requests"] == 1
    led.note_dequeued(Req())
    assert led.snapshot()["tenants"]["_other"]["queued_requests"] == 0
    # ...and fairness still bites on the overflow aggregate.
    led.note_enqueued(Req())
    assert led.over_fair_share("churn-99", 20, 0.5, 60, 100)


def test_ledger_fair_share_math_tokens_and_seats():
    led = TenantLedger("m", clock=FakeClock())

    class Req:
        prompt_ids = [1] * 10
        max_new_tokens = 10
        tenant = "a"
        ledger_t0 = 0.0
        ledger_admitted = 0.0
        ledger_done = False

    led.note_enqueued(Req())  # a holds 20 queued tokens / 1 seat
    # Token-denominated (budget_tokens set): 20 + 20 > 0.5 × 60 → over.
    assert led.over_fair_share("a", 20, 0.5, 60, 100)
    assert not led.over_fair_share("a", 20, 0.8, 60, 100)
    # Seat-denominated (no token budget): 1 + 1 > 0.5 × 2 → over.
    assert led.over_fair_share("a", 20, 0.5, 0, 2)
    assert not led.over_fair_share("a", 20, 0.5, 0, 100)
    # Another tenant holds nothing; untenanted never trips.
    assert not led.over_fair_share("b", 20, 0.5, 60, 100)
    assert not led.over_fair_share("", 10 ** 6, 0.01, 60, 100)


# ----------------------------------------------------------------------
# SLOEngine units
# ----------------------------------------------------------------------


def test_burn_rate_window_math_and_recovery():
    clock = FakeClock(10_000.0)
    m = tenant_metrics()
    slo = SLOEngine(
        "m", ttft_ms=100.0, availability=0.99, metrics=m, clock=clock,
    )
    # 8 good + 2 bad TTFTs → bad fraction 0.2, budget 0.01 → burn 20.
    for i in range(10):
        slo.observe("ok", {"ttft_s": 0.05 if i < 8 else 0.5})
        clock.advance(1.0)
    assert slo.burn_rate("ttft", "5m") == pytest.approx(20.0)
    assert slo.burn_rate("ttft", "1h") == pytest.approx(20.0)
    # Availability saw 10 ok → burning nothing.
    assert slo.burn_rate("availability", "5m") == 0.0
    assert not slo.compliant()
    gauge = [
        i for i in m.instruments() if i.name == "app_tpu_slo_compliant"
    ][0]
    assert list(gauge.collect().values()) == [0.0]
    # Sheds charge availability (the server failed the client) but not
    # the latency SLOs (a shed has no TTFT); cancels count nowhere.
    slo.observe("shed", {})
    slo.observe("cancelled", {"ttft_s": 9.9, "e2e_s": 9.9})
    assert slo.burn_rate("availability", "5m") == pytest.approx(
        (1 / 11) / 0.01
    )
    # Recovery: 6 minutes later the 5m window has aged out, the 1h one
    # still remembers.
    clock.advance(360.0)
    assert slo.burn_rate("ttft", "5m") == 0.0
    assert slo.burn_rate("ttft", "1h") > 0.0
    clock.advance(3600.0)
    assert slo.burn_rate("ttft", "1h") == 0.0
    assert slo.compliant()


def test_slo_snapshot_shape():
    slo = SLOEngine("m", e2e_ms=200.0, clock=FakeClock(5.0))
    slo.observe("ok", {"e2e_s": 0.1})
    snap = slo.snapshot()
    assert snap["enabled"] and snap["compliant"]
    w = snap["slos"]["e2e"]["windows"]
    assert w["5m"]["total"] == 1 and w["5m"]["good"] == 1
    assert set(w) == {"5m", "1h"}
    assert snap["slos"]["e2e"]["target"] == 0.99  # latency default


# ----------------------------------------------------------------------
# engine integration: conservation at tp=1 and tp=2
# ----------------------------------------------------------------------


def _run_mixed_tenants(eng, m):
    handles = []
    for i, tenant in enumerate(
        ("alice", "bob", "alice", "", "carol", "bob")
    ):
        handles.append(eng.submit_generate(
            f"conserve {i:02d} {'x' * (4 * i)}", max_new_tokens=4 + i,
            temperature=0.0, stop_on_eos=False, tenant=tenant,
        ))
    results = [h.future.result(timeout=300) for h in handles]
    rep = eng.tenant_report()
    t = rep["tenants"]
    # KV conservation: Σ tenants == the pool-wide integral from the
    # same ticks, compared on the UNROUNDED accumulators (the snapshot
    # rounds for JSON; float-add order differs between the two sums,
    # hence approx — under the unit test's integer clock it is exact).
    led = eng._tenant_ledger
    assert sum(
        s.kv_block_seconds for s in led._stats.values()
    ) == pytest.approx(led.pool_block_seconds, rel=1e-9)
    assert rep["pool_kv_block_seconds"] > 0.0
    # Token conservation: per-tenant decode totals sum to the engine's
    # aggregate generated-token counter; prefill totals to the known
    # prompt lengths.
    assert sum(s["decode_tokens"] for s in t.values()) == sum(
        len(r.token_ids) for r in results
    ) == counter_value(m, "app_tpu_tokens_generated")
    assert sum(s["prefill_tokens"] for s in t.values()) == sum(
        len(h.prompt_ids) for h in handles
    )
    # Attribution named the right tenants.
    assert t["alice"]["requests"]["ok"] == 2
    assert t["_untenanted"]["requests"]["ok"] == 1
    return results


def test_conservation_tp1():
    m = tenant_metrics()
    eng = make_engine(metrics=m)
    try:
        _run_mixed_tenants(eng, m)
    finally:
        eng.close()


def test_conservation_tp2():
    """The attribution spine is host bookkeeping — device-count
    agnostic, so the same invariants hold on a GSPMD-sharded engine
    (conftest's 8 virtual devices)."""
    import jax

    m = tenant_metrics()
    eng = make_engine(metrics=m, tp=2, devices=jax.devices()[:2])
    try:
        _run_mixed_tenants(eng, m)
    finally:
        eng.close()


# ----------------------------------------------------------------------
# fairness-aware shedding: THE acceptance path
# ----------------------------------------------------------------------

WB_PROMPTS = [f"well behaved {i:02d}" for i in range(4)]


def _wb_streams(eng):
    handles = [
        eng.submit_generate(
            p, max_new_tokens=6, temperature=0.0, stop_on_eos=False,
            tenant=f"wb-{i % 2}",
        )
        for i, p in enumerate(WB_PROMPTS)
    ]
    return [h.future.result(timeout=300).token_ids for h in handles]


def test_fairness_shed_acceptance_path():
    """A hog saturating the queue is shed reason=tenant_fair_share —
    the hog only; well-behaved tenants' greedy streams stay
    byte-identical to a no-hog run; the availability burn rate rises
    then recovers; /debug/tenants names the hog."""
    # Reference: the same well-behaved traffic with no hog at all.
    ref_eng = make_engine()
    try:
        reference = _wb_streams(ref_eng)
    finally:
        ref_eng.close()

    m = tenant_metrics()
    clock = FakeClock(50_000.0)
    eng = make_engine(
        metrics=m,
        queue_max_tokens=512,
        tenant_fair_share=0.3,
        slo_availability=0.999,
    )
    eng._slo._clock = clock  # stated time for the burn windows
    try:
        # The hog floods: its queued share caps at 0.3 × 512 tokens —
        # about one 80-token request at a time — so past that every hog
        # submit sheds with the fairness reason while the queue keeps
        # room for everyone else.
        hog_handles, hog_sheds = [], 0
        for i in range(24):
            try:
                hog_handles.append(eng.submit_generate(
                    "H" * 64 + f" {i:02d}", max_new_tokens=16,
                    temperature=0.0, stop_on_eos=False, tenant="hog",
                ))
            except ErrorTooManyRequests as exc:
                hog_sheds += 1
                assert "tenant_fair_share" in str(exc)
        # Degraded, not banned: the hog keeps its share of service and
        # only the burst beyond it is shed.
        assert hog_sheds > 0 and hog_handles
        assert counter_value(
            m, "app_tpu_requests_shed_total", reason="tenant_fair_share"
        ) == hog_sheds
        # No other shed reason fired: the fairness shed kept the global
        # budgets un-exhausted, so only the hog paid.
        assert counter_value(
            m, "app_tpu_requests_shed_total"
        ) == hog_sheds
        # Well-behaved tenants ride through the hog's burst untouched.
        streams = _wb_streams(eng)
        assert streams == reference
        for h in hog_handles:
            h.future.result(timeout=300)
        # Burn rose: the hog's sheds are availability failures.
        assert eng._slo.burn_rate("availability", "5m") > 1.0
        rep = eng.tenant_report()
        assert rep["tenants"]["hog"]["requests"]["shed"] == hog_sheds
        # The attribution table /debug/tenants serves names the hog —
        # by shed count AND occupancy share.
        top = eng.capacity_report()["tenants"]
        assert any(
            e["tenant"] == "hog" and e["shed"] == hog_sheds
            for e in top
        )
        assert rep["tenants"]["hog"]["kv_block_seconds"] > 0
        # ... and recovered: 6 minutes of clean traffic later the 5m
        # window has aged the sheds out (the 1h window still remembers
        # — sustained-burn alerts are supposed to outlive the page).
        clock.advance(360.0)
        _wb_streams(eng)
        assert eng._slo.burn_rate("availability", "5m") == 0.0
        assert eng._slo.burn_rate("availability", "1h") > 0.0
        # An hour later the sustained window is clean too.
        clock.advance(3700.0)
        _wb_streams(eng)
        assert eng.slo_report()["compliant"] is True
    finally:
        eng.close()


def test_fairness_off_is_default_and_ledger_off_means_no_hooks():
    """TPU_TENANT_FAIR_SHARE unset → no fairness shed path at all;
    TPU_TENANT_LEDGER=0 → the whole layer is one is-not-None check:
    no ledger object, no request stamps, tenant_report disabled."""
    eng = make_engine(tenant_ledger=False)
    try:
        assert eng._tenant_ledger is None
        assert eng.tenant_fair_share == 0.0
        h = eng.submit_generate(
            "no ledger", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False, tenant="alice",
        )
        h.future.result(timeout=300)
        # The request was never stamped: zero attribution work done.
        assert h.ledger_t0 == 0.0 and not h.ledger_done
        assert eng.tenant_report() == {"enabled": False}
        assert "tenants" not in eng.flight_records()
    finally:
        eng.close()


# ----------------------------------------------------------------------
# advertisement: health, probes, pool stamps
# ----------------------------------------------------------------------


def test_health_probe_and_pool_advertisement():
    from gofr_tpu.service.replica_pool import EngineReplica, ReplicaPool

    m = tenant_metrics()
    eng = make_engine(metrics=m, slo_ttft_ms=60_000)
    try:
        eng.generate_sync(
            "advertise", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False, tenant="alice", timeout=300,
        )
        health = eng.health_check()
        assert health["details"]["slo"]["compliant"] is True
        assert "ttft" in health["details"]["slo"]["burn_rate_5m"]
        assert health["details"]["tenant_ledger"]["tenants"] >= 1
        replica = EngineReplica("r0", eng)
        desc = replica.describe()
        assert desc["slo_compliant"] is True
        pool = ReplicaPool([replica])
        flights = pool.flight_records()["replicas"]["r0"]
        assert flights["slo_compliant"] is True
        assert flights["tenants"][0]["tenant"] in ("alice", "_untenanted")
        caps = pool.capacity_report()["replicas"]["r0"]
        assert caps["slo_compliant"] is True
        tenants = pool.tenant_report()["replicas"]["r0"]
        assert "alice" in tenants["tenants"]
        slo_rep = pool.slo_report()["replicas"]["r0"]
        assert slo_rep["enabled"] and slo_rep["compliant"]
    finally:
        eng.close()


# ----------------------------------------------------------------------
# compile-cache persistence (gofr_tpu/compile_cache.py)
# ----------------------------------------------------------------------


@pytest.fixture
def persistent_compile_cache(tmp_path):
    """JAX's persistent cache at a temp dir, as a process entry point
    would place it — and persisting sub-second CPU programs, which
    JAX's default thresholds skip. conftest.py turns the cache off for
    the rest of the suite."""
    import jax
    from jax._src import compilation_cache

    knobs = {
        "jax_compilation_cache_dir": str(tmp_path / "xla-cache"),
        "jax_enable_compilation_cache": True,
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    saved = {k: getattr(jax.config, k) for k in knobs}
    for knob, value in knobs.items():
        jax.config.update(knob, value)
    compilation_cache.reset_cache()
    yield knobs["jax_compilation_cache_dir"]
    for knob, value in saved.items():
        jax.config.update(knob, value)
    compilation_cache.reset_cache()


def test_compile_cache_dir_recorded_and_no_steady_state_regression(
    persistent_compile_cache,
):
    """A second engine boot against a populated cache dir serves with
    zero steady-state recompiles, and the cache's provenance rides
    health and /debug/capacity."""
    cache_dir = persistent_compile_cache

    def boot():
        eng = make_engine()
        eng.generate_sync(
            "cache me", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False, timeout=300,
        )
        return eng

    eng1 = boot()
    cache1 = eng1.compile_stats()["compile_cache"]
    assert cache1["dir"] == cache_dir
    assert cache1["enabled"] and cache1["entries"] > 0
    health = eng1.health_check()
    assert (
        health["details"]["compiles"]["compile_cache"]["dir"] == cache_dir
    )
    eng1.close()

    eng2 = boot()
    try:
        # Warm-up fence armed after the boot request: any further
        # compile is a regression — a populated cache dir must never
        # ADD steady-state recompiles.
        eng2.mark_steady_state()
        eng2.generate_sync(
            "cache me again", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False, timeout=300,
        )
        stats = eng2.compile_stats()
        assert stats["steady_state_recompiles"] == 0
        assert stats["compile_cache"]["dir"] == cache_dir
        assert eng2.capacity_report()["compiles"]["compile_cache"][
            "dir"
        ] == cache_dir
    finally:
        eng2.close()
