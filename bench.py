"""Headline benchmark — flagship LLM serving throughput on TPU.

Boots the serving engine (continuous batching, fused decode+sample, donated
KV cache) with the largest Llama-family config that fits the available chip,
runs concurrent generation, and prints ONE JSON line:

    {"metric": "decode_tokens_per_sec_per_chip", "value": N,
     "unit": "tok/s/chip", "vs_baseline": N/1000}

``vs_baseline``: the reference (GoFr) publishes no perf numbers, so the
denominator is a fixed 1000 tok/s/chip nominal target for a ~1B bf16
model on one v5e — chosen once so the ratio is comparable across rounds.
Details (TTFT p50/p99, per-request rates) go to stderr.

One process, which owns the chip from start to exit. It fails, printing
no result, when JAX's first device is not a TPU or its ``device_kind``
has no entry in ``DEVICE_PEAKS``; the headline row names ``platform``,
``device_kind`` and ``device_count``. The compile cache is where
``gofr_tpu/compile_cache.py`` says.

Env knobs: BENCH_MODEL (default llama-1b),
BENCH_REQUESTS (default 64), BENCH_NEW_TOKENS (default 128),
BENCH_SLOTS (default 32), BENCH_MAX_LEN (default 1024),
BENCH_WINDOW (default 8), BENCH_DEPTH (default 2), BENCH_MEGA
(mega-window dispatch amortization; default 8 on TPU, 0 = streaming
pipelined mode elsewhere), BENCH_PREFILL_DEPTH (multi-chunk prefill),
BENCH_QUANT (default int8 on TPU — weight-only int8, the production
serving configuration; set BENCH_QUANT=none for bf16 weights),
BENCH_LORA / BENCH_LORA_RANK (N random adapters, requests round-robin
over base + adapters — the multi-LoRA overhead A/B),
BENCH_PREFIX_WORKLOAD=1 (repeated-prefix burst: one shared
BENCH_PREFIX_TOKENS=512 preamble + distinct suffixes on a paged engine;
reports prefix hit-token ratio and warm-vs-cold TTFT;
BENCH_AUTO_PREFIX=0 runs the same workload with the radix cache off —
the prefix-caching A/B),
BENCH_TP_WORKLOAD=1 (GSPMD-sharded serving A/B: the SAME burst on a
tp=1 then a tp=2 engine — token-identity enforced, the tp-invariance
contract — emitting tp1_tps/tp2_tps/tp_speedup in one JSON line; needs
two chips),
BENCH_TENANT_WORKLOAD=1 (mixed-tenant burst: one hog tenant floods the
queue while BENCH_TENANTS=3 well-behaved tenants submit small requests;
the same burst runs with fairness shedding off then on
(BENCH_TENANT_FAIR_SHARE=0.3) and the JSON line carries tenant_count,
per-tenant tok/s spread, the well-behaved tenants' TTFT under both
policies, hog fair-share shed counts, and the TTFT SLO's 5m burn rate),
BENCH_OVERLOAD_WORKLOAD=1 (overload-storm A/B: batch-class flood +
interactive arrivals under an always-breaching TTFT SLO, run with the
brownout ladder off then on — the JSON line carries
interactive_goodput_{off,on}, ttft_p99_{off,on}_ms,
shed_{batch,interactive}_total, and max_brownout_level),
BENCH_TIER_WORKLOAD=1 (disaggregated-tier transfer-leg A/B: the same
prefill-heavy burst through a prefill+decode pool with the transfer leg
pinned to host-bounce then to the device leg — the JSON line carries
transfer_ms_{host,device} p50/p95, per-leg decode-tier cold TTFT, and
tier_transfers_total{leg,result}; acceptance = device p50 strictly
below host),
BENCH_SPEC_WORKLOAD=1 (n-gram speculation A/B: a repeated-text burst
on spec=0 vs spec=BENCH_SPEC_G=2 engines, emitting plain/spec tok/s,
the measured app_tpu_spec_tokens_per_step acceptance, and the
per-request greedy-identity verdict — the default-on decision data),
BENCH_CONTROL_WORKLOAD=1 (control-plane A/B: a diurnal hog-tenant ramp
over a small queue with BENCH_TENANTS=3 well-behaved tenants, run with
the control plane off then on — the JSON line carries per-tenant
goodput min/max under both policies, the hog's highest per-tenant
ladder level, the predictive loop's scale lead time, and the plane's
degraded-signal / eval-error counts),
BENCH_ASYNC_WORKLOAD=1 (durable async-serving idle-soak A/B: the same
interactive trickle with the async plane off then on against a
request-topic backlog — with poison messages riding along so the
redelivery/dead-letter path is priced too — emitting async_tps,
interactive_ttft_p95_{off,on}_ms, redelivered and dead_lettered; the
claim priced is that async soaks idle capacity WITHOUT moving
interactive TTFT).
Workload: BENCH_ARRIVAL_MS / BENCH_TOKEN_SPREAD (TPU default 25 / 0.5 —
steady-state; the reported value is then the mid-window sustained rate,
with the end-to-end rate in e2e_tps; set both to 0 for the synchronized
burst pre-r4 campaign rows used).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pct(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile over an ascending list (0 on empty)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _latency_fields(results: list) -> dict:
    """Per-request TTFT and inter-token-latency percentiles (ms) for
    the JSON result line, so BENCH_* trajectories capture tail latency
    alongside throughput. ITL per request = (duration - ttft) over the
    gaps between its generated tokens; requests with <2 tokens have no
    gap and are skipped."""
    ttfts = sorted(r.ttft_s * 1e3 for r in results)
    itls = sorted(
        (r.duration_s - r.ttft_s) / (len(r.token_ids) - 1) * 1e3
        for r in results if len(r.token_ids) >= 2
    )
    return {
        "ttft_p50": round(_pct(ttfts, 0.50), 2),
        "ttft_p95": round(_pct(ttfts, 0.95), 2),
        "ttft_p99": round(_pct(ttfts, 0.99), 2),
        "itl_p50": round(_pct(itls, 0.50), 3),
        "itl_p95": round(_pct(itls, 0.95), 3),
        "itl_p99": round(_pct(itls, 0.99), 3),
    }


def _device_resource_fields(engine) -> dict:
    """Device-resource fields for the JSON result line (ISSUE 11):
    total XLA compiles, compiles that fired AFTER the warm-up fence
    (always a fixed-shape bug — see ``_recompile_guard``), and peak
    per-device HBM (the runtime's own peak when the platform reports
    one, else the ledger's per-device accounting)."""
    stats = engine.compile_stats()
    ledger = engine.hbm_ledger()
    # The ledger snapshot already carries the platform cross-check
    # (mesh-aware device pick); reuse it rather than re-probing.
    mem = ledger.get("device") or {}
    peak = int(
        mem.get("peak_bytes_in_use") or mem.get("bytes_in_use") or 0
    )
    return {
        "compiles_total": int(stats["total"]),
        "steady_state_recompiles": int(stats["steady_state_recompiles"]),
        "hbm_peak_bytes": max(peak, int(ledger.get("per_device_bytes", 0))),
    }


def _loop_fields(engine) -> dict:
    """Scheduler-loop profiler fields for the JSON result line
    (ISSUE 15): the loop's busy fraction, the host-bookkeeping share
    of busy time (THE "is host bookkeeping starving the TPU" number a
    real-TPU row must carry next to tok/s), stall count, and per-phase
    rolling p50s. Empty marker when the layer is off — the
    TPU_LOOP_PROFILE=0 overhead A/B."""
    prof = getattr(engine, "_loop_prof", None)
    if prof is None:
        return {"loop_profile": False}
    return {
        "loop_util": round(prof.utilization(), 4),
        "host_overhead_ratio": round(prof.host_overhead_ratio(), 4),
        "loop_stalls": int(prof.stalls),
        "loop_phase_p50_ms": prof.phase_p50_ms(),
    }


def _recompile_guard(engine) -> None:
    """The fixed-shape contract as a bench guard (the compile-tracker
    twin of BENCH_TP_WORKLOAD's token-identity exit): any XLA compile
    after ``mark_steady_state`` means the measured run was serialized
    behind a trace+compile — the number would be garbage AND the
    serving config has a shape-discipline bug. Exit 6, no JSON."""
    stats = engine.compile_stats()
    if stats["steady_state_recompiles"]:
        log(f"bench: {stats['steady_state_recompiles']} STEADY-STATE "
            f"RECOMPILE(S) after the warm-up fence "
            f"({ {k: v['compiles'] for k, v in stats['programs'].items() if v['compiles']} }) "
            f"— fixed-shape contract broken; refusing to report a "
            f"compile-serialized number")
        os._exit(6)


# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s). A device that is not in the
# table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0, "bf16_tflops": 197.0, "int8_tops": 393.0,
        "hbm_gb": 16.0,
    },
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench: no published peaks for device_kind {device_kind!r}; "
            f"add it to DEVICE_PEAKS with its source"
        )
    return DEVICE_PEAKS[device_kind]


def _decode_attn_ab(engine, n_slots: int, kv_quant: str) -> None:
    """In-graph decode-attention A/B (kernel grid vs fused dense).

    Timing sequential un-donated dispatches lets per-call dispatch
    overhead swamp device time (an earlier probe that did so printed
    per-layer numbers whose sum exceeded the measured full step by 40×
    and inverted the kernel/dense ordering). This probe chains the op M times inside ONE jitted program — the output
    feeds the next iteration's query, so XLA can't elide or reorder
    iterations — and differences two trip counts: constant per-dispatch
    overhead cancels exactly, leaving pure per-layer device time that
    sums consistently with the measured step.
    """
    import jax
    import jax.numpy as jnp

    from gofr_tpu.ops.attention import decode_attention
    from gofr_tpu.ops.kv_cache import quantize_kv

    cfg = engine.cfg
    S, T = n_slots, engine.max_len
    key = jax.random.PRNGKey(0)
    qa = jax.random.normal(key, (S, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kc = jax.random.normal(key, (S, cfg.n_kv_heads, T, cfg.head_dim), jnp.bfloat16)
    vc = kc + 1
    ks = vs = None
    if kv_quant:  # mirror the served cache dtype (int8 + scale planes)
        kc, ksc = quantize_kv(kc)
        vc, vsc = quantize_kv(vc)
        rep8 = lambda s: jnp.broadcast_to(  # noqa: E731
            s[:, :, None, :], (S, cfg.n_kv_heads, 8, T)
        ).astype(jnp.float32)
        ks, vs = rep8(ksc), rep8(vsc)
    lens = jnp.full((S,), T // 2, jnp.int32)  # typical half-full slots
    # Windowed models (mistral): measure the attention the engine
    # actually serves — the kernel skips out-of-window blocks, the dense
    # path can't, so the A/B verdict differs from the unwindowed one.
    window = getattr(cfg, "sliding_window", 0) or 0
    L = cfg.n_layers
    m1, m2 = L, 9 * L  # differenced trip counts (both amortize dispatch)
    for name, kern in (("kernel", True), ("dense", False)):
        try:

            def chained(q, k, v, le, sk, sv, m, kn=kern):
                def body(_, qc):
                    return decode_attention(
                        qc, k, v, le, k_scale=sk, v_scale=sv, kernel=kn,
                        window=window,
                    )

                return jax.lax.fori_loop(0, m, body, q)

            fn = jax.jit(chained, donate_argnums=(0,))
            times = {}
            for m in (m1, m2):
                md = jnp.int32(m)
                jax.block_until_ready(
                    fn(jnp.array(qa), kc, vc, lens, ks, vs, md)
                )  # compile (shared across m: trip count is traced)
                reps, out = 3, None
                t_ab = time.perf_counter()
                for _ in range(reps):
                    # Fresh query copy per call (the carry is donated);
                    # the D2D copy is per-call-constant → cancels in the
                    # difference below.
                    out = fn(jnp.array(qa), kc, vc, lens, ks, vs, md)
                jax.block_until_ready(out)
                times[m] = (time.perf_counter() - t_ab) / reps
            per = (times[m2] - times[m1]) / (m2 - m1) * 1e3
            const = times[m1] * 1e3 - per * m1
            wtag = f" window={window}" if window else ""
            log(f"profile: decode-attn[{name}] ({kv_quant or 'bf16'} kv"
                f"{wtag}) {per:.4f} ms/layer in-graph → ~{per * L:.2f} "
                f"ms/step attn total (per-dispatch const ≈{const:.1f} ms, "
                f"cancelled)")
        except Exception as exc:  # noqa: BLE001 — A/B is advisory
            log(f"profile: decode-attn[{name}] probe failed: {exc}")


def _prefill_attn_ab(engine, n_slots: int, kv_quant: str) -> None:
    """In-graph chunked-prefill attention A/B (kernel vs dense), same
    dispatch-cancelling differencing as ``_decode_attn_ab``. Answers
    whether the chunk kernel's length-skipping beats one fused dense op
    at the serving chunk shape (TTFT attribution)."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.ops.attention import cache_chunk_attention
    from gofr_tpu.ops.kv_cache import quantize_kv

    cfg = engine.cfg
    S, T, c = n_slots, engine.max_len, engine.prefill_chunk
    P = engine.prefill_batch
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (P, c, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kc = jax.random.normal(
        key, (S, cfg.n_kv_heads, T, cfg.head_dim), jnp.bfloat16
    )
    vc = kc + 1
    ks = vs = None
    if kv_quant:
        kc, ksc = quantize_kv(kc)
        vc, vsc = quantize_kv(vc)
        rep8 = lambda s: jnp.broadcast_to(  # noqa: E731
            s[:, :, None, :], (S, cfg.n_kv_heads, 8, T)
        ).astype(jnp.float32)
        ks, vs = rep8(ksc), rep8(vsc)
    slots = jnp.arange(P, dtype=jnp.int32) % S
    starts = jnp.full((P,), T // 2, jnp.int32)  # mid-prompt chunk
    lens = jnp.full((P,), c, jnp.int32)
    window = getattr(cfg, "sliding_window", 0) or 0
    L = cfg.n_layers
    m1, m2 = L, 9 * L
    for name, kern in (("kernel", True), ("dense", False)):
        try:

            def chained(q, k, v, sl, st, ln, sk, sv, m, kn=kern):
                def body(_, qc):
                    return cache_chunk_attention(
                        qc, k, v, sl, st, ln, k_scale=sk, v_scale=sv,
                        kernel=kn, window=window,
                    )

                return jax.lax.fori_loop(0, m, body, q)

            fn = jax.jit(chained, donate_argnums=(0,))
            times = {}
            for m in (m1, m2):
                md = jnp.int32(m)
                jax.block_until_ready(
                    fn(jnp.array(q), kc, vc, slots, starts, lens, ks, vs, md)
                )
                reps, out = 3, None
                t_ab = time.perf_counter()
                for _ in range(reps):
                    out = fn(
                        jnp.array(q), kc, vc, slots, starts, lens, ks, vs,
                        md,
                    )
                jax.block_until_ready(out)
                times[m] = (time.perf_counter() - t_ab) / reps
            per = (times[m2] - times[m1]) / (m2 - m1) * 1e3
            wtag = f" window={window}" if window else ""
            log(f"profile: prefill-attn[{name}] ({P}x{c} chunk, "
                f"{kv_quant or 'bf16'} kv{wtag}) {per:.4f} ms/layer "
                f"in-graph → ~{per * L:.2f} ms/chunk attn total")
        except Exception as exc:  # noqa: BLE001 — A/B is advisory
            log(f"profile: prefill-attn[{name}] probe failed: {exc}")


def _set_stage(name: str) -> None:
    """Say where the run is: the tail of a chip run is all that is seen."""
    log(f"bench: stage {name}")


def _prefix_workload(on_tpu: bool) -> None:
    """BENCH_PREFIX_WORKLOAD=1: repeated-prefix burst — every request
    shares one 512-token preamble and carries a distinct suffix, the
    shape real traffic (system prompts, few-shot preambles, multi-turn
    history) re-prefills today. Reports the prefix hit-token ratio and
    warm-vs-cold TTFT alongside the usual JSON line fields;
    BENCH_AUTO_PREFIX=0 runs the identical workload cold (the A/B).
    Self-contained: paged engine, no profile phase, CPU-safe."""
    import statistics

    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    auto = os.environ.get("BENCH_AUTO_PREFIX", "1").lower() not in (
        "0", "false", "no",
    )
    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_requests = int(os.environ.get("BENCH_REQUESTS", "16" if on_tpu else "8"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "32" if on_tpu else "8"))
    n_slots = int(os.environ.get("BENCH_SLOTS", "8"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "1024"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "128" if on_tpu else "64"))
    preamble_tokens = int(os.environ.get("BENCH_PREFIX_TOKENS", "512"))
    # Proactive eviction watermark A/B (BENCH_PREFIX_EVICT_WM, blocks;
    # 0 = shortfall-only eviction, the pre-watermark behavior).
    evict_wm = int(os.environ.get("BENCH_PREFIX_EVICT_WM", "0"))
    quant = os.environ.get("BENCH_QUANT", "int8" if on_tpu else "")
    if quant.lower() in ("none", "0"):
        quant = ""

    log(f"bench[prefix]: model={model} requests={n_requests} "
        f"preamble={preamble_tokens}tok kv_block={kv_block} "
        f"auto_prefix={auto} evict_wm={evict_wm}")
    _set_stage("engine-init")
    engine = InferenceEngine(
        model, n_slots=n_slots, max_len=max_len, tokenizer=ByteTokenizer(),
        window_k=int(os.environ.get("BENCH_WINDOW", "8")),
        pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
        quant=quant,
        kv_block=kv_block,
        auto_prefix=auto,
        prefix_evict_watermark=evict_wm,
        prefill_chunk=int(os.environ.get("BENCH_PREFILL_CHUNK", "256")),
    )
    engine.start_sync()

    # ByteTokenizer: 1 char = 1 token, so the shared preamble is exactly
    # preamble_tokens long and a multiple of nothing in particular —
    # the boundary block exercises the partial-block path. Clamp to what
    # the engine can actually admit (llama-tiny's config caps max_len at
    # 256 on the CPU fallback) while keeping ≥ 2 full KV blocks shared.
    cap = engine.max_prompt_tokens - new_tokens - 32
    if preamble_tokens > cap:
        # Never exceed the admissible prompt length — with a large
        # BENCH_KV_BLOCK on the CPU fallback, 2 full blocks may simply
        # not fit; warn rather than crash the first cold generate.
        preamble_tokens = max(cap, 1)
        log(f"bench[prefix]: preamble clamped to {preamble_tokens} tokens "
            f"(engine max prompt {engine.max_prompt_tokens})")
        if preamble_tokens < 2 * kv_block:
            log(f"bench[prefix]: WARNING preamble < 2 KV blocks "
                f"({kv_block} tok each) — little or nothing to share; "
                f"lower BENCH_KV_BLOCK or raise BENCH_MAX_LEN")
    preamble = "S" * preamble_tokens
    _set_stage("warmup")
    engine.generate_sync(
        "w" * 8, max_new_tokens=2, temperature=0.0, stop_on_eos=False
    )
    # Warm-up fence: the chunked-prefill and decode programs are
    # compiled; anything that compiles during the measured phase is a
    # fixed-shape bug (exit 6 below) and would serialize the burst.
    engine.mark_steady_state()

    _set_stage("measure")
    # COLD: the first preamble-carrying request prefills everything
    # (and, with auto_prefix, seeds the radix index as it retires).
    t0 = time.time()
    cold = engine.generate_sync(
        preamble + " request cold", max_new_tokens=new_tokens,
        temperature=0.0, stop_on_eos=False,
    )
    cold_ttft_ms = cold.ttft_s * 1e3
    # WARM burst: distinct suffixes behind the shared preamble.
    reqs = [
        engine.submit_generate(
            f"{preamble} request {i:04d}", max_new_tokens=new_tokens,
            temperature=0.0, stop_on_eos=False,
        )
        for i in range(n_requests)
    ]
    results = [r.future.result(timeout=1800) for r in reqs]
    wall = time.time() - t0
    warm_ttfts = sorted(r.ttft_s * 1e3 for r in results)
    warm_p50 = statistics.median(warm_ttfts)
    total_prompt = sum(
        len(f"{preamble} request {i:04d}") for i in range(n_requests)
    ) + len(preamble + " request cold")
    hit_tokens = engine._prefix_hit_tokens
    hit_ratio = hit_tokens / total_prompt if total_prompt else 0.0
    total_tokens = sum(len(r.token_ids) for r in results) + len(cold.token_ids)
    latency = _latency_fields([cold, *results])
    log(f"bench[prefix]: {total_tokens} tokens in {wall:.2f}s; "
        f"hit_tokens={hit_tokens}/{total_prompt} ({100 * hit_ratio:.1f}%); "
        f"TTFT cold={cold_ttft_ms:.1f}ms warm_p50={warm_p50:.1f}ms; "
        f"ttft p50/p95/p99={latency['ttft_p50']}/{latency['ttft_p95']}/"
        f"{latency['ttft_p99']}ms itl p50/p95/p99={latency['itl_p50']}/"
        f"{latency['itl_p95']}/{latency['itl_p99']}ms")
    device_fields = _device_resource_fields(engine)
    loop_fields = _loop_fields(engine)
    _recompile_guard(engine)
    engine.stop_sync()
    _set_stage("done")
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(total_tokens / wall, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(total_tokens / wall / 1000.0, 4),
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "prefix",
        "auto_prefix": auto,
        "prefix_evict_wm": evict_wm,
        "prefix_hit_token_ratio": round(hit_ratio, 4),
        "prefix_hit_tokens": int(hit_tokens),
        "cold_ttft_ms": round(cold_ttft_ms, 2),
        "warm_ttft_p50_ms": round(warm_p50, 2),
        **latency,
        **device_fields,
        **loop_fields,
    }), flush=True)
    os._exit(0)


def _loop_workload(on_tpu: bool) -> None:
    """BENCH_LOOP_WORKLOAD=1: the scheduler-loop profiler overhead A/B
    (ISSUE 15) — the identical steady burst with TPU_LOOP_PROFILE off
    then on, pinning the layer's cost next to the signals it buys
    (loop utilization, host-overhead ratio, per-phase p50s). The
    profiler's own measured summarization cost rides the line too.
    Self-contained: paged engine, no profile phase, CPU-safe."""
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_requests = int(os.environ.get("BENCH_REQUESTS", "16" if on_tpu else "8"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "32" if on_tpu else "16"))
    eng_kw = dict(
        n_slots=int(os.environ.get("BENCH_SLOTS", "8")),
        max_len=int(os.environ.get("BENCH_MAX_LEN", "1024")),
        window_k=int(os.environ.get("BENCH_WINDOW", "8")),
        pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
        kv_block=int(os.environ.get("BENCH_KV_BLOCK", "128" if on_tpu else "64")),
        auto_prefix=True,
        prefill_chunk=int(os.environ.get("BENCH_PREFILL_CHUNK", "256")),
        tokenizer=ByteTokenizer(),
    )
    quant = os.environ.get("BENCH_QUANT", "int8" if on_tpu else "")
    if quant.lower() not in ("none", "0", ""):
        eng_kw["quant"] = quant
    log(f"bench[loop]: model={model} requests={n_requests} "
        f"new_tokens={new_tokens} — TPU_LOOP_PROFILE off/on A/B")

    def run(profile: bool) -> tuple[float, object]:
        _set_stage("engine-init")
        engine = InferenceEngine(model, loop_profile=profile, **eng_kw)
        engine.start_sync()
        _set_stage("warmup")
        engine.generate_sync(
            "w" * 8, max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        engine.mark_steady_state()
        _set_stage("measure")
        t0 = time.time()
        reqs = [
            engine.submit_generate(
                f"loop burst request {i:04d}", max_new_tokens=new_tokens,
                temperature=0.0, stop_on_eos=False,
            )
            for i in range(n_requests)
        ]
        results = [r.future.result(timeout=1800) for r in reqs]
        wall = time.time() - t0
        tokens = sum(len(r.token_ids) for r in results)
        _recompile_guard(engine)
        return tokens / wall, engine

    tps_off, eng_off = run(False)
    eng_off.stop_sync()
    tps_on, eng_on = run(True)
    loop_fields = _loop_fields(eng_on)
    prof = eng_on._loop_prof
    self_overhead_s = float(prof.self_overhead_s) if prof is not None else 0.0
    passes = int(prof.passes) if prof is not None else 0
    eng_on.stop_sync()
    _set_stage("done")
    overhead_pct = (
        (tps_off - tps_on) / tps_off * 100.0 if tps_off > 0 else 0.0
    )
    log(f"bench[loop]: off={tps_off:.1f} on={tps_on:.1f} tok/s "
        f"({overhead_pct:+.2f}% overhead); loop_util="
        f"{loop_fields.get('loop_util')} host_overhead_ratio="
        f"{loop_fields.get('host_overhead_ratio')}; profiler self-cost "
        f"{self_overhead_s * 1e3:.2f}ms over {passes} passes")
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(tps_on, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(tps_on / 1000.0, 4),
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "loop-profile",
        "tps_profile_off": round(tps_off, 2),
        "tps_profile_on": round(tps_on, 2),
        "loop_profile_overhead_pct": round(overhead_pct, 2),
        "loop_self_overhead_ms": round(self_overhead_s * 1e3, 3),
        "loop_passes": passes,
        **loop_fields,
    }), flush=True)
    os._exit(0)


def _tenant_workload(on_tpu: bool) -> None:
    """BENCH_TENANT_WORKLOAD=1: mixed-tenant burst — one hog tenant
    floods the queue with long-prompt requests while N well-behaved
    tenants submit small interactive ones, the shape a multi-tenant pod
    degrades under today. Runs the SAME burst twice: fairness shedding
    off, then on (``TPU_TENANT_FAIR_SHARE``, default
    BENCH_TENANT_FAIR_SHARE=0.3) — the A/B that decides whether the
    hog's burst degrades the hog or the fleet. Reports per-tenant tok/s
    spread, the well-behaved tenants' TTFT under both policies, the
    hog's fair-share shed count, and the TTFT SLO's 5m burn rate.
    Self-contained: paged engine, no profile phase, CPU-safe."""
    from gofr_tpu.errors import ErrorTooManyRequests
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_tenants = int(os.environ.get("BENCH_TENANTS", "3"))
    wb_requests = int(os.environ.get("BENCH_REQUESTS", "4"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "16" if on_tpu else "8"))
    n_slots = int(os.environ.get("BENCH_SLOTS", "2"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "256"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "32"))
    hog_requests = int(os.environ.get("BENCH_HOG_REQUESTS", "16"))
    fair_share = float(os.environ.get("BENCH_TENANT_FAIR_SHARE", "0.3"))
    slo_ttft_ms = float(os.environ.get("BENCH_SLO_TTFT_MS", "1000"))

    log(f"bench[tenant]: model={model} tenants={n_tenants} "
        f"wb_requests={wb_requests} hog_requests={hog_requests} "
        f"fair_share={fair_share} slots={n_slots}")

    def run(share: float) -> dict:
        _set_stage(f"engine-init-fair{share}")
        engine = InferenceEngine(
            model, n_slots=n_slots, max_len=max_len,
            tokenizer=ByteTokenizer(),
            window_k=int(os.environ.get("BENCH_WINDOW", "8")),
            pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
            kv_block=kv_block,
            # The queue-token budget the fair share divides: small
            # enough that the hog's flood saturates it.
            queue_max_tokens=int(os.environ.get(
                "BENCH_QUEUE_TOKENS", "512"
            )),
            tenant_ledger=True,
            tenant_fair_share=share,
            slo_ttft_ms=slo_ttft_ms,
            seed=0,
        )
        engine.start_sync()
        _set_stage(f"warmup-fair{share}")
        engine.generate_sync(
            "w" * 8, max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        engine.mark_steady_state()
        _set_stage(f"measure-fair{share}")
        hog_prompt = "H" * min(96, engine.max_prompt_tokens - new_tokens - 8)
        t0 = time.time()
        hog_handles = []
        hog_shed = 0
        # The hog floods first — its queued cost is what the fairness
        # share caps; the well-behaved tenants' small submits follow
        # behind it, exactly the arrival order that starves them today.
        for i in range(hog_requests):
            try:
                hog_handles.append(engine.submit_generate(
                    hog_prompt + f" {i:03d}", max_new_tokens=new_tokens,
                    temperature=0.0, stop_on_eos=False, tenant="hog",
                ))
            except ErrorTooManyRequests:
                hog_shed += 1
        wb_handles: dict = {}
        for t in range(n_tenants):
            name = f"wb-{t}"
            wb_handles[name] = []
            for i in range(wb_requests):
                try:
                    wb_handles[name].append(engine.submit_generate(
                        f"tenant {name} request {i:02d}",
                        max_new_tokens=new_tokens, temperature=0.0,
                        stop_on_eos=False, tenant=name,
                    ))
                except ErrorTooManyRequests:
                    pass
        per_tenant: dict = {}
        wb_results = []
        for name, handles in wb_handles.items():
            results = [h.future.result(timeout=1800) for h in handles]
            wb_results.extend(results)
            per_tenant[name] = sum(len(r.token_ids) for r in results)
        hog_results = [h.future.result(timeout=1800) for h in hog_handles]
        per_tenant["hog"] = sum(len(r.token_ids) for r in hog_results)
        wall = time.time() - t0
        slo = engine.slo_report()
        burn = (
            slo["slos"]["ttft"]["windows"]["5m"]["burn_rate"]
            if slo.get("enabled") else 0.0
        )
        tenants_table = engine.tenant_report()["tenants"]
        _recompile_guard(engine)
        engine.stop_sync()
        tps = {
            name: round(tokens / wall, 2)
            for name, tokens in per_tenant.items()
        }
        wb_ttfts = sorted(r.ttft_s * 1e3 for r in wb_results)
        # The bench's own except-counter and the ledger's shed outcome
        # count the SAME submit-time events — report one, cross-check
        # the other.
        ledger_shed = int(
            tenants_table.get("hog", {})
            .get("requests", {}).get("shed", 0)
        )
        if ledger_shed != hog_shed:
            log(f"bench[tenant]: WARNING ledger hog sheds "
                f"({ledger_shed}) != submit-path sheds ({hog_shed})")
        out = {
            "wall_s": round(wall, 2),
            "tenant_tps": tps,
            "tenant_tps_min": min(tps.values()),
            "tenant_tps_max": max(tps.values()),
            "wb_ttft_p95_ms": round(_pct(wb_ttfts, 0.95), 2),
            "hog_shed": hog_shed,
            "slo_ttft_burn": round(burn, 4),
        }
        log(f"bench[tenant]: fair_share={share} → wb ttft_p95="
            f"{out['wb_ttft_p95_ms']}ms hog_shed={out['hog_shed']} "
            f"tps={tps} slo_ttft_burn={out['slo_ttft_burn']}")
        return out

    unfair = run(0.0)
    fair = run(fair_share)
    _set_stage("done")
    total_tps = sum(unfair["tenant_tps"].values())
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(total_tps, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(total_tps / 1000.0, 4),
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "tenant",
        "tenant_count": n_tenants + 1,  # N well-behaved + the hog
        "fair_share": fair_share,
        "tenant_tps_min": unfair["tenant_tps_min"],
        "tenant_tps_max": unfair["tenant_tps_max"],
        "slo_ttft_burn": unfair["slo_ttft_burn"],
        # The fairness A/B: the well-behaved tenants' TTFT with the
        # hog shed on its own budget vs sharing the pain.
        "wb_ttft_p95_unfair_ms": unfair["wb_ttft_p95_ms"],
        "wb_ttft_p95_fair_ms": fair["wb_ttft_p95_ms"],
        "hog_shed_unfair": unfair["hog_shed"],
        "hog_shed_fair": fair["hog_shed"],
        "slo_ttft_burn_fair": fair["slo_ttft_burn"],
    }), flush=True)
    os._exit(0)


def _overload_workload(on_tpu: bool) -> None:
    """BENCH_OVERLOAD_WORKLOAD=1: overload-storm A/B — a batch-class
    hog floods the queue while interactive requests arrive, with an
    aggressive TTFT SLO (BENCH_SLO_TTFT_MS=1, every request breaches)
    so the burn rate pegs immediately. The SAME storm runs twice:
    brownout off (TPU_BROWNOUT=0 behavior — everyone queues until the
    static budgets trip) then on (the ladder climbs, batch sheds first,
    interactive keeps flowing). Reports interactive goodput and TTFT
    p99 under both policies, per-class shed counts, and the highest
    ladder level reached. Self-contained: paged engine, no profile
    phase, CPU-safe."""
    from gofr_tpu.errors import ErrorTooManyRequests
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_interactive = int(os.environ.get("BENCH_REQUESTS", "8"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "16" if on_tpu else "8"))
    n_slots = int(os.environ.get("BENCH_SLOTS", "2"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "256"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "32"))
    batch_requests = int(os.environ.get("BENCH_HOG_REQUESTS", "16"))
    queue_tokens = int(os.environ.get("BENCH_QUEUE_TOKENS", "512"))
    # Every request breaches a 1ms TTFT objective → the 5m burn pegs
    # at 1/error-budget from the first retirement: a deterministic
    # storm signal without waiting out real latency degradation.
    slo_ttft_ms = float(os.environ.get("BENCH_SLO_TTFT_MS", "1"))

    log(f"bench[overload]: model={model} interactive={n_interactive} "
        f"batch={batch_requests} queue_tokens={queue_tokens} "
        f"slo_ttft_ms={slo_ttft_ms}")

    def run(brownout: bool) -> dict:
        _set_stage(f"engine-init-brownout{int(brownout)}")
        engine = InferenceEngine(
            model, n_slots=n_slots, max_len=max_len,
            tokenizer=ByteTokenizer(),
            window_k=int(os.environ.get("BENCH_WINDOW", "8")),
            pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
            kv_block=kv_block,
            queue_max_tokens=queue_tokens,
            slo_ttft_ms=slo_ttft_ms,
            slo_availability=0.999,
            brownout=brownout,
            # Sub-second sustain windows so the ladder climbs inside
            # the bench's storm (production defaults are 10s/30s).
            brownout_sustain_s=0.05,
            brownout_exit_sustain_s=30.0,
            brownout_max_new=max(4, new_tokens // 2),
            seed=0,
        )
        engine.start_sync()
        _set_stage(f"warmup-brownout{int(brownout)}")
        engine.generate_sync(
            "w" * 8, max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        engine.mark_steady_state()
        _set_stage(f"measure-brownout{int(brownout)}")
        batch_prompt = "B" * min(96, engine.max_prompt_tokens - new_tokens - 8)
        shed = {"batch": 0, "interactive": 0}
        max_level = 0
        t0 = time.time()
        handles = []
        interactive_results = []
        # Interleave: batch floods ~2:1 against interactive arrivals,
        # with a breather between waves so the scheduler retires work
        # (retirements feed the burn; the ladder needs a few windows).
        waves = max(n_interactive, 1)
        for w in range(waves):
            for i in range(max(1, batch_requests // waves)):
                try:
                    handles.append(engine.submit_generate(
                        batch_prompt + f" {w:02d}{i:02d}",
                        max_new_tokens=new_tokens, temperature=0.0,
                        stop_on_eos=False, slo_class="batch",
                        tenant="hog",
                    ))
                except ErrorTooManyRequests:
                    shed["batch"] += 1
            try:
                interactive_results.append(engine.generate_sync(
                    f"interactive {w:02d}", max_new_tokens=new_tokens,
                    temperature=0.0, stop_on_eos=False,
                    slo_class="interactive", timeout=1800,
                ))
            except ErrorTooManyRequests:
                shed["interactive"] += 1
            max_level = max(max_level, engine.brownout_level() or 0)
        for h in handles:
            h.future.result(timeout=1800)
        wall = time.time() - t0
        goodput = sum(
            len(r.token_ids) for r in interactive_results
        ) / wall
        ttfts = sorted(r.ttft_s * 1e3 for r in interactive_results)
        bc = engine._brownout
        if bc is not None:
            shed["batch"] = max(shed["batch"], bc.shed_count("batch"))
            shed["interactive"] = max(
                shed["interactive"], bc.shed_count("interactive")
            )
        _recompile_guard(engine)
        engine.stop_sync()
        out = {
            "wall_s": round(wall, 2),
            "interactive_goodput": round(goodput, 2),
            "ttft_p99_ms": round(_pct(ttfts, 0.99), 2) if ttfts else -1.0,
            "shed_batch": shed["batch"],
            "shed_interactive": shed["interactive"],
            "max_level": max_level,
        }
        log(f"bench[overload]: brownout={brownout} → goodput="
            f"{out['interactive_goodput']} tok/s ttft_p99="
            f"{out['ttft_p99_ms']}ms shed={shed} max_level={max_level}")
        return out

    off = run(False)
    on = run(True)
    _set_stage("done")
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": on["interactive_goodput"],
        "unit": "tok/s/chip",
        "vs_baseline": round(on["interactive_goodput"] / 1000.0, 4),
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "overload",
        # The brownout A/B: what graded degradation buys interactive
        # traffic during a storm, and who paid for it.
        "interactive_goodput_off": off["interactive_goodput"],
        "interactive_goodput_on": on["interactive_goodput"],
        "ttft_p99_off_ms": off["ttft_p99_ms"],
        "ttft_p99_on_ms": on["ttft_p99_ms"],
        "shed_batch_total": off["shed_batch"] + on["shed_batch"],
        "shed_interactive_total": (
            off["shed_interactive"] + on["shed_interactive"]
        ),
        "shed_batch_on": on["shed_batch"],
        "shed_interactive_on": on["shed_interactive"],
        "max_brownout_level": on["max_level"],
    }), flush=True)
    os._exit(0)


def _tp_workload(on_tpu: bool) -> None:
    """BENCH_TP_WORKLOAD=1: the GSPMD-sharded serving A/B — one
    synchronized greedy burst served by a tp=1 engine, then the SAME
    burst by a tp=2 engine (params Megatron-sharded, KV head axis
    sharded). Greedy streams must be TOKEN-IDENTICAL between the two
    (the tp-invariance contract the sharded-serving suite pins); a
    mismatch fails the row rather than reporting a wrong-answer
    speedup. On CPU virtual devices the collective overhead dominates,
    so the row is degraded / NOT comparable — it captures the sharded
    engine's step-time trajectory until a real multi-chip TPU window
    lands."""
    import jax

    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_requests = int(os.environ.get("BENCH_REQUESTS", "16" if on_tpu else "8"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "32" if on_tpu else "8"))
    n_slots = int(os.environ.get("BENCH_SLOTS", "8"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "1024" if on_tpu else "256"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "0"))
    devices = jax.devices()
    if len(devices) < 2:
        log(f"bench[tp]: only {len(devices)} device(s) visible — "
            f"cannot A/B tp=2; rerun with 2+ chips or the CPU backend")
        os._exit(4)
    log(f"bench[tp]: model={model} requests={n_requests} "
        f"new_tokens={new_tokens} slots={n_slots} devices={len(devices)}")

    prompt = "The quick brown fox jumps over the lazy dog. " * 3

    def run(tp: int) -> tuple[float, list]:
        _set_stage(f"engine-init-tp{tp}")
        engine = InferenceEngine(
            model, n_slots=n_slots, max_len=max_len,
            tokenizer=ByteTokenizer(),
            window_k=int(os.environ.get("BENCH_WINDOW", "8")),
            pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
            kv_block=kv_block,
            tp=tp, devices=devices[:tp] if tp > 1 else None, seed=0,
        )
        engine.start_sync()
        _set_stage(f"warmup-tp{tp}")
        engine.generate_sync(
            prompt, max_new_tokens=4, temperature=0.0, stop_on_eos=False
        )
        _set_stage(f"measure-tp{tp}")
        t0 = time.time()
        reqs = [
            engine.submit_generate(
                prompt, max_new_tokens=new_tokens, temperature=0.0,
                stop_on_eos=False,
            )
            for _ in range(n_requests)
        ]
        results = [r.future.result(timeout=1800) for r in reqs]
        wall = time.time() - t0
        toks = sum(len(r.token_ids) for r in results)
        engine.stop_sync()
        log(f"bench[tp]: tp={tp} → {toks} tokens in {wall:.2f}s "
            f"({toks / wall:.1f} tok/s)")
        return toks / wall, [r.token_ids for r in results]

    tp1_tps, streams1 = run(1)
    tp2_tps, streams2 = run(2)
    if streams1 != streams2:
        log("bench[tp]: TOKEN MISMATCH between tp=1 and tp=2 — the "
            "tp-invariance contract is broken; refusing to report a "
            "wrong-answer speedup")
        os._exit(5)
    _set_stage("done")
    platform = "tpu" if on_tpu else "cpu"
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(tp2_tps / 2, 2),  # per-CHIP: tp=2 spans two
        "unit": "tok/s/chip",
        "vs_baseline": round(tp2_tps / 2 / 1000.0, 4),
        "platform": platform,
        # CPU virtual devices measure gloo-collective overhead, not ICI:
        # degraded rows never impersonate TPU numbers.
        "degraded": not on_tpu,
        "model": model,
        "workload": "tp_ab",
        "tp1_tps": round(tp1_tps, 2),
        "tp2_tps": round(tp2_tps, 2),
        "tp_speedup": round(tp2_tps / tp1_tps, 3) if tp1_tps else None,
        "token_identical": True,
    }), flush=True)
    os._exit(0)


def _tier_workload(on_tpu: bool) -> None:
    """BENCH_TIER_WORKLOAD=1: disaggregated-tier transfer-leg A/B — the
    SAME prefill-heavy burst served through a 1-prefill + 1-decode
    in-proc pool with the transfer leg pinned to host-bounce, then to
    the device leg. One JSON line carries per-leg transfer latency
    (p50/p95 ms, from the request timelines' tpu.transfer hops), the
    decode-tier cold TTFT per leg, streamed-token identity across legs,
    and the pool's tier_transfers_total{leg,result} counters. The
    acceptance bar: the device leg's transfer p50 strictly below the
    host bounce's on the same workload (CPU fallback rows are marked
    degraded as usual — PCIe/ICI asymmetry only exists on real
    hardware, but the zero-host-copy path must already win on CPU
    because it skips two full plane materializations)."""
    import random

    from gofr_tpu.metrics import new_metrics_manager
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer
    from gofr_tpu.service.replica_pool import EngineReplica, ReplicaPool

    model = os.environ.get("BENCH_MODEL", "llama-tiny")
    n_requests = int(os.environ.get("BENCH_REQUESTS", "8"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "8"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "32"))
    prompt_tokens = int(os.environ.get("BENCH_TIER_PROMPT", "96"))

    metrics = new_metrics_manager()
    metrics.new_counter("app_tpu_tier_transfers_total")
    metrics.new_counter("app_tpu_tier_transfer_bytes_total")
    metrics.new_histogram("app_tpu_tier_transfer_seconds")
    metrics.new_gauge("app_tpu_tier_mode")

    log(f"bench[tier]: model={model} requests={n_requests}/leg "
        f"prompt={prompt_tokens}tok kv_block={kv_block}")
    _set_stage("engine-init")

    def mk():
        eng = InferenceEngine(
            model, n_slots=4, max_len=256, window_k=4, pipeline_depth=1,
            prefill_chunk=32, kv_block=kv_block, auto_prefix=True,
            tokenizer=ByteTokenizer(),
        )
        eng.start_sync()
        return eng

    pf, dc = mk(), mk()
    pool = ReplicaPool(
        [
            EngineReplica("pf", pf, role="prefill"),
            EngineReplica("dc", dc, role="decode"),
        ],
        probe_interval_s=0, hedge_delay_s=300.0,
        rng=random.Random(7), metrics=metrics,
    )

    _SALTS = {
        "host": 0, "device": 101, "dma": 211, "source": 271,
        "warm-host": 53, "warm-device": 157, "warm-dma": 59,
    }

    def prompt(leg: str, i: int) -> list:
        # Distinct per (leg, request): every transfer ships cold
        # content — a collision would dedupe against the decode tier's
        # radix and skip the very leg being measured.
        base = [2 + (i * 7 + _SALTS[leg]) % 200]
        return (base * prompt_tokens)[:prompt_tokens - 1] + [3 + i]

    def run_leg(leg: str) -> dict:
        pool.transfer_leg = leg
        reqs = [
            pool.submit_generate(
                prompt(leg, i), max_new_tokens=new_tokens,
                temperature=0.0,
            )
            for i in range(n_requests)
        ]
        results = [r.future.result(timeout=600) for r in reqs]
        hops = [
            hop
            for r in reqs if r.timeline is not None
            for hop in r.timeline.transfers
        ]
        xfer_ms = sorted(
            (end - start) * 1e3
            for _, _, start, end, result, hop_leg in hops
            if result == "ok" and hop_leg == leg
        )
        ttfts = sorted(r.ttft_s * 1e3 for r in results)
        return {
            "tokens": [list(r.token_ids) for r in results],
            f"transfer_ms_{leg}": {
                "p50": round(_pct(xfer_ms, 0.50), 3),
                "p95": round(_pct(xfer_ms, 0.95), 3),
            },
            f"cold_ttft_{leg}_p50_ms": round(_pct(ttfts, 0.50), 2),
            f"transfers_{leg}": len(xfer_ms),
        }

    def run_source() -> dict:
        """The remote-source pull seam's data path, in-proc: the
        prefill tier exports cached blocks (``export_cached``), stages
        them on the loopback transfer server, the decode tier redeems
        the claim ticket (``dma_fetch``) and applies it
        (``import_payload``) — the full ``/ops/tier-export`` cycle
        minus the HTTP control round-trip."""
        from gofr_tpu.service.dma import dma_fetch, get_transfer_server

        times, hits = [], 0
        for i in range(n_requests):
            ids = prompt("source", i)
            # Populate the prefill tier's radix the way a real source
            # has it populated: by serving the request.
            pf.generate_sync(ids, max_new_tokens=2, temperature=0.0)
            t0 = time.time()
            payload = pf.export_cached(ids, timeout_s=10.0)
            if payload is None:
                continue
            handle = get_transfer_server().offer(payload, src="pf")
            fetched = dma_fetch(
                handle, connect_timeout_s=2.0, read_timeout_s=10.0,
            )
            if dc.import_payload(fetched, wait_s=5.0) == "imported":
                hits += 1
            times.append((time.time() - t0) * 1e3)
        ms = sorted(times)
        return {
            "source_pull_ms": {
                "p50": round(_pct(ms, 0.50), 3),
                "p95": round(_pct(ms, 0.95), 3),
            },
            "source_pulls": len(ms),
            "source_hits": hits,
        }

    _set_stage("warmup")
    # One transfer per leg compiles extract/move (device) and the
    # insert path (host) BEFORE the fence — a steady-state transfer
    # must never hide a recompile (exit 6 below if one does). The dma
    # leg's warm run also brings up the loopback transfer server.
    for warm_leg in ("host", "device", "dma"):
        pool.transfer_leg = warm_leg
        pool.generate_sync(
            prompt(f"warm-{warm_leg}", 0), max_new_tokens=new_tokens,
            temperature=0.0, timeout=600,
        )
    pf.mark_steady_state()
    dc.mark_steady_state()

    _set_stage("measure")
    t0 = time.time()
    host = run_leg("host")
    device = run_leg("device")
    dma = run_leg("dma")
    source = run_source()
    wall = time.time() - t0
    # Prompts differ per leg by design (each leg must transfer COLD
    # content); the legs-move-bytes-not-meaning identity contract is
    # pinned in CI (tests/test_tier_d2d.py) against a fused reference.
    host.pop("tokens")
    device.pop("tokens")
    dma.pop("tokens")
    counters = {}
    for inst in metrics.instruments():
        if inst.name == "app_tpu_tier_transfers_total":
            for key, value in inst.collect().items():
                counters["|".join("=".join(p) for p in key)] = value
    device_fields = _device_resource_fields(dc)
    loop_fields = _loop_fields(dc)
    for eng in (pf, dc):
        _recompile_guard(eng)
    host_p50 = host["transfer_ms_host"]["p50"]
    dev_p50 = device["transfer_ms_device"]["p50"]
    dma_p50 = dma["transfer_ms_dma"]["p50"]
    log(f"bench[tier]: transfer p50 host={host_p50}ms "
        f"device={dev_p50}ms dma={dma_p50}ms "
        f"source_pull p50={source['source_pull_ms']['p50']}ms "
        f"({wall:.2f}s total); device_wins={dev_p50 < host_p50}")
    pf.close()
    dc.close()
    _set_stage("done")
    row = {
        "metric": "tier_transfer_ms_p50_device",
        "value": dev_p50,
        "unit": "ms",
        "vs_baseline": round(
            host_p50 / dev_p50, 3
        ) if dev_p50 else None,
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "tier_legs",
        **{k: v for k, v in host.items()},
        **{k: v for k, v in device.items()},
        **{k: v for k, v in dma.items()},
        **source,
        "device_leg_faster": bool(dev_p50 < host_p50),
        "tier_transfers_total": counters,
        **device_fields,
        **loop_fields,
    }
    print(json.dumps(row), flush=True)
    os._exit(0)


def _spec_workload(on_tpu: bool) -> None:
    """BENCH_SPEC_WORKLOAD=1: n-gram speculation A/B — the SAME
    repeated-text burst (the prompt-lookup-friendly shape: the
    continuation keeps re-walking substrings of the prompt) served by a
    spec=0 engine and a spec=G (BENCH_SPEC_G=2) engine. Since the
    exact-verify redesign (ISSUE 20) the spec window runs the literal
    decode-step program per candidate position, so identity is
    ENFORCED, not reported: any stream divergence vs spec=0 exits 5
    (the BENCH_TP_WORKLOAD idiom) — a diverged run is a correctness
    bug, never a number worth publishing. The JSON line carries both
    throughputs, the speedup, the acceptance series summary
    (mean + ``acc_p50``/``acc_p95`` over per-window tokens-per-step),
    ``host_overhead_ratio_{off,on}`` (the loop profiler's
    host-bookkeeping share — the metric the default-on gate reads,
    since exact verify wins by DISPATCH amortization, not compute),
    the composed ``default_on_gate`` verdict (tok/s strictly up AND
    host overhead not regressing — exactly when
    ``TPU_SPEC_TOKENS=auto`` resolves ON), and the run-over-run
    trajectory vs the newest committed BENCH_*.json row."""
    from gofr_tpu.metrics import new_metrics_manager
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    model = os.environ.get("BENCH_MODEL", "llama-tiny")
    n_requests = int(os.environ.get("BENCH_REQUESTS", "8"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "32"))
    spec_g = int(os.environ.get("BENCH_SPEC_G", "2"))
    # Repeated text: "abcabcabc…" with a per-request rotation — the
    # n-gram draft's best case, and exactly the retrieval/multi-turn
    # shape the prefix cache already targets.
    prompts = [
        ("abcdefgh"[i % 4:] + "abcdefgh" * 12)[:64]
        for i in range(n_requests)
    ]

    log(f"bench[spec]: model={model} requests={n_requests} "
        f"new_tokens={new_tokens} spec_g={spec_g}")
    _set_stage("engine-init")

    def serve(spec_tokens: int) -> tuple:
        metrics = new_metrics_manager()
        metrics.new_histogram("app_tpu_spec_tokens_per_step")
        # Raw acceptance series alongside the bucketed histogram: the
        # scheduler records one tokens-per-live-step value per window;
        # percentiles need the raw samples, not bucket edges.
        acc_series: list = []
        inst = {
            i.name: i for i in metrics.instruments()
        }["app_tpu_spec_tokens_per_step"]
        inner_record = inst.record

        def recording(value, labels):
            acc_series.append(float(value))
            inner_record(value, labels)

        inst.record = recording  # type: ignore[method-assign]
        eng = InferenceEngine(
            model, n_slots=8, max_len=256, window_k=4,
            tokenizer=ByteTokenizer(), spec_tokens=spec_tokens,
            metrics=metrics,
        )
        eng.start_sync()
        eng.generate_sync(
            "warm" * 4, max_new_tokens=2, temperature=0.0,
            stop_on_eos=False,
        )
        eng.mark_steady_state()
        t0 = time.time()
        reqs = [
            eng.submit_generate(
                p, max_new_tokens=new_tokens, temperature=0.0,
                stop_on_eos=False,
            )
            for p in prompts
        ]
        results = [r.future.result(timeout=600) for r in reqs]
        wall = time.time() - t0
        _recompile_guard(eng)
        loop = _loop_fields(eng)
        device = _device_resource_fields(eng)
        eng.close()
        total = sum(len(r.token_ids) for r in results)
        return (
            total / wall,
            sorted(acc_series),
            [list(r.token_ids) for r in results],
            loop,
            device,
        )

    _set_stage("measure")
    plain_tps, _, plain_tokens, loop_off, _ = serve(0)
    spec_tps, acc_series, spec_tokens_out, loop_on, device_on = serve(spec_g)
    diverged = sum(
        1 for a, b in zip(plain_tokens, spec_tokens_out) if a != b
    )
    acceptance = (
        sum(acc_series) / len(acc_series) if acc_series else None
    )
    log(f"bench[spec]: plain={plain_tps:.1f} tok/s "
        f"spec={spec_tps:.1f} tok/s "
        f"acceptance={acceptance if acceptance is None else round(acceptance, 3)} "
        f"diverged={diverged}/{len(plain_tokens)}")
    if diverged:
        # The exact-verify contract is the whole point of default-on:
        # a diverged stream means the verify path stopped reproducing
        # decode numerics. Refuse the row (exit 5, like tp identity).
        log(f"bench[spec]: {diverged}/{len(plain_tokens)} STREAM(S) "
            "DIVERGED from spec=0 — the exact-verify contract is "
            "broken; refusing to report a wrong-answer speedup")
        os._exit(5)
    host_off = loop_off.get("host_overhead_ratio")
    host_on = loop_on.get("host_overhead_ratio")
    tok_s_up = spec_tps > plain_tps
    # "Not regressing": within 5% relative (plus epsilon absolute for
    # near-zero ratios) of the spec=0 run's host-bookkeeping share.
    host_flat = (
        host_off is None or host_on is None
        or host_on <= host_off * 1.05 + 0.005
    )
    _set_stage("done")
    row = {
        "metric": "spec_decode_tokens_per_sec",
        "value": round(spec_tps, 2),
        "unit": "tok/s",
        "vs_baseline": round(spec_tps / plain_tps, 3) if plain_tps else None,
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "spec_ab",
        "spec_g": spec_g,
        "plain_tps": round(plain_tps, 2),
        "spec_tps": round(spec_tps, 2),
        "spec_speedup": round(spec_tps / plain_tps, 3) if plain_tps else None,
        "spec_tokens_per_step": (
            round(acceptance, 3) if acceptance is not None else None
        ),
        "acc_p50": round(_pct(acc_series, 0.50), 3),
        "acc_p95": round(_pct(acc_series, 0.95), 3),
        "spec_identical": True,  # enforced above: divergence exits 5
        "diverged_requests": diverged,
        "host_overhead_ratio_off": host_off,
        "host_overhead_ratio_on": host_on,
        # The two-metric verdict the TPU_SPEC_TOKENS=auto default rides
        # on: flip on only where speculation pays on THIS platform.
        "default_on_gate": {
            "tok_s_up": tok_s_up,
            "host_overhead_flat": host_flat,
            "pass": bool(tok_s_up and host_flat),
        },
        **device_on,
    }
    print(json.dumps(row), flush=True)
    os._exit(0)


def _control_workload(on_tpu: bool) -> None:
    """BENCH_CONTROL_WORKLOAD=1: control-plane A/B — a diurnal ramp
    (one hog tenant's flood swells wave by wave, then recedes) over a
    small queue while well-behaved tenants submit steadily, run with
    the control plane off then on (``TPU_CONTROL_PLANE``). With
    ``slo_availability`` armed, the hog's admission sheds burn ITS
    availability SLO alone, so the per-tenant ladder climbs for the hog
    while everyone else stays at L0 — the isolation the A/B prices.
    Reports per-tenant goodput min/max under both policies, the hog's
    highest ladder level, the predictive loop's scale LEAD TIME (first
    scale-pressure assertion vs the queue actually reaching the
    reactive depth), and the control plane's degraded-signal and
    eval-error counts. Self-contained: paged engine, no profile phase,
    CPU-safe."""
    from gofr_tpu.errors import ErrorTooManyRequests
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_tenants = int(os.environ.get("BENCH_TENANTS", "3"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "16" if on_tpu else "8"))
    n_slots = int(os.environ.get("BENCH_SLOTS", "2"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "256"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "32"))
    queue_tokens = int(os.environ.get("BENCH_QUEUE_TOKENS", "256"))
    # The hog's per-wave submit count is weight x unit over this
    # diurnal shape: quiet shoulders, a rising edge for the predictive
    # loop's trend fit, a saturating plateau, then the ebb that lets
    # the ladder's exit hysteresis run.
    ramp = (0, 1, 2, 4, 4, 2, 1, 0)
    hog_unit = int(os.environ.get("BENCH_HOG_UNIT", "3"))
    predict_depth = float(os.environ.get("BENCH_PREDICT_DEPTH", "6"))

    log(f"bench[control]: model={model} tenants={n_tenants} "
        f"hog_unit={hog_unit} queue_tokens={queue_tokens} "
        f"predict_depth={predict_depth}")

    def run(control: bool) -> dict:
        _set_stage(f"engine-init-control{int(control)}")
        engine = InferenceEngine(
            model, n_slots=n_slots, max_len=max_len,
            tokenizer=ByteTokenizer(),
            window_k=int(os.environ.get("BENCH_WINDOW", "8")),
            pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
            kv_block=kv_block,
            # Small enough that the plateau's flood sheds at admission:
            # those sheds are what burn the hog's availability SLO.
            queue_max_tokens=queue_tokens,
            slo_availability=0.999,
            control_plane=control,
            # Sub-second sustain windows so the per-tenant ladder
            # climbs inside the bench (production defaults are 10s).
            control_tenant_sustain_s=0.05,
            control_tenant_exit_sustain_s=30.0,
            # Short trend window/horizon matched to wave cadence, and
            # no hold-down replay: the lead-time number should reflect
            # the FIRST assertion.
            control_predict_window_s=30.0,
            control_predict_horizon_s=5.0,
            control_predict_depth=predict_depth,
            seed=0,
        )
        engine.start_sync()
        _set_stage(f"warmup-control{int(control)}")
        engine.generate_sync(
            "w" * 8, max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        engine.mark_steady_state()
        _set_stage(f"measure-control{int(control)}")
        hog_prompt = "H" * min(96, engine.max_prompt_tokens - new_tokens - 8)
        t0 = time.time()
        hog_handles = []
        hog_shed = 0
        wb_shed = 0
        wb_results: dict = {name: [] for name in
                            (f"wb-{t}" for t in range(n_tenants))}
        max_level = 0
        t_pressure = None  # first control scale-pressure assertion
        t_reactive = None  # queue first reaches the reactive depth
        for w, weight in enumerate(ramp):
            for i in range(weight * hog_unit):
                try:
                    hog_handles.append(engine.submit_generate(
                        hog_prompt + f" {w:02d}{i:02d}",
                        max_new_tokens=new_tokens, temperature=0.0,
                        stop_on_eos=False, tenant="hog",
                    ))
                except ErrorTooManyRequests:
                    hog_shed += 1
            # The scale-lead-time probe: the predictive loop should
            # assert pressure on the rising edge's TREND, before the
            # depth itself crosses the reactive threshold.
            depth = float(engine._pending.qsize())
            if t_reactive is None and depth >= predict_depth:
                t_reactive = time.time() - t0
            if control and t_pressure is None:
                if engine.control_scale_pressure() == 1:
                    t_pressure = time.time() - t0
            cp = engine._control
            if cp is not None:
                max_level = max(max_level, cp.tenant_level("hog"))
            # One synchronous interactive request per well-behaved
            # tenant per wave: retirements pace the waves and feed the
            # per-tenant burn windows.
            for name in wb_results:
                try:
                    wb_results[name].append(engine.generate_sync(
                        f"tenant {name} wave {w:02d}",
                        max_new_tokens=new_tokens, temperature=0.0,
                        stop_on_eos=False, tenant=name, timeout=1800,
                    ))
                except ErrorTooManyRequests:
                    wb_shed += 1
        for h in hog_handles:
            try:
                h.future.result(timeout=1800)
            except ErrorTooManyRequests:
                # L3 fair-share shed can fail an already-queued hog
                # request at admission re-check; that is the ladder
                # working, not a bench failure.
                hog_shed += 1
        wall = time.time() - t0
        report = engine.control_report()
        _recompile_guard(engine)
        engine.stop_sync()
        wb_tps = {
            name: round(sum(len(r.token_ids) for r in rs) / wall, 2)
            for name, rs in wb_results.items()
        }
        degraded = sorted(
            name for name, s in report.get("signals", {}).items()
            if s.get("status") != "ok"
        )
        out = {
            "wall_s": round(wall, 2),
            "wb_goodput_min": min(wb_tps.values()),
            "wb_goodput_max": max(wb_tps.values()),
            "hog_shed": hog_shed,
            "wb_shed": wb_shed,
            "max_tenant_level": max_level,
            "scale_lead_s": (
                round(t_reactive - t_pressure, 3)
                if t_pressure is not None and t_reactive is not None
                and t_reactive > t_pressure else None
            ),
            "pressure_asserted": t_pressure is not None,
            "degraded_signals": len(degraded),
            "control_passes": int(report.get("passes", 0)),
            "control_eval_errors": int(report.get("eval_errors", 0)),
        }
        log(f"bench[control]: control={control} → wb goodput "
            f"[{out['wb_goodput_min']}, {out['wb_goodput_max']}] tok/s "
            f"hog_shed={hog_shed} wb_shed={wb_shed} "
            f"max_tenant_level={max_level} "
            f"scale_lead_s={out['scale_lead_s']} degraded={degraded}")
        return out

    off = run(False)
    on = run(True)
    _set_stage("done")
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": on["wb_goodput_min"],
        "unit": "tok/s/chip",
        "vs_baseline": round(on["wb_goodput_min"] / 1000.0, 4),
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "control",
        "tenant_count": n_tenants + 1,  # N well-behaved + the hog
        # The control A/B: does the ladder keep the hog's storm off
        # the well-behaved tenants' goodput floor?
        "wb_goodput_min_off": off["wb_goodput_min"],
        "wb_goodput_min_on": on["wb_goodput_min"],
        "wb_goodput_max_off": off["wb_goodput_max"],
        "wb_goodput_max_on": on["wb_goodput_max"],
        "hog_shed_off": off["hog_shed"],
        "hog_shed_on": on["hog_shed"],
        "wb_shed_off": off["wb_shed"],
        "wb_shed_on": on["wb_shed"],
        "max_tenant_level": on["max_tenant_level"],
        "scale_lead_s": on["scale_lead_s"],
        "pressure_asserted": on["pressure_asserted"],
        "degraded_signals": on["degraded_signals"],
        "control_passes": on["control_passes"],
        "control_eval_errors": on["control_eval_errors"],
    }), flush=True)
    os._exit(0)


def _async_workload(on_tpu: bool) -> None:
    """BENCH_ASYNC_WORKLOAD=1: durable async-serving idle-soak A/B
    (serving/async_serving.py) — the same interactive trickle measured
    with the async plane off, then on against a request-topic backlog.
    Poison messages ride along so the redelivery/dead-letter machinery
    is priced too, not just the happy path. The claim the A/B prices:
    async (batch-class) work soaks the idle capacity between
    interactive arrivals WITHOUT moving interactive TTFT — the p95
    pair off/on is the headline, async_tps is what that idle capacity
    bought, redelivered/dead_lettered prove the contract machinery ran.
    Self-contained: paged engine, in-memory broker, CPU-safe."""
    from gofr_tpu.pubsub import InMemoryBroker
    from gofr_tpu.serving.async_serving import AsyncServingPlane
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer
    from gofr_tpu.service.options import RetryConfig

    model = os.environ.get(
        "BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny"
    )
    n_interactive = int(os.environ.get("BENCH_REQUESTS", "12"))
    n_async = int(os.environ.get("BENCH_ASYNC_BACKLOG", "24"))
    n_poison = int(os.environ.get("BENCH_ASYNC_POISON", "2"))
    new_tokens = int(os.environ.get(
        "BENCH_NEW_TOKENS", "16" if on_tpu else "8"
    ))
    n_slots = int(os.environ.get("BENCH_SLOTS", "4"))
    # The trickle's inter-arrival gap IS the idle capacity async soaks.
    arrival_s = float(os.environ.get("BENCH_ARRIVAL_MS", "150")) / 1000.0

    log(f"bench[async]: model={model} interactive={n_interactive} "
        f"backlog={n_async}+{n_poison} poison arrival_ms="
        f"{arrival_s * 1000:.0f}")

    def run(async_on: bool) -> dict:
        _set_stage(f"engine-init-async{int(async_on)}")
        engine = InferenceEngine(
            model, n_slots=n_slots,
            max_len=int(os.environ.get("BENCH_MAX_LEN", "256")),
            tokenizer=ByteTokenizer(),
            window_k=int(os.environ.get("BENCH_WINDOW", "8")),
            pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
            kv_block=int(os.environ.get("BENCH_KV_BLOCK", "32")),
            seed=0,
        )
        engine.start_sync()
        _set_stage(f"warmup-async{int(async_on)}")
        engine.generate_sync(
            "w" * 8, max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        engine.mark_steady_state()
        plane = None
        if async_on:
            broker = InMemoryBroker()
            plane = AsyncServingPlane(
                engine, broker,
                redelivery_max=2, lease_s=60.0, max_inflight=n_slots,
                # Fast backoff so poison reaches the DLQ inside the
                # bench window (production default is 1s base).
                retry=RetryConfig(
                    backoff_s=0.05, jitter=0.5, max_backoff_s=0.5
                ),
                poll_s=0.005,
            )
            for i in range(n_async):
                broker.publish(plane.request_topic, json.dumps({
                    "prompt": f"async soak {i:03d} " + "a" * 24,
                    "max_new_tokens": new_tokens,
                    "temperature": 0.0, "stop_on_eos": False,
                }))
            for i in range(n_poison):
                broker.publish(plane.request_topic, f"poison {i}")
            plane.start()
        _set_stage(f"measure-async{int(async_on)}")
        t0 = time.time()
        ttfts_ms = []
        for i in range(n_interactive):
            r = engine.generate_sync(
                f"interactive trickle {i:03d}",
                max_new_tokens=new_tokens, temperature=0.0,
                stop_on_eos=False, slo_class="interactive", timeout=1800,
            )
            ttfts_ms.append(r.ttft_s * 1000.0)
            time.sleep(arrival_s)
        async_tokens = 0
        replies = 0
        counters: dict = {}
        if plane is not None:
            # Soak until the backlog fully drains (replied or parked).
            drain_deadline = time.time() + float(
                os.environ.get("BENCH_ASYNC_DRAIN_S", "300")
            )
            while (
                time.time() < drain_deadline
                and plane.broker.size(plane.request_topic) > 0
            ):
                time.sleep(0.02)
            wall = time.time() - t0
            for m in plane.broker.peek_all(plane.reply_topic):
                replies += 1
                async_tokens += len(
                    json.loads(m.value).get("token_ids") or []
                )
            counters = dict(plane.counters)
            plane.stop(drain_s=10.0)
        else:
            wall = time.time() - t0
        ttfts_ms.sort()
        p95 = ttfts_ms[min(len(ttfts_ms) - 1, int(0.95 * len(ttfts_ms)))]
        _recompile_guard(engine)
        engine.stop_sync()
        out = {
            "wall_s": round(wall, 2),
            "ttft_p95_ms": round(p95, 2),
            "async_tps": round(async_tokens / wall, 2) if wall > 0 else 0.0,
            "async_replies": replies,
            "redelivered": int(counters.get("redelivered", 0)),
            "dead_lettered": int(counters.get("dead_lettered", 0)),
        }
        log(f"bench[async]: async={async_on} → ttft_p95="
            f"{out['ttft_p95_ms']}ms async_tps={out['async_tps']} "
            f"replies={replies} redelivered={out['redelivered']} "
            f"dead_lettered={out['dead_lettered']}")
        return out

    off = run(False)
    on = run(True)
    _set_stage("done")
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": on["async_tps"],
        "unit": "tok/s/chip",
        "vs_baseline": round(on["async_tps"] / 1000.0, 4),
        "platform": "tpu" if on_tpu else "cpu",
        "degraded": not on_tpu,
        "model": model,
        "workload": "async",
        # The idle-soak A/B: async throughput bought from idle capacity,
        # priced against the interactive-TTFT pair it must not move.
        "async_tps": on["async_tps"],
        "interactive_ttft_p95_off_ms": off["ttft_p95_ms"],
        "interactive_ttft_p95_on_ms": on["ttft_p95_ms"],
        "redelivered": on["redelivered"],
        "dead_lettered": on["dead_lettered"],
        "async_replies": on["async_replies"],
        "async_backlog": n_async + n_poison,
        "interactive_requests": n_interactive,
    }), flush=True)
    os._exit(0)


def main() -> None:
    # One process: it owns the chip from here to exit, and it fails where
    # there is none. A CPU number is never printed under a device metric.
    from gofr_tpu.compile_cache import enable_compile_cache

    _set_stage("jax-init")
    import jax

    enable_compile_cache()
    device = jax.devices()[0]
    platform = device.platform
    if platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU, JAX found {len(jax.devices())} × "
            f"{platform!r} ({device.device_kind!r}); no result"
        )
    peaks = device_peaks(device.device_kind)
    _set_stage("config")
    on_tpu = True
    if os.environ.get("BENCH_PREFIX_WORKLOAD", "") in ("1", "true", "yes"):
        _prefix_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_TP_WORKLOAD", "") in ("1", "true", "yes"):
        _tp_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_LOOP_WORKLOAD", "") in ("1", "true", "yes"):
        _loop_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_TENANT_WORKLOAD", "") in ("1", "true", "yes"):
        _tenant_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_OVERLOAD_WORKLOAD", "") in ("1", "true", "yes"):
        _overload_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_TIER_WORKLOAD", "") in ("1", "true", "yes"):
        _tier_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_SPEC_WORKLOAD", "") in ("1", "true", "yes"):
        _spec_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_CONTROL_WORKLOAD", "") in ("1", "true", "yes"):
        _control_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    if os.environ.get("BENCH_ASYNC_WORKLOAD", "") in ("1", "true", "yes"):
        _async_workload(on_tpu)
        return  # unreachable (os._exit) — keeps the control flow obvious
    model = os.environ.get("BENCH_MODEL", "llama-1b" if on_tpu else "llama-tiny")
    n_requests = int(os.environ.get("BENCH_REQUESTS", "64"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
    n_slots = int(os.environ.get("BENCH_SLOTS", "32"))
    max_len = int(os.environ.get("BENCH_MAX_LEN", "1024"))
    quant = os.environ.get("BENCH_QUANT", "int8" if on_tpu else "")
    if quant.lower() in ("none", "0"):
        quant = ""
    kv_quant = os.environ.get("BENCH_KV_QUANT", "")
    if kv_quant.lower() in ("none", "0"):
        kv_quant = ""
    spec_tokens = int(os.environ.get("BENCH_SPEC", "0"))
    kv_block = int(os.environ.get("BENCH_KV_BLOCK", "0"))
    # TPU default: mega windows ON (m=8) — the dispatch-RTT amortizer is
    # the production throughput configuration; BENCH_MEGA=0 restores the
    # streaming-granularity pipelined mode (the pre-r4 campaign rows).
    mega = int(os.environ.get("BENCH_MEGA", "8" if on_tpu else "0"))
    # Multi-LoRA workload: BENCH_LORA=N loads N random rank-BENCH_LORA_RANK
    # adapters and assigns requests round-robin over (base + adapters) —
    # measures the per-slot gather + rank-einsum cost of heterogeneous
    # adapter batches against the same config with BENCH_LORA=0.
    n_lora = int(os.environ.get("BENCH_LORA", "0"))
    lora_rank = int(os.environ.get("BENCH_LORA_RANK", "16"))

    log(f"bench: platform={platform} model={model} requests={n_requests} "
        f"new_tokens={new_tokens} slots={n_slots} quant={quant or 'bf16'} "
        f"kv_quant={kv_quant or 'bf16'} spec={spec_tokens} "
        f"kv_block={kv_block} mega={mega} lora={n_lora}")

    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    _set_stage("engine-init")
    t0 = time.time()
    engine = InferenceEngine(
        model, n_slots=n_slots, max_len=max_len, tokenizer=ByteTokenizer(),
        window_k=int(os.environ.get("BENCH_WINDOW", "8")),
        pipeline_depth=int(os.environ.get("BENCH_DEPTH", "2")),
        quant=quant,
        kv_quant=kv_quant,
        spec_tokens=spec_tokens,
        kv_block=kv_block,
        mega_windows=mega,
        prefill_depth=int(os.environ.get("BENCH_PREFILL_DEPTH", "1")),
        lora_slots=n_lora,
        lora_rank=lora_rank,
    )
    engine.start_sync()
    log(f"engine up in {time.time() - t0:.1f}s")
    adapters = [""]
    if n_lora:
        import jax as _jax

        from gofr_tpu.models.transformer import lora_dims

        _set_stage("lora-load")
        for ai in range(n_lora):
            leaves = {}
            for ti, t in enumerate(("wq", "wk", "wv", "wo")):
                d_in, d_out = lora_dims(engine.cfg, t)
                k1, k2 = _jax.random.split(
                    _jax.random.fold_in(_jax.random.PRNGKey(1000 + ai), ti),
                    2,
                )
                leaves[t] = (
                    0.02 * _jax.random.normal(
                        k1, (engine.cfg.n_layers, d_in, lora_rank)
                    ),
                    0.02 * _jax.random.normal(
                        k2, (engine.cfg.n_layers, lora_rank, d_out)
                    ),
                )
            engine.load_lora(f"bench-{ai}", leaves)
            adapters.append(f"bench-{ai}")
        log(f"loaded {n_lora} rank-{lora_rank} adapters; requests cycle "
            f"over base + adapters")

    prompt = "The quick brown fox jumps over the lazy dog. " * 3  # ~135 bytes

    # Device profile BEFORE the scheduler starts (doubles as compile
    # warmup): per-window device time vs fetch RTT, achieved HBM GB/s vs
    # peak — so the throughput number below is attributable (VERDICT r1
    # weak #4: "nobody knows where it goes").
    _set_stage("profile")
    t0 = time.time()
    engine.stop_sync()
    prof = engine.profile_decode(n_windows=8)
    engine.start_sync()
    step_ms = prof["step_s"] * 1e3
    pbytes = engine.param_bytes()
    peak_gbps = peaks["hbm_gbps"]
    gbps = pbytes / prof["step_s"] / 1e9
    device_bound_tps = n_slots / prof["step_s"]
    log(f"profile: decode window({engine.window_k} steps)="
        f"{prof['window_s'] * 1e3:.1f}ms → step={step_ms:.2f}ms; "
        f"host<->device rtt={prof['rtt_s'] * 1e3:.1f}ms; "
        f"prefill chunk({engine.prefill_batch}x{engine.prefill_chunk})="
        f"{prof['prefill_s'] * 1e3:.1f}ms")
    log(f"profile: weight stream {pbytes / 1e9:.2f} GB/step → "
        f"{gbps:.0f} GB/s = {100 * gbps / peak_gbps:.0f}% of "
        f"{peak_gbps:.0f} GB/s peak (weight-stream bound: "
        f"{peak_gbps * 1e9 / pbytes * n_slots:.0f} tok/s; device-bound: "
        f"{device_bound_tps:.0f} tok/s)")

    # Decode-attention path A/B (kernel grid vs fused dense) at the real
    # serving shapes and kv dtype — answers GOFR_TPU_FLASH_DECODE's
    # question from one run. Kernel probe only where it compiles natively
    # (interpret mode off-TPU is meaninglessly slow). Helper scope so the
    # probe tensors (GB-scale at 8B/8k shapes) free before the measured run.
    if on_tpu:
        _decode_attn_ab(engine, n_slots, kv_quant)
        _prefill_attn_ab(engine, n_slots, kv_quant)
    log(f"profile in {time.time() - t0:.1f}s")

    # Warmup: compile the real prefill bucket + steady-state decode path.
    _set_stage("warmup")
    t0 = time.time()
    engine.generate_sync(prompt, max_new_tokens=4, temperature=0.0, stop_on_eos=False)
    log(f"warmup (compile) in {time.time() - t0:.1f}s")
    # Warm-up fence: every serving program the measured run will touch
    # is compiled; a compile past this point serializes the measurement
    # behind XLA and is a fixed-shape bug — exit 6 (no JSON) below.
    engine.mark_steady_state()

    # Measured run: n_requests concurrent, engine batches them over n_slots.
    # BENCH_ARRIVAL_MS staggers submissions (0 = one synchronized burst,
    # which quantizes retirements into waves and understates continuous
    # batching); BENCH_TOKEN_SPREAD varies budgets ±fraction so slots
    # retire and refill independently, the steady state real serving
    # lives in.
    import random

    # The TPU default workload is STEADY-STATE (staggered arrivals, varied
    # budgets): a synchronized burst quantizes retirements into waves and
    # the end-to-end number divides by ramp/drain phases, understating
    # continuous batching and confounding round-over-round deltas
    # (VERDICT r3 #10). BENCH_ARRIVAL_MS=0 BENCH_TOKEN_SPREAD=0 restores
    # the burst workload for A/Bs against pre-r4 campaign rows.
    arrival_ms = float(
        os.environ.get("BENCH_ARRIVAL_MS", "25" if on_tpu else "0")
    )
    spread = float(
        os.environ.get("BENCH_TOKEN_SPREAD", "0.5" if on_tpu else "0")
    )
    rng = random.Random(0)
    _set_stage("measure")
    t0 = time.time()
    reqs = []
    for i in range(n_requests):
        if arrival_ms > 0 and i:
            time.sleep(arrival_ms / 1e3)
        nt = new_tokens
        if spread > 0:
            nt = max(8, int(new_tokens * (1 - spread + 2 * spread * rng.random())))
        reqs.append(engine.submit_generate(
            prompt, max_new_tokens=nt, temperature=0.0, stop_on_eos=False,
            adapter=adapters[i % len(adapters)],
        ))
    results = [r.future.result(timeout=1800) for r in reqs]
    measure_wall = time.time() - t0

    total_tokens = sum(len(r.token_ids) for r in results)
    tps = total_tokens / measure_wall
    ttfts = sorted(r.ttft_s * 1e3 for r in results)
    p50 = statistics.median(ttfts)
    p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
    # Tail-latency fields for the JSON line: per-request TTFT and
    # inter-token latency p50/p95/p99 — BENCH_* trajectories must
    # capture the tail, not just throughput.
    latency = _latency_fields(results)

    log(f"generated {total_tokens} tokens in {measure_wall:.2f}s "
        f"→ {tps:.1f} tok/s/chip end-to-end")
    log(f"ITL p50={latency['itl_p50']}ms p95={latency['itl_p95']}ms "
        f"p99={latency['itl_p99']}ms (per-request mean gap between "
        f"generated tokens)")
    workload = "burst"
    steady_tps = None
    if arrival_ms > 0 or spread > 0:
        # Steady-state estimate for staggered runs: the overall number
        # above divides by the ramp-up and drain phases too, understating
        # continuous batching. Use the middle half of the completion
        # timeline (25th→75th percentile completion) — and REPORT it as
        # the headline value: it is the number a loaded replica actually
        # sustains (VERDICT r3 #10). The end-to-end rate stays in the
        # JSON as e2e_tps for cross-checking.
        comps = sorted(
            (q.enqueued_at + r.duration_s, len(r.token_ids))
            for q, r in zip(reqs, results)
        )
        lo, hi = comps[len(comps) // 4][0], comps[3 * len(comps) // 4][0]
        mid_tokens = sum(n for t, n in comps if lo < t <= hi)
        if hi > lo and mid_tokens:
            workload = "steady"
            steady_tps = mid_tokens / (hi - lo)
            log(f"steady-state (middle half of completions): "
                f"{steady_tps:.1f} tok/s/chip — reported as the headline "
                f"value; NOT comparable to burst rows")
        else:
            # Label must not claim steady when the value is end-to-end —
            # harvesters compare JSON lines by workload.
            workload = "steady-degenerate-e2e"
            log("steady-state window degenerate (too few/fast completions)"
                " — falling back to the end-to-end rate")
    log(f"TTFT p50={p50:.1f}ms p99={p99:.1f}ms (includes queueing behind "
        f"{n_requests} concurrent requests on {n_slots} slots)")

    # Unloaded TTFT: sequential single requests against an idle engine —
    # the honest latency number (north star: p50 < 50ms, BASELINE.json).
    _set_stage("unloaded-ttft")
    unloaded = []
    for _ in range(5):
        r = engine.generate_sync(
            prompt, max_new_tokens=2, temperature=0.0, stop_on_eos=False
        )
        unloaded.append(r.ttft_s * 1e3)
    log(f"unloaded TTFT p50={statistics.median(unloaded):.1f}ms "
        f"(min={min(unloaded):.1f} max={max(unloaded):.1f}, "
        f"short prompt, empty queue)")

    device_fields = _device_resource_fields(engine)
    loop_fields = _loop_fields(engine)
    _recompile_guard(engine)
    engine.stop_sync()
    _set_stage("done")

    # platform/degraded: a CPU fallback number must never impersonate the
    # TPU tok/s/chip artifact (VERDICT r2 weak #3).
    headline = steady_tps if steady_tps is not None else tps
    row = {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(headline, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(headline / 1000.0, 4),
        "platform": platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "model": model,
        "workload": workload,
        "e2e_tps": round(tps, 2),
        **latency,
        **device_fields,
        **loop_fields,
        **({"lora": n_lora} if n_lora else {}),
    }
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
