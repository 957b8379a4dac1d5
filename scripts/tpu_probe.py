"""Decode-step component probe (run on the real chip, after bench).

Answers "where do the 12.6 ms/step go?" (round-3 profile: llama-1b int8,
32 slots → step 12.64 ms vs a ~2.5 ms roofline estimate: 1.5 ms int8
weight stream + ~0.9 ms bf16 cache reads + ~0.4 ms MXU). Times jitted
variants of the decode step at the exact serving shapes, each wrapped in a
lax.scan of K steps per dispatch so the per-dispatch host↔device round
trip amortizes out:

  * full        — the engine's decode step (matmuls + attention + argmax)
  * noattn      — attention monkeypatched to zeros (isolates matmul +
                  cache-write cost)
  * matmul-only — the 22-layer int8 einsum stack alone, no cache at all
                  (isolates the weight stream: if this alone is ~8 ms the
                  int8→bf16 convert is materializing weight copies in HBM)
  * attn-only   — decode attention alone over the full cache, dense vs
                  pallas kernel
  * dtypes      — bf16 vs int8 vs int4 full step

Usage:  python scripts/tpu_probe.py [model] [n_slots] [max_len]
Prints one line per probe: name, ms/step, implied tok/s at n_slots.
"""

from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

MODEL = sys.argv[1] if len(sys.argv) > 1 else "llama-1b"
SLOTS = int(sys.argv[2]) if len(sys.argv) > 2 else 32
MAX_LEN = int(sys.argv[3]) if len(sys.argv) > 3 else 1024
K = 8  # steps per dispatch
REPS = 4  # dispatches per timing


def probe(name, fn, *args):
    try:
        jax.block_until_ready(fn(*args))  # compile
        t0 = time.perf_counter()
        out = None
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        per_step = (time.perf_counter() - t0) / REPS / K * 1e3
        print(
            f"probe: {name:<28} {per_step:8.3f} ms/step  "
            f"→ {SLOTS / per_step * 1e3:7.0f} tok/s @ {SLOTS} slots",
            flush=True,
        )
        return per_step
    except Exception as exc:  # noqa: BLE001 — probes are advisory
        print(f"probe: {name:<28} FAILED: {exc!r}", flush=True)
        return None


def main() -> None:
    import gofr_tpu.models.transformer as tr
    from gofr_tpu.models.registry import get_model
    from gofr_tpu.ops.kv_cache import KVCache
    from gofr_tpu.ops.quant import quantize_params

    from gofr_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        # Every line this prints is a device timing.
        raise SystemExit(
            f"probe: needs a TPU, JAX found {device.platform!r}; no result"
        )
    spec = get_model(MODEL)
    cfg = spec.config
    max_len = min(MAX_LEN, cfg.max_len)
    print(
        f"probe: model={MODEL} slots={SLOTS} max_len={max_len} "
        f"K={K} platform={device.platform} device_kind={device.device_kind}",
        flush=True,
    )

    t0 = time.time()
    params8 = _init_quant(spec, cfg, "int8")
    print(f"probe: int8 params in {time.time() - t0:.1f}s", flush=True)

    cache = KVCache.create(
        cfg.n_layers, SLOTS, max_len, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    )
    # Warm cache: pretend every slot holds a half-full sequence.
    cache = cache._replace(
        lengths=jnp.full((SLOTS,), max_len // 2, jnp.int32)
    )
    tokens = jnp.ones((SLOTS,), jnp.int32)
    active = jnp.ones((SLOTS,), bool)

    def window(params, tokens, cache):
        def body(carry, _):
            tokens, cache = carry
            logits, cache = tr.transformer_decode_step(
                params, tokens, cache, active, cfg
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache), None

        (tokens, cache), _ = jax.lax.scan(body, (tokens, cache), length=K)
        return tokens, cache.lengths

    full = jax.jit(window)
    base = probe("full int8 (argmax)", full, params8, tokens, cache)

    # --- attention monkeypatched out (still writes K/V into the cache).
    real_attn = tr.decode_attention
    tr.decode_attention = (
        lambda q, ck, cv, lens, **kw: jnp.zeros_like(q)
    )
    try:
        probe("int8 attention-zeroed", jax.jit(window), params8, tokens, cache)
    finally:
        tr.decode_attention = real_attn

    # --- matmul stack only: exact decode einsums, no cache, no attention.
    def matmul_window(params, x0):
        lhd = cfg.n_heads * cfg.head_dim
        kvd = cfg.n_kv_heads * cfg.head_dim

        def step(x, _):
            def body(x, lp):
                h = tr.rms_norm(x[:, None, :], lp["attn_norm"], cfg.norm_eps)[:, 0]
                q = tr._wein("bd,dh->bh", h, lp["wq"])
                k = tr._wein("bd,dh->bh", h, lp["wk"])
                v = tr._wein("bd,dh->bh", h, lp["wv"])
                attn = (
                    q + jnp.tile(k, (1, lhd // kvd)) + jnp.tile(v, (1, lhd // kvd))
                )
                x = x + tr._wein("bh,hd->bd", attn, lp["wo"])
                h = tr.rms_norm(x[:, None, :], lp["mlp_norm"], cfg.norm_eps)
                ffn = tr._ffn_dense(h, lp, cfg)
                return x + ffn[:, 0], None

            x, _ = jax.lax.scan(body, x, params["layers"])
            x = tr.rms_norm(x[:, None, :], params["final_norm"], cfg.norm_eps)[:, 0]
            logits = tr._wein("bd,dv->bv", x, params["lm_head"])
            return x * 0.999 + logits[:, :1] * 1e-6, None

        x, _ = jax.lax.scan(step, x0, length=K)
        return x

    x0 = jax.random.normal(jax.random.PRNGKey(2), (SLOTS, cfg.d_model), cfg.dtype)
    probe("matmul-stack int8", jax.jit(matmul_window), params8, x0)

    # --- attention alone at serving shapes, chained per dispatch.
    from gofr_tpu.ops.attention import decode_attention

    q0 = jax.random.normal(
        jax.random.PRNGKey(1), (SLOTS, cfg.n_heads, cfg.head_dim), cfg.dtype
    )
    kc, vc = cache.k[0], cache.v[0]

    def attn_window(q, kern):
        def body(q, _):
            o = decode_attention(q, kc, vc, cache.lengths, kernel=kern)
            return o * 0.999, None

        q, _ = jax.lax.scan(body, q, length=K * cfg.n_layers)
        return q

    for kern, nm in ((False, "dense"), (True, "kernel")):
        t = probe(
            f"decode-attn[{nm}] full stack",
            jax.jit(partial(attn_window, kern=kern)), q0,
        )

    # --- weight-dtype variants of the full window.
    del params8
    t0 = time.time()
    params_bf16 = jax.jit(lambda k: spec.init(k, cfg))(jax.random.PRNGKey(0))
    print(f"probe: bf16 params in {time.time() - t0:.1f}s", flush=True)
    probe("full bf16", full, params_bf16, tokens, cache)
    # Dispatch-cost probe (BEFORE the int4 quantize donates params_bf16):
    # how long does ONE jit call hold the host thread (async dispatch
    # return — NOT device completion)? The serving scheduler issues one
    # window call per cycle; if a dispatch holds the thread for a full
    # round trip, the cycle floor is that round trip regardless of pipeline depth,
    # and overlapping dispatch with processing in separate threads is
    # the fix.
    for burst in (1, 4):
        t0 = time.perf_counter()
        outs = [full(params_bf16, tokens, cache) for _ in range(burst)]
        t_disp = (time.perf_counter() - t0) / burst * 1e3
        jax.block_until_ready(outs[-1])
        t_total = (time.perf_counter() - t0) * 1e3
        print(
            f"probe: dispatch burst={burst}: {t_disp:.1f} ms/call host-"
            f"blocked, {t_total:.1f} ms to completion",
            flush=True,
        )
    params4 = jax.jit(
        partial(quantize_params, mode="int4"), donate_argnums=(0,)
    )(params_bf16)
    probe("full int4", full, params4, tokens, cache)
    if base is not None:
        print(
            f"probe: roofline check — int8 step {base:.2f} ms; int8 weight "
            f"bytes alone need ~1.5 ms at 819 GB/s",
            flush=True,
        )


def _init_quant(spec, cfg, mode):
    from gofr_tpu.serving.engine import InferenceEngine

    eng = InferenceEngine.__new__(InferenceEngine)
    eng._jax, eng._jnp = jax, jnp
    eng.spec, eng.cfg, eng.quant = spec, cfg, mode
    return InferenceEngine._init_llm_quantized(eng, 0)


if __name__ == "__main__":
    main()
