"""Int8 KV cache (VERDICT r2 next #9): quantized-cache attention matches
the bf16 cache within quantization tolerance, at every level — the
quantize/dequant ops, the flash kernels (interpret mode), the decode/
prefill steps, and the serving engine end to end."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.registry import get_model
from gofr_tpu.models.transformer import (
    transformer_decode_step,
    transformer_prefill_chunk,
)
from gofr_tpu.ops.attention import cache_chunk_attention, decode_attention
from gofr_tpu.ops.kv_cache import KVCache, quantize_kv
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer


def test_quantize_kv_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 64), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (4, 2)
    recon = q.astype(jnp.float32) * s[..., None]
    np.testing.assert_allclose(recon, x, atol=float(jnp.abs(x).max()) / 120)


def test_kv_cache_create_int8_halves_bytes():
    bf16 = KVCache.create(2, 4, 128, 2, 64)
    q8 = KVCache.create(2, 4, 128, 2, 64, quant="int8")
    assert q8.quantized and not bf16.quantized
    assert q8.k.dtype == jnp.int8
    assert q8.hbm_bytes() < bf16.hbm_bytes()
    with pytest.raises(ValueError):
        KVCache.create(2, 4, 128, 2, 64, quant="int4")


def _filled_cache(key, b, n_kv, max_len, hd, lengths):
    """bf16 cache + its int8 twin holding the same values."""
    k = jax.random.normal(key, (b, n_kv, max_len, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.float32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    rep8 = lambda s: jnp.broadcast_to(  # noqa: E731
        s[:, :, None, :], (b, n_kv, 8, max_len)
    ).astype(jnp.float32)
    return (
        k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        kq, vq, rep8(ks), rep8(vs), jnp.asarray(lengths, jnp.int32),
    )


@pytest.mark.parametrize("kernel", [False, True])
def test_int8_decode_attention_matches_bf16(kernel):
    b, n_kv, max_len, hd, n_heads = 4, 2, 128, 64, 4
    k, v, kq, vq, ks, vs, lens = _filled_cache(
        jax.random.PRNGKey(2), b, n_kv, max_len, hd, [5, 64, 128, 1]
    )
    q = jax.random.normal(jax.random.PRNGKey(3), (b, n_heads, hd), jnp.bfloat16)
    want = decode_attention(q, k, v, lens, kernel=kernel)
    got = decode_attention(
        q, kq, vq, lens, k_scale=ks, v_scale=vs, kernel=kernel
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=0.05, rtol=0.05,
    )


@pytest.mark.parametrize("kernel", [False, True])
def test_int8_chunk_attention_matches_bf16(kernel):
    S, n_kv, max_len, hd, n_heads, P, c = 4, 2, 128, 64, 4, 2, 16
    k, v, kq, vq, ks, vs, _ = _filled_cache(
        jax.random.PRNGKey(4), S, n_kv, max_len, hd, [0] * S
    )
    q = jax.random.normal(
        jax.random.PRNGKey(5), (P, c, n_heads, hd), jnp.bfloat16
    )
    slots = jnp.asarray([0, 2], jnp.int32)
    starts = jnp.asarray([8, 32], jnp.int32)
    lens = jnp.asarray([16, 9], jnp.int32)
    # One entry of the stacked planes the prefill step hands in.
    layer = jnp.int32(0)
    want = cache_chunk_attention(
        q, k[None], v[None], slots, starts, lens, layer=layer, kernel=kernel
    )
    got = cache_chunk_attention(
        q, kq[None], vq[None], slots, starts, lens, layer=layer,
        k_scale=ks[None], v_scale=vs[None], kernel=kernel,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=0.05, rtol=0.05,
    )


def test_prefill_chunk_and_decode_steps_with_int8_cache():
    """Full steps write quantized K/V + scales and stay numerically close
    to the bf16-cache steps."""
    spec = get_model("llama-tiny")
    cfg = spec.config
    params = spec.init(jax.random.PRNGKey(0), cfg)
    S, max_len = 2, 64
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    mk = lambda q: KVCache.create(  # noqa: E731
        cfg.n_layers, S, max_len, cfg.n_kv_heads, cfg.head_dim,
        cfg.dtype, quant=q,
    )
    out = {}
    for mode in ("", "int8"):
        cache = mk(mode)
        logits, cache = transformer_prefill_chunk(
            params, tokens, cache,
            jnp.asarray([0], jnp.int32), jnp.asarray([0], jnp.int32),
            jnp.asarray([8], jnp.int32), cfg,
        )
        cache = cache._replace(lengths=cache.lengths.at[0].set(8))
        step_logits, cache = transformer_decode_step(
            params, jnp.asarray([9, 0], jnp.int32), cache,
            jnp.asarray([True, False]), cfg,
        )
        out[mode] = (np.asarray(logits), np.asarray(step_logits))
        if mode == "int8":
            assert cache.k.dtype == jnp.int8
            # Prompt positions got real scales; untouched tail stays 1.0.
            assert float(jnp.max(cache.k_s[0, 0, 0, 0, :8])) < 1.0
            assert float(cache.k_s[0, 0, 0, 0, -2]) == 1.0
    scale = np.abs(out[""][0]).max()
    np.testing.assert_allclose(
        out["int8"][0], out[""][0], atol=0.05 * scale, rtol=0.1
    )
    np.testing.assert_allclose(
        out["int8"][1][0], out[""][1][0], atol=0.05 * scale, rtol=0.1
    )


def test_engine_serves_with_int8_kv_cache():
    eng = InferenceEngine(
        "llama-tiny", n_slots=2, max_len=64, tokenizer=ByteTokenizer(),
        kv_quant="int8",
    )
    assert eng.cache.quantized
    eng.start_sync()
    try:
        r1 = eng.generate_sync(
            "kv quant", max_new_tokens=8, temperature=0.0, stop_on_eos=False
        )
        r2 = eng.generate_sync(
            "kv quant", max_new_tokens=8, temperature=0.0, stop_on_eos=False
        )
    finally:
        eng.stop_sync()
    assert len(r1.token_ids) == 8
    assert r1.token_ids == r2.token_ids  # deterministic across slots/steps


def test_engine_int8_kv_from_config_with_mesh():
    """TPU_KV_QUANT composes with TPU_MESH_TP (+ weight int8): the full
    production stack boots and generates."""
    from gofr_tpu.config import MockConfig

    eng = InferenceEngine.from_config(MockConfig({
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2", "TPU_MAX_LEN": "64",
        "TPU_MESH_TP": "2", "TPU_QUANT": "int8", "TPU_KV_QUANT": "int8",
    }))
    assert eng.cache.quantized and eng.quant == "int8"
    assert "tp" in str(eng.cache.k_s.sharding.spec)
    eng.start_sync()
    try:
        r = eng.generate_sync(
            "all together", max_new_tokens=6, temperature=0.0,
            stop_on_eos=False,
        )
    finally:
        eng.stop_sync()
    assert len(r.token_ids) == 6
