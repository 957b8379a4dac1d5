"""Sliding-window attention (Mistral): every query attends only the
last `sliding_window` positions. Oracles: the torch MistralForCausalLM
with an ACTIVE window (seq > window), window >= seq == full attention,
and cross-path consistency — the engine's chunked-prefill + split-decode
stream must reproduce a step-by-step full-forward greedy rollout."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.registry import ModelSpec, get_model, register_model
from gofr_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_forward,
)

SWA_CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_len=128, rope_theta=10000.0, dtype=jnp.float32,
    sliding_window=8,
)


def test_window_geq_seq_equals_full():
    """A window at least as long as the sequence is exactly full causal
    attention."""
    full = dataclasses.replace(SWA_CFG, sliding_window=0)
    wide = dataclasses.replace(SWA_CFG, sliding_window=64)
    params = init_transformer(jax.random.PRNGKey(0), full)
    toks = jnp.arange(1, 33, dtype=jnp.int32)[None, :]
    lf = np.asarray(transformer_forward(params, toks, full))
    lw = np.asarray(transformer_forward(params, toks, wide))
    np.testing.assert_allclose(lf, lw, atol=1e-6)
    # An ACTIVE window must change late-position logits.
    nw = np.asarray(transformer_forward(params, toks, SWA_CFG))
    assert not np.allclose(lf[:, -1], nw[:, -1], atol=1e-3)
    # ...but positions inside the window are identical.
    np.testing.assert_allclose(lf[:, :8], nw[:, :8], atol=1e-6)


def test_swa_matches_torch_mistral_oracle():
    """Active-window logit parity against MistralForCausalLM (seq 24,
    window 8): pins the (q_pos-window, q_pos] masking convention."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from gofr_tpu.serving.hf_loader import config_from_hf, load_hf_llama

    import tempfile

    with tempfile.TemporaryDirectory() as path:
        hf_cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            rope_theta=10000.0, rms_norm_eps=1e-6, sliding_window=8,
            tie_word_embeddings=False, attention_dropout=0.0,
        )
        torch.manual_seed(5)
        model = transformers.MistralForCausalLM(hf_cfg)
        model.eval()
        model.save_pretrained(path, safe_serialization=True)

        cfg = config_from_hf(path)
        assert cfg.sliding_window == 8
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = load_hf_llama(path, cfg)
        rng = np.random.default_rng(0)
        tokens = rng.integers(1, 128, size=(1, 24)).astype(np.int32)
        ours = np.asarray(
            transformer_forward(params, jnp.asarray(tokens), cfg)
        )
        with torch.no_grad():
            theirs = model(
                torch.tensor(tokens, dtype=torch.long)
            ).logits.numpy()
        np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def _rollout_reference(params, cfg, prompt_ids, n_new):
    """Greedy rollout via repeated FULL forwards — the cross-path oracle
    for the engine's chunked-prefill + split-decode stream."""
    ids = list(prompt_ids)
    for _ in range(n_new):
        logits = transformer_forward(
            params, jnp.asarray([ids], dtype=jnp.int32), cfg
        )
        ids.append(int(np.asarray(logits)[0, -1].argmax()))
    return ids[len(prompt_ids):]


def test_engine_swa_matches_full_forward_rollout():
    """The serving stream (chunked prefill, split-cache decode) must
    equal the full-forward greedy rollout when generation CROSSES the
    window boundary."""
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    params = init_transformer(jax.random.PRNGKey(3), SWA_CFG)
    register_model(ModelSpec(
        name="swa-test", family="llm", config=SWA_CFG,
        init=lambda key, c: params,
    ))
    prompt = [ord(c) for c in "sliding windows"]  # 15 tokens > window 8
    want = _rollout_reference(params, SWA_CFG, prompt, 12)
    # kv_block=8 makes the paged pool's block axis equal the window — the
    # shape that used to zero the window in decode_attention (the pool's
    # shape[2] is the BLOCK axis, not capacity) and attend beyond it.
    for kv_block in (0, 8):
        eng = InferenceEngine(
            "swa-test", n_slots=2, max_len=128, window_k=4,
            prefill_chunk=16, tokenizer=ByteTokenizer(), params=params,
            kv_block=kv_block,
        )
        eng.start_sync()
        try:
            got = eng.generate_sync(
                prompt, max_new_tokens=12, temperature=0.0,
                stop_on_eos=False, timeout=120,
            ).token_ids
        finally:
            eng.stop_sync()
        assert got == want, f"kv_block={kv_block}"


def test_mistral_registry_carries_window():
    cfg = get_model("mistral-7b").config
    assert cfg.sliding_window == 4096
    assert cfg.max_len == 8192  # context can exceed the window now
