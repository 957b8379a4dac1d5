"""Real-weights ingestion: HF Llama safetensors → our param pytree.

The oracle is the `transformers` LlamaForCausalLM itself (torch CPU): a
tiny random HF model is saved with safe_serialization and loaded by
``serving/hf_loader``; logits must match — which validates the name map,
the [out,in]→[in,out] transposes, the RoPE convention, and RMSNorm eps in
one shot (VERDICT r1 #5)."""

from __future__ import annotations


import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from gofr_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    transformer_forward,
)
from gofr_tpu.serving.hf_loader import (  # noqa: E402
    config_from_hf,
    is_hf_checkpoint,
    load_hf_llama,
    params_have_q8,
)


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf-llama")
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def _our_cfg(dtype=jnp.float32) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_len=128, rope_theta=10000.0, norm_eps=1e-6,
        dtype=dtype,
    )


def test_is_hf_checkpoint_and_config(hf_checkpoint):
    path, _ = hf_checkpoint
    assert is_hf_checkpoint(path)
    cfg = config_from_hf(path)
    assert cfg.d_model == 64
    assert cfg.n_kv_heads == 2
    assert not is_hf_checkpoint("/nonexistent")


def test_hf_llama_logit_parity(hf_checkpoint):
    path, model = hf_checkpoint
    cfg = _our_cfg()
    params = load_hf_llama(path, cfg)
    tokens = np.array([[1, 5, 9, 2, 7, 3, 11, 90]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def test_hf_llama_int8_load_coherent(hf_checkpoint):
    """int8-on-load: quantized params produce near-identical greedy
    next-token picks."""
    path, _ = hf_checkpoint
    cfg = _our_cfg()
    ref = load_hf_llama(path, cfg)
    q = load_hf_llama(path, cfg, quant="int8")
    assert params_have_q8(q)
    assert not params_have_q8(ref)
    tokens = np.array([[1, 5, 9, 2, 7, 3]], dtype=np.int32)
    lr = np.asarray(transformer_forward(ref, jnp.asarray(tokens), cfg))
    lq = np.asarray(transformer_forward(q, jnp.asarray(tokens), cfg))
    # Weight-only int8 keeps top-1 agreement on most positions.
    agree = (lr.argmax(-1) == lq.argmax(-1)).mean()
    assert agree >= 0.8


def test_hf_checkpoint_serves_through_engine(hf_checkpoint):
    """TPU_CHECKPOINT boot seam end to end: the engine boots from the HF
    dir and generates deterministically with real weights."""
    from gofr_tpu.config import MockConfig
    from gofr_tpu.models.registry import ModelSpec, register_model
    from gofr_tpu.serving.engine import InferenceEngine

    path, _ = hf_checkpoint
    cfg = _our_cfg(dtype=jnp.float32)
    register_model(ModelSpec(
        name="hf-tiny-test", family="llm", config=cfg,
        init=lambda key, c: (_ for _ in ()).throw(
            AssertionError("engine must not random-init when params given")
        ),
    ))
    eng = InferenceEngine.from_config(MockConfig({
        "TPU_MODEL": "hf-tiny-test",
        "TPU_CHECKPOINT": path,
        "TPU_KV_SLOTS": "2",
        "TPU_MAX_LEN": "64",
    }))
    eng.start_sync()
    try:
        r1 = eng.generate_sync(
            [1, 5, 9], max_new_tokens=6, temperature=0.0, stop_on_eos=False
        )
        r2 = eng.generate_sync(
            [1, 5, 9], max_new_tokens=6, temperature=0.0, stop_on_eos=False
        )
        assert r1.token_ids == r2.token_ids
        assert len(r1.token_ids) == 6
    finally:
        eng.stop_sync()


def test_hf_llama_int4_load(hf_checkpoint):
    """W4A16 group-wise load: Q4 leaves, logits track the bf16 load."""
    from gofr_tpu.serving.hf_loader import params_quant_mode

    path, _ = hf_checkpoint
    cfg = _our_cfg()
    ref = load_hf_llama(path, cfg)
    q = load_hf_llama(path, cfg, quant="int4")
    assert params_quant_mode(q) == "int4"
    assert q["layers"]["wq"].q.dtype.name == "uint8"  # nibble-packed
    tokens = np.array([[1, 5, 9, 2, 7, 3]], dtype=np.int32)
    lr = np.asarray(transformer_forward(ref, jnp.asarray(tokens), cfg))
    lq = np.asarray(transformer_forward(q, jnp.asarray(tokens), cfg))
    corr = np.corrcoef(lr.ravel(), lq.ravel())[0, 1]
    assert corr >= 0.95  # group-wise 4-bit tracks closely


def test_hf_llama_loads_onto_mesh(hf_checkpoint):
    """mesh= places every leaf with its Megatron NamedSharding as it
    lands; logits must match the unsharded load exactly."""
    from gofr_tpu.parallel import make_mesh

    path, _ = hf_checkpoint
    cfg = _our_cfg()
    mesh = make_mesh({"tp": 2})
    ref = load_hf_llama(path, cfg)
    sharded = load_hf_llama(path, cfg, mesh=mesh)
    assert "tp" in str(sharded["layers"]["wq"].sharding.spec)
    assert "tp" in str(sharded["lm_head"].sharding.spec)
    tokens = np.array([[1, 5, 9, 2, 7, 3]], dtype=np.int32)
    lr = np.asarray(transformer_forward(ref, jnp.asarray(tokens), cfg))
    ls = np.asarray(transformer_forward(sharded, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(lr, ls, atol=1e-4, rtol=1e-4)


def test_hf_llama_int8_onto_mesh(hf_checkpoint):
    """The north-star trio minus the chip: real weights + int8 + tp mesh.
    Q8 scale vectors shard with the output-channel axis."""
    from gofr_tpu.parallel import make_mesh

    path, _ = hf_checkpoint
    cfg = _our_cfg()
    mesh = make_mesh({"tp": 2})
    ref = load_hf_llama(path, cfg, quant="int8")
    q = load_hf_llama(path, cfg, quant="int8", mesh=mesh)
    assert params_have_q8(q)
    assert "tp" in str(q["layers"]["wq"].q.sharding.spec)
    assert "tp" in str(q["layers"]["wq"].s.sharding.spec)
    tokens = np.array([[1, 5, 9, 2, 7, 3]], dtype=np.int32)
    lr = np.asarray(transformer_forward(ref, jnp.asarray(tokens), cfg))
    lq = np.asarray(transformer_forward(q, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(lr, lq, atol=1e-4, rtol=1e-4)


def test_config_mismatch_rejected(hf_checkpoint):
    path, _ = hf_checkpoint
    bad = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_len=128,
    )
    with pytest.raises(ValueError, match="d_model"):
        load_hf_llama(path, bad)


def test_tied_embeddings(tmp_path):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=True,
    )
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=64, max_len=64, rope_theta=10000.0, norm_eps=1e-6,
        dtype=jnp.float32,
    )
    params = load_hf_llama(str(tmp_path), cfg)
    tokens = np.array([[1, 5, 9, 2]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


@pytest.fixture(scope="module")
def hf_mixtral_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf-mixtral")
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    model = transformers.MixtralForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hf_mixtral_logit_parity(hf_mixtral_checkpoint):
    """MoE checkpoint: router + stacked expert weights load into our
    dense-einsum top-k formulation and match the HF Mixtral logits."""
    import dataclasses

    path, model = hf_mixtral_checkpoint
    cfg = dataclasses.replace(
        _our_cfg(), n_experts=4, n_experts_active=2
    )
    loaded = config_from_hf(path)
    assert loaded.n_experts == 4 and loaded.n_experts_active == 2
    params = load_hf_llama(path, cfg)
    assert params["layers"]["w_gate"].shape == (2, 4, 64, 128)
    tokens = np.array([[1, 5, 9, 2, 7, 3, 11, 90]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def test_hf_mixtral_int8_serves(hf_mixtral_checkpoint):
    """int8-quantized Mixtral weights (router kept bf16) generate
    through the engine."""
    import dataclasses

    from gofr_tpu.ops.quant import Q8
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    path, _ = hf_mixtral_checkpoint
    cfg = dataclasses.replace(_our_cfg(), n_experts=4, n_experts_active=2)
    params = load_hf_llama(path, cfg, quant="int8")
    assert isinstance(params["layers"]["w_gate"], Q8)
    assert not isinstance(params["layers"]["router"], Q8)

    from gofr_tpu.models.registry import ModelSpec, register_model

    register_model(ModelSpec(
        name="mixtral-test", family="llm", config=cfg,
        init=lambda key, c: params,
    ))
    eng = InferenceEngine(
        "mixtral-test", n_slots=2, max_len=64, tokenizer=ByteTokenizer(),
        params=params,
    )
    eng.start_sync()
    try:
        r = eng.generate_sync(
            "hi", max_new_tokens=5, temperature=0.0, stop_on_eos=False
        )
        assert len(r.token_ids) == 5
    finally:
        eng.stop_sync()


@pytest.fixture(scope="module")
def hf_qwen2_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf-qwen2")
    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    model = transformers.Qwen2ForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hf_qwen2_logit_parity(hf_qwen2_checkpoint):
    """Qwen2 = llama architecture + QKV projection bias; the torch model
    is the oracle for the bias plumbing through every forward path."""
    import dataclasses

    path, model = hf_qwen2_checkpoint
    cfg = config_from_hf(path)
    assert cfg.attn_bias
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = load_hf_llama(path, cfg)
    assert "wq_b" in params["layers"]
    tokens = np.array([[1, 5, 9, 2, 7, 3, 11, 90]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


@pytest.fixture(scope="module")
def hf_gemma_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf-gemma")
    # head_dim=32 deliberately differs from hidden/heads (64/4=16) to
    # exercise the override; Gemma always ties lm_head to the embedding.
    hf_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, hidden_act="gelu_pytorch_tanh",
        hidden_activation="gelu_pytorch_tanh",
    )
    torch.manual_seed(1)
    model = transformers.GemmaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hf_gemma_logit_parity(hf_gemma_checkpoint):
    """Gemma vs torch oracle: validates the head_dim override, GeGLU,
    the (1+w) RMSNorm offset, sqrt(d_model) embedding scaling, and the
    tied lm_head in one shot."""
    import dataclasses

    path, model = hf_gemma_checkpoint
    cfg = config_from_hf(path)
    assert cfg.head_dim == 32 and cfg.act == "gelu"
    assert cfg.norm_offset and cfg.embed_scale
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = load_hf_llama(path, cfg)
    assert params["layers"]["wq"].shape == (2, 64, 4 * 32)
    tokens = np.array([[1, 5, 9, 2, 7, 3, 11, 90]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def _engine_streams_the_full_forwards_rollout(model_name, path, cfg):
    """The engine's greedy stream of 10 tokens (chunked prefill, then the
    decode window) against ten full forwards over a fixed-length buffer
    (causal: what follows a position does not reach it), in float32."""
    import jax

    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    params = load_hf_llama(path, cfg)
    tokenizer = ByteTokenizer()
    ids = list(tokenizer.encode("ab"))
    n_prompt = len(ids)
    forward = jax.jit(lambda buf: transformer_forward(params, buf, cfg))
    for _ in range(10):
        buf = np.zeros((1, n_prompt + 10), dtype=np.int32)
        buf[0, : len(ids)] = ids
        logits = np.asarray(forward(jnp.asarray(buf)))
        ids.append(int(logits[0, len(ids) - 1].argmax()))
    eng = InferenceEngine(
        model_name, n_slots=2, max_len=96, window_k=4,
        tokenizer=tokenizer, params=params,
    )
    eng.start_sync()
    try:
        got = eng.generate_sync(
            "ab", max_new_tokens=10, temperature=0.0, stop_on_eos=False,
            timeout=120,
        ).token_ids
    finally:
        eng.stop_sync()
    assert got == ids[n_prompt:]


def test_hf_gemma_serves_through_engine(hf_gemma_checkpoint):
    """Gemma arch switches hold through chunked prefill and the decode
    window: the engine's greedy stream is the full forward's rollout."""
    import dataclasses

    from gofr_tpu.models.registry import ModelSpec, register_model

    path, _ = hf_gemma_checkpoint
    cfg = dataclasses.replace(config_from_hf(path), dtype=jnp.float32)
    register_model(ModelSpec(
        name="gemma-test", family="llm", config=cfg,
        init=lambda key, c: load_hf_llama(path, c), eos_token=1,
    ))
    _engine_streams_the_full_forwards_rollout("gemma-test", path, cfg)


@pytest.fixture(scope="module")
def hf_neox_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf-neox")
    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=128, rotary_pct=0.25,
        rotary_emb_base=10000.0, layer_norm_eps=1e-5,
        use_parallel_residual=True, hidden_act="gelu",
        tie_word_embeddings=False,
    )
    torch.manual_seed(2)
    model = transformers.GPTNeoXForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hf_neox_logit_parity(hf_neox_checkpoint):
    """GPT-NeoX vs torch oracle: validates the fused-QKV split, the
    LayerNorm+bias pairs, parallel residual, partial rotary (25% of
    head_dim), the non-gated erf-gelu MLP, and every dense bias."""
    import dataclasses

    path, model = hf_neox_checkpoint
    cfg = config_from_hf(path)
    assert cfg.norm == "ln" and cfg.parallel_residual
    assert cfg.rotary_pct == 0.25 and cfg.ffn == "mlp"
    assert cfg.rope_dims == 4  # head_dim 16 × 0.25
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = load_hf_llama(path, cfg)
    assert params["layers"]["wq"].shape == (2, 64, 64)
    assert "attn_norm_b" in params["layers"]
    assert "final_norm_b" in params
    tokens = np.array([[1, 5, 9, 2, 7, 3, 11, 90]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def test_hf_neox_serves_through_engine(hf_neox_checkpoint):
    """NeoX arch switches hold through chunked prefill and the decode
    window: the engine's greedy stream is the full forward's rollout."""
    import dataclasses

    from gofr_tpu.models.registry import ModelSpec, register_model

    path, _ = hf_neox_checkpoint
    cfg = dataclasses.replace(config_from_hf(path), dtype=jnp.float32)
    register_model(ModelSpec(
        name="neox-test", family="llm", config=cfg,
        init=lambda key, c: load_hf_llama(path, c), eos_token=0,
    ))
    _engine_streams_the_full_forwards_rollout("neox-test", path, cfg)


@pytest.fixture(scope="module")
def hf_gpt2_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf-gpt2")
    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=128,
        n_inner=None, layer_norm_epsilon=1e-5,
        attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0,
    )
    torch.manual_seed(3)
    model = transformers.GPT2LMHeadModel(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hf_gpt2_logit_parity(hf_gpt2_checkpoint):
    """GPT-2 vs torch oracle: validates the learned position table, the
    Conv1D [in, out] no-transpose layout, the contiguous c_attn q/k/v
    split, LayerNorm pairs, tanh-gelu MLP, and the tied lm_head."""
    import dataclasses

    path, model = hf_gpt2_checkpoint
    cfg = config_from_hf(path)
    assert cfg.pos_emb == "learned" and cfg.norm == "ln"
    assert cfg.d_ff == 256  # n_inner None → 4*n_embd
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = load_hf_llama(path, cfg)
    assert params["pos_embed"].shape == (128, 64)
    tokens = np.array([[1, 5, 9, 2, 7, 3, 11, 90]], dtype=np.int32)
    ours = np.asarray(transformer_forward(params, jnp.asarray(tokens), cfg))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def test_hf_gpt2_serves_through_engine(hf_gpt2_checkpoint):
    """Learned positions hold through chunked prefill + decode
    (positions come from cache lengths, not rope tables): the engine's
    greedy stream is the full forward's rollout."""
    import dataclasses

    from gofr_tpu.models.registry import ModelSpec, register_model

    path, _ = hf_gpt2_checkpoint
    cfg = dataclasses.replace(config_from_hf(path), dtype=jnp.float32)
    register_model(ModelSpec(
        name="gpt2-test", family="llm", config=cfg,
        init=lambda key, c: load_hf_llama(path, c),
    ))
    _engine_streams_the_full_forwards_rollout("gpt2-test", path, cfg)


def test_hf_qwen2_serves_through_engine(hf_qwen2_checkpoint):
    """The decode and prefill paths both apply the qkv bias: the engine's
    greedy stream from the qwen2 checkpoint is the full forward's rollout."""
    import dataclasses

    from gofr_tpu.models.registry import ModelSpec, register_model

    path, _ = hf_qwen2_checkpoint
    cfg = dataclasses.replace(config_from_hf(path), dtype=jnp.float32)
    register_model(ModelSpec(
        name="qwen2-test", family="llm", config=cfg,
        init=lambda key, c: load_hf_llama(path, c),
    ))
    _engine_streams_the_full_forwards_rollout("qwen2-test", path, cfg)


def test_gpt2_learned_pos_guards(hf_gpt2_checkpoint):
    """max_len beyond the learned position table is rejected at load
    (the clip in _embed would silently reuse the last row)."""
    import dataclasses

    path, model = hf_gpt2_checkpoint
    cfg = dataclasses.replace(
        config_from_hf(path), dtype=jnp.float32, max_len=4096
    )
    with pytest.raises(ValueError, match="position table"):
        load_hf_llama(path, cfg)


def test_gpt2_untied_head_wins(hf_gpt2_checkpoint, tmp_path):
    """An untied fine-tune's own lm_head.weight overrides the wte
    transpose (safetensors dedups the tied case, so this copies the
    checkpoint and injects a distinct head)."""
    import dataclasses
    import shutil

    from safetensors.numpy import save_file
    from safetensors import safe_open

    path, _ = hf_gpt2_checkpoint
    dst = tmp_path / "untied"
    shutil.copytree(path, dst)
    st = next(iter(dst.glob("*.safetensors")))
    tensors = {}
    with safe_open(str(st), framework="numpy") as h:
        for name in h.keys():
            tensors[name] = h.get_tensor(name)
    rng = np.random.default_rng(7)
    wte_name = (
        "wte.weight" if "wte.weight" in tensors
        else "transformer.wte.weight"
    )
    head = rng.standard_normal(
        tensors[wte_name].shape
    ).astype(np.float32) * 0.02
    tensors["lm_head.weight"] = head
    save_file(tensors, str(st))

    cfg = dataclasses.replace(config_from_hf(str(dst)), dtype=jnp.float32)
    params = load_hf_llama(str(dst), cfg)
    np.testing.assert_allclose(
        np.asarray(params["lm_head"]), head.T, atol=1e-6
    )
