"""Model correctness tests on the CPU backend.

The load-bearing test is prefill+decode vs. full-forward equivalence: the
serving path (KV cache, RoPE offsets, padding masks) must reproduce the
training path logits token for token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.bert import bert_embed, init_bert
from gofr_tpu.models.registry import get_model, list_models
from gofr_tpu.models.resnet import init_resnet, resnet_forward
from gofr_tpu.models.transformer import (
    init_transformer,
    transformer_decode_step,
    transformer_forward,
    transformer_prefill,
)
from gofr_tpu.ops.kv_cache import KVCache


@pytest.fixture(scope="module")
def tiny():
    spec = get_model("llama-tiny")
    cfg = spec.config
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_registry_contents():
    names = list_models()
    for expected in ("llama-3-8b", "llama-1b", "llama-tiny", "moe-tiny", "bert-base", "resnet-50"):
        assert expected in names
    with pytest.raises(KeyError):
        get_model("nope")


def test_forward_shapes_and_finiteness(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = transformer_forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_param_count_flagship_configs():
    cfg8b = get_model("llama-3-8b").config
    # Count without materializing: eval_shape.
    shapes = jax.eval_shape(lambda k: init_transformer(k, cfg8b), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert 7.5e9 < n < 8.7e9  # Llama-3-8B ballpark (incl. untied lm_head)


def test_prefill_decode_matches_full_forward():
    """Serving path == training path, token for token (f32 so the comparison
    is precision-tight; bf16 paths diverge only by rounding)."""
    import dataclasses

    cfg = dataclasses.replace(get_model("llama-tiny").config, dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    b, prompt_len, gen_len = 2, 10, 5
    total = prompt_len + gen_len
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (b, total), 0, cfg.vocab_size)

    # Ground truth: full causal forward over the whole sequence.
    full_logits = transformer_forward(params, tokens, cfg)

    # Serving path: prefill the prompt, then decode one token at a time
    # (teacher-forced with the same tokens so logits must match).
    cache = KVCache.create(
        cfg.n_layers, n_slots=4, max_len=64, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, dtype=cfg.dtype,
    )
    slots = jnp.array([0, 2])  # non-contiguous slots on purpose
    lengths = jnp.array([prompt_len, prompt_len])
    logits_p, cache = transformer_prefill(
        params, tokens[:, :prompt_len], lengths, cache, slots, cfg
    )
    np.testing.assert_allclose(
        np.asarray(logits_p),
        np.asarray(full_logits[:, prompt_len - 1]),
        rtol=1e-4, atol=1e-4,
    )

    # Decode runs over ALL slots; place each sequence's token at its slot and
    # mark only those slots active.
    active = jnp.zeros((4,), dtype=bool).at[slots].set(True)
    for step in range(gen_len):
        pos = prompt_len + step
        slot_tokens = jnp.zeros((4,), dtype=tokens.dtype).at[slots].set(tokens[:, pos])
        logits_d, cache = transformer_decode_step(
            params, slot_tokens, cache, active, cfg
        )
        np.testing.assert_allclose(
            np.asarray(logits_d[slots]),
            np.asarray(full_logits[:, pos]),
            rtol=1e-4, atol=1e-4,
            err_msg=f"decode step {step} diverged from full forward",
        )
    assert cache.lengths[0] == prompt_len + gen_len
    assert cache.lengths[1] == 0  # inactive slot length untouched


def test_prefill_respects_padding(tiny):
    """Right-padded short prompt must give same last-token logits as unpadded."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab_size)
    cache = KVCache.create(cfg.n_layers, 2, 32, cfg.n_kv_heads, cfg.head_dim, cfg.dtype)
    logits_a, _ = transformer_prefill(
        params, tokens, jnp.array([6]), cache, jnp.array([0]), cfg
    )
    padded = jnp.pad(tokens, ((0, 0), (0, 4)))  # junk zeros after the prompt
    logits_b, _ = transformer_prefill(
        params, padded, jnp.array([6]), cache, jnp.array([1]), cfg
    )
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), rtol=1e-4, atol=1e-4
    )


def test_moe_forward_runs():
    spec = get_model("moe-tiny")
    cfg = spec.config
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    logits = transformer_forward(params, tokens, cfg)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_bert_embed():
    spec = get_model("bert-tiny")
    cfg = spec.config
    params = init_bert(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    mask = jnp.ones((2, 16), dtype=jnp.int32)
    emb = bert_embed(params, tokens, mask, cfg)
    assert emb.shape == (2, cfg.d_model)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(emb), axis=-1), 1.0, rtol=1e-5
    )


def test_bert_mask_changes_output():
    spec = get_model("bert-tiny")
    cfg = spec.config
    params = init_bert(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    full = bert_embed(params, tokens, jnp.ones((1, 8), jnp.int32), cfg)
    half = bert_embed(
        params, tokens, jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32), cfg
    )
    assert not np.allclose(np.asarray(full), np.asarray(half), atol=1e-3)


def test_resnet_forward():
    spec = get_model("resnet-tiny")
    cfg = spec.config
    params = init_resnet(jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    logits = resnet_forward(params, images, cfg)
    assert logits.shape == (2, cfg.num_classes)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_sampling():
    from gofr_tpu.ops.sampling import sample_logits

    logits = jnp.array([[0.0, 10.0, 0.0, 0.0], [0.0, 0.0, 0.0, 10.0]])
    greedy = sample_logits(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert greedy.tolist() == [1, 3]
    sampled = sample_logits(
        logits, jax.random.PRNGKey(0), temperature=1.0, top_k=1
    )
    assert sampled.tolist() == [1, 3]  # top_k=1 → argmax regardless of temp


def test_llama_70b_registered_and_shardable_tp8():
    """Scale target sanity: llama-3-70b's param count matches the real
    model (~70.6B), every weight leaf divides a tp=8 mesh cleanly under
    its partition spec, and the int8/int4 per-chip weight bytes fit a
    16 GB v5e with room for cache — the capacity math behind serving
    70B on one v5e-8 slice."""
    import jax

    from gofr_tpu.models.registry import get_model
    from gofr_tpu.models.transformer import (
        kv_cache_specs,
        transformer_param_specs,
    )

    spec = get_model("llama-3-70b")
    cfg = spec.config
    shapes = jax.eval_shape(lambda k: spec.init(k, cfg), jax.random.PRNGKey(0))
    n_params = sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    )
    assert 70e9 < n_params < 72e9, n_params

    TP = 8
    specs = transformer_param_specs(cfg)

    def check(leaf, s):
        for axis, entry in enumerate(s):
            if entry == "tp":
                assert leaf.shape[axis] % TP == 0, (leaf.shape, s)

    jax.tree_util.tree_map(
        check, shapes, specs,
        is_leaf=lambda x: hasattr(x, "shape") or x is None,
    )
    # KV cache shards its kv-head axis over tp: check via the cache's
    # own specs on a representative shape [L, slots, kv, len, hd].
    cache_shape = (cfg.n_layers, 8, cfg.n_kv_heads, 128, cfg.head_dim)
    for axis, entry in enumerate(kv_cache_specs().k):
        if entry == "tp":
            assert cache_shape[axis] % TP == 0, (cache_shape, axis)

    # Weight bytes per chip: int8 ≈ total params (1 B) / TP + scales.
    int8_per_chip = n_params / TP / 1e9
    assert int8_per_chip < 10, int8_per_chip  # < 10 GB of 16 GB HBM
    int4_per_chip = n_params / 2 / TP / 1e9
    assert int4_per_chip < 5, int4_per_chip


def test_mistral_7b_registered():
    from gofr_tpu.models.registry import get_model

    cfg = get_model("mistral-7b").config
    assert cfg.n_kv_heads == 8 and cfg.d_ff == 14336


def test_vit_forward_and_engine_classify():
    """ViT joins the vision family: forward shape and the engine's
    batched classify path (same surface ResNet serves)."""
    import jax

    from gofr_tpu.models.vit import vit_forward
    from gofr_tpu.serving.engine import InferenceEngine

    spec = get_model("vit-tiny")
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    img = jnp.ones((1, 32, 32, 3), jnp.float32)
    logits = vit_forward(params, img, spec.config)
    assert logits.shape == (1, 10)

    eng = InferenceEngine("vit-tiny", max_batch=4)
    eng.start_sync()
    try:
        out = eng.classify_sync(np.ones((32, 32, 3), np.float32))
        assert np.asarray(out).shape[-1] == 10
    finally:
        eng.stop_sync()


def test_vit_matches_torch_oracle():
    """Patchify + one-matmul patch embedding must equal the HF conv
    patch embedding, and the whole encoder must match
    ViTForImageClassification logits (validates q/k/v/o maps, pre-LN
    placement, CLS head)."""
    import dataclasses

    import pytest

    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from gofr_tpu.models.vit import ViTConfig, vit_forward

    hf_cfg = transformers.ViTConfig(
        image_size=32, patch_size=8, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, num_labels=10,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        layer_norm_eps=1e-12,
    )
    torch.manual_seed(4)
    model = transformers.ViTForImageClassification(hf_cfg)
    model.eval()
    sd = {k: v.numpy() for k, v in model.state_dict().items()}

    cfg = dataclasses.replace(
        ViTConfig(
            image_size=32, patch_size=8, d_model=64, n_layers=2,
            n_heads=4, d_ff=128, num_classes=10,
        ),
        dtype=jnp.float32,
    )
    L = cfg.n_layers
    pre = "vit.encoder.layer.{}."

    def stack(fmt, transpose=False):
        a = np.stack([sd[fmt.format(i)] for i in range(L)])
        return jnp.asarray(
            np.swapaxes(a, -1, -2) if transpose else a, jnp.float32
        )

    conv_w = sd["vit.embeddings.patch_embeddings.projection.weight"]
    # HF conv kernel [D, 3, P, P] → our flattened [(P, P, 3) row-major, D].
    patch_proj = jnp.asarray(
        conv_w.transpose(2, 3, 1, 0).reshape(-1, conv_w.shape[0]),
        jnp.float32,
    )
    params = {
        "patch_proj": patch_proj,
        "patch_proj_b": jnp.asarray(
            sd["vit.embeddings.patch_embeddings.projection.bias"]
        ),
        "cls_token": jnp.asarray(sd["vit.embeddings.cls_token"]),
        "pos_embed": jnp.asarray(
            sd["vit.embeddings.position_embeddings"][0]
        ),
        "layers": {
            "ln1": stack(pre + "layernorm_before.weight"),
            "ln1_b": stack(pre + "layernorm_before.bias"),
            "wq": stack(pre + "attention.attention.query.weight", True),
            "wq_b": stack(pre + "attention.attention.query.bias"),
            "wk": stack(pre + "attention.attention.key.weight", True),
            "wk_b": stack(pre + "attention.attention.key.bias"),
            "wv": stack(pre + "attention.attention.value.weight", True),
            "wv_b": stack(pre + "attention.attention.value.bias"),
            "wo": stack(pre + "attention.output.dense.weight", True),
            "wo_b": stack(pre + "attention.output.dense.bias"),
            "ln2": stack(pre + "layernorm_after.weight"),
            "ln2_b": stack(pre + "layernorm_after.bias"),
            "w_up": stack(pre + "intermediate.dense.weight", True),
            "w_up_b": stack(pre + "intermediate.dense.bias"),
            "w_down": stack(pre + "output.dense.weight", True),
            "w_down_b": stack(pre + "output.dense.bias"),
        },
        "ln_f": jnp.asarray(sd["vit.layernorm.weight"]),
        "ln_f_b": jnp.asarray(sd["vit.layernorm.bias"]),
        "head": jnp.asarray(np.swapaxes(sd["classifier.weight"], 0, 1)),
        "head_b": jnp.asarray(sd["classifier.bias"]),
    }
    rng = np.random.default_rng(0)
    img = rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
    ours = np.asarray(vit_forward(params, jnp.asarray(img), cfg))
    with torch.no_grad():
        # HF expects NCHW.
        theirs = model(
            torch.tensor(img.transpose(0, 3, 1, 2))
        ).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)
