"""Named model registry (the serving engine resolves ``TPU_MODEL`` here).

Entries bundle a config with init/apply functions so the engine and bench
code are model-agnostic. Sizes: ``*-tiny`` for tests/compile checks,
``llama-1b`` fits a single v5e chip in bf16 for benchmarking, ``llama-3-8b``
is the flagship target config (BASELINE.json config 5).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

from gofr_tpu.models.transformer import TransformerConfig


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    family: str  # "llm" | "encoder" | "vision"
    config: Any
    init: Callable
    eos_token: int = 2
    # Per-model forward (vision family): fn(params, inputs, cfg) → logits.
    # LLM/encoder paths are architecture-generic and ignore this.
    forward: Any = None

    def describe(self) -> dict:
        return {"name": self.name, "family": self.family}


_REGISTRY: dict[str, ModelSpec] = {}


def register_model(spec: ModelSpec) -> None:
    _REGISTRY[spec.name] = spec


def get_model(name: str) -> ModelSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name]


def list_models() -> list[str]:
    return sorted(_REGISTRY)


def _kinds(runs: str) -> tuple:
    """``"s l l"`` -> the mixers' published names, a layer a letter."""
    from gofr_tpu.models.transformer import LIN_KIND, SPARSE_KIND

    return tuple({"s": SPARSE_KIND, "l": LIN_KIND}[c] for c in runs.split())


# MiniCPM-SALA's ``mixer_types`` as published (8 sparse, 24 lightning).
_SALA_KINDS = _kinds(
    "s l l l l l l l l s l l l l l l s s l l l l s l l l l l l s s s"
)
_SALA_TINY_KINDS = _kinds("s l l s s l l l")


def _register_llms() -> None:
    from gofr_tpu.models.transformer import init_transformer

    llm_configs = {
        # Flagship target: Llama-3-8B dims (BASELINE.json config 5).
        "llama-3-8b": TransformerConfig(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_len=8192, rope_theta=500000.0,
        ),
        # Multi-host scale target: Llama-3-70B dims — serves tp=8 per
        # v5e-8 slice (tp is capped by the 8 kv heads the cache shards
        # over); scale FURTHER with dp replicas / pp stages across hosts
        # via the DCN runtime (parallel/dcn.py). Capacity math in
        # tests/test_models.py.
        "llama-3-70b": TransformerConfig(
            vocab_size=128256, d_model=8192, n_layers=80, n_heads=64,
            n_kv_heads=8, d_ff=28672, max_len=8192, rope_theta=500000.0,
        ),
        # Mixtral-8x7B (MoE: 8 experts, top-2; 47B params total, ~13B
        # active). Serves tp-sharded — experts shard over the tp axis
        # (expert parallelism rides the model axis,
        # models/transformer.py transformer_param_specs); int4+tp2 or
        # int8+tp4 fit v5e slices. HF loader maps
        # block_sparse_moe.{gate,experts.*.w1/w2/w3}.
        "mixtral-8x7b": TransformerConfig(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_len=8192, rope_theta=1e6,
            n_experts=8, n_experts_active=2,
        ),
        # Mistral-7B dims (HF loader accepts model_type=mistral):
        # sliding-window attention — every token attends the last 4096
        # positions, so max_len can exceed the window (the cache stores
        # max_len positions; the window is a masking contract).
        "mistral-7b": TransformerConfig(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_len=8192, rope_theta=10000.0,
            sliding_window=4096,
        ),
        # Ouro-2.6B (ByteDance, config.json): a LOOPED decoder — the 48
        # layers run 4 times over one set of weights, the final norm after
        # every pass, sandwich norms on both sublayers, MHA, untied head.
        # 192 cache entries a token (1.5 MiB in bf16, twelve times
        # mistral-7b's) in a 2.67 B-parameter model: docs/advanced-guide/
        # looped-models.md has the slot arithmetic.
        "ouro-2.6b": TransformerConfig(
            vocab_size=49152, d_model=2048, n_layers=48, n_heads=16,
            n_kv_heads=16, d_ff=5632, max_len=65536, rope_theta=1e6,
            norm_eps=1e-6, n_passes=4, post_norm=True,
        ),
        # Qwen2-7B dims (HF loader accepts model_type=qwen2; QKV bias).
        "qwen2-7b": TransformerConfig(
            vocab_size=152064, d_model=3584, n_layers=28, n_heads=28,
            n_kv_heads=4, d_ff=18944, max_len=8192, rope_theta=1e6,
            attn_bias=True,
        ),
        # Gemma-7B dims (HF loader accepts model_type=gemma): GeGLU FFN,
        # (1+w) RMSNorm, sqrt(d_model)-scaled tied embeddings, and an
        # explicit head_dim 256 (n_heads*head_dim = 4096 ≠ d_model 3072).
        "gemma-7b": TransformerConfig(
            vocab_size=256000, d_model=3072, n_layers=28, n_heads=16,
            n_kv_heads=16, d_ff=24576, max_len=8192, rope_theta=10000.0,
            norm_eps=1e-6, head_dim_override=256, act="gelu",
            norm_offset=True, embed_scale=3072**0.5,
        ),
        # Gemma-2B: MQA (1 kv head), head_dim 256.
        "gemma-2b": TransformerConfig(
            vocab_size=256000, d_model=2048, n_layers=18, n_heads=8,
            n_kv_heads=1, d_ff=16384, max_len=8192, rope_theta=10000.0,
            norm_eps=1e-6, head_dim_override=256, act="gelu",
            norm_offset=True, embed_scale=2048**0.5,
        ),
        # ~1.1B config that fits one v5e chip comfortably for benching.
        "llama-1b": TransformerConfig(
            vocab_size=32768, d_model=2048, n_layers=22, n_heads=16,
            n_kv_heads=4, d_ff=5632, max_len=4096, rope_theta=500000.0,
        ),
        # Test-size models (fast CPU compile).
        "llama-tiny": TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, max_len=256, rope_theta=10000.0,
        ),
        # f32 twin: the exact-comparison oracle for tests where bf16
        # argmax tie-breaks differ between execution shapes (e.g. a
        # prefill step of 1 row and of 8).
        "llama-tiny-f32": TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, max_len=256, rope_theta=10000.0,
            dtype=jnp.float32,
        ),
        "moe-tiny": TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, max_len=256, rope_theta=10000.0,
            n_experts=4, n_experts_active=2,
        ),
        # Pythia-6.9B dims (HF loader accepts model_type=gpt_neox):
        # LayerNorm+bias, parallel residual, partial rotary (25% of
        # head_dim), non-gated erf-gelu MLP, biases on every projection.
        "pythia-6.9b": TransformerConfig(
            vocab_size=50432, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=32, d_ff=16384, max_len=2048, rope_theta=10000.0,
            norm_eps=1e-5, norm="ln", parallel_residual=True,
            rotary_pct=0.25, ffn="mlp", act="gelu_exact", attn_bias=True,
            proj_bias=True,
        ),
        # GPT-2 (124M) dims (HF loader accepts model_type=gpt2):
        # learned positions, LayerNorm+bias, tanh-gelu MLP, tied head.
        "gpt2": TransformerConfig(
            vocab_size=50257, d_model=768, n_layers=12, n_heads=12,
            n_kv_heads=12, d_ff=3072, max_len=1024, norm="ln",
            ffn="mlp", act="gelu", attn_bias=True, proj_bias=True,
            pos_emb="learned",
        ),
        # openPangu-Ultra-MoE-718B (FreedomIntelligence, config.json,
        # model_type pangu_ultra_moe), the published sizes whole: latent
        # attention (one 512 + 64 row a token a layer in the cache),
        # 3 dense layers then 58 with 256 routed experts (sigmoid scores,
        # 8 a token, normalised, x 2.5) and a shared one, sandwich norms.
        # No chip holds one expert layer (24.6 GB in bf16): it is served
        # as a share (n_layers, n_dense_layers, n_experts_held and
        # vocab_size overridden; benchmark/configs/
        # openpangu-ultra-moe-718b-ep16.json, docs/advanced-guide/
        # latent-attention-expert-share.md). The multi-token-prediction
        # module (num_nextn_predict_layers 1) is not built: ROADMAP M5.
        "openpangu-ultra-moe-718b": TransformerConfig(
            vocab_size=153600, d_model=7680, n_layers=61, n_heads=128,
            n_kv_heads=128, d_ff=18432, max_len=131072, rope_theta=25.6e6,
            norm_eps=1e-5, post_norm=True, q_lora_rank=1536,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, n_experts=256, n_experts_active=8,
            d_ff_expert=2048, n_shared_experts=1, n_dense_layers=3,
            router_score="sigmoid", routed_scale=2.5,
        ),
        # Its test size: 1 dense + 2 expert layers, 8 experts, 2 a token,
        # 1 shared, sandwich norms, a 16 + 8 row a token in the cache.
        "mla-moe-tiny": TransformerConfig(
            vocab_size=512, d_model=64, n_layers=3, n_heads=4,
            n_kv_heads=4, d_ff=160, max_len=256, rope_theta=10000.0,
            norm_eps=1e-5, post_norm=True, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_experts=8, n_experts_active=2,
            d_ff_expert=48, n_shared_experts=1, n_dense_layers=1,
            router_score="sigmoid", routed_scale=2.5,
        ),
        # MiniCPM-SALA (openbmb, config.json, model_type minicpm_sala), the
        # published sizes whole: 32 layers of two kinds of mixer in the
        # published order (mixer_types: 8 ``minicpm4`` block-sparse
        # attention layers, 32 query / 2 kv heads, no rotary values, and 24
        # ``lightning-attn`` linear-attention layers, 32 heads with a
        # [128, 128] float32 state a slot), qk norms, output gates, the
        # family's three scalars. 18.95 GB in bf16: no one chip holds it; it
        # is served cut in depth (n_layers and layer_kinds overridden;
        # benchmark/configs/minicpm-sala-d16.json, docs/advanced-guide/
        # hybrid-sparse-linear-models.md). config.json has no key for the
        # selection's sizes (MiniCPM4's sparse_config) nor the decay.
        "minicpm-sala": TransformerConfig(
            vocab_size=73448, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=2, d_ff=16384, max_len=524288, rope_theta=10000.0,
            norm_eps=1e-6, head_dim_override=128,
            layer_kinds=_SALA_KINDS, published_layer_kinds=_SALA_KINDS,
            lin_heads=32, lin_head_dim=128, qk_norm=True,
            attn_out_gate=True, lin_out_gate=True, lin_out_norm=True,
            attn_rope=False, lin_rope=True, embed_scale=12.0,
            scale_depth=1.4, mup_denominator=32, dim_model_base=256,
            sparse_kernel=32, sparse_stride=16, sparse_block=64,
            sparse_topk=64, sparse_init_blocks=1, sparse_window=2048,
            sparse_dense_len=8192,
        ),
        # Its test size: 8 layers of both kinds in irregular runs, a tiny
        # selection (dense under 32 positions, blocks of 4, top 4 with the
        # first block and the last 2 forced, compressed keys over 4 keys
        # every 2) so that every branch is reached within 128 positions.
        "sala-tiny": TransformerConfig(
            vocab_size=512, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
            d_ff=128, max_len=256, rope_theta=10000.0, norm_eps=1e-6,
            head_dim_override=16,
            layer_kinds=_SALA_TINY_KINDS, published_layer_kinds=_SALA_TINY_KINDS,
            lin_heads=4, lin_head_dim=16, qk_norm=True, attn_out_gate=True,
            lin_out_gate=True, lin_out_norm=True, attn_rope=False,
            lin_rope=True, embed_scale=12.0, scale_depth=1.4,
            mup_denominator=8, dim_model_base=16, sparse_kernel=4,
            sparse_stride=2, sparse_block=4, sparse_topk=4,
            sparse_init_blocks=1, sparse_window=8, sparse_dense_len=32,
        ),
        # Looped-arch test size: 2 layers run 3 times (6 cache entries),
        # sandwich norms.
        "looped-tiny": TransformerConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=4, d_ff=128, max_len=256, rope_theta=10000.0,
            norm_eps=1e-6, n_passes=3, post_norm=True,
        ),
        # GPT-2-arch test size (learned positions).
        "gpt2-tiny": TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=4, d_ff=256, max_len=256, norm="ln",
            ffn="mlp", act="gelu", attn_bias=True, proj_bias=True,
            pos_emb="learned",
        ),
        # GPT-NeoX-arch test size.
        "neox-tiny": TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=4, d_ff=256, max_len=256, rope_theta=10000.0,
            norm="ln", parallel_residual=True, rotary_pct=0.25,
            ffn="mlp", act="gelu_exact", attn_bias=True, proj_bias=True,
        ),
        # Gemma-arch test size: exercises head_dim override (64 ≠ 128/4),
        # GeGLU, (1+w) norms, and scaled embeddings on the fast CPU path.
        "gemma-tiny": TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, max_len=256, rope_theta=10000.0,
            norm_eps=1e-6, head_dim_override=64, act="gelu",
            norm_offset=True, embed_scale=128**0.5,
        ),
    }
    eos_tokens = {"gemma-7b": 1, "gemma-2b": 1, "gemma-tiny": 1,
                  "pythia-6.9b": 0, "neox-tiny": 0,
                  "gpt2": 50256, "gpt2-tiny": 0}
    for name, cfg in llm_configs.items():
        register_model(
            ModelSpec(
                name=name, family="llm", config=cfg, init=init_transformer,
                eos_token=eos_tokens.get(name, 2),
            )
        )


def _register_encoders() -> None:
    from gofr_tpu.models.bert import BertConfig, init_bert

    register_model(
        ModelSpec(
            name="bert-base",
            family="encoder",
            config=BertConfig(),
            init=init_bert,
        )
    )
    register_model(
        ModelSpec(
            name="bert-tiny",
            family="encoder",
            config=BertConfig(
                vocab_size=512, d_model=128, n_layers=2, n_heads=4, d_ff=256,
                max_len=128,
            ),
            init=init_bert,
        )
    )


def _register_seq2seq() -> None:
    from gofr_tpu.models.t5 import T5Config, init_t5

    register_model(
        ModelSpec(
            name="flan-t5-small",
            family="seq2seq",
            # t5-v1.1-small / flan-t5-small dims: gated-gelu, untied head.
            config=T5Config(
                d_model=512, d_kv=64, n_heads=6, n_layers=8, d_ff=1024,
            ),
            init=init_t5,
            eos_token=1,
        )
    )
    register_model(
        ModelSpec(
            name="t5-tiny",
            family="seq2seq",
            config=T5Config(
                vocab_size=512, d_model=64, d_kv=16, n_heads=4,
                n_layers=2, d_ff=128, max_len=128,
            ),
            init=init_t5,
            eos_token=1,
        )
    )


def _register_vision() -> None:
    from gofr_tpu.models.resnet import ResNetConfig, init_resnet, resnet_forward

    register_model(
        ModelSpec(
            name="resnet-50",
            family="vision",
            config=ResNetConfig(),
            init=init_resnet,
            forward=resnet_forward,
        )
    )
    from gofr_tpu.models.vit import ViTConfig, init_vit, vit_forward

    register_model(
        ModelSpec(
            name="vit-base",
            family="vision",
            config=ViTConfig(),
            init=init_vit,
            forward=vit_forward,
        )
    )
    register_model(
        ModelSpec(
            name="vit-tiny",
            family="vision",
            config=ViTConfig(
                image_size=32, patch_size=8, d_model=64, n_layers=2,
                n_heads=4, d_ff=128, num_classes=10,
            ),
            init=init_vit,
            forward=vit_forward,
        )
    )
    register_model(
        ModelSpec(
            name="resnet-tiny",
            family="vision",
            config=ResNetConfig(stage_sizes=(1, 1, 1, 1), width=16, num_classes=10),
            init=init_resnet,
            forward=resnet_forward,
        )
    )


_register_llms()
_register_encoders()
_register_seq2seq()
_register_vision()
