"""Llama-family decoder-only transformer (flagship model).

TPU-first design decisions:

* **Scan over layers** — per-layer params are stacked along a leading axis
  and iterated with ``lax.scan``, so the program XLA compiles is one layer
  body regardless of depth (fast compiles, perfect for pjit);
* **bf16 params / f32 accumulation** — matmuls run on the MXU in bf16 with
  ``preferred_element_type=f32`` where it matters (attention softmax, loss);
* **GQA + RoPE + RMSNorm + SwiGLU** (Llama-3 architecture), optional
  **MoE** FFN (top-k routing over stacked experts) so expert parallelism is
  a first-class sharding axis;
* **Functional KV cache** threaded through prefill/decode (see
  ``gofr_tpu/ops/kv_cache.py``).

Partition specs for every param live next to the model
(:func:`transformer_param_specs`) keyed by logical mesh axes ``dp``/``tp``
— the scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
the collectives.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gofr_tpu.ops.attention import (
    attention,
    cache_chunk_attention,
    decode_attention,
    decode_read_index,
    decode_read_rungs,
)
from gofr_tpu.ops.kv_cache import (
    KVCache,
    PagedKVCache,
    fake_quantize_kv,
    quantize_kv,
)
from gofr_tpu.ops.norms import layer_norm, rms_norm
from gofr_tpu.ops.rotary import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # MoE: n_experts == 0 → dense SwiGLU FFN.
    n_experts: int = 0
    n_experts_active: int = 2
    # Qwen2-style QKV projection bias (llama/mistral/mixtral: False).
    attn_bias: bool = False
    # Gemma-family switches: explicit head_dim (Gemma-7B: 256 with
    # n_heads*head_dim != d_model), tanh-approximate GeGLU FFN, RMSNorm
    # computed as x/rms * (1 + w), and sqrt(d_model)-scaled embeddings.
    head_dim_override: int = 0
    act: str = "silu"  # "silu" | "gelu" | "gelu_exact"
    norm_offset: bool = False
    embed_scale: bool = False
    # GPT-NeoX/Pythia-family switches: LayerNorm (with bias) instead of
    # RMSNorm, x + attn(ln1 x) + mlp(ln2 x) parallel residual, partial
    # rotary (rope on the first rotary_pct of head_dim), a non-gated
    # act(x·W_up)·W_down MLP, and biases on every projection.
    norm: str = "rms"  # "rms" | "ln"
    parallel_residual: bool = False
    rotary_pct: float = 1.0
    ffn: str = "swiglu"  # "swiglu" | "mlp"
    proj_bias: bool = False  # wo/w_up/w_down biases (NeoX dense biases)
    # GPT-2: learned absolute position embeddings instead of RoPE (a
    # [max_len, d_model] table added at the embedding; rope is skipped).
    pos_emb: str = "rope"  # "rope" | "learned"
    # Mistral: sliding-window attention — every query attends only the
    # last `sliding_window` positions (0 = full causal). The cache still
    # stores max_len positions; the window is a masking contract, which
    # is what lets max_len exceed the window.
    sliding_window: int = 0
    # Looped (universal-transformer) stack: the n_layers weight layers run
    # n_passes times over one set of weights, the shared final norm applied
    # after every pass. Each pass keeps its own keys and values in each
    # layer, so the cache holds n_passes * n_layers entries a token
    # (``n_cache_entries``). ``exit_threshold`` is the cumulative exit
    # probability at which a step would leave the stack early; only 1.0
    # (every pass runs) is served — the engine refuses anything lower.
    n_passes: int = 1
    exit_threshold: float = 1.0
    # Sandwich norms: a second norm on each sublayer's OUTPUT, before the
    # residual add (``attn_post_norm`` / ``mlp_post_norm`` leaves).
    post_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def n_cache_entries(self) -> int:
        """Leading axis of the KV cache: one entry a layer APPLICATION."""
        return self.n_layers * self.n_passes

    @property
    def kv_bytes_per_token(self) -> int:
        """Unquantised cache bytes one token holds (keys and values)."""
        return (
            self.n_cache_entries * 2 * self.n_kv_heads * self.head_dim
            * jnp.dtype(self.dtype).itemsize
        )

    @property
    def rope_dims(self) -> int:
        nd = int(self.head_dim * self.rotary_pct)
        return nd - (nd % 2)  # rotate-half needs an even subspace

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def norm_init(name: str, shape: tuple, cfg: TransformerConfig) -> jnp.ndarray:
    """Initial scale of the norm leaf ``name``: identity, except a sandwich
    norm's, which starts at (2 L)^-0.5.

    The depth scaling that GPT-2 and Megatron give a residual branch's
    output projection would be erased by a norm on the branch's output, so
    it goes on that norm's scale: the 2 L branches of one pass then add
    unit variance to the stream together. At scale 1 every branch adds a
    unit-rms vector to a stream that a looped stack's pass norm has just
    brought back to rms 1, and seeded random weights at the published
    sizes (48 layers x 4 passes) amplify a bfloat16 rounding, wherever it
    is made, into 0.2-0.4 nats at the logits (PERF.md section 6, PR 28):
    no precision short of float32 then agrees with the reference. Scales
    of ``norm_offset`` models are stored less 1.
    """
    scale = (2 * cfg.n_layers) ** -0.5 if name.endswith("_post_norm") else 1.0
    return jnp.full(shape, scale - (1.0 if cfg.norm_offset else 0.0), cfg.dtype)


def init_transformer(key: jax.Array, cfg: TransformerConfig) -> dict:
    """Random-init params as a pytree with stacked per-layer leaves."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense_init(key, shape, fan_in):
        scale = fan_in**-0.5
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(
            cfg.dtype
        )

    D, H, KV, hd, F, L = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.n_layers,
    )
    ks = jax.random.split(k_layers, 12)
    layers: dict[str, jnp.ndarray] = {
        "wq": dense_init(ks[0], (L, D, H * hd), D),
        "wk": dense_init(ks[1], (L, D, KV * hd), D),
        "wv": dense_init(ks[2], (L, D, KV * hd), D),
        "wo": dense_init(ks[3], (L, H * hd, D), H * hd),
        # norm_offset models (Gemma) store w with the +1 applied in the
        # forward, so identity init is zeros there, ones otherwise.
        "attn_norm": norm_init("attn_norm", (L, D), cfg),
        "mlp_norm": norm_init("mlp_norm", (L, D), cfg),
    }
    if cfg.norm == "ln":
        layers.update(
            attn_norm_b=jnp.zeros((L, D), dtype=cfg.dtype),
            mlp_norm_b=jnp.zeros((L, D), dtype=cfg.dtype),
        )
    if cfg.post_norm:
        for name in ("attn_post_norm", "mlp_post_norm"):
            layers[name] = norm_init(name, (L, D), cfg)
    if cfg.proj_bias:
        layers.update(
            wo_b=jnp.zeros((L, D), dtype=cfg.dtype),
            w_up_b=jnp.zeros((L, F), dtype=cfg.dtype),
            w_down_b=jnp.zeros((L, D), dtype=cfg.dtype),
        )
    if cfg.attn_bias:
        layers.update(
            wq_b=jnp.zeros((L, H * hd), dtype=cfg.dtype),
            wk_b=jnp.zeros((L, KV * hd), dtype=cfg.dtype),
            wv_b=jnp.zeros((L, KV * hd), dtype=cfg.dtype),
        )
    if cfg.is_moe:
        E = cfg.n_experts
        layers.update(
            router=dense_init(ks[4], (L, D, E), D),
            w_gate=dense_init(ks[5], (L, E, D, F), D),
            w_up=dense_init(ks[6], (L, E, D, F), D),
            w_down=dense_init(ks[7], (L, E, F, D), F),
        )
    elif cfg.ffn == "mlp":
        layers.update(
            w_up=dense_init(ks[6], (L, D, F), D),
            w_down=dense_init(ks[7], (L, F, D), F),
        )
    else:
        layers.update(
            w_gate=dense_init(ks[5], (L, D, F), D),
            w_up=dense_init(ks[6], (L, D, F), D),
            w_down=dense_init(ks[7], (L, F, D), F),
        )
    out = {
        "embed": dense_init(k_embed, (cfg.vocab_size, D), D),
        "layers": layers,
        "final_norm": norm_init("final_norm", (D,), cfg),
        "lm_head": dense_init(k_head, (D, cfg.vocab_size), D),
    }
    if cfg.norm == "ln":
        out["final_norm_b"] = jnp.zeros((D,), dtype=cfg.dtype)
    if cfg.pos_emb == "learned":
        out["pos_embed"] = dense_init(
            jax.random.fold_in(k_embed, 1), (cfg.max_len, D), D
        )
    if cfg.n_passes > 1:
        # The per-pass exit gate, sigmoid(u · w + b). Carried in the tree
        # for checkpoints and the reference; the serving programs never
        # read it while exit_threshold is 1 (it cannot change a logit).
        out["exit_gate_w"] = dense_init(jax.random.fold_in(k_head, 1), (D, 1), D)
        out["exit_gate_b"] = jnp.zeros((1,), dtype=cfg.dtype)
    return out


def transformer_param_specs(cfg: TransformerConfig, pp: bool = False) -> dict:
    """PartitionSpecs over logical axes ('dp', 'tp', optionally 'pp') for
    every param leaf.

    Megatron-style: attention QKV column-parallel / O row-parallel over
    ``tp``; FFN gate/up column-parallel, down row-parallel; embeddings and
    lm_head vocab-parallel; norms replicated. MoE experts sharded over
    ``tp`` on the expert axis (expert parallelism rides the model axis).
    With ``pp`` the stacked layer axis (leading dim of every layer leaf)
    shards over the pipeline axis — each stage owns a contiguous slice of
    layers (see ``parallel/pipeline.py``).
    """
    if pp and cfg.n_passes > 1:
        raise ValueError(
            f"pipeline-parallel parameter specs are not implemented for a "
            f"looped stack (n_passes={cfg.n_passes}): every stage would "
            f"need every pass's activations"
        )
    lax_ = "pp" if pp else None  # leading (layer) axis of stacked leaves
    layers = {
        "wq": P(lax_, None, "tp"),
        "wk": P(lax_, None, "tp"),
        "wv": P(lax_, None, "tp"),
        "wo": P(lax_, "tp", None),
        "attn_norm": P(lax_, None),
        "mlp_norm": P(lax_, None),
    }
    if cfg.attn_bias:
        layers.update(
            wq_b=P(lax_, "tp"),
            wk_b=P(lax_, "tp"),
            wv_b=P(lax_, "tp"),
        )
    if cfg.norm == "ln":
        layers.update(attn_norm_b=P(lax_, None), mlp_norm_b=P(lax_, None))
    if cfg.post_norm:
        layers.update(attn_post_norm=P(lax_, None), mlp_post_norm=P(lax_, None))
    if cfg.proj_bias:
        # Row-parallel outputs (wo, w_down) have replicated biases; the
        # column-parallel up-projection bias shards with its outputs.
        layers.update(
            wo_b=P(lax_, None),
            w_up_b=P(lax_, "tp"),
            w_down_b=P(lax_, None),
        )
    if cfg.is_moe:
        layers.update(
            router=P(lax_, None, None),
            w_gate=P(lax_, "tp", None, None),
            w_up=P(lax_, "tp", None, None),
            w_down=P(lax_, "tp", None, None),
        )
    elif cfg.ffn == "mlp":
        layers.update(
            w_up=P(lax_, None, "tp"),
            w_down=P(lax_, "tp", None),
        )
    else:
        layers.update(
            w_gate=P(lax_, None, "tp"),
            w_up=P(lax_, None, "tp"),
            w_down=P(lax_, "tp", None),
        )
    out = {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }
    if cfg.norm == "ln":
        out["final_norm_b"] = P(None)
    if cfg.pos_emb == "learned":
        out["pos_embed"] = P(None, None)
    if cfg.n_passes > 1:
        out["exit_gate_w"] = P(None, None)
        out["exit_gate_b"] = P(None)
    return out


def kv_cache_specs(
    quantized: bool = False, paged: bool = False, cp: bool = False
):
    """Cache layout [entries, slots|blocks, kv_heads, len|block, hd]
    (entries = ``cfg.n_cache_entries``, replicated): kv_heads over ``tp``. Int8 mode adds per-position scales whose kv_heads axis
    shards the same way; the paged pool shards identically (axis 2) with
    a replicated block table.

    ``cp`` (serving context parallelism): the LENGTH axis additionally
    shards over the ``cp`` mesh axis — each chip holds a slice of every
    sequence and GSPMD partitions the dense decode/prefill attention
    (sharded softmax reductions become collectives). This is what lets
    max_len exceed one chip's cache HBM. Not combinable with paging.
    """
    seq = "cp" if cp else None
    kv = P(None, None, "tp", seq, None)
    if paged:
        if cp:
            raise ValueError("paged cache and cp sharding are exclusive")
        return PagedKVCache(
            k=kv,
            v=kv,
            block_table=P(None, None),
            lengths=P(None),
            k_s=kv if quantized else None,
            v_s=kv if quantized else None,
        )
    scale = P(None, None, "tp", None, seq)
    return KVCache(
        k=kv,
        v=kv,
        lengths=P(None),
        k_s=scale if quantized else None,
        v_s=scale if quantized else None,
    )


# ---------------------------------------------------------------------------
# layer body (shared by train/prefill/decode)
# ---------------------------------------------------------------------------


def _wein(subscripts, x, w):
    """einsum whose weight operand may be int8-quantized (ops/quant.Q8).

    Per-output-channel scales commute with the contraction (every Q8
    scale reduces the -2 axis, the one every ``_wein`` call contracts),
    so dequant is applied to the OUTPUT: ``(x · q) * s``. The weight
    operand then carries only an int8→bf16 convert — which XLA can fuse
    into the matmul's operand read — instead of a convert+multiply that
    risks materializing a full bf16 weight copy in HBM each decode step.
    The cast is exact (|q| ≤ 127 is representable in bf16).

    Every call site contracts w's -2 axis and keeps w's remaining dims
    as the output's trailing dims, so ``squeeze(s, -2)`` broadcasts onto
    the output directly (checked for dense, stacked, MoE, and lm_head
    shapes).
    """
    from gofr_tpu.ops.quant import Q4, Q8, dequantize

    if isinstance(w, Q8):
        out = jnp.einsum(subscripts, x, w.q.astype(x.dtype))
        return (out * jnp.squeeze(w.s, -2).astype(jnp.float32)).astype(x.dtype)
    if isinstance(w, Q4):
        # Group-wise scales don't commute with the full contraction, so
        # Q4 dequantizes the operand (int4 → bf16 × group scale); XLA
        # fuses or materializes per its cost model — the int4 HBM
        # footprint win holds either way.
        return jnp.einsum(subscripts, x, dequantize(w, x.dtype))
    return jnp.einsum(subscripts, x, w)


# ---------------------------------------------------------------------------
# multi-LoRA (batched per-slot adapters)
# ---------------------------------------------------------------------------

# Projections LoRA can target (MoE expert weights excluded: per-token
# routing × per-slot adapters would need a double gather; out of scope).
LORA_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def lora_dims(cfg: TransformerConfig, target: str) -> tuple[int, int]:
    """(d_in, d_out) of a LoRA-targetable projection."""
    D, F = cfg.d_model, cfg.d_ff
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": (D, H * hd),
        "wk": (D, KV * hd),
        "wv": (D, KV * hd),
        "wo": (H * hd, D),
        "w_gate": (D, F),
        "w_up": (D, F),
        "w_down": (F, D),
    }[target]


def init_lora(
    cfg: TransformerConfig,
    n_adapters: int,
    rank: int,
    targets: tuple[str, ...] = ("wq", "wk", "wv", "wo"),
) -> dict:
    """Zero LoRA leaves to merge into ``params["layers"]``.

    Layout ``{t}_lora_a: [L, N, d_in, r]`` / ``{t}_lora_b: [L, N, r,
    d_out]`` — layer-major so the leaves ride the existing ``lax.scan``
    over ``params["layers"]`` (each step sees the per-layer [N, ...]
    slice), adapter-slot second so a per-row gather ``a[aids]`` batches
    every live adapter into one einsum. All-zero init makes every
    adapter slot — and in particular slot 0, which requests without an
    adapter use — an exact no-op on the base model.
    """
    if cfg.is_moe:
        raise ValueError("LoRA serving does not support MoE models")
    leaves = {}
    for t in targets:
        if t not in LORA_TARGETS:
            raise ValueError(f"unknown LoRA target {t!r} (of {LORA_TARGETS})")
        d_in, d_out = lora_dims(cfg, t)
        leaves[t + "_lora_a"] = jnp.zeros(
            (cfg.n_layers, n_adapters, d_in, rank), dtype=cfg.dtype
        )
        leaves[t + "_lora_b"] = jnp.zeros(
            (cfg.n_layers, n_adapters, rank, d_out), dtype=cfg.dtype
        )
    return leaves


def lora_param_specs(
    targets: tuple[str, ...], pp: bool = False
) -> dict:
    """PartitionSpecs for the stacked LoRA leaves, matching the base
    projection's Megatron sharding: column-parallel targets shard B's
    output axis over ``tp`` (delta lands tp-sharded like the base
    output); row-parallel targets (wo, w_down) shard A's input axis so
    the rank-space contraction partial-sums over tp exactly where the
    base matmul does. Rank axes stay replicated (r is tiny)."""
    lax_ = "pp" if pp else None
    specs = {}
    for t in targets:
        if t in ("wo", "w_down"):
            specs[t + "_lora_a"] = P(lax_, None, "tp", None)
            specs[t + "_lora_b"] = P(lax_, None, None, None)
        else:
            specs[t + "_lora_a"] = P(lax_, None, None, None)
            specs[t + "_lora_b"] = P(lax_, None, None, "tp")
    return specs


def _lora(x, lp, name, aids):
    """Per-row LoRA delta for projection ``name``; 0.0 when the engine
    compiled without adapters (leaf absent — trace-time static) or the
    caller has no adapter plane. x rows map 1:1 onto ``aids`` entries;
    the rank-space bottleneck keeps the gathered [rows, d, r] operands
    small."""
    ka = name + "_lora_a"
    if aids is None or ka not in lp:
        return 0.0
    a = lp[ka][aids]  # [rows, d_in, r]
    b = lp[name + "_lora_b"][aids]  # [rows, r, d_out]
    if x.ndim == 2:
        xa = jnp.einsum("sd,sdr->sr", x, a)
        return jnp.einsum("sr,sro->so", xa, b)
    xa = jnp.einsum("btd,bdr->btr", x, a)
    return jnp.einsum("btr,bro->bto", xa, b)


def _act(cfg):
    """FFN activation — silu (Llama/SwiGLU), tanh-approximate gelu
    (Gemma/GeGLU), or erf gelu (GPT-NeoX); static per config, so each
    compiles its own program."""
    if cfg.act == "gelu":
        return partial(jax.nn.gelu, approximate=True)
    if cfg.act == "gelu_exact":
        return partial(jax.nn.gelu, approximate=False)
    return jax.nn.silu


def _norm(x, w, cfg, b=None):
    if cfg.norm == "ln":
        return layer_norm(x, w, b, cfg.norm_eps)
    return rms_norm(x, w, cfg.norm_eps, 1.0 if cfg.norm_offset else 0.0)


def _post_norm(out, lp, name, cfg):
    """Sandwich norm on a sublayer's output (``cfg.post_norm``); the
    leaf's absence from the config is trace-time static."""
    return _norm(out, lp[name], cfg) if cfg.post_norm else out


def _scan_stack(body, x, params, cfg, cache_xs=()):
    """Run the layer stack: ``body(x, (lp, *cache_slices)) -> (x, ys)``
    over the stacked layers, ``cfg.n_passes`` times over the one set of
    weights.

    One pass — every model but a looped one — is the plain ``lax.scan``
    over ``(layers, *cache_xs)``, the program it always was. A looped
    stack stays ONE scan, over the n_passes * n_layers cache entries that
    ``cache_xs`` lead with (entry ``t * L + l`` is pass t's layer l, so a
    pass reads and writes only its own keys and values): step i takes
    layer ``i % L``'s weights out of the stacked leaves, which is what a
    scan does with its xs anyway, and the shared final norm ends every
    pass but the last (the caller's own final norm ends that one). The
    cache rides xs → ys exactly as it does for one pass, so a looped
    model costs the prefill program no further copy of it. (The decode
    step, which only reads the cache, scans the entries' indices instead
    and closes over the planes.)
    """
    layers = params["layers"]
    if cfg.n_passes == 1:
        return jax.lax.scan(body, x, (layers, *cache_xs))
    L, n = cfg.n_layers, cfg.n_cache_entries

    def pass_norm(x):
        return _norm(x, params["final_norm"], cfg, params.get("final_norm_b"))

    def step(x, scanned):
        i, cache_slices = scanned
        l = i % L
        lp = jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False),
            layers,
        )
        with jax.named_scope("pass"):
            x, ys = body(x, (lp, *cache_slices))
            with jax.named_scope("pass_norm"):
                x = jax.lax.cond(
                    (l == L - 1) & (i < n - 1), pass_norm, lambda x: x, x
                )
        return x, ys

    return jax.lax.scan(step, x, (jnp.arange(n), tuple(cache_xs)))


# The serving steps below run under ``jax.named_scope`` with a fixed
# vocabulary — embed, attn, kv_commit, ffn, moe_router, moe_experts,
# lm_head here, pass and pass_norm around them in a looped stack; sample
# in serving/programs.py — so that an op in the profiler's trace says which
# part of the model it belongs to (its ``tf_op`` reads
# ``jit(decode_window)/…/attn/dot_general``). Compile-time metadata only: no
# shape, value or fusion depends on it.


@jax.named_scope("embed")
def _embed(params, tokens, cfg, positions=None):
    """Token embedding lookup; Gemma scales by sqrt(d_model) — the scalar
    is cast to the activation dtype first (HF casts the normalizer to the
    hidden dtype, and bf16 parity needs the same rounding). Learned
    position embeddings (GPT-2) add the position table here; rope models
    ignore ``positions``."""
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, dtype=x.dtype)
    if cfg.pos_emb == "learned":
        pos = jnp.clip(positions, 0, params["pos_embed"].shape[0] - 1)
        x = x + params["pos_embed"][pos]
    return x


@jax.named_scope("ffn")
def _ffn_dense(x, lp, cfg, aids=None):
    if cfg.ffn == "mlp":
        # Non-gated act(x·W_up + b)·W_down + b (GPT-NeoX/GPT-2 shape).
        h = _wein("bsd,df->bsf", x, lp["w_up"]) + _lora(x, lp, "w_up", aids)
        if "w_up_b" in lp:
            h = h + lp["w_up_b"]
        h = _act(cfg)(h)
        out = _wein("bsf,fd->bsd", h, lp["w_down"]) + _lora(
            h, lp, "w_down", aids
        )
        if "w_down_b" in lp:
            out = out + lp["w_down_b"]
        return out
    gate = _wein("bsd,df->bsf", x, lp["w_gate"]) + _lora(x, lp, "w_gate", aids)
    up = _wein("bsd,df->bsf", x, lp["w_up"]) + _lora(x, lp, "w_up", aids)
    h = _act(cfg)(gate) * up
    return _wein("bsf,fd->bsd", h, lp["w_down"]) + _lora(h, lp, "w_down", aids)


def _ffn_moe(x, lp, cfg):
    """Top-k MoE FFN. x: [b, s, D]. Dense-einsum formulation: every expert
    computes, weighted by routing probs — the XLA-friendly formulation for
    small expert counts (no ragged dispatch); capacity-based a2a dispatch is
    the scale-out variant (see parallel/moe_dispatch)."""
    b, s, D = x.shape
    with jax.named_scope("moe_router"):
        router_logits = _wein(
            "bsd,de->bse", x, lp["router"]
        ).astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)
        topk_probs, topk_idx = jax.lax.top_k(probs, cfg.n_experts_active)
        topk_probs = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)
        # weights[b,s,E]: zero except the chosen experts.
        weights = jnp.zeros_like(probs).at[
            jnp.arange(b)[:, None, None],
            jnp.arange(s)[None, :, None],
            topk_idx,
        ].set(topk_probs)
    with jax.named_scope("moe_experts"):
        gate = _wein("bsd,edf->bsef", x, lp["w_gate"])
        up = _wein("bsd,edf->bsef", x, lp["w_up"])
        hidden = _act(cfg)(gate) * up
        out = _wein("bsef,efd->bsed", hidden, lp["w_down"])
        return jnp.einsum("bsed,bse->bsd", out, weights.astype(x.dtype))


@jax.named_scope("lm_head")
def _lm_head(eq, x, params):
    return _wein(eq, x, params["lm_head"]).astype(jnp.float32)


def _qkv(h, lp, eq, H, KV, hd, *lead, aids=None):
    """QKV projections with optional Qwen2-style bias (bias leaves exist
    only when cfg.attn_bias — dict membership is trace-time static)."""
    q = _wein(eq, h, lp["wq"]) + _lora(h, lp, "wq", aids)
    k = _wein(eq, h, lp["wk"]) + _lora(h, lp, "wk", aids)
    v = _wein(eq, h, lp["wv"]) + _lora(h, lp, "wv", aids)
    if "wq_b" in lp:
        q = q + lp["wq_b"]
        k = k + lp["wk_b"]
        v = v + lp["wv_b"]
    return (
        q.reshape(*lead, H, hd),
        k.reshape(*lead, KV, hd),
        v.reshape(*lead, KV, hd),
    )


def _layer_prefill(x, lp, cfg, cos, sin, positions, mask, attn_fn=None,
                   lengths=None, norm_out=None, aids=None):
    """One decoder layer over a full sequence. Returns (x, (k, v)).

    attn_fn: optional override for the attention call, e.g. a
    context-parallel (ring/Ulysses) implementation — signature
    ``attn_fn(q, k, v, mask)``. lengths: per-row valid prefix lengths
    (right-padded serving prefill) — keeps the flash-kernel path, unlike
    a dense ``mask``. norm_out: optional sharding hook applied to each
    block's normed input — the Megatron-SP block boundary: the sequence-
    parallel residual all-gathers over tp HERE, so the head sharding of
    q/k/v flows purely from the tp-sharded weights and RoPE's split/
    concat never sees a seq→head reshard (which GSPMD can only do by
    involuntary full rematerialization when n_kv_heads < tp).
    """
    b, s, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h = _norm(x, lp["attn_norm"], cfg, lp.get("attn_norm_b"))
    if norm_out is not None:
        h = norm_out(h)
    q, k, v = _qkv(h, lp, "bsd,dh->bsh", H, KV, hd, b, s, aids=aids)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    if attn_fn is None:
        attn = attention(
            q, k, v, causal=True, mask=mask, lengths=lengths,
            window=cfg.sliding_window,
        )
    else:
        if cfg.sliding_window:
            raise ValueError(
                "sliding_window is not supported with ring/Ulysses "
                "context-parallel attention"
            )
        attn = attn_fn(q, k, v, mask)
    ao = attn.reshape(b, s, H * hd)
    attn_out = _wein("bsh,hd->bsd", ao, lp["wo"]) + _lora(ao, lp, "wo", aids)
    if "wo_b" in lp:
        attn_out = attn_out + lp["wo_b"]
    attn_out = _post_norm(attn_out, lp, "attn_post_norm", cfg)

    # Parallel residual (GPT-NeoX): both branches read the SAME input;
    # sequential (default): the MLP reads the attention-updated stream.
    mlp_in = x if cfg.parallel_residual else x + attn_out
    h = _norm(mlp_in, lp["mlp_norm"], cfg, lp.get("mlp_norm_b"))
    if norm_out is not None:
        h = norm_out(h)
    ffn = _ffn_moe(h, lp, cfg) if cfg.is_moe else _ffn_dense(h, lp, cfg, aids)
    ffn = _post_norm(ffn, lp, "mlp_post_norm", cfg)
    if cfg.parallel_residual:
        return x + attn_out + ffn, (k, v)
    return mlp_in + ffn, (k, v)


# ---------------------------------------------------------------------------
# public forwards
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "remat"))
def transformer_forward(
    params: dict,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    remat: bool = False,
    aids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Training/eval forward: tokens [b, s] → logits [b, s, vocab] (f32)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(params, tokens, cfg, positions)
    cos, sin = rope_frequencies(cfg.rope_dims, s, cfg.rope_theta)

    def body(x, scanned):
        out, _ = _layer_prefill(
            x, scanned[0], cfg, cos, sin, positions, mask=None, aids=aids
        )
        return out, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = _scan_stack(body, x, params, cfg)
    x = _norm(x, params["final_norm"], cfg, params.get("final_norm_b"))
    return _lm_head("bsd,dv->bsv", x, params)


def transformer_prefill(
    params: dict,
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    cache: KVCache,
    slots: jnp.ndarray,
    cfg: TransformerConfig,
    aids: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Serving prefill: right-padded prompt batch → last-token logits +
    populated cache.

    tokens: [b, s_pad]; lengths: [b] true lengths; slots: [b] cache slots.
    """
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(params, tokens, cfg, positions)
    cos, sin = rope_frequencies(cfg.rope_dims, cache.max_len, cfg.rope_theta)
    # Per-row lengths mask invalid (right-padding) keys INSIDE the flash
    # kernel — prefill stays on the O(s)-memory kernel path instead of the
    # dense O(s²) masked softmax (VERDICT r1 weak #3).
    lengths = lengths.astype(jnp.int32)

    def body(x, scanned):
        out, kv = _layer_prefill(
            x, scanned[0], cfg, cos, sin, positions, mask=None,
            lengths=lengths, aids=aids,
        )
        return out, kv

    x, (ks, vs) = _scan_stack(body, x, params, cfg)
    # ks: [L, b, s, KV, hd] → heads-major [L, b, KV, s, hd], pad the seq dim
    # to max_len, write each sequence's prefix into its slot.
    pad_len = cache.max_len - s
    ks = jnp.swapaxes(ks, 2, 3)
    vs = jnp.swapaxes(vs, 2, 3)
    ks = jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad_len), (0, 0)))
    vs = jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad_len), (0, 0)))
    if cache.quantized:

        ks, k_sc = quantize_kv(ks)  # scales [L, b, KV, max_len]
        vs, v_sc = quantize_kv(vs)
        rep8 = lambda sc: jnp.broadcast_to(  # noqa: E731
            sc[:, :, :, None, :], sc.shape[:3] + (8,) + sc.shape[3:]
        )
        cache = cache._replace(
            k_s=cache.k_s.at[:, slots].set(rep8(k_sc)),
            v_s=cache.v_s.at[:, slots].set(rep8(v_sc)),
        )
    new_k = cache.k.at[:, slots].set(ks.astype(cache.k.dtype))
    new_v = cache.v.at[:, slots].set(vs.astype(cache.v.dtype))
    cache = cache._replace(k=new_k, v=new_v)
    cache = cache._replace(lengths=cache.lengths.at[slots].set(lengths.astype(jnp.int32)))

    x = _norm(x, params["final_norm"], cfg, params.get("final_norm_b"))
    last_idx = jnp.maximum(lengths - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    logits = _lm_head("bd,dv->bv", x_last, params)
    return logits, cache


def transformer_prefill_chunk(
    params: dict,
    tokens: jnp.ndarray,
    cache: KVCache,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    cfg: TransformerConfig,
    dense_attn: bool = False,
    aids: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Chunked serving prefill: one [P, c] chunk step.

    The engine splits prompts into chunks and interleaves chunk steps with
    decode windows (VERDICT r1 weak #9 — admission must not stall decode),
    so no prefill program depends on a prompt's length: the chunk length
    c is fixed, and the row count P is one of two rungs (1 and the
    engine's ``prefill_batch``; ``serving/programs.py``), each
    compiled before the engine serves and chosen at a dispatch by how
    many rows wait. Rows are (slot, start-offset, valid-len) tuples;
    padding rows, up to the rung, duplicate row 0 (idempotent duplicate
    writes).

    tokens: [P, c] chunk token ids (right-padded per row);
    slots/starts/lens: [P] int32 — cache slot, global position of the
    chunk's first token, valid tokens in this chunk.
    Returns ([P, vocab] logits at each row's LAST VALID token, cache).
    ``cache.lengths`` is NOT updated here — the engine sets it when a
    prompt's final chunk lands.
    """
    P, c = tokens.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = starts[:, None] + jnp.arange(c)[None, :]  # [P, c] global
    x = _embed(params, tokens, cfg, positions)  # [P, c, D]
    cos, sin = rope_frequencies(cfg.rope_dims, cache.max_len, cfg.rope_theta)
    paged = isinstance(cache, PagedKVCache)

    idx_kv = jnp.arange(KV)[None, :, None]
    s_kv = jnp.arange(KV)[None, :, None, None]
    s_sub = jnp.arange(8)[None, None, :, None]
    if paged:
        # Map global positions onto (pool block, offset) via the rows'
        # table entries; positions past a row's allocation resolve to the
        # parking block 0 (padding columns only — live prompt positions
        # are allocated ahead by the engine).
        B = cache.block
        bt_rows = cache.block_table[slots]  # [P, max_blocks]
        blk = jnp.take_along_axis(
            bt_rows,
            jnp.minimum(positions // B, bt_rows.shape[1] - 1),
            axis=1,
        )  # [P, c]
        # Padding columns past max_len MUST park in block 0: the slot
        # cache dropped them as out-of-bounds scatter updates, but the
        # min-clamp above would remap them INTO the last real block on
        # top of live prompt K/V.
        in_range = positions < cache.max_len
        blk = jnp.where(in_range, blk, 0)
        off = jnp.where(in_range, positions % B, B - 1)
        idx_row = blk[:, None, :]  # [P, 1, c] pool block per position
        idx_pos = off[:, None, :]
        s_row = blk[:, None, None, :]
        s_pos = off[:, None, None, :]
    else:
        idx_row = slots[:, None, None]
        idx_pos = positions[:, None, :]  # [P, 1, c]
        # Scale-write indices (int8 mode): [S, KV, 8, max_len] layer slice.
        s_row = slots[:, None, None, None]
        s_pos = positions[:, None, None, :]  # [P, 1, 1, c]

    def body(x, scanned):
        lp, ck, cv, cks, cvs = scanned  # ck/cv: [S, KV, max_len, hd]
        with jax.named_scope("attn"):
            h = _norm(x, lp["attn_norm"], cfg, lp.get("attn_norm_b"))
            q, k, v = _qkv(h, lp, "pcd,dh->pch", H, KV, hd, P, c, aids=aids)
            if cfg.pos_emb == "rope":
                q = apply_rope(q, cos, sin, positions)
                k = apply_rope(k, cos, sin, positions)
        # Write the chunk's K/V into the cache, then attend against the
        # cache in place (kernel reads only blocks up to starts+lens).
        with jax.named_scope("kv_commit"):
            if cks is not None:
                k, k_sc = quantize_kv(k)  # scales [P, c, KV]
                v, v_sc = quantize_kv(v)
                cks = cks.at[s_row, s_kv, s_sub, s_pos].set(
                    k_sc.transpose(0, 2, 1)[:, :, None, :]
                )
                cvs = cvs.at[s_row, s_kv, s_sub, s_pos].set(
                    v_sc.transpose(0, 2, 1)[:, :, None, :]
                )
            ck = ck.at[idx_row, idx_kv, idx_pos].set(k.transpose(0, 2, 1, 3))
            cv = cv.at[idx_row, idx_kv, idx_pos].set(v.transpose(0, 2, 1, 3))
        with jax.named_scope("attn"):
            attn = cache_chunk_attention(
                q, ck, cv, slots, starts, lens, k_scale=cks, v_scale=cvs,
                block_table=cache.block_table if paged else None,
                kernel=False if dense_attn else None,
                window=cfg.sliding_window,
            )
            ao = attn.reshape(P, c, H * hd)
            attn_out = (
                _wein("pch,hd->pcd", ao, lp["wo"]) + _lora(ao, lp, "wo", aids)
            )
            if "wo_b" in lp:
                attn_out = attn_out + lp["wo_b"]
            attn_out = _post_norm(attn_out, lp, "attn_post_norm", cfg)
        mlp_in = x if cfg.parallel_residual else x + attn_out
        h = _norm(mlp_in, lp["mlp_norm"], cfg, lp.get("mlp_norm_b"))
        ffn = _ffn_moe(h, lp, cfg) if cfg.is_moe else _ffn_dense(
            h, lp, cfg, aids
        )
        ffn = _post_norm(ffn, lp, "mlp_post_norm", cfg)
        x = x + attn_out + ffn if cfg.parallel_residual else mlp_in + ffn
        return x, (ck, cv, cks, cvs)

    x, (new_k, new_v, new_ks, new_vs) = _scan_stack(
        body, x, params, cfg, (cache.k, cache.v, cache.k_s, cache.v_s)
    )
    cache = cache._replace(k=new_k, v=new_v, k_s=new_ks, v_s=new_vs)

    x = _norm(x, params["final_norm"], cfg, params.get("final_norm_b"))
    last_idx = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    return _lm_head("pd,dv->pv", x_last, params), cache


def transformer_decode_step(
    params: dict,
    tokens: jnp.ndarray,
    cache: KVCache,
    active: jnp.ndarray,
    cfg: TransformerConfig,
    dense_attn: bool = False,
    aids: Optional[jnp.ndarray] = None,
    bound_read: bool = True,
) -> tuple[jnp.ndarray, KVCache]:
    """One decode step over ALL cache slots (static batch = n_slots).

    tokens: [n_slots] current token per slot (anything for inactive slots);
    active: [n_slots] bool — only active slots get their K/V write kept and
    their length bumped; an inactive row's logits are discarded. Every
    slot is computed however few are live (static shapes, no gather or
    scatter of the cache, the whole [L, S, KV, max_len, hd] buffers
    update in place via donation); what IS bounded by what is live is
    the dense attention's read of a contiguous cache: the first
    ``decode_read_rungs(max_len)[i]`` positions of every slot, the
    smallest rung that holds the longest ACTIVE slot, chosen here on the
    device at every step (so it follows the lengths as they grow inside a
    window). An inactive slot's stale length does not count.
    bound_read: False keeps the whole read — for a cache whose position
    axis is sharded (context parallel), where a prefix lives on the first
    chips only.
    Returns ([n_slots, vocab] logits, updated cache).
    """
    S = cache.n_slots
    L = cfg.n_cache_entries  # a looped stack commits every pass's entry
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = cache.lengths  # [S] — write position for each slot's new token
    x = _embed(params, tokens, cfg, positions)  # [S, D]
    cos, sin = rope_frequencies(cfg.rope_dims, cache.max_len, cfg.rope_theta)

    # Inactive slots must not write at their stale ``lengths`` position: a
    # slot mid-CHUNKED-prefill has fresh K/V there that a concurrent decode
    # window would corrupt. Park inactive writes at max_len-1 — never
    # attended (admission reserves room so live lengths stay < max_len-1)
    # and rewritten by real decode before it could matter.
    write_pos = jnp.where(active, positions, cache.max_len - 1)
    slot_idx = jnp.arange(S)

    # The cache stays READ-ONLY inside the layer scan: each layer attends
    # the cache prefix + its fresh (k, v) via the split softmax
    # (ops/attention.decode_attention k_new path) and returns the tiny
    # [S, KV, hd] pair as scan ys. One scatter below commits all layers.
    # Round-tripping the full cache through scan ys instead costs ~11 ms
    # of pure HBM copy per step at llama-1b/32 slots (the nested window
    # scan defeats XLA's ys/xs aliasing — scripts/tpu_probe.py).
    # The stacked planes are closed over and the scan carries the entry's
    # index: the attention slices entry i where it reads it (inside the
    # rung's branch, where the slice fuses into the dot), which is what a
    # scan over the planes as xs lowers to anyway. Handing a branch the
    # entry already sliced would copy it out whole first, every layer.
    paged = isinstance(cache, PagedKVCache)
    read = None
    if bound_read and not paged:
        read = decode_read_index(
            decode_read_rungs(cache.max_len),
            jnp.max(jnp.where(active, cache.lengths, 0)),
        )

    def body(x, scanned):
        lp, entry = scanned
        with jax.named_scope("attn"):
            h = _norm(
                x[:, None, :], lp["attn_norm"], cfg, lp.get("attn_norm_b")
            )[:, 0]
            q, k, v = _qkv(h, lp, "bd,dh->bh", H, KV, hd, S, aids=aids)
            pos2 = positions[:, None]  # [S, 1]
            if cfg.pos_emb == "rope":
                q = apply_rope(q[:, None], cos, sin, pos2)[:, 0]
                k = apply_rope(k[:, None], cos, sin, pos2)[:, 0]
            if cache.quantized:
                # Attend what the cache will hold: fake-quantize the
                # fresh K/V so the split path matches a write-then-attend
                # int8 cache bit for bit (commit re-quantizes to the same
                # int8).
                k, v = fake_quantize_kv(k), fake_quantize_kv(v)
            attn = decode_attention(
                q, cache.k, cache.v, positions, k_new=k, v_new=v,
                k_scale=cache.k_s, v_scale=cache.v_s,
                block_table=cache.block_table if paged else None,
                kernel=False if dense_attn else None,
                window=cfg.sliding_window, layer=entry, read=read,
            )
            ao = attn.reshape(S, H * hd)
            attn_out = (
                _wein("bh,hd->bd", ao, lp["wo"]) + _lora(ao, lp, "wo", aids)
            )
            if "wo_b" in lp:
                attn_out = attn_out + lp["wo_b"]
            attn_out = _post_norm(attn_out, lp, "attn_post_norm", cfg)
        mlp_in = x if cfg.parallel_residual else x + attn_out
        h = _norm(
            mlp_in[:, None, :], lp["mlp_norm"], cfg, lp.get("mlp_norm_b")
        )
        ffn = _ffn_moe(h, lp, cfg) if cfg.is_moe else _ffn_dense(
            h, lp, cfg, aids
        )
        ffn = _post_norm(ffn, lp, "mlp_post_norm", cfg)
        if cfg.parallel_residual:
            x = x + attn_out + ffn[:, 0]
        else:
            x = mlp_in + ffn[:, 0]
        return x, (k, v)

    x, (new_k, new_v) = _scan_stack(body, x, params, cfg, (jnp.arange(L),))
    # Commit every layer's token in one scatter: [L, S, KV, hd] values at
    # [l, s, kv, write_pos[s]] (slot cache) or [l, table[s, p//B], kv,
    # p%B] (paged pool; inactive slots park in block 0) — donation makes
    # this in-place.
    with jax.named_scope("kv_commit"):
        li = jnp.arange(L)[:, None, None]
        ki = jnp.arange(KV)[None, None, :]
        if paged:
            B = cache.block
            blk_log = positions // B
            blk = jnp.take_along_axis(
                cache.block_table,
                jnp.minimum(blk_log, cache.block_table.shape[1] - 1)[:, None],
                axis=1,
            )[:, 0]
            row = jnp.where(active, blk, 0)[None, :, None]
            wp = jnp.where(active, positions % B, B - 1)[None, :, None]
        else:
            row = slot_idx[None, :, None]
            wp = write_pos[None, :, None]
        if cache.quantized:
            new_k, k_sc = quantize_kv(new_k)  # scales [L, S, KV]
            new_v, v_sc = quantize_kv(new_v)
            sidx = (
                li[..., None], row[..., None], ki[..., None],
                jnp.arange(8)[None, None, None, :], wp[..., None],
            )
            cache = cache._replace(
                k_s=cache.k_s.at[sidx].set(k_sc[..., None]),
                v_s=cache.v_s.at[sidx].set(v_sc[..., None]),
            )
        cache = cache._replace(
            k=cache.k.at[li, row, ki, wp].set(new_k.astype(cache.k.dtype)),
            v=cache.v.at[li, row, ki, wp].set(new_v.astype(cache.v.dtype)),
            lengths=cache.lengths + active.astype(jnp.int32),
        )
    x = _norm(x[:, None, :], params["final_norm"], cfg, params.get("final_norm_b"))[:, 0]
    return _lm_head("bd,dv->bv", x, params), cache


def count_params(params: dict) -> int:
    """LOGICAL parameter count — a nibble-packed Q4 leaf stores two
    weights per uint8 element, so physical ``.size`` would halve it."""
    from gofr_tpu.ops.quant import Q4, Q8

    total = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, (Q4, Q8))
    ):
        if isinstance(leaf, (Q4, Q8)):
            total += int(np.prod(leaf.shape))  # Q4.shape is logical
        else:
            total += int(leaf.size)
    return total
