"""The plain reference that ``mistral-7b`` and ``mixtral-8x7b-d4`` name
(``"reference": "decoder"`` in their files): one mathematics, written from
the published description, together with its knowledge of the engine's
weight tree. A configuration with other mathematics names a file of its
own beside this one; the harness asks a reference module for two things,
``ABLATIONS`` and ``reference_logprobs``, and nothing else.

Mistral-7B (arXiv:2310.06825) and Mixtral-8x7B (arXiv:2401.04088): a
pre-norm decoder of RMSNorm, rotary position embedding (half-split pairs,
as the published ``config.json`` checkpoints use it), grouped-query causal
attention with an optional sliding window (query ``i`` sees keys ``j`` with
``i - window < j <= i``), and a SwiGLU feed-forward — one, or the top ``k``
of ``E`` experts chosen by a linear router, their softmax gates renormalised
over the chosen ``k``.

Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul is otherwise computed in bfloat16 passes), a
Python loop over layers and experts, no cache, no kernels, no batching, and
no import from ``gofr_tpu``. Weights come through a ``Weights`` object one
matrix at a time, so nothing larger than one feed-forward matrix is ever
added to the chip. ``EngineWeights`` is the one place that knows how the
engine keeps them: ``{"embed": [V, d], "layers": {name: [L, ...]},
"final_norm": [d], "lm_head": [d, V]}``; a quantised leaf is a pair
``(q int8, s float32)`` whose scale reduces the contraction (second to
last) axis, so the float32 matrix is ``q * s``. Matrices are already
[in, out]. Each call returns one float32 piece and keeps nothing.

Departures from the publications: none in the mathematics. ``ablate``
removes one piece on purpose — the tests and every probe use it to show
that the comparison would catch that piece going missing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol

import jax
import jax.numpy as jnp

# The pieces ``ablate`` can remove; "" removes none.
ABLATIONS = ("causal", "window", "expert")


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the mathematics needs, under their published names."""

    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float
    sliding_window: int = 0          # 0: full causal attention
    num_local_experts: int = 0       # 0: one dense feed-forward
    num_experts_per_tok: int = 0

    def ablated(self, ablate: str) -> "Shape":
        if ablate == "window":
            return dataclasses.replace(self, sliding_window=0)
        if ablate == "expert":
            return dataclasses.replace(
                self, num_experts_per_tok=self.num_experts_per_tok - 1
            )
        return self

    def applies(self, ablate: str, length: int) -> bool:
        """Whether removing the piece changes the function at all here."""
        if ablate == "window":
            return 0 < self.sliding_window < length
        if ablate == "expert":
            return self.num_local_experts > 0 and self.num_experts_per_tok > 1
        return ablate in ("", *ABLATIONS)


class Weights(Protocol):
    """Float32 weights, one piece at a time. Matrices are [in, out]."""

    def embed(self, tokens: Any) -> Any: ...            # [s] -> [s, d]
    def vector(self, name: str, layer: int = -1) -> Any: ...
    def matrix(self, name: str, layer: int, expert: int = -1) -> Any: ...
    def head_columns(self, lo: int, hi: int) -> Any: ...  # [d, hi - lo]
    @property
    def vocab(self) -> int: ...


def rms_norm(x: Any, weight: Any, eps: float) -> Any:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotary(x: Any, theta: float) -> Any:
    """x: [s, heads, head_dim]; position p rotates pair (i, i + hd/2) by
    p * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x: Any, wq: Any, wk: Any, wv: Any, wo: Any, shape: Shape,
              causal: bool) -> Any:
    s = x.shape[0]
    H, KV, hd = (shape.num_attention_heads, shape.num_key_value_heads,
                 shape.head_dim)
    q = rotary((x @ wq).reshape(s, H, hd), shape.rope_theta)
    k = rotary((x @ wk).reshape(s, KV, hd), shape.rope_theta)
    v = (x @ wv).reshape(s, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)  # each kv head serves H/KV queries
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(jnp.float32(hd))
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = (j <= i) if causal else jnp.ones((s, s), bool)
    if shape.sliding_window:
        seen = seen & (j > i - shape.sliding_window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, H * hd) @ wo


def swiglu(x: Any, w_gate: Any, w_up: Any, w_down: Any) -> Any:
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_gates(x: Any, router: Any, k: int) -> Any:
    """[s, E]: softmax over the experts, the top k kept and renormalised,
    the others zero."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


_attention = jax.jit(attention, static_argnames=("shape", "causal"))
_swiglu = jax.jit(swiglu)
_gates = jax.jit(expert_gates, static_argnames=("k",))
_norm = jax.jit(rms_norm, static_argnames=("eps",))


def hidden_states(weights: Weights, shape: Shape, tokens: list[int],
                  causal: bool = True, gated: Any = _swiglu) -> Any:
    """[s, d]: the residual stream after the last layer's final norm.
    ``gated(h, w_gate, w_up, w_down)`` is the feed-forward: SwiGLU here; a
    reference with another gate passes its own."""
    eps = shape.rms_norm_eps
    x = weights.embed(jnp.asarray(tokens, jnp.int32))
    for layer in range(shape.num_hidden_layers):
        h = _norm(x, weights.vector("attn_norm", layer), eps)
        x = x + _attention(
            h, weights.matrix("wq", layer), weights.matrix("wk", layer),
            weights.matrix("wv", layer), weights.matrix("wo", layer),
            shape=shape, causal=causal,
        )
        h = _norm(x, weights.vector("mlp_norm", layer), eps)
        if shape.num_local_experts:
            gates = _gates(
                h, weights.matrix("router", layer), k=shape.num_experts_per_tok
            )
            for e in range(shape.num_local_experts):
                x = x + gates[:, e:e + 1] * gated(
                    h, weights.matrix("w_gate", layer, e),
                    weights.matrix("w_up", layer, e),
                    weights.matrix("w_down", layer, e),
                )
        else:
            x = x + gated(
                h, weights.matrix("w_gate", layer),
                weights.matrix("w_up", layer), weights.matrix("w_down", layer),
            )
    return _norm(x, weights.vector("final_norm"), eps)


def teacher_forced_logprobs(
    weights: Weights, shape: Shape, tokens: list[int], n_prompt: int,
    ablate: str = "", head_block: int = 8192, gated: Any = _swiglu,
) -> list[float]:
    """log p(tokens[t] | tokens[:t]) for every t >= n_prompt, from one
    full forward pass over the whole sequence."""
    if ablate and ablate not in ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}; known: {ABLATIONS}")
    with jax.default_matmul_precision("highest"):
        x = hidden_states(
            weights, shape.ablated(ablate), tokens, causal=ablate != "causal",
            gated=gated,
        )
        x = x[n_prompt - 1: len(tokens) - 1]  # the positions that predict
        logits = jnp.concatenate([
            x @ weights.head_columns(lo, min(lo + head_block, weights.vocab))
            for lo in range(0, weights.vocab, head_block)
        ], axis=-1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        targets = jnp.asarray(tokens[n_prompt:], jnp.int32)
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return [float(v) for v in picked]


def f32(leaf: Any, *index: int) -> Any:
    """One float32 piece of a leaf, indexed along its leading axes."""
    if hasattr(leaf, "q") and hasattr(leaf, "s"):
        q, s = leaf.q, leaf.s
        for i in index:
            q, s = q[i], s[i]
        return q.astype(jnp.float32) * s.astype(jnp.float32)
    for i in index:
        leaf = leaf[i]
    return leaf.astype(jnp.float32)


class EngineWeights:
    def __init__(self, params: dict) -> None:
        self.params = params

    @property
    def vocab(self) -> int:
        return int(self.params["embed"].shape[0])

    def embed(self, tokens: Any) -> Any:
        return self.params["embed"][tokens].astype(jnp.float32)

    def vector(self, name: str, layer: int = -1) -> Any:
        if layer < 0:
            return f32(self.params[name])
        return f32(self.params["layers"][name], layer)

    def matrix(self, name: str, layer: int, expert: int = -1) -> Any:
        leaf = self.params["layers"][name]
        return f32(leaf, layer) if expert < 0 else f32(leaf, layer, expert)

    def head_columns(self, lo: int, hi: int) -> Any:
        head = self.params["lm_head"]
        if hasattr(head, "q"):
            return head.q[:, lo:hi].astype(jnp.float32) * head.s[:, lo:hi]
        return head[:, lo:hi].astype(jnp.float32)


def shape_of(cfg: Any) -> Shape:
    """The engine's config under the published names."""
    return Shape(
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=float(cfg.rope_theta),
        rms_norm_eps=float(cfg.norm_eps),
        sliding_window=int(cfg.sliding_window),
        num_local_experts=int(cfg.n_experts),
        num_experts_per_tok=int(cfg.n_experts_active) if cfg.n_experts else 0,
    )


def reference_logprobs(
    engine: Any, sequences: list, n_prompt: int, ablate: str = "",
) -> list:
    """Per sequence, the reference's log-probability of every token after
    the prompt; None for an ablation that changes nothing at this length."""
    shape = shape_of(engine.cfg)
    weights = EngineWeights(engine.params)
    return [
        teacher_forced_logprobs(weights, shape, list(seq), n_prompt, ablate)
        if shape.applies(ablate, len(seq)) else None
        for seq in sequences
    ]
