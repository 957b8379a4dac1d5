"""The one place that knows how the engine keeps its weights.

The engine's tree is ``{"embed": [V, d], "layers": {name: [L, ...]},
"final_norm": [d], "lm_head": [d, V]}``; a quantised leaf is a pair
``(q int8, s float32)`` whose scale reduces the contraction (second to
last) axis, so the float32 matrix is ``q * s``. Matrices are already
[in, out]. Each call returns one float32 piece and keeps nothing.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from benchmark.reference.decoder import Shape, teacher_forced_logprobs


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
