"""The plain reference that ``ouro-2.6b`` names (``"reference": "looped"``):
a LOOPED decoder, a stack of L layers run T times over one set of weights,
written from the equations in the issue that added it (PR 28) together with
its knowledge of the engine's weight tree. The harness asks it for two
things, ``ABLATIONS`` and ``reference_logprobs``, and nothing else.

Ouro (ByteDance, ``config.json``: ``total_ut_steps`` T, ``num_hidden_layers``
L, ``early_exit_threshold``). ``h = E[token]``; for pass t = 0..T-1, for
layer l = 0..L-1, with layer l's weights in every pass::

    a = rms(h, g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l     (no bias, no q/k norm)
    q, k = rope(q, k) at the token's position               (the same in every pass)
    o = softmax(q K(t,l)^T / sqrt(hd) + causal) V(t,l)      (this pass's own k, v)
    h = h + rms(o Wo_l, g2_l)                               (sandwich: a norm on the output)
    m = rms(h, g3_l);  f = (silu(m Wg_l) * (m Wu_l)) Wd_l
    h = h + rms(f, g4_l)
    after layer L-1:  h = rms(h, g_final);  u_t = h;  lambda_t = sigmoid(u_t w_exit + b_exit)

A pass attends only the keys and values that the same pass computed for the
earlier tokens. The exit rule: ``p_t = lambda_t * prod_{j<t}(1 - lambda_j)``
for t < T-1, ``p_{T-1}`` the remainder; a token leaves the stack at the first
pass whose cumulative ``p`` reaches ``early_exit_threshold``. The published
threshold is 1, so every token runs all T passes and
``logits = u_{T-1} W_head``.

Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul is otherwise computed in bfloat16 passes), a
Python loop over passes and layers, the full forward over the whole
sequence, no cache, no kernels, and no import from ``gofr_tpu``. The
probe's sequences of one length go through a layer together (``vmap`` of
the one-sequence layer: no sequence sees another). One layer's weights at a
time are made float32, inside the layer's jit, so nothing larger than a
layer is ever added to the chip and a layer is one dispatch. What it knows
of the engine's tree: ``{"embed": [V, d], "layers": {name: [L, ...]},
"final_norm": [d], "lm_head": [d, V], "exit_gate_w": [d, 1],
"exit_gate_b": [1]}`` with the
layer leaves ``wq wk wv wo w_gate w_up w_down`` ([in, out]) and the four
norms ``attn_norm`` (g1), ``attn_post_norm`` (g2), ``mlp_norm`` (g3),
``mlp_post_norm`` (g4); a quantised leaf is a pair ``(q int8, s float32)``
whose float32 matrix is ``q * s``.

Departure from the description, one: the serving programs do not compute
``lambda`` while the threshold is >= 1 (it cannot change a logit); the gate's
weights are in the parameter tree all the same, and this reference computes
the rule on them and asserts that it picks the last pass for every token.
``ablate`` removes one piece on purpose — the tests and every probe use it to
show that the comparison would catch that piece going missing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

# The pieces ``ablate`` can remove; "" removes none.
#   causal     no causal mask
#   passes     T - 1 passes
#   pass_norm  no norm between passes, only after the last
#   post_norm  without the sandwich norms g2, g4
#   pass_cache every pass attends pass 0's keys and values: what a cache
#              whose leading axis folded the passes together would compute
ABLATIONS = ("causal", "passes", "pass_norm", "post_norm", "pass_cache")

LAYER_LEAVES = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
)


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the mathematics needs, under their published names."""

    num_hidden_layers: int
    total_ut_steps: int
    early_exit_threshold: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float


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


def head_columns(head: Any, lo: int, hi: int) -> Any:
    """[d, hi - lo] of the output head, float32."""
    if hasattr(head, "q") and hasattr(head, "s"):
        return head.q[:, lo:hi].astype(jnp.float32) * head.s[:, lo:hi]
    return head[:, lo:hi].astype(jnp.float32)


def layer(x: Any, w: dict, shape: Shape, causal: bool, post_norm: bool,
          kv: Optional[tuple] = None) -> tuple:
    """One layer over the whole sequence: ([s, d], weights) -> ([s, d],
    (k, v)). ``kv`` replaces this pass's own keys and values (the
    ``pass_cache`` ablation)."""
    s = x.shape[0]
    H, KV, hd = (shape.num_attention_heads, shape.num_key_value_heads,
                 shape.head_dim)
    eps = shape.rms_norm_eps
    a = rms_norm(x, w["attn_norm"], eps)
    q = rotary((a @ w["wq"]).reshape(s, H, hd), shape.rope_theta)
    k = rotary((a @ w["wk"]).reshape(s, KV, hd), shape.rope_theta)
    v = (a @ w["wv"]).reshape(s, KV, hd)
    own = (k, v)
    if kv is not None:
        k, v = kv
    k = jnp.repeat(k, H // KV, axis=1)  # each kv head serves H/KV queries
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(jnp.float32(hd))
    if causal:
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
    o = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, axis=-1), v)
    o = o.reshape(s, H * hd) @ w["wo"]
    x = x + (rms_norm(o, w["attn_post_norm"], eps) if post_norm else o)
    m = rms_norm(x, w["mlp_norm"], eps)
    f = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
    x = x + (rms_norm(f, w["mlp_post_norm"], eps) if post_norm else f)
    return x, own


@functools.partial(jax.jit, static_argnames=("shape", "causal", "post_norm"))
def layer_at(x: Any, layers: dict, l: Any, kv: Optional[tuple], shape: Shape,
             causal: bool, post_norm: bool) -> tuple:
    """``layer`` with layer ``l``'s weights, made float32 here, on each
    sequence of ``x`` [b, s, d] by itself."""
    w = {name: f32(layers[name], l) for name in LAYER_LEAVES}
    return jax.vmap(
        lambda xi, kvi: layer(xi, w, shape, causal, post_norm, kvi),
        in_axes=(0, None if kv is None else 0),
    )(x, kv)


_norm = jax.jit(rms_norm, static_argnames=("eps",))


def exit_pass(gates: list, threshold: float) -> Any:
    """The pass each token leaves the stack at. ``gates`` holds lambda_t
    of every token for every pass."""
    last = len(gates) - 1
    stay = jnp.ones_like(gates[0])       # prod_{j<t} (1 - lambda_j)
    cumulative = jnp.zeros_like(gates[0])
    chosen = jnp.full(gates[0].shape, last, jnp.int32)
    for t in range(last):                # the last pass takes the remainder
        cumulative = cumulative + gates[t] * stay
        stay = stay * (1.0 - gates[t])
        chosen = jnp.where((cumulative >= threshold) & (chosen == last), t, chosen)
    return chosen


def hidden_states(params: dict, shape: Shape, tokens: list, ablate: str) -> Any:
    """[b, s, d]: u of the last pass, the residual stream after its norm,
    for ``tokens``, b sequences of one length."""
    eps = shape.rms_norm_eps
    passes = shape.total_ut_steps - (ablate == "passes")
    final_norm = f32(params["final_norm"])
    x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    first_kv: list = []                  # pass 0's keys and values, per layer
    gates = []
    for t in range(passes):
        for l in range(shape.num_hidden_layers):
            x, kv = layer_at(
                x, params["layers"], l,
                first_kv[l] if ablate == "pass_cache" and t else None,
                shape=shape, causal=ablate != "causal",
                post_norm=ablate != "post_norm",
            )
            if t == 0 and ablate == "pass_cache":
                first_kv.append(kv)
        if ablate != "pass_norm" or t == passes - 1:
            x = _norm(x, final_norm, eps)
        gates.append(jax.nn.sigmoid(
            (x @ f32(params["exit_gate_w"]))[..., 0] + f32(params["exit_gate_b"])[0]
        ))
    if not ablate:
        leaves_at = exit_pass(gates, shape.early_exit_threshold)
        assert bool(jnp.all(leaves_at == passes - 1)), (
            f"the exit rule at threshold {shape.early_exit_threshold} lets a "
            f"token leave before the last pass: {leaves_at.tolist()}"
        )
    return x


def teacher_forced_logprobs(
    params: dict, shape: Shape, tokens: list, n_prompt: int,
    ablate: str = "", head_block: int = 8192,
) -> list:
    """Per sequence of ``tokens`` (all of one length), log p(tokens[t] |
    tokens[:t]) for every t >= n_prompt, from one full forward pass over
    the whole sequence."""
    if ablate and ablate not in ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}; known: {ABLATIONS}")
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, shape, tokens, ablate)
        x = x[:, n_prompt - 1: -1]       # the positions that predict
        vocab = int(params["embed"].shape[0])
        logits = jnp.concatenate([
            x @ head_columns(params["lm_head"], lo, lo + head_block)
            for lo in range(0, vocab, head_block)
        ], axis=-1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        targets = jnp.asarray(tokens, jnp.int32)[:, n_prompt:]
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return [[float(v) for v in row] for row in picked]


def shape_of(cfg: Any) -> Shape:
    """The engine's config under the published names."""
    return Shape(
        num_hidden_layers=cfg.n_layers,
        total_ut_steps=cfg.n_passes,
        early_exit_threshold=float(cfg.exit_threshold),
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=float(cfg.rope_theta),
        rms_norm_eps=float(cfg.norm_eps),
    )


def reference_logprobs(
    engine: Any, sequences: list, n_prompt: int, ablate: str = "",
) -> list:
    """Per sequence, the reference's log-probability of every token after
    the prompt. Every ablation changes the function at any length (T >= 2).
    Sequences of one length go through together, each by itself."""
    shape = shape_of(engine.cfg)
    out: list = [None] * len(sequences)
    for length in sorted({len(seq) for seq in sequences}):
        group = [i for i, seq in enumerate(sequences) if len(seq) == length]
        found = teacher_forced_logprobs(
            engine.params, shape, [list(sequences[i]) for i in group],
            n_prompt, ablate,
        )
        for i, logprobs in zip(group, found):
            out[i] = logprobs
    return out
