"""The plain reference that ``minicpm-sala-d16`` names (``"reference":
"hybrid_sparse_linear"``): a decoder whose layers are of TWO kinds of mixer
in a published order, lightning linear attention (a decayed recurrence with
a fixed-size state, no softmax) and block-sparse softmax attention that
picks the blocks of keys each query attends. Written from the equations in
the issue that added it (PR 35) together with its knowledge of the engine's
weight tree. The harness asks it for two things, ``ABLATIONS`` and
``reference_logprobs``, and nothing else.

MiniCPM-SALA (openbmb ``config.json``, ``model_type`` ``minicpm_sala``). With
``N`` an RMSNorm (eps ``rms_norm_eps``), ``r = scale_depth /
sqrt(mup_denominator)`` and ``x`` the residual stream::

    x_0 = scale_emb * E[token]
    x <- x + r Mixer_l(N1 x)             (the layer's kind: mixer_types[l])
    x <- x + r SwiGLU(N2 x)              (width intermediate_size)
    logits = (N(x_L) * dim_model_base / hidden_size) W_head

*Lightning layer* (``lightning-attn``), ``a = N1 x``, ``lightning_nh`` heads of
``lightning_head_dim`` = d::

    q, k, v = a W_q, a W_k, a W_v;  q, k <- N_head(q), N_head(k)      (qk_norm: RMSNorm over a
                                                                       head's d values, one scale a projection)
    q, k <- RoPE at the token's position                              (lightning_use_rope)
    o_t = sum_{j<=t} lambda_h^(t-j) (q_t . k_j / sqrt(d)) v_j         (no softmax, no normaliser)
    y = (N(concat_h o_t) * sigmoid(a W_g)) W_o                        (use_output_norm, use_output_gate)

which is the recurrence ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = (q_t
/ sqrt(d)) S_t`` written as its QUADRATIC sum (the program runs the
recurrence, chunk-wise in its prefill step). ``lambda_h = exp(-s_h c_l)``,
``s_h = 2^(-8 h / n_heads)`` for h = 1..n_heads, ``c_l = 1 - l / (L - 1) +
1e-5`` with ``l`` the layer's PUBLISHED index and ``L`` the published depth.

*Sparse layer* (``minicpm4``), ``a = N1 x``, H query heads and G kv heads of d::

    q = a W_q;  k, v = a W_k, a W_v;  q, k <- N_head(q), N_head(k);  no rotary values (attn_use_rope false)
    a query at position t < dense_len: causal softmax over every j <= t of q . k / sqrt(d)
    otherwise, for its kv head g:
      C_{g,m} = mean(k_{g,j} : stride m <= j < stride m + kernel)     for every m with stride m + kernel <= t + 1
      p_h = softmax_m(q_{h,t} . C_{g,m} / sqrt(d))                    for each query head h of g
      P_g = sum_h p_h;   B_{g,b} = max(P_{g,m} : window m overlaps block b = [block b, block b + block))
      blocks 0 .. init_blocks - 1 and the window / block blocks that end with the query's own score +inf
      I = the topk blocks of highest B;  causal softmax over j <= t in the blocks of I only, the same I
      for every query head of g
    y = (concat_h o_t * sigmoid(a W_g)) W_o                           (attn_use_output_gate)

Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul is otherwise computed in bfloat16 passes), Python
loops over layers, sequences and kv heads, the full forward over the whole
sequence, no cache, no state carried, no kernels, and no import from
``gofr_tpu``. A layer is a few jitted pieces, each making float32 only the
weights it uses and each running over BLOCKS of queries (``lax.map``), so
that the [heads, block, s] scores of a 24,576-token sequence fit on the chip
beside the engine. What it knows of the engine's tree: ``{"embed": [V, d],
"layers": {name: [sparse layers, ...]}, "lin_layers": {name: [lightning
layers, ...]}, "final_norm": [d], "lm_head": [d, V]}``; every layer has ``wq
wk wv wg wo`` ([in, out], heads major in the out axis), ``q_norm k_norm``
[head_dim], ``attn_norm`` (N1), ``mlp_norm`` (N2), ``w_gate w_up w_down``; a
lightning layer also ``out_norm``. (Its ``log_decay`` leaf is NOT read: the
decay is computed here from the rule.)

Departures from the published model and assumed values, each on purpose:

* **the cut**: the layers kept are a run of the published ``mixer_types``
  (``first_layer`` onward); the decay's ``l`` and ``L`` and the scalar ``r``
  stay the published ones;
* **the decay** has no key in ``config.json``: Lightning Attention's
  published rule as above; **the selection's sizes** (kernel 32, stride 16,
  block 64, top 64, 1 initial block, a window of 2,048, dense under 8,192)
  are MiniCPM4's ``sparse_config``, not in the catalog's ``config``;
* **the switch to the selection is per query position** (the family's code
  switches on the length of the call, which chunked prefill and decoding
  would make depend on how a prompt was cut), and the selection is computed
  exactly as written (no approximation of the softmax over a coarser
  pooling);
* **the RoPE pairing** is half-split (value i pairs with i + d/2), as the
  program's ``ops/rotary.py`` computes it: a permutation of W_q's and W_k's
  columns, immaterial under random weights;
* ``mup_denominator`` stands where the family's code has the layer count,
  which it equals as published; the norm is applied before the gate.

``ablate`` removes one piece on purpose: the tests and every probe use it to
show that the comparison would catch that piece going missing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Every piece ``ablate`` can remove; "" removes none.
#   causal          the sparse layers attend the future too
#   decay           lambda = 1: the lightning layers never forget
#   lin_rope        no rotary values in the lightning layers
#   qk_norm         without the per-head norms on queries and keys
#   out_gate        without the sigmoid output gates (both kinds)
#   out_norm        without the norm on the lightning heads' joined output
#   residual_scale  r read as 1
#   logit_scale     the final hidden state not divided
#   select          dense attention in place of the choice of blocks
#   forced_blocks   no initial block and no local window forced into the choice
CANDIDATES = (
    "causal", "decay", "lin_rope", "qk_norm", "out_gate", "out_norm",
    "residual_scale", "logit_scale", "select", "forced_blocks",
)
# The candidates asked of every probe. All ten were read once on the chip at
# the probe's 4 x 9,224 tokens (PERF.md section 6, PR 35; the limit is a
# median of 0.08 nats): ``decay`` 3.49, ``lin_rope`` 3.21, ``qk_norm`` 0.87,
# ``out_gate`` 2.53, ``out_norm`` 3.99, ``residual_scale`` 3.67,
# ``logit_scale`` 7.13, ``select`` 0.41, ``forced_blocks`` 0.41, each with at
# most 28% of the tokens within 0.25: nine fail with a margin of five times
# and more. ``causal`` reads 0.075 and PASSES: a query past the dense length
# sees of the future only the rest of its own block, so the mask's absence
# reaches the probe's tokens through the first 8,192 positions alone; it is
# left out (section 7) and held by tests/test_hybrid_sparse_linear.py and
# scripts/hybrid_long_compare.py. Each one asked costs every probe a float32
# forward of four 9,224-token sequences (27 s on the v5e), so five are: the
# two kinds' own mechanisms (the decay and the rotary values of the lightning
# layers; the choice of blocks and its forced blocks) and the gate both share.
ABLATIONS = ("decay", "lin_rope", "out_gate", "select", "forced_blocks")
# These two change nothing on a sequence that never leaves the dense branch.
SELECTION_ONLY = ("select", "forced_blocks")

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
GROUP = {SPARSE: "layers", LIGHTNING: "lin_layers"}
MIXER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")
FFN_LEAVES = ("mlp_norm", "w_gate", "w_up", "w_down")
QUERY_BLOCK = 128  # queries whose [heads, block, s] scores are alive at once
FFN_BLOCK = 1024   # rows whose [block, intermediate_size] hidden is alive


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the mathematics needs, under their published names (the
    selection's under MiniCPM4's ``sparse_config`` names), and the cut."""

    mixer_types: tuple        # the layers kept, in order
    first_layer: int          # the published index of the first of them
    published_layers: int     # the published depth (the decay's L)
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    scale_emb: float
    scale_depth: float
    mup_denominator: int
    dim_model_base: int
    kernel_size: int
    kernel_stride: int
    block_size: int
    topk: int
    init_blocks: int
    window_size: int
    dense_len: int


def rms_norm(x: Any, weight: Any, eps: float) -> Any:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotary(x: Any, theta: float) -> Any:
    """x: [s, heads, d]; position p rotates pair (i, i + d/2) by
    p * theta^(-2i/d)."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def f32(leaf: Any, *index: Any) -> Any:
    """One float32 piece of a leaf, indexed along its leading axes."""
    for i in index:
        leaf = leaf[i]
    return leaf.astype(jnp.float32)


def weights(layers: dict, names: tuple, l: Any) -> dict:
    return {name: f32(layers[name], l) for name in names if name in layers}


def only(ablate: str, *pieces: str) -> str:
    """``ablate`` if a jitted piece can see it, else "": an ablation that
    does not touch a piece reuses the piece's compiled program."""
    return ablate if ablate in pieces else ""


def residual_scale(shape: Shape, ablate: str) -> float:
    if ablate == "residual_scale":
        return 1.0
    return shape.scale_depth / shape.mup_denominator**0.5


def in_query_blocks(fn: Any, s: int, *per_query: Any) -> Any:
    """``fn(positions [B], *blocks)`` over blocks of ``QUERY_BLOCK`` queries,
    one after the other; ``per_query`` arrays lead with the s queries.
    Returns fn's results joined, [s, ...]."""
    n = -(-s // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - s

    def blocked(a: Any) -> Any:
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(n, QUERY_BLOCK, *a.shape[1:])

    out = jax.lax.map(
        lambda xs: fn(*xs),
        (blocked(jnp.arange(s)), *(blocked(a) for a in per_query)),
    )
    return out.reshape(n * QUERY_BLOCK, *out.shape[2:])[:s]


def decay_rates(shape: Shape, layer: int, ablate: str) -> Any:
    """-log lambda_h [heads] of the kept layer ``layer``: s_h c_l."""
    nh = shape.lightning_nh
    if ablate == "decay":
        return jnp.zeros((nh,), jnp.float32)
    slopes = 2.0 ** (-8.0 * jnp.arange(1, nh + 1, dtype=jnp.float32) / nh)
    l = shape.first_layer + layer
    return slopes * (1.0 - l / (shape.published_layers - 1) + 1e-5)


def lightning(a: Any, w: dict, rates: Any, shape: Shape, ablate: str) -> Any:
    """a [s, hidden] -> the lightning mixer's output [s, hidden]: the
    quadratic sum, a block of queries against every key."""
    s = a.shape[0]
    nh, d, eps = shape.lightning_nh, shape.lightning_head_dim, shape.rms_norm_eps
    q, k, v = ((a @ w[n]).reshape(s, nh, d) for n in ("wq", "wk", "wv"))
    if ablate != "qk_norm":
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    if ablate != "lin_rope":
        q, k = rotary(q, shape.rope_theta), rotary(k, shape.rope_theta)
    j = jnp.arange(s)

    def block(t: Any, q_b: Any) -> Any:  # t [B], q_b [B, nh, d]
        dist = t[:, None] - j[None, :]  # [B, s]
        weight = jnp.where(
            dist >= 0,
            jnp.exp(-rates[:, None, None] * jnp.maximum(dist, 0)[None]), 0.0,
        )  # [nh, B, s]
        scores = jnp.einsum("qhd,jhd->hqj", q_b, k) / d**0.5
        return jnp.einsum("hqj,jhd->qhd", scores * weight, v)

    o = in_query_blocks(block, s, q).reshape(s, nh * d)
    if ablate != "out_norm":
        o = rms_norm(o, w["out_norm"], eps)
    if ablate != "out_gate":
        o = o * jax.nn.sigmoid(a @ w["wg"])
    return o @ w["wo"]


def overlapping_windows(shape: Shape, n_windows: int, n_blocks: int) -> tuple:
    """For each block the first and last compressed-key window that overlaps
    it, from the intervals themselves: window m is keys [stride m, stride m +
    kernel), block b keys [block b, block b + block). first > last: none."""
    lo = np.arange(n_windows) * shape.kernel_stride
    hi = lo + shape.kernel_size
    first = np.full((n_blocks,), n_windows, np.int32)
    last = np.full((n_blocks,), -1, np.int32)
    for b in range(n_blocks):
        hit = np.nonzero(
            (lo < (b + 1) * shape.block_size) & (hi > b * shape.block_size)
        )[0]
        if hit.size:
            first[b], last[b] = hit[0], hit[-1]
    return first, last


def chosen_blocks(q_b: Any, t: Any, c_g: Any, shape: Shape, n_blocks: int,
                  ablate: str) -> Any:
    """[B, n_blocks] bool: the blocks each query of the block attends, for
    one kv head. q_b [rep, B, d] its query heads' queries, t [B] their
    positions, c_g [M, d] the compressed keys of the sequence."""
    n_windows, d = c_g.shape
    m = jnp.arange(n_windows)
    counts = (m * shape.kernel_stride + shape.kernel_size)[None, :] <= (t + 1)[:, None]
    scores = jnp.einsum("rqd,md->rqm", q_b, c_g) / d**0.5
    scores = jnp.where(counts[None], scores, -jnp.inf)
    p = jnp.where(counts[None], jax.nn.softmax(scores, axis=-1), 0.0)
    shared = jnp.where(counts, jnp.sum(p, axis=0), -jnp.inf)  # [B, M]
    first, last = overlapping_windows(shape, n_windows, n_blocks)
    block_score = jnp.full((t.shape[0], n_blocks), -jnp.inf)
    for step in range(int(max((last - first).max(), -1)) + 1):
        window = first + step
        at = shared[:, np.clip(window, 0, max(n_windows - 1, 0))]
        block_score = jnp.maximum(
            block_score, jnp.where((window <= last)[None, :], at, -jnp.inf)
        )
    b = jnp.arange(n_blocks)
    own = (t // shape.block_size)[:, None]
    if ablate != "forced_blocks":
        forced = (b[None, :] < shape.init_blocks) | (
            (b[None, :] <= own)
            & (b[None, :] > own - shape.window_size // shape.block_size)
        )
        block_score = jnp.where(forced, jnp.inf, block_score)
    top = jax.lax.top_k(block_score, min(shape.topk, n_blocks))[1]
    picked = jnp.zeros((t.shape[0], n_blocks), bool).at[
        jnp.arange(t.shape[0])[:, None], top
    ].set(True)
    return picked & (block_score > -jnp.inf)


def sparse(a: Any, w: dict, shape: Shape, ablate: str) -> Any:
    """a [s, hidden] -> the sparse mixer's output [s, hidden], one kv head
    and one block of queries at a time."""
    s = a.shape[0]
    nh, ng, d = shape.num_attention_heads, shape.num_key_value_heads, shape.head_dim
    rep, eps = nh // ng, shape.rms_norm_eps
    q = (a @ w["wq"]).reshape(s, nh, d)
    k, v = ((a @ w[n]).reshape(s, ng, d) for n in ("wk", "wv"))
    if ablate != "qk_norm":
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    n_windows = max((s - shape.kernel_size) // shape.kernel_stride + 1, 0)
    n_blocks = -(-s // shape.block_size)
    taps = (
        np.arange(n_windows)[:, None] * shape.kernel_stride
        + np.arange(shape.kernel_size)[None, :]
    )  # [M, kernel]
    j = jnp.arange(s)
    select = ablate != "select" and s > shape.dense_len
    heads = []
    for g in range(ng):
        k_g, v_g = k[:, g], v[:, g]
        c_g = jnp.mean(k_g[taps], axis=1) if n_windows else jnp.zeros((0, d))

        def block(t: Any, q_b: Any, k_g=k_g, v_g=v_g, c_g=c_g) -> Any:
            q_b = q_b.transpose(1, 0, 2)  # [rep, B, d]
            allowed = jnp.ones((t.shape[0], s), bool)
            if ablate != "causal":
                allowed = j[None, :] <= t[:, None]
            if select:
                picked = chosen_blocks(q_b, t, c_g, shape, n_blocks, ablate)
                in_picked = jnp.repeat(picked, shape.block_size, axis=1)[:, :s]
                allowed &= in_picked | (t < shape.dense_len)[:, None]
            scores = jnp.einsum("rqd,jd->rqj", q_b, k_g) / d**0.5
            p = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("rqj,jd->qrd", p, v_g)

        heads.append(in_query_blocks(block, s, q[:, g * rep:(g + 1) * rep]))
    o = jnp.concatenate(heads, axis=1).reshape(s, nh * d)
    if ablate != "out_gate":
        o = o * jax.nn.sigmoid(a @ w["wg"])
    return o @ w["wo"]


# One jit a piece of a layer, each making float32 only the weights it uses,
# each on one sequence x [s, hidden].


@functools.partial(jax.jit, static_argnames=("shape", "kind", "ablate"))
def mixer_at(x: Any, layers: dict, l: Any, rates: Any, shape: Shape, kind: str,
             ablate: str) -> Any:
    """x <- x + r Mixer(N1 x) with layer ``l`` of its kind's stacked group;
    ``rates`` its decay rates [heads] (a lightning layer's; one compiled
    program serves every layer of a kind)."""
    w = weights(layers, MIXER_LEAVES + ("out_norm",), l)
    a = rms_norm(x, w["attn_norm"], shape.rms_norm_eps)
    if kind == LIGHTNING:
        y = lightning(a, w, rates, shape, ablate)
    else:
        y = sparse(a, w, shape, ablate)
    return x + residual_scale(shape, ablate) * y


@functools.partial(jax.jit, static_argnames=("shape", "ablate"))
def ffn_at(x: Any, layers: dict, l: Any, shape: Shape, ablate: str) -> Any:
    """x <- x + r SwiGLU(N2 x), rows a block at a time."""
    w = weights(layers, FFN_LEAVES, l)
    s = x.shape[0]
    n = -(-s // FFN_BLOCK)

    def block(rows: Any) -> Any:
        b = rms_norm(rows, w["mlp_norm"], shape.rms_norm_eps)
        return (jax.nn.silu(b @ w["w_gate"]) * (b @ w["w_up"])) @ w["w_down"]

    padded = jnp.pad(x, ((0, n * FFN_BLOCK - s), (0, 0)))
    y = jax.lax.map(block, padded.reshape(n, FFN_BLOCK, -1)).reshape(
        n * FFN_BLOCK, -1
    )[:s]
    return x + residual_scale(shape, ablate) * y


def hidden_states(params: dict, shape: Shape, tokens: Any, ablate: str) -> Any:
    """[b, s, hidden]: the residual stream after the final norm and the
    logit scale, for ``tokens``, b sequences of one length, each by itself."""
    out = []
    for ids in tokens:
        x = shape.scale_emb * f32(params["embed"], jnp.asarray(ids, jnp.int32))
        seen = {SPARSE: 0, LIGHTNING: 0}
        for layer, kind in enumerate(shape.mixer_types):
            layers, l = params[GROUP[kind]], seen[kind]
            seen[kind] += 1
            x = mixer_at(
                x, layers, l, decay_rates(shape, layer, ablate), shape=shape,
                kind=kind, ablate=only(
                    ablate,
                    *(("lin_rope", "out_norm") if kind == LIGHTNING
                      else ("causal", *SELECTION_ONLY)),
                    "qk_norm", "out_gate", "residual_scale",
                ),
            )
            x = ffn_at(x, layers, l, shape=shape,
                       ablate=only(ablate, "residual_scale"))
        x = rms_norm(x, f32(params["final_norm"]), shape.rms_norm_eps)
        if ablate != "logit_scale":
            x = x * (shape.dim_model_base / shape.hidden_size)
        out.append(x)
    return jnp.stack(out)


def logits_of(params: dict, x: Any, head_block: int = 8192) -> Any:
    """x [.., hidden] -> float32 logits, the head's columns a block at a
    time."""
    vocab = int(params["lm_head"].shape[1])
    return jnp.concatenate([
        x @ params["lm_head"][:, lo:lo + head_block].astype(jnp.float32)
        for lo in range(0, vocab, head_block)
    ], axis=-1)


def changes_nothing(shape: Shape, ablate: str, length: int) -> bool:
    """An ablation of the selection on a sequence that never leaves the
    dense branch."""
    return ablate in SELECTION_ONLY and length <= shape.dense_len


def full_logits(params: dict, shape: Shape, tokens: Any, ablate: str = "",
                last: int = 0, precision: str = "highest") -> Any:
    """The full forward pass's logits [b, s, V] for ``tokens`` [b, s]
    (``last`` > 0: of the last ``last`` positions only). ``precision`` is
    "highest" wherever the reference decides anything; a control reading
    asks for a lower one (scripts/hybrid_long_compare.py)."""
    if ablate and ablate not in CANDIDATES:
        raise ValueError(f"unknown ablation {ablate!r}; known: {CANDIDATES}")
    with jax.default_matmul_precision(precision):
        x = hidden_states(params, shape, tokens, ablate)
        return logits_of(params, x[:, -last:] if last else x)


def teacher_forced_logprobs(
    params: dict, shape: Shape, tokens: list, n_prompt: int, ablate: str = "",
) -> list:
    """Per sequence of ``tokens`` (all of one length), log p(tokens[t] |
    tokens[:t]) for every t >= n_prompt, from one full forward pass over
    the whole sequence."""
    if ablate and ablate not in CANDIDATES:
        raise ValueError(f"unknown ablation {ablate!r}; known: {CANDIDATES}")
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, shape, tokens, ablate)
        logp = jax.nn.log_softmax(
            logits_of(params, x[:, n_prompt - 1: -1]), axis=-1
        )
        targets = jnp.asarray(tokens, jnp.int32)[:, n_prompt:]
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return [[float(v) for v in row] for row in picked]


def shape_of(cfg: Any) -> Shape:
    """The engine's config under the published names, with its cut."""
    kinds = tuple(cfg.layer_kinds)
    if not kinds or set(kinds) - {SPARSE, LIGHTNING}:
        raise ValueError(
            f"this reference computes layers of kinds {SPARSE!r} and "
            f"{LIGHTNING!r}; the engine's config says {kinds!r}"
        )
    if not (cfg.qk_norm and cfg.attn_out_gate and cfg.lin_out_gate
            and cfg.lin_out_norm and cfg.lin_rope) or cfg.attn_rope:
        raise ValueError(
            "this reference has qk norms, both output gates, the lightning "
            "output norm, rotary values in the lightning layers only"
        )
    return Shape(
        mixer_types=kinds,
        first_layer=int(cfg.layer_offset),
        published_layers=len(cfg.published_layer_kinds) or len(kinds),
        hidden_size=cfg.d_model,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        lightning_nh=cfg.lin_heads,
        lightning_head_dim=cfg.lin_head_dim,
        rope_theta=float(cfg.rope_theta),
        rms_norm_eps=float(cfg.norm_eps),
        scale_emb=float(cfg.embed_scale),
        scale_depth=float(cfg.scale_depth),
        mup_denominator=int(cfg.mup_denominator),
        dim_model_base=int(cfg.dim_model_base),
        kernel_size=cfg.sparse_kernel,
        kernel_stride=cfg.sparse_stride,
        block_size=cfg.sparse_block,
        topk=cfg.sparse_topk,
        init_blocks=cfg.sparse_init_blocks,
        window_size=cfg.sparse_window,
        dense_len=cfg.sparse_dense_len,
    )


def reference_logprobs(
    engine: Any, sequences: list, n_prompt: int, ablate: str = "",
) -> list:
    """Per sequence, the reference's log-probability of every token after
    the prompt; None for a sequence on which removing ``ablate`` changes
    nothing (the selection's pieces, on a sequence within the dense length).
    Sequences of one length go through together, each by itself."""
    shape = shape_of(engine.cfg)
    out: list = [None] * len(sequences)
    for length in sorted({len(seq) for seq in sequences}):
        if changes_nothing(shape, ablate, length):
            continue
        group = [i for i, seq in enumerate(sequences) if len(seq) == length]
        found = teacher_forced_logprobs(
            engine.params, shape, [list(sequences[i]) for i in group],
            n_prompt, ablate,
        )
        for i, logprobs in zip(group, found):
            out[i] = logprobs
    return out

