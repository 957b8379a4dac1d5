"""Latent attention over a one-row cache and one chip's share of an expert
layer (ISSUE 33), served through the normal path.

The yardstick is ``benchmark/reference/latent_moe.py``, the plain float32
full forward written from the issue's equations with no import of the
program. Everything here runs the tiny preset ``mla-moe-tiny`` (1 dense + 2
expert layers, 8 experts of which 2 a token, 1 shared, sandwich norms, a
16 + 8 row a token in the cache) on seeded random weights whose norm scales
are drawn away from 1, so that a norm applied with another's weights, or
left out, shows.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.metrics.exposition import render_prometheus
from gofr_tpu.models.registry import get_model, register_model
from gofr_tpu.models.transformer import (
    TransformerConfig,
    _ffn_moe,
    _ffn_moe_grouped,
    _swiglu,
    init_lora,
    init_transformer,
    kv_cache_specs,
    moe_grouped_experts,
    moe_route,
    transformer_decode_step,
    transformer_forward,
    transformer_param_specs,
    transformer_prefill,
    transformer_prefill_chunk,
)
from gofr_tpu.ops.attention import (
    LATENT_CHUNK_BLOCK,
    chunk_block_counts,
    chunk_visit_ratio,
    decode_read_index,
    decode_read_plan,
    decode_read_rungs,
    latent_chunk_attention,
    latent_decode_attention,
)
from gofr_tpu.ops.kv_cache import KVCache, LatentKVCache
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

from benchmark.harness.cells import load_file

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_file(
    "latent_moe_reference_for_tests",
    os.path.join(CHECKOUT, "benchmark", "reference", "latent_moe.py"),
)

MODEL = "mla-moe-tiny-f32"
BASE = get_model("mla-moe-tiny").config
CFG = dataclasses.replace(BASE, dtype=jnp.float32)
register_model(dataclasses.replace(
    get_model("mla-moe-tiny"), name=MODEL, config=CFG
))
# One chip of two: experts 4..7 of the 8 and the shared expert.
SHARE = dataclasses.replace(CFG, n_experts_held=4, expert_share_index=1)

# Program and reference both compute in float32; what is left between them
# is the order of the reductions (the blocked running softmax, the decode
# step's split softmax, the grouped product's order of rows) through 3
# layers: 1e-5 at most here, against 0.1 and more for any piece removed.
LOGIT_TOLERANCE = 1e-4
ABLATED_AT_LEAST = 0.05
# bfloat16 against the float32 reference on this 3-layer stack: weights,
# activations and cache rows carry 8 bits of mantissa (relative 2^-9 a
# rounding), through ~30 roundings a layer a logit of magnitude ~1 moves by
# a few hundredths; a token whose two router scores nearly tie may take
# another expert than float32 does and move by more, so the limit is on the
# median row and leaves room for one such token. Any piece removed reads 0.2
# and more.
BF16_MEDIAN_TOLERANCE = 0.06


def seeded_params(cfg: TransformerConfig = CFG, seed: int = 0) -> dict:
    params = init_transformer(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)

    def away_from_one(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" not in name:
            return leaf
        fold = jax.random.fold_in(key, sum(map(ord, name)))
        return (leaf * (1.0 + 0.3 * jax.random.normal(fold, leaf.shape))).astype(
            leaf.dtype
        )

    return jax.tree_util.tree_map_with_path(away_from_one, params)


def tokens_of(seed: int, n: int, vocab: int = CFG.vocab_size) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(3, vocab, n)]


def share_of(params: dict, cfg: TransformerConfig) -> dict:
    """The whole model's weights cut to ``cfg``'s share of the experts."""
    lo, hi = cfg.held_range
    return {**params, "experts": [
        {name: w[lo:hi] for name, w in held.items()}
        for held in params["experts"]
    ]}


def reference_logits(params: dict, cfg: TransformerConfig, tokens: list,
                     ablate: str = "") -> np.ndarray:
    return np.asarray(reference.full_logits(
        params, reference.shape_of(cfg), [tokens], ablate
    )[0])


@pytest.fixture(scope="module")
def params():
    return seeded_params()


def serve(params, cfg, seqs, *, chunk=8, n_prompt=24, max_len=64):
    """Chunked prefill then decode through a latent cache: {position: [rows,
    vocab] logits} at each chunk's last token and every decoded token."""
    rows, n_total = len(seqs), len(seqs[0])
    toks = jnp.asarray(seqs, jnp.int32)
    cache = LatentKVCache.create(
        cfg.n_cache_entries, rows + 1, max_len, cfg.cache_row, cfg.dtype
    )
    slots = jnp.arange(1, rows + 1, dtype=jnp.int32)
    prefill_chunk = jax.jit(transformer_prefill_chunk, static_argnames="cfg")
    decode_step = jax.jit(transformer_decode_step, static_argnames="cfg")
    served = {}
    for start in range(0, n_prompt, chunk):
        logits, cache = prefill_chunk(
            params, toks[:, start:start + chunk], cache, slots,
            jnp.full((rows,), start, jnp.int32),
            jnp.full((rows,), chunk, jnp.int32), cfg=cfg,
        )
        served[start + chunk - 1] = np.asarray(logits, np.float32)
    cache = cache._replace(lengths=cache.lengths.at[slots].set(n_prompt))
    active = jnp.zeros((rows + 1,), bool).at[slots].set(True)
    for pos in range(n_prompt, n_total):
        step_tokens = jnp.zeros((rows + 1,), jnp.int32).at[slots].set(toks[:, pos])
        logits, cache = decode_step(params, step_tokens, cache, active, cfg=cfg)
        served[pos] = np.asarray(logits[1:], np.float32)
    assert np.asarray(cache.lengths).tolist() == [0] + [n_total] * rows
    return served


@pytest.mark.parametrize("cfg", [
    pytest.param(CFG, id="all-experts-held"),
    pytest.param(SHARE, id="share-4-of-8"),
])
def test_chunked_prefill_then_cached_decode_gives_the_reference_logits(
    params, cfg,
):
    """Two rows, a 24-token prompt each in three chunks of 8 and then 8
    absorbed decode steps through the latent cache: the logits at every
    position the serving path computes them for are the reference's (given
    the same share), and with any one of its pieces removed they are not."""
    held = share_of(params, cfg)
    seqs = [tokens_of(11 + r, 32) for r in range(2)]
    served = serve(held, cfg, seqs)

    def worst(ablate: str) -> float:
        want = [reference_logits(held, cfg, seq, ablate) for seq in seqs]
        return max(
            float(np.max(np.abs(got[r] - want[r][pos])))
            for r in range(2) for pos, got in served.items()
        )

    assert worst("") <= LOGIT_TOLERANCE
    for ablate in reference.CANDIDATES:
        assert worst(ablate) >= ABLATED_AT_LEAST, ablate
    # ... and the test-only full forward is the same function.
    full = transformer_forward(held, jnp.asarray(seqs, jnp.int32), cfg)
    want = np.stack([reference_logits(held, cfg, seq) for seq in seqs])
    assert float(np.max(np.abs(np.asarray(full) - want))) <= LOGIT_TOLERANCE


def test_bfloat16_serving_stays_within_its_stated_tolerance():
    cfg = dataclasses.replace(SHARE, dtype=jnp.bfloat16)
    held = share_of(seeded_params(dataclasses.replace(CFG, dtype=jnp.bfloat16)), cfg)
    seqs = [tokens_of(21 + r, 32) for r in range(2)]
    served = serve(held, cfg, seqs)

    def median_row(ablate: str) -> float:
        want = [reference_logits(held, cfg, seq, ablate) for seq in seqs]
        return float(np.median([
            np.median(np.abs(got[r] - want[r][pos]))
            for r in range(2) for pos, got in served.items()
        ]))

    assert median_row("") <= BF16_MEDIAN_TOLERANCE
    for ablate in reference.CANDIDATES:
        assert median_row(ablate) > 2 * BF16_MEDIAN_TOLERANCE, ablate


# ---------------------------------------------------------------------------
# the attention ops
# ---------------------------------------------------------------------------

RANK, ROPE, NOPE, VD, HEADS = 16, 8, 16, 16, 4


def random_plane(key, entries=2, slots=3, max_len=64):
    """A latent plane as the cache holds it: content, zeros to the lane tile."""
    content = jax.random.normal(key, (entries, slots, 1, max_len, RANK + ROPE))
    width = LatentKVCache.width_for(RANK + ROPE)
    return jnp.pad(content, [(0, 0)] * 4 + [(0, width - RANK - ROPE)])


def test_absorbed_and_expanded_chunk_attention_agree_blocked_or_not():
    """The loop over blocks of positions is the unblocked mathematics
    (``block`` >= max_len is one step), and the two forms are one function:
    the decode step's absorbed attention at a chunk row's last position,
    through the value up-projection, is the prefill's expanded one."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    plane = random_plane(ks[0])
    P, c = 2, 8
    slots, starts, lens = (jnp.array([2, 0]), jnp.array([16, 40]),
                           jnp.array([8, 5]))
    q = jax.random.normal(ks[1], (P, c, HEADS, NOPE + ROPE))
    w_uk = jax.random.normal(ks[2], (RANK, HEADS, NOPE)) * RANK**-0.5
    w_uv = jax.random.normal(ks[3], (RANK, HEADS, VD)) * RANK**-0.5
    scale, layer = (NOPE + ROPE) ** -0.5, jnp.int32(1)
    expanded = {
        block: latent_chunk_attention(
            q, plane, slots, starts, lens, w_uk, w_uv, scale=scale,
            layer=layer, block=block,
        ) for block in (16, 64)
    }
    np.testing.assert_allclose(expanded[16], expanded[64], atol=2e-5)
    # rows past a chunk's valid tokens return 0, and the valid ones do not
    assert float(jnp.abs(expanded[16][1, 5:]).max()) == 0.0
    assert float(jnp.abs(expanded[16][1, :5]).min()) > 0.0
    # one entry handed in by itself is the stacked plane's entry
    alone = latent_chunk_attention(
        q, plane[1], slots, starts, lens, w_uk, w_uv, scale=scale, block=16
    )
    np.testing.assert_allclose(alone, expanded[16], atol=1e-6)
    # absorbed: each row's last query projected into the latent space, the
    # cache read as it lies, the current token's row attended beside it
    last = starts + lens - 1  # [P] the last query's position
    q_last = q[jnp.arange(P), lens - 1]  # [P, H, nope + rope]
    q_abs = jnp.concatenate([
        jnp.einsum("phn,rhn->phr", q_last[..., :NOPE], w_uk), q_last[..., NOPE:]
    ], axis=-1)
    n_slots = plane.shape[1]
    absorbed = latent_decode_attention(
        jnp.zeros((n_slots, HEADS, RANK + ROPE)).at[slots].set(q_abs), plane,
        jnp.ones((n_slots,), jnp.int32).at[slots].set(last),
        plane[1, :, 0, :, :RANK + ROPE][
            jnp.arange(n_slots),
            jnp.zeros((n_slots,), jnp.int32).at[slots].set(last),
        ],
        rank=RANK, scale=scale, layer=layer,
    )
    np.testing.assert_allclose(
        jnp.einsum("shr,rhv->shv", absorbed, w_uv)[slots],
        expanded[64][jnp.arange(P), lens - 1], atol=2e-5,
    )


# A step as the scheduler fills one, a row a slot, each at its own depth
# (max_len 128 in blocks of 16, chunk 8): a row at 0; one that ends exactly
# on a block's edge; one a token past an edge; one in max_len's last block;
# one with no token; one with a partial chunk; two that duplicate row 0, as
# the scheduler pads a step.
ROWS_APART = dict(
    slots=np.array([2, 0, 1, 3, 4, 5, 2, 2]),
    starts=np.array([0, 24, 25, 116, 40, 64, 0, 0]),
    lens=np.array([8, 8, 8, 8, 0, 5, 8, 8]),
)
ROWS_APART_BLOCKS = [1, 2, 3, 8, 0, 5, 1, 1]  # each row's own, of 16


def beyond_own_blocks_poisoned(plane, rows=ROWS_APART, block=16):
    """``plane`` ([..., S, heads, max_len, width]) with NaN at every
    position past the last block that a row of its slot attends: a loop that
    ran a row one block beyond its own would return NaN (0 x NaN)."""
    plane = np.array(plane)
    reach = np.zeros(plane.shape[-4], np.int64)
    np.maximum.at(
        reach, rows["slots"],
        chunk_block_counts(rows["starts"], rows["lens"], block) * block,
    )
    for slot, upto in enumerate(reach):
        plane[..., slot, :, upto:, :] = np.nan
    return jnp.asarray(plane)


def test_the_block_steps_of_a_step_are_the_sum_of_its_rows_own():
    """The pure function the loops take their trip counts from, and the
    ratio the scheduler's histogram records for the same starts and lens."""
    starts, lens = ROWS_APART["starts"], ROWS_APART["lens"]
    counts = chunk_block_counts(starts, lens, 16)
    assert counts.tolist() == ROWS_APART_BLOCKS
    assert chunk_block_counts(
        jnp.asarray(starts), jnp.asarray(lens), 16
    ).tolist() == ROWS_APART_BLOCKS
    assert chunk_visit_ratio(starts, lens, 16) == sum(ROWS_APART_BLOCKS) / (8 * 8)
    # every row as deep as the deepest, and a lone row: nothing to skip
    assert chunk_visit_ratio(np.full(8, 512), np.full(8, 256), 512) == 1.0
    assert chunk_visit_ratio(np.array([4096]), np.array([256]), 512) == 1.0
    # a block that is the whole slot: one step a row
    assert chunk_visit_ratio(starts, lens, 128) == 7 / 8
    assert chunk_visit_ratio(np.zeros(2, int), np.zeros(2, int), 16) == 0.0


@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "one_entry"])
def test_rows_far_apart_each_visit_their_own_blocks_and_no_more(stacked):
    """The loop bounded a row is the one-step mathematics (``block`` >=
    max_len) in float32, and a row never touches a block past its own last
    position: those hold NaN here."""
    ks = jax.random.split(jax.random.PRNGKey(36), 4)
    plane = random_plane(ks[0], entries=2, slots=6, max_len=128)
    slots, starts, lens = (jnp.asarray(a) for a in ROWS_APART.values())
    q = jax.random.normal(ks[1], (8, 8, HEADS, NOPE + ROPE))
    q = q.at[6:].set(q[0])  # a padding row holds row 0's tokens too
    w_uk = jax.random.normal(ks[2], (RANK, HEADS, NOPE)) * RANK**-0.5
    w_uv = jax.random.normal(ks[3], (RANK, HEADS, VD)) * RANK**-0.5
    scale = (NOPE + ROPE) ** -0.5
    entry = lambda pl: (pl, jnp.int32(1)) if stacked else (pl[1], None)  # noqa: E731

    def attend(plane, block):
        plane, layer = entry(plane)
        return latent_chunk_attention(
            q, plane, slots, starts, lens, w_uk, w_uv, scale=scale,
            layer=layer, block=block,
        )

    one_step = attend(plane, 128)
    bounded = attend(beyond_own_blocks_poisoned(plane), 16)
    assert bool(jnp.all(jnp.isfinite(bounded)))
    np.testing.assert_allclose(bounded, one_step, atol=2e-5)
    assert float(jnp.abs(bounded[4]).max()) == 0.0  # the row with no token
    assert float(jnp.abs(bounded[5, 5:]).max()) == 0.0  # the partial chunk
    np.testing.assert_array_equal(bounded[6], bounded[0])  # the duplicates
    np.testing.assert_array_equal(bounded[7], bounded[0])


def test_the_bounded_decode_read_equals_the_whole_read_at_every_rung():
    """At 512 positions the rungs are 128 / 256 / 384 / 512: a slot that
    fits its rung reads the same attention as over the whole cache."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    max_len, slots = 512, 3
    plane = random_plane(ks[0], entries=2, slots=slots, max_len=max_len)
    q = jax.random.normal(ks[1], (slots, HEADS, RANK + ROPE))
    row_new = jax.random.normal(ks[2], (slots, RANK + ROPE))
    rungs = decode_read_rungs(max_len)
    assert rungs == (128, 256, 384, 512)
    assert decode_read_plan(max_len, latent=True) == rungs
    # a latent cache never goes to a kernel, whatever its length
    assert decode_read_plan(8192, latent=True) == (2048, 4096, 6144, 8192)
    kw = dict(rank=RANK, scale=0.2, layer=jnp.int32(1))
    for i, rung in enumerate(rungs):
        lengths = jnp.array([rung, rung // 2, 1])
        assert decode_read_index(rungs, rung) == i
        whole = latent_decode_attention(q, plane, lengths, row_new, **kw)
        bounded = latent_decode_attention(
            q, plane, lengths, row_new, read=jnp.int32(i), **kw
        )
        assert whole.shape == (slots, HEADS, RANK)
        np.testing.assert_allclose(bounded, whole, atol=1e-6)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def expert_layer_of(params, l=0):
    """One expert layer: (its leaves of the stack, its own experts' leaves)."""
    return {k: v[l] for k, v in params["layers"].items()}, params["experts"][l]


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Over the 8 / 2 disjoint held ranges, the routed parts summed and the
    shared expert counted once equal the uncut layer: in the program and in
    the reference alike."""
    lp, held = expert_layer_of(params)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, CFG.d_model))
    whole, _ = _ffn_moe_grouped(x, lp, CFG, experts=[held])
    shared = _swiglu(
        x.reshape(-1, CFG.d_model), lp["ws_gate"], lp["ws_up"], lp["ws_down"],
        CFG,
    ).reshape(x.shape)
    routed, held_routes = 0.0, 0
    for index in range(4):
        cfg = dataclasses.replace(CFG, n_experts_held=2, expert_share_index=index)
        lo, hi = cfg.held_range
        assert (lo, hi) == (2 * index, 2 * index + 2)
        mine = {n: w[lo:hi] for n, w in held.items()}
        part, (held_rows, load) = _ffn_moe_grouped(x, lp, cfg, experts=[mine])
        routed = routed + (part - shared)
        held_routes += int(held_rows.sum())
        assert int(load.sum()) == int(held_rows.sum())
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)
    # every route landed on exactly one share
    assert held_routes == 2 * 9 * CFG.n_experts_active

    shape = reference.shape_of(CFG)
    w = {k: np.asarray(v, np.float32) for k, v in {**lp, **held}.items()}
    b = np.asarray(x[0], np.float32)
    gates = reference.gates_of(b, w["router"], shape, "")
    by_expert = [
        gates[:, e:e + 1] * reference.swiglu(
            b, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        for e in range(8)
    ]
    uncut = reference.swiglu(b, w["ws_gate"], w["ws_up"], w["ws_down"]) + sum(by_expert)
    np.testing.assert_allclose(uncut, whole[0], atol=2e-5)


def test_the_grouped_product_is_the_dense_einsum_on_the_same_gates(params):
    lp, held = expert_layer_of(params, 1)
    cfg = SHARE
    lo, hi = cfg.held_range
    mine = {n: w[lo:hi] for n, w in held.items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (40, CFG.d_model))
    idx, gates = moe_route(x, lp["router"], cfg)
    assert gates.dtype == jnp.float32
    # normalised over ALL the chosen, held here or not, then the routed scale
    np.testing.assert_allclose(gates.sum(-1), cfg.routed_scale, rtol=1e-6)
    # this layer's leaves picked out of two layers' by the traced index
    other = jax.tree.map(jnp.zeros_like, mine)
    got, held, sizes = jax.jit(
        lambda layer: moe_grouped_experts(x, idx, gates, [other, mine], cfg, layer)
    )(jnp.int32(1))
    weights = jnp.zeros((40, hi - lo + 1)).at[
        jnp.arange(40)[:, None], jnp.where(held, idx - lo, hi - lo)
    ].add(jnp.where(held, gates, 0.0))[:, : hi - lo]
    hidden = jax.nn.silu(jnp.einsum("td,edf->tef", x, mine["w_gate"])) * (
        jnp.einsum("td,edf->tef", x, mine["w_up"])
    )
    want = jnp.einsum(
        "ted,te->td", jnp.einsum("tef,efd->ted", hidden, mine["w_down"]), weights
    )
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(sizes.sum()) == int(held.sum()) < idx.size


def test_no_token_is_dropped_when_every_row_goes_to_one_expert(params):
    """A router that sends all 40 rows to held expert 5 (and to absent
    expert 0): the one expert gets all 40, with no capacity to overflow."""
    _, held = expert_layer_of(params)
    cfg = SHARE
    lo, hi = cfg.held_range
    mine = {n: w[lo:hi] for n, w in held.items()}
    x = jax.random.normal(jax.random.PRNGKey(7), (40, CFG.d_model))
    idx = jnp.tile(jnp.array([[5, 0]]), (40, 1))
    gates = jnp.tile(jnp.array([[1.5, 1.0]]), (40, 1))
    got, held, sizes = moe_grouped_experts(x, idx, gates, [mine], cfg)
    assert sizes.tolist() == [0, 40, 0, 0] and int(held.sum()) == 40
    e = 5 - lo
    want = 1.5 * _swiglu(
        x, mine["w_gate"][e], mine["w_up"][e], mine["w_down"][e], cfg
    )
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got).min(axis=1).max()) > 0  # every row has its part


def shapes_in(jaxpr) -> list:
    """(primitive, output shape, output dtype) of every equation, through
    every nested jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                found.append((eqn.primitive.name, tuple(var.aval.shape),
                              var.aval.dtype, eqn))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += shapes_in(inner)
    return found


def test_no_product_over_held_x_rows_and_no_whole_score_array(params):
    """The served programs of a share at 512 positions: the expert weights
    meet the rows in ``ragged_dot`` alone (no dot_general over an operand of
    the expert leaves' shape, which is what ``held x rows`` would be), and no
    float32 array holds rows x heads x chunk x max_len scores."""
    cfg = SHARE
    held = share_of(params, cfg)
    rows, c, max_len, slots = 2, 32, 512, 3
    cache = LatentKVCache.create(
        cfg.n_cache_entries, slots, max_len, cfg.cache_row, cfg.dtype
    )
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    prefill = jax.make_jaxpr(
        lambda p, k: transformer_prefill_chunk(
            p, i32(rows, c), LatentKVCache(k, i32(slots)), i32(rows), i32(rows),
            jnp.full((rows,), c, jnp.int32), cfg, stats=True,
        )
    )(held, cache.k)
    decode = jax.make_jaxpr(
        lambda p, k: transformer_decode_step(
            p, i32(slots), LatentKVCache(k, i32(slots)),
            jnp.ones((slots,), bool), cfg, stats=True,
        )
    )(held, cache.k)
    leaf_shapes = {tuple(w.shape) for w in held["experts"][0].values()}
    assert all(  # the expert leaves are operands of their own, not a stack
        n not in held["layers"] for n in ("w_gate", "w_up", "w_down"))
    for program, n_rows in ((prefill, rows * c), (decode, slots)):
        eqns = shapes_in(program.jaxpr)
        ragged = [e for name, *_, e in eqns if name == "ragged_dot_general"]
        # one scanned expert layer body, a branch for each layer's leaves
        assert len(ragged) == 3 * cfg.n_moe_layers
        for name, shape, dtype, eqn in eqns:
            if name == "dot_general":
                operands = {tuple(v.aval.shape) for v in eqn.invars}
                assert not operands & leaf_shapes, (name, operands)
            # held x rows x width, in any order of the three
            assert not (
                {cfg.experts_held, n_rows * cfg.n_experts_active}
                <= set(shape) and cfg.expert_width in shape
            ), (name, shape)
            # never scores of heads x chunk x every position at once
            assert not {cfg.n_heads, c, 2048} <= set(shape), (name, shape)
    # at 2,048 positions the prefill scores are alive a block at a time,
    longer = LatentKVCache.create(
        cfg.n_cache_entries, slots, 2048, cfg.cache_row, cfg.dtype
    )
    blocked = jax.make_jaxpr(
        lambda p, k: transformer_prefill_chunk(
            p, i32(rows, c), LatentKVCache(k, i32(slots)), i32(rows), i32(rows),
            jnp.full((rows,), c, jnp.int32), cfg,
        )
    )(held, longer.k)
    # a row's own: no float32 array of the loop keeps the step's rows axis
    scores = [
        shape for _, shape, dtype, _ in shapes_in(blocked.jaxpr)
        if dtype == jnp.float32 and shape[-3:-1] == (cfg.n_heads, c)
    ]
    assert (cfg.n_heads, c, LATENT_CHUNK_BLOCK) in scores
    assert (rows, cfg.n_heads, c, LATENT_CHUNK_BLOCK) not in scores
    assert not any(2048 in shape for shape in scores)
    for name, shape, _, _ in shapes_in(blocked.jaxpr):
        assert not {cfg.n_heads, c, 2048} <= set(shape), (name, shape)


# ---------------------------------------------------------------------------
# the cache, the engine, the refusals
# ---------------------------------------------------------------------------


def engine_of(model: str = MODEL, **kw):
    kw = {"n_slots": 2, "max_len": 128, "prefill_chunk": 16, "window_k": 4,
          "pipeline_depth": 1, **kw}
    return InferenceEngine(model, tokenizer=ByteTokenizer(), **kw)


@pytest.fixture(scope="module")
def engine(params):
    # Container registration is the real instrument set, the one /metrics
    # renders and the benchmark's readers parse.
    metrics = Container.create(MockConfig({"APP_NAME": "latent-test"})).metrics
    e = engine_of(params=params, metrics=metrics)
    e.start_sync()
    yield e
    e.close()


def test_the_engines_programs_serve_the_references_log_probabilities(engine):
    """Through submit -> chunked prefill (a 40-token prompt in chunks of 16)
    -> decode windows over the latent cache: the log-probability the engine
    reports for each greedy token is the reference's teacher-forced one;
    with any of the reference's pieces removed it is not."""
    prompt = tokens_of(5, 40)
    result = engine.generate_sync(
        prompt, max_new_tokens=12, temperature=0.0, stop_on_eos=False,
        timeout=300,
    )
    assert len(result.token_ids) == 12
    sequence = prompt + result.token_ids

    def worst(ablate: str) -> float:
        want = reference.reference_logprobs(engine, [sequence], len(prompt), ablate)
        return max(abs(a - b) for a, b in zip(result.token_logprobs, want[0]))

    assert worst("") <= LOGIT_TOLERANCE
    for ablate in reference.CANDIDATES:
        assert worst(ablate) >= ABLATED_AT_LEAST, ablate
    # still exactly the two serving programs, both rungs compiled at boot
    programs = engine.compile_stats()["programs"]
    assert set(programs) == {"prefill_chunk", "decode_window"}


def test_the_cache_is_one_row_a_token_and_says_its_bytes(engine):
    published = get_model("openpangu-ultra-moe-718b").config
    share = dataclasses.replace(
        published, n_layers=5, n_dense_layers=1, n_experts_held=16,
        vocab_size=19200,
    )
    # entries x (kv rank + rope width) x 2 B of content a token ...
    assert share.n_cache_entries == 5 and share.cache_row == 512 + 64
    assert share.kv_bytes_per_token == 5 * 576 * 2 == 5_760
    assert published.kv_bytes_per_token == 61 * 576 * 2
    # ... allocated in whole 128-lane tiles: 640 wide
    assert LatentKVCache.width_for(576) == 640
    assert get_model("mistral-7b").config.kv_bytes_per_token == 131_072
    cache = engine.cache
    assert isinstance(cache, LatentKVCache) and cache.v is None
    assert cache.k.shape == (3, 2, 1, 128, 128) and not cache.quantized
    assert CFG.kv_bytes_per_token == 3 * 24 * 4
    assert engine.kv_bytes_per_token() == 3 * 128 * 4 == cache.hbm_bytes() // 256
    assert engine.health_check()["details"]["kv_bytes_per_token"] == 3 * 128 * 4
    assert engine.decode_read_rungs == decode_read_rungs(128)


def counter(metrics, name: str, **labels) -> float:
    from benchmark.harness import prom

    series = prom.parse(render_prometheus(metrics)).get(name, {})
    return sum(
        value for text, value in series.items()
        if all(f'{k}="{v}"' in text for k, v in labels.items())
    )


def test_the_route_counters_and_span_attributes_read_under_load(engine):
    metrics = engine._metrics
    before = {
        where: counter(metrics, "app_tpu_moe_routes_total", where=where)
        for where in ("held", "absent")
    }
    records = counter(metrics, "app_tpu_moe_expert_load_ratio_count")
    prompt = tokens_of(8, 33)
    engine.generate_sync(
        prompt, max_new_tokens=8, temperature=0.0, stop_on_eos=False, timeout=300
    )
    # all 8 experts are held by this engine: every route lands, and a
    # route is a computed token x 2 expert layers x 2 chosen experts. The
    # prompt's 33 tokens, then whole windows of 4 steps. The request's
    # future resolves inside the last window's processing, a moment before
    # that window's routes are counted: wait for them.
    deadline = time.monotonic() + 30
    while True:
        after = {
            where: counter(metrics, "app_tpu_moe_routes_total", where=where)
            for where in ("held", "absent")
        }
        held, absent = (after[w] - before[w] for w in ("held", "absent"))
        if held >= (33 + 8 - 1) * 2 * 2 or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    assert absent == 0 and held >= (33 + 8 - 1) * 2 * 2
    assert held % (2 * 2) == 0
    # one record of the load ratio a prefill step: 33 tokens in chunks of 16
    assert counter(metrics, "app_tpu_moe_expert_load_ratio_count") - records == 3
    assert engine._obs.model_attrs == {
        "experts_held": 8, "router_width": 8, "cache_row": 24}
    assert engine_of("llama-tiny")._obs.model_attrs == {}
    # the cache's own series read for a latent cache as for any other
    assert counter(metrics, "app_tpu_kv_bytes_per_token") == 3 * 128 * 4
    assert counter(metrics, "app_tpu_kv_live_ratio_count") > 0
    assert counter(metrics, "app_tpu_decode_read_ratio_count") > 0


@pytest.mark.parametrize("kw,says", [
    ({"kv_block": 16}, "TPU_KV_BLOCK > 0 (the paged pool) is not served"),
    ({"kv_block": 16, "auto_prefix": True}, "TPU_KV_BLOCK > 0"),
    ({"auto_prefix": True}, "TPU_AUTO_PREFIX (the radix prefix cache) is not served"),
    ({"prefix_slots": 2}, "TPU_PREFIX_SLOTS > 0 (the prefix pool) is not served"),
    ({"kv_quant": "int8"}, "TPU_KV_QUANT=int8 is not served"),
    ({"tp": 2}, "TPU_TP > 1 (or a mesh) is not served"),
    ({"lora_slots": 2}, "TPU_LORA_SLOTS > 0 (targets 'wq,wk,wv,wo') is not served"),
])
def test_what_a_latent_cache_cannot_serve_is_refused_at_boot(kw, says):
    with pytest.raises(ValueError, match="latent attention keeps one 24-value row") as info:
        engine_of(**kw)
    assert says in str(info.value)


def test_weight_quantisation_is_refused_for_the_grouped_product():
    with pytest.raises(ValueError, match="TPU_QUANT=int8 is not served: the grouped"):
        engine_of(quant="int8")


def test_the_refusals_outside_the_constructor(engine):
    with pytest.raises(ValueError, match="pipeline-parallel parameter specs"):
        transformer_param_specs(CFG, pp=True)
    specs = transformer_param_specs(CFG)
    assert set(specs) >= {"dense_layers", "layers"}
    assert "router" in specs["layers"] and "router" not in specs["dense_layers"]
    with pytest.raises(ValueError, match="a latent cache has no partition specs"):
        kv_cache_specs(latent=True)
    with pytest.raises(ValueError, match="LoRA serving does not support MoE"):
        init_lora(CFG, 2, 4)
    with pytest.raises(ValueError, match="KV export / import payloads"):
        engine.set_tier_exporter(lambda request, payload: True)
    engine.set_tier_exporter(None)
    assert engine.import_payload(object()) == "fused"  # never a wrong answer
    with pytest.raises(ValueError, match="transformer_prefill_chunk only"):
        transformer_prefill(
            None, jnp.zeros((1, 8), jnp.int32), jnp.array([8]), engine.cache,
            jnp.array([0]), CFG,
        )
    with pytest.raises(ValueError, match="served over a LatentKVCache"):
        transformer_decode_step(
            engine.params, jnp.zeros((2,), jnp.int32),
            KVCache.create(3, 2, 128, 4, 24, jnp.float32),
            jnp.ones((2,), bool), CFG,
        )
    # Mixtral's stacked layer refuses a share instead of computing held x
    # rows: a share is "ragged" at every shape, where a stacked all-held
    # layer picks its product from the step's rows (test_moe_tiled_product)
    rows = (1, 32, 2048)
    assert {c.expert_product(r) for c in (CFG, SHARE) for r in rows} == {"ragged"}
    assert not CFG.experts_stacked and get_model("moe-tiny").config.experts_stacked
    assert [get_model("moe-tiny").config.expert_product(r) for r in rows] == [
        "einsum", "einsum", "tiles"]
    with pytest.raises(ValueError, match="go through _ffn_moe_grouped"):
        _ffn_moe(jnp.zeros((1, 4, CFG.d_model)), {}, SHARE)
