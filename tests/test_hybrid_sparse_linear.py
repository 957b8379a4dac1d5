"""A stack of two kinds of mixer, lightning linear attention and block-sparse
attention selected per query (ISSUE 35: MiniCPM-SALA), served through the
normal path over a ``HybridCache``.

The yardstick is ``benchmark/reference/hybrid_sparse_linear.py``, the plain
float32 full forward written from the issue's equations with no import of
the program (the lightning layers as their quadratic sum, the selection as
written). Everything here runs the tiny preset ``sala-tiny`` (8 layers in
runs of 1, 2, 2, 3; dense under 32 positions, blocks of 4, top 4 with the
first block and the last two forced, compressed keys over 4 keys every 2) in
float32 on seeded random weights whose norm scales are drawn away from 1, so
that every branch is reached within 128 positions and a norm applied with
another's weights, or left out, shows.
"""

from __future__ import annotations

import dataclasses
import json
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
    LIN_KIND,
    SPARSE_KIND,
    Kinds,
    TransformerConfig,
    init_transformer,
    transformer_decode_step,
    transformer_forward,
    transformer_param_specs,
    transformer_prefill,
    transformer_prefill_chunk,
)
from gofr_tpu.ops.attention import (
    cache_chunk_attention,
    sparse_block_scores,
    sparse_chunk_attention,
)
from gofr_tpu.ops.kv_cache import HybridCache, KVCache, LatentKVCache
from gofr_tpu.ops.linear_attention import (
    lightning_chunk,
    lightning_log_decay,
    lightning_step,
)
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

from benchmark.harness.cells import load_file
from tests.test_latent_moe import ROWS_APART, beyond_own_blocks_poisoned

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_file(
    "hybrid_reference_for_tests",
    os.path.join(CHECKOUT, "benchmark", "reference", "hybrid_sparse_linear.py"),
)

MODEL = "sala-tiny-f32"
CFG = dataclasses.replace(get_model("sala-tiny").config, dtype=jnp.float32)
register_model(dataclasses.replace(get_model("sala-tiny"), name=MODEL, config=CFG))

# Program and reference both compute in float32; what is left between them is
# the order of the reductions (the blocked running softmax, the decode step's
# split softmax, the chunk-wise form against the quadratic sum) through 8
# layers: 1e-6 here, against 0.15 and more for any piece removed.
LOGIT_TOLERANCE = 1e-4
ABLATED_AT_LEAST = 0.05


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


def reference_logits(params: dict, cfg: TransformerConfig, tokens: list,
                     ablate: str = "") -> np.ndarray:
    return np.asarray(reference.full_logits(
        params, reference.shape_of(cfg), [tokens], ablate
    )[0])


def cache_of(cfg: TransformerConfig, slots: int, max_len: int) -> HybridCache:
    return HybridCache.for_config(cfg, slots, max_len)


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def steps():
    """The two traced steps, jitted once for the module."""
    return (
        jax.jit(transformer_prefill_chunk,
                static_argnames=("cfg", "stats")),
        jax.jit(transformer_decode_step, static_argnames=("cfg", "stats")),
    )


def serve(steps, params, cfg, seqs, n_prompt, *, chunk=16, rows=3, slots=None,
          max_len=128, n_slots=4):
    """Chunked prefill of prompts of UNEQUAL length in steps of ``rows``
    rows (the rows that have no chunk left are padding rows that duplicate
    row 0), then decode steps through the cache, over a cache whose planes
    a former occupant left dirty. Returns {(sequence, position): logits}
    and the per-step counts."""
    prefill_chunk, decode_step = steps
    slots = slots or list(range(1, len(seqs) + 1))
    cache = cache_of(cfg, n_slots, max_len)
    cache = cache._replace(
        state=cache.state + 3.0, ck=cache.ck + 5.0, k=cache.k + 7.0,
        v=cache.v - 2.0,
    )
    served, selected = {}, 0
    done = [0] * len(seqs)
    while any(d < n for d, n in zip(done, n_prompt)):
        waiting = [i for i in range(len(seqs)) if done[i] < n_prompt[i]]
        tk = np.zeros((rows, chunk), np.int32)
        sl, st, ln = (np.zeros((rows,), np.int32) for _ in range(3))
        valid = np.zeros((rows,), bool)
        for r, i in enumerate(waiting):
            n = min(chunk, n_prompt[i] - done[i])
            tk[r, :n] = seqs[i][done[i]:done[i] + n]
            sl[r], st[r], ln[r], valid[r] = slots[i], done[i], n, True
        for r in range(len(waiting), rows):  # padding rows duplicate row 0
            tk[r], sl[r], st[r], ln[r] = tk[0], sl[0], st[0], ln[0]
        logits, cache, counts = prefill_chunk(
            params, jnp.asarray(tk), cache, jnp.asarray(sl), jnp.asarray(st),
            jnp.asarray(ln), cfg=cfg, row_valid=jnp.asarray(valid), stats=True,
        )
        selected += int(np.asarray(counts).sum())
        for r, i in enumerate(waiting):
            done[i] += int(ln[r])
            served[i, done[i] - 1] = np.asarray(logits[r], np.float32)
    lengths = np.zeros((n_slots,), np.int32)
    active = np.zeros((n_slots,), bool)
    for i, slot in enumerate(slots):
        lengths[slot], active[slot] = n_prompt[i], True
    cache = cache._replace(lengths=jnp.asarray(lengths))
    attended = []
    for step in range(max(len(s) - n for s, n in zip(seqs, n_prompt))):
        tk = np.zeros((n_slots,), np.int32)
        for i, slot in enumerate(slots):
            pos = n_prompt[i] + step
            active[slot] = pos < len(seqs[i])
            tk[slot] = seqs[i][pos] if active[slot] else 0
        logits, cache, counts = decode_step(
            params, jnp.asarray(tk), cache, jnp.asarray(active), cfg=cfg,
            stats=True,
        )
        attended.append([np.asarray(c) for c in counts])
        for i, slot in enumerate(slots):
            if active[slot]:
                served[i, n_prompt[i] + step] = np.asarray(
                    logits[slot], np.float32
                )
    return served, selected, attended


def worst(served: dict, want: list) -> float:
    return max(
        float(np.max(np.abs(got - want[i][pos])))
        for (i, pos), got in served.items()
    )


@pytest.mark.parametrize("chunk,rows", [(16, 3), (8, 2), (32, 4)])
def test_chunked_prefill_then_cached_decode_gives_the_reference_logits(
    params, steps, chunk, rows,
):
    """Two prompts of 90 and 37 tokens (both cross the dense length of 32,
    at other places of their chunks), chunks with a padded tail, steps with a
    padding row, planes a former occupant left dirty; then 10 decode steps
    through the cache: the logits at every position the serving path
    computes them for are the reference's full forward's."""
    seqs = [tokens_of(11, 100), tokens_of(12, 47)]
    served, selected, attended = serve(
        steps, params, CFG, seqs, [90, 37], chunk=chunk, rows=rows,
        slots=[3, 1],
    )
    want = [reference_logits(params, CFG, seq) for seq in seqs]
    assert worst(served, want) <= LOGIT_TOLERANCE
    # what the steps count beside their tokens: the prompt positions past
    # the dense length, whatever the chunks; each decode query's attended
    # positions (at most 4 blocks of 4, its own block part full) and context
    assert selected == (90 - 32) + (37 - 32)
    through, context = attended[0]
    assert context.tolist() == [0, 38, 0, 91]
    assert 13 <= through[1] <= 16 and 13 <= through[3] <= 16


@pytest.mark.parametrize("ablate", reference.CANDIDATES)
def test_each_piece_removed_moves_the_logits(params, steps, ablate):
    seqs = [tokens_of(21, 100)]
    served, _, _ = serve(steps, params, CFG, seqs, [90])
    want = [reference_logits(params, CFG, seqs[0], ablate)]
    assert worst(served, want) >= ABLATED_AT_LEAST, ablate


def test_the_full_forward_is_the_same_function(params):
    seqs = [tokens_of(31 + r, 100) for r in range(2)]
    full = transformer_forward(params, jnp.asarray(seqs, jnp.int32), CFG)
    want = np.stack([reference_logits(params, CFG, seq) for seq in seqs])
    assert float(np.max(np.abs(np.asarray(full) - want))) <= LOGIT_TOLERANCE
    with pytest.raises(ValueError, match="transformer_prefill_chunk only"):
        transformer_prefill(
            params, jnp.zeros((1, 8), jnp.int32), jnp.full((1,), 8),
            cache_of(CFG, 1, 32), jnp.zeros((1,), jnp.int32), CFG,
        )
    with pytest.raises(ValueError, match="no partition specs"):
        transformer_param_specs(CFG)
    with pytest.raises(ValueError, match="served over a HybridCache"):
        transformer_decode_step(
            params, jnp.zeros((2,), jnp.int32),
            KVCache.create(3, 2, 32, 2, 16, jnp.float32),
            jnp.ones((2,), bool), CFG,
        )


def test_the_selection_is_dense_attention_when_it_holds_the_context(
    params, steps,
):
    """With ``topk x block`` at least the context every block is chosen, so
    the sparse branch gives what the dense one gives; with it smaller the
    two differ."""
    seqs = [tokens_of(41, 60)]
    dense = dataclasses.replace(CFG, sparse_dense_len=128)
    holds_all = dataclasses.replace(CFG, sparse_topk=16, sparse_dense_len=64)
    want = [reference_logits(params, dense, seqs[0])]
    # the choice starts at 64 in ``holds_all``'s own config; serve it from 32
    early = dataclasses.replace(holds_all, sparse_dense_len=32, sparse_topk=8)
    assert early.sparse_topk * early.sparse_block == 32
    with pytest.raises(ValueError, match="dense_len >= topk x block"):
        dataclasses.replace(CFG, sparse_topk=16)
    all_blocks, _, _ = serve(steps, params, holds_all, seqs, [50])
    assert worst(all_blocks, want) <= LOGIT_TOLERANCE  # under 64: dense
    # 16 blocks of 4 hold all of a 60-token context from position 32 on:
    # build that config around the constructor's check (dense_len >= 64)
    chosen_all = dataclasses.replace(holds_all, sparse_block=4)
    object.__setattr__(chosen_all, "sparse_dense_len", 32)
    got, selected, _ = serve(steps, params, chosen_all, seqs, [50])
    assert selected == 50 - 32
    assert worst(got, want) <= LOGIT_TOLERANCE
    few, _, _ = serve(steps, params, CFG, seqs, [50])
    assert worst(few, want) >= ABLATED_AT_LEAST


def test_the_chunk_wise_form_is_the_recurrence():
    """A row's chunks through ``lightning_chunk``, each with a padded tail,
    give the outputs and the state that one ``lightning_step`` a token
    gives; a row of no valid position leaves its state as it was."""
    P, c, H, hd, n = 2, 8, 3, 16, 21
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(key[i], (P, 24, H, hd)) for i in range(3))
    log_decay = lightning_log_decay(H, [2], 4)[0]
    assert log_decay.shape == (H,) and bool(jnp.all(log_decay < 0))
    state0 = jax.random.normal(key[3], (P, H, hd, hd))
    state, outs = state0, []
    for t in range(n):
        o, state = lightning_step(
            q[:, t], k[:, t], v[:, t], state, log_decay,
            jnp.ones((P,), bool), hd**-0.5,
        )
        outs.append(o)
    chunked, got = state0, []
    for start in range(0, 24, c):
        lens = jnp.clip(jnp.asarray([n, n]) - start, 0, c)
        o, chunked = lightning_chunk(
            q[:, start:start + c], k[:, start:start + c],
            v[:, start:start + c], chunked, log_decay, lens, hd**-0.5,
        )
        got.append(o)
    got = jnp.concatenate(got, axis=1)[:, :n]
    assert float(jnp.max(jnp.abs(got - jnp.stack(outs, axis=1)))) <= 1e-4
    assert float(jnp.max(jnp.abs(chunked - state))) <= 1e-4
    # an inactive slot keeps its state; a row with nothing valid too
    _, kept = lightning_step(
        q[:, 0], k[:, 0], v[:, 0], state0, log_decay,
        jnp.asarray([True, False]), hd**-0.5,
    )
    assert bool(jnp.all(kept[1] == state0[1])) and not bool(
        jnp.all(kept[0] == state0[0])
    )
    _, same = lightning_chunk(
        q[:, :c], k[:, :c], v[:, :c], state0, log_decay,
        jnp.zeros((P,), jnp.int32), hd**-0.5,
    )
    assert bool(jnp.all(same == state0))


def test_the_decay_follows_the_published_rule():
    got = np.asarray(lightning_log_decay(32, [9, 24], 32))
    h = np.arange(1, 33)
    for row, l in zip(got, (9, 24)):
        want = -(2.0 ** (-8.0 * h / 32)) * (1 - l / 31 + 1e-5)
        np.testing.assert_allclose(row, want, rtol=1e-6)
    # the program holds it beside the weights, by the layers' PUBLISHED
    # indices: the reference computes the same numbers from the rule
    shape = reference.shape_of(CFG)
    params = init_transformer(jax.random.PRNGKey(0), CFG)
    lin = [i for i, kind in enumerate(CFG.layer_kinds) if kind == LIN_KIND]
    for entry, layer in enumerate(lin):
        np.testing.assert_allclose(
            -np.asarray(params["lin_layers"]["log_decay"][entry]),
            np.asarray(reference.decay_rates(shape, layer, "")), rtol=1e-6,
        )


def test_attention_over_blocks_of_positions_is_the_one_step_mathematics():
    """``sparse_chunk_attention`` in a loop over blocks of 16 positions, the
    planes stacked and the entry picked by index, against one step over the
    whole slot; with every block allowed it is ``cache_chunk_attention``."""
    P, c, H, KV, hd, S, max_len = 2, 8, 4, 2, 16, 3, 64
    key = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(key[0], (P, c, H, hd))
    k_pl = jax.random.normal(key[1], (2, S, KV, max_len, hd))
    v_pl = jax.random.normal(key[2], (2, S, KV, max_len, hd))
    allowed = jax.random.bernoulli(key[3], 0.5, (P, KV, c, max_len // 4))
    slots, starts, lens = (jnp.asarray(a) for a in ([2, 0], [40, 16], [8, 5]))
    one = sparse_chunk_attention(
        q, k_pl[1], v_pl[1], slots, starts, lens, allowed, sel_block=4,
        block=max_len,
    )
    looped = sparse_chunk_attention(
        q, k_pl, v_pl, slots, starts, lens, allowed, sel_block=4, layer=1,
        block=16,
    )
    assert float(jnp.max(jnp.abs(one - looped))) <= 1e-5
    assert bool(jnp.all(looped[1, 5:] == 0))  # the padded tail
    everything = sparse_chunk_attention(
        q, k_pl, v_pl, slots, starts, lens, None, sel_block=4, layer=1, block=16,
    )
    dense = cache_chunk_attention(
        q, k_pl, v_pl, slots, starts, lens, layer=1, kernel=False
    )
    assert float(jnp.max(jnp.abs(everything - dense))) <= 1e-5
    assert float(jnp.max(jnp.abs(everything - looped))) >= 0.05
    with pytest.raises(ValueError, match="must divide"):
        sparse_chunk_attention(
            q, k_pl, v_pl, slots, starts, lens, None, sel_block=4, layer=1,
            block=24,
        )


@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "one_entry"])
@pytest.mark.parametrize("masked", [False, True], ids=["all_allowed", "random_allowed"])
def test_rows_far_apart_each_visit_their_own_blocks_and_no_more(masked, stacked):
    """The loop bounded a row (a row at 0, one ending on a block's edge, one
    a token past it, one in max_len's last block, one with no token, one
    with a partial chunk, two duplicates of row 0 as the scheduler pads) is
    the one-step mathematics (``block`` >= max_len) in float32, and a row
    never touches a block past its own last position: those hold NaN here."""
    P, c, H, KV, hd, S, max_len = 8, 8, 4, 2, 16, 6, 128
    key = jax.random.split(jax.random.PRNGKey(36), 4)
    q = jax.random.normal(key[0], (P, c, H, hd))
    q = q.at[6:].set(q[0])  # a padding row holds row 0's tokens too
    k_pl = jax.random.normal(key[1], (2, S, KV, max_len, hd))
    v_pl = jax.random.normal(key[2], (2, S, KV, max_len, hd))
    allowed = None
    if masked:
        allowed = jax.random.bernoulli(key[3], 0.5, (P, KV, c, max_len // 4))
        allowed = allowed.at[6:].set(allowed[0])
    slots, starts, lens = (jnp.asarray(a) for a in ROWS_APART.values())

    def attend(k_pl, v_pl, block):
        planes = (k_pl, v_pl) if stacked else (k_pl[1], v_pl[1])
        return sparse_chunk_attention(
            q, *planes, slots, starts, lens, allowed, sel_block=4,
            layer=jnp.int32(1) if stacked else None, block=block,
        )

    one_step = attend(k_pl, v_pl, max_len)
    bounded = attend(
        beyond_own_blocks_poisoned(k_pl), beyond_own_blocks_poisoned(v_pl), 16
    )
    assert bool(jnp.all(jnp.isfinite(bounded)))
    np.testing.assert_allclose(bounded, one_step, atol=2e-5)
    assert float(jnp.abs(bounded[4]).max()) == 0.0  # the row with no token
    assert float(jnp.abs(bounded[5, 5:]).max()) == 0.0  # the partial chunk
    np.testing.assert_array_equal(bounded[6], bounded[0])  # the duplicates
    np.testing.assert_array_equal(bounded[7], bounded[0])
    if masked:  # the mask is in force: every block allowed reads otherwise
        everything = sparse_chunk_attention(
            q, k_pl, v_pl, slots, starts, lens, None, sel_block=4,
            layer=jnp.int32(1), block=16,
        )
        assert float(jnp.max(jnp.abs(everything - one_step))) >= 0.05


def test_the_block_scores_are_the_references_choice():
    """``sparse_block_scores`` against the reference's ``chosen_blocks``: the
    same set of blocks a query, ties between neighbouring blocks (one window
    overlaps both) broken towards the lower index as ``top_k`` breaks them."""
    shape = reference.shape_of(CFG)
    s, H, KV, hd = 64, 4, 2, 16
    key = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(key[0], (1, s, H, hd))
    k = jax.random.normal(key[1], (s, KV, hd))
    M = s // CFG.sparse_stride
    taps = np.minimum(
        np.arange(M)[:, None] * CFG.sparse_stride
        + np.arange(CFG.sparse_kernel)[None, :], s - 1,
    )
    ck = jnp.stack([jnp.mean(k[taps, g], axis=1) for g in range(KV)])[None]
    scores = sparse_block_scores(
        q, ck, jnp.arange(s)[None], kernel=CFG.sparse_kernel,
        stride=CFG.sparse_stride, block=CFG.sparse_block,
        init_blocks=CFG.sparse_init_blocks, window=CFG.sparse_window,
        scale=hd**-0.5,
    )
    assert scores.shape == (1, KV, s, s // CFG.sparse_block)
    n_windows = (s - CFG.sparse_kernel) // CFG.sparse_stride + 1
    ties = 0
    for g in range(KV):
        picked = reference.chosen_blocks(
            q[0, :, 2 * g:2 * g + 2].transpose(1, 0, 2), jnp.arange(s),
            ck[0, g, :n_windows], shape, s // CFG.sparse_block, "",
        )
        for t in range(CFG.sparse_dense_len, s):
            row = np.asarray(scores[0, g, t])
            top = np.asarray(jax.lax.top_k(scores[0, g, t], CFG.sparse_topk)[1])
            assert set(top) == set(np.nonzero(np.asarray(picked[t]))[0]), (g, t)
            finite = row[np.isfinite(row)]
            ties += len(finite) - len(set(finite.tolist()))
            # the forced blocks: the first, and the two that end with its own
            assert {0, t // 4, t // 4 - 1} <= set(top)
    assert ties > 0  # the case the tie rule is for does occur


def test_the_kinds_are_a_tuple_that_equals_its_json_list():
    listed = ["minicpm4", "lightning-attn"]
    kinds = Kinds(listed)
    assert kinds == listed and kinds == tuple(listed) and not kinds != listed
    assert hash(kinds) == hash(tuple(listed)) and kinds != listed[::-1]
    cfg = dataclasses.replace(CFG, n_layers=2, layer_kinds=listed)
    assert isinstance(cfg.layer_kinds, Kinds) and hash(cfg) is not None
    assert cfg.layer_runs == ((SPARSE_KIND, 1), (LIN_KIND, 1))
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(CFG, n_layers=3)
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(CFG, layer_kinds=["minicpm4"] * 7 + ["mamba"])
    with pytest.raises(ValueError, match="not implemented"):
        dataclasses.replace(CFG, n_passes=2)


def test_the_depth_cut_keeps_the_published_order():
    """The 16-layer model's runs are ``mixer_types[9:25]`` of the published
    file: 4 sparse and 12 lightning layers in runs of 1, 6, 2, 4, 1, 2, the
    lightning layers' decays those of the published layers 9-24."""
    with open(os.path.join(
        CHECKOUT, "tests", "benchmark_suite", "published", "minicpm-sala-d16.json"
    )) as fh:
        published = json.load(fh)
    with open(os.path.join(
        CHECKOUT, "benchmark", "configs", "minicpm-sala-d16.json"
    )) as fh:
        config = json.load(fh)
    whole = get_model(config["base"]).config
    assert whole.layer_kinds == published["mixer_types"]
    assert (whole.n_sparse_layers, whole.n_lin_layers) == (8, 24)
    cut = dataclasses.replace(whole, **config["overrides"])
    assert cut.layer_kinds == published["mixer_types"][9:25]
    assert (cut.n_sparse_layers, cut.n_lin_layers) == (4, 12)
    assert cut.layer_offset == 9 and whole.layer_offset == 0
    assert [n for _, n in cut.layer_runs] == [1, 6, 2, 4, 1, 2]
    assert [kind for kind, _ in cut.layer_runs] == [SPARSE_KIND, LIN_KIND] * 3
    assert cut.residual_scale == pytest.approx(1.4 / 32**0.5)
    assert cut.logit_scale == 1 / 16 and cut.embed_scale == 12
    # what a token and a slot hold: K, V and a compressed key every 16
    # tokens of 4 layers x 2 heads x 128 x 2 B; 12 states of [32, 128, 128]
    assert cut.n_cache_entries == 4
    assert cut.kv_bytes_per_token == 4096 + 128 == 4_224
    assert cut.state_bytes_per_slot == 12 * 32 * 128 * 128 * 4 == 25_165_824
    assert whole.kv_bytes_per_token == 2 * 4_224
    shapes = jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0), cut))
    n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert 5.03e9 < n < 5.05e9  # 10.08 GB in bf16
    assert shapes["lin_layers"]["log_decay"].shape == (12, 32)
    assert shapes["layers"]["wk"].shape == (4, 4096, 256)
    assert shapes["lin_layers"]["wk"].shape == (12, 4096, 4096)
    assert "out_norm" not in shapes["layers"]


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------


def engine_of(model: str = MODEL, **kw) -> InferenceEngine:
    kw = {"n_slots": 2, "max_len": 128, "prefill_chunk": 16, "window_k": 4,
          "pipeline_depth": 1, **kw}
    return InferenceEngine(model, tokenizer=ByteTokenizer(), **kw)


@pytest.fixture(scope="module")
def engine(params):
    metrics = Container.create(MockConfig({"APP_NAME": "hybrid-test"})).metrics
    e = engine_of(params=params, metrics=metrics)
    e.start_sync()
    yield e
    e.close()


def greedy(engine, prompt, n=12):
    return engine.generate_sync(
        prompt, max_new_tokens=n, temperature=0.0, stop_on_eos=False,
        timeout=300,
    )


def test_the_engines_programs_serve_the_references_log_probabilities(engine):
    """Through submit -> chunked prefill (a 50-token prompt in chunks of 16,
    crossing the dense length) -> decode windows over the hybrid cache: the
    log-probability the engine reports for each greedy token is the
    reference's teacher-forced one."""
    prompt = tokens_of(5, 50)
    result = greedy(engine, prompt)
    assert len(result.token_ids) == 12
    want = reference.reference_logprobs(
        engine, [prompt + result.token_ids], len(prompt)
    )
    assert max(
        abs(a - b) for a, b in zip(result.token_logprobs, want[0])
    ) <= LOGIT_TOLERANCE
    # the selection's pieces change nothing on a sequence within the dense
    # length, and the harness is told so
    short = tokens_of(6, 20)
    assert reference.reference_logprobs(engine, [short], 12, "select") == [None]
    assert reference.reference_logprobs(engine, [short], 12, "decay") != [None]
    # still exactly the two serving programs, both rungs compiled at boot
    programs = engine.compile_stats()["programs"]
    assert set(programs) == {"prefill_chunk", "decode_window"}


def test_a_slot_admitted_again_starts_from_a_fresh_state(engine, params):
    """A slot used, released and admitted again gives the logits of a fresh
    engine: its lightning states start from zero at the prompt's first
    chunk and its former occupant's compressed keys are never read."""
    first, second = tokens_of(7, 60), tokens_of(8, 45)
    for _ in range(2):  # fill and release both slots
        greedy(engine, first, 6)
    again = greedy(engine, second)
    fresh_engine = engine_of(params=params)
    fresh_engine.start_sync()
    try:
        fresh = greedy(fresh_engine, second)
    finally:
        fresh_engine.close()
    assert again.token_ids == fresh.token_ids
    assert again.token_logprobs == fresh.token_logprobs


def test_two_prompts_alone_and_together_give_the_same_greedy_tokens(engine):
    import concurrent.futures

    prompts = [tokens_of(9, 57), tokens_of(10, 38)]
    alone = [greedy(engine, p).token_ids for p in prompts]
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        together = list(pool.map(lambda p: greedy(engine, p).token_ids, prompts))
    assert together == alone


def counter(metrics, name: str, **labels) -> float:
    from benchmark.harness import prom

    series = prom.parse(render_prometheus(metrics)).get(name, {})
    return sum(
        value for text, value in series.items()
        if all(f'{k}="{v}"' in text for k, v in labels.items())
    )


def test_the_cache_says_its_two_figures_and_the_counters_read(engine):
    cache, metrics = engine.cache, engine._metrics
    assert isinstance(cache, HybridCache) and not cache.quantized
    assert cache.k.shape == cache.v.shape == (3, 2, 2, 128, 16)
    assert cache.ck.shape == (3, 2, 2, 64, 16)
    assert cache.state.shape == (5, 2, 4, 16, 16)
    assert cache.state.dtype == jnp.float32
    # K, V and a compressed key every 2 tokens of 3 layers x 2 heads x 16 x 4 B
    assert engine.kv_bytes_per_token() == CFG.kv_bytes_per_token == 960
    assert engine.state_bytes_per_slot() == CFG.state_bytes_per_slot == 20_480
    assert cache.hbm_bytes() == 960 * 2 * 128 + 20_480 * 2
    details = engine.health_check()["details"]
    assert details["kv_bytes_per_token"] == 960
    assert details["state_bytes_per_slot"] == 20_480
    assert "state_bytes_per_slot" not in engine_of("llama-tiny").health_check()[
        "details"
    ]
    # every cache says its own bytes a token (one place for all of them)
    assert KVCache.create(2, 3, 32, 2, 8).bytes_per_token() == 2 * 2 * 2 * 8 * 2
    assert LatentKVCache.create(2, 3, 32, 24).bytes_per_token() == 2 * 128 * 2
    assert counter(metrics, "app_tpu_kv_bytes_per_token") == 960
    assert counter(metrics, "app_tpu_state_bytes_per_slot") == 20_480

    def queries(**labels):
        return counter(metrics, "app_tpu_sparse_attn_queries_total", **labels)

    before = {
        (b, p): queries(branch=b, program=p)
        for b in ("selected", "dense") for p in ("prefill_chunk", "decode_window")
    }
    resets = counter(metrics, "app_tpu_state_resets_total")
    ratios = counter(metrics, "app_tpu_sparse_attn_read_ratio_count")
    greedy(engine, tokens_of(13, 27), 9)  # decodes across the dense length
    deadline = time.monotonic() + 30
    while True:  # the last window's counts land a moment after the future
        moved = {
            key: queries(branch=key[0], program=key[1]) - was
            for key, was in before.items()
        }
        if (moved["selected", "decode_window"] + moved["dense", "decode_window"]
                >= 3 * 8 or time.monotonic() > deadline):
            break
        time.sleep(0.01)
    # a query is a computed token x 3 sparse layers: 27 prompt tokens, all
    # under 32; decode positions 27..31 dense, from 32 on selected; whole
    # windows of 4 steps are computed
    assert moved["dense", "prefill_chunk"] == 27 * 3
    assert moved["selected", "prefill_chunk"] == 0
    assert moved["dense", "decode_window"] == 5 * 3
    assert moved["selected", "decode_window"] >= 3 * 3
    assert counter(metrics, "app_tpu_state_resets_total") - resets == 1
    assert counter(metrics, "app_tpu_sparse_attn_read_ratio_count") > ratios
    mean = counter(metrics, "app_tpu_sparse_attn_read_ratio_sum") / counter(
        metrics, "app_tpu_sparse_attn_read_ratio_count"
    )
    assert 0.1 < mean < 0.6  # 13-16 positions of 33-60 in context
    assert engine._obs.model_attrs == {
        "layer_kinds": "1xminicpm4 2xlightning-attn 2xminicpm4 3xlightning-attn",
        "state_bytes": 20_480, "blocks_chosen": 4,
    }
    # the cache's own series read for a hybrid cache as for any other
    assert counter(metrics, "app_tpu_kv_live_ratio_count") > 0
    assert counter(metrics, "app_tpu_decode_read_ratio_count") > 0
    assert counter(metrics, "app_tpu_window_occupancy_count") > 0


@pytest.mark.parametrize("kw,says", [
    ({"kv_block": 16}, "TPU_KV_BLOCK > 0 (the paged pool) is not served"),
    ({"auto_prefix": True}, "TPU_AUTO_PREFIX (the radix prefix cache) is not served"),
    ({"prefix_slots": 2}, "TPU_PREFIX_SLOTS > 0 (the prefix pool) is not served"),
    ({"kv_quant": "int8"}, "TPU_KV_QUANT=int8 is not served"),
    ({"quant": "int8"}, "TPU_QUANT=int8 is not served"),
    ({"tp": 2}, "TPU_TP > 1 (or a mesh, pipeline stages among them) is not served"),
    ({"lora_slots": 2}, "TPU_LORA_SLOTS > 0 (targets 'wq,wk,wv,wo') is not served"),
    ({"max_len": 126}, "TPU_MAX_LEN=126 is not served"),
])
def test_what_cannot_run_over_a_hybrid_cache_is_refused_by_name(kw, says):
    with pytest.raises(ValueError) as refused:
        engine_of(**kw)
    assert says in str(refused.value)
    assert str(refused.value).startswith(f"{MODEL}: ")


def test_a_prefill_tier_role_is_refused_by_name(engine):
    with pytest.raises(ValueError, match="a prefill-tier role"):
        engine.set_tier_exporter(lambda req, payload: False)
    engine.set_tier_exporter(None)  # asking for nothing is served
