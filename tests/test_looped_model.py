"""A looped decoder (ISSUE 28): L layers run T times over one set of weights,
T x L cache entries a token, served through the normal path.

The yardstick is ``benchmark/reference/looped.py``, the plain float32 full
forward written from the issue's equations with no import of the program.
Everything here runs the tiny preset ``looped-tiny`` (2 layers x 3 passes,
sandwich norms) in float32 on seeded random weights whose norm scales are
drawn away from 1, so that a norm applied with another's weights, or left
out, shows.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.registry import get_model, register_model
from gofr_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_decode_step,
    transformer_param_specs,
    transformer_prefill_chunk,
)
from gofr_tpu.ops.kv_cache import KVCache, PagedKVCache
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.serving.types import _GenRequest

from benchmark.harness.cells import load_file

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_file(
    "looped_reference_for_tests",
    os.path.join(CHECKOUT, "benchmark", "reference", "looped.py"),
)

MODEL = "looped-tiny-f32"
CFG = dataclasses.replace(get_model("looped-tiny").config, dtype=jnp.float32)
register_model(dataclasses.replace(
    get_model("looped-tiny"), name=MODEL, config=CFG
))

# Program and reference both compute in float32; what is left between them
# is the order of the reductions (the decode step's split softmax over cache
# and fresh token, XLA's blocking of the matrix products) through 6 layer
# applications: 1e-5 at most here, against 0.3 nats and more for any of the
# reference's five pieces removed.
LOGIT_TOLERANCE = 2e-4
ABLATED_AT_LEAST = 0.05


def seeded_params(cfg: TransformerConfig = CFG, seed: int = 0) -> dict:
    params = init_transformer(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    for i, name in enumerate(
        ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")
    ):
        if name in params["layers"]:
            leaf = params["layers"][name]
            params["layers"][name] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, leaf.dtype
            )
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 9), params["final_norm"].shape, cfg.dtype
    )
    return params


def reference_logits(params: dict, cfg: TransformerConfig, tokens: list,
                     ablate: str = "") -> np.ndarray:
    """[s, vocab]: the reference's full forward, every position."""
    with jax.default_matmul_precision("highest"):
        x = reference.hidden_states(
            params, reference.shape_of(cfg), [tokens], ablate
        )[0]
        return np.asarray(x @ params["lm_head"].astype(jnp.float32))


def tokens_of(seed: int, n: int, vocab: int = CFG.vocab_size) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(3, vocab, n)]


def empty_cache(layout: str, n_slots: int, max_len: int):
    args = (CFG.n_cache_entries, n_slots, max_len, CFG.n_kv_heads,
            CFG.head_dim, CFG.dtype)
    if layout == "contiguous":
        return KVCache.create(*args)
    cache = PagedKVCache.create(*args, block=16)
    per_slot = max_len // 16  # slot s owns blocks 1 + s*per_slot ...
    table = 1 + np.arange(n_slots * per_slot).reshape(n_slots, per_slot)
    return cache._replace(block_table=jnp.asarray(table, jnp.int32))


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_chunked_prefill_then_cached_decode_gives_the_full_forward_logits(
    params, layout,
):
    """Two rows, a 24-token prompt each in three chunks of 8 and then 8
    decode steps through the cache: the logits at every position the
    serving path computes them for (each chunk's last token, every decoded
    token) are the reference's, and no ablation of the reference's are."""
    rows, chunk, n_prompt, n_total = 2, 8, 24, 32
    seqs = [tokens_of(11 + r, n_total) for r in range(rows)]
    toks = jnp.asarray(seqs, jnp.int32)
    cache = empty_cache(layout, rows, 64)
    slots = jnp.arange(rows, dtype=jnp.int32)
    served = {}
    prefill_chunk = jax.jit(transformer_prefill_chunk, static_argnames="cfg")
    decode_step = jax.jit(transformer_decode_step, static_argnames="cfg")
    for start in range(0, n_prompt, chunk):
        logits, cache = prefill_chunk(
            params, toks[:, start:start + chunk], cache, slots,
            jnp.full((rows,), start, jnp.int32),
            jnp.full((rows,), chunk, jnp.int32), cfg=CFG,
        )
        served[start + chunk - 1] = np.asarray(logits)
    cache = cache._replace(lengths=jnp.full((rows,), n_prompt, jnp.int32))
    active = jnp.ones((rows,), bool)
    for pos in range(n_prompt, n_total):
        logits, cache = decode_step(params, toks[:, pos], cache, active, cfg=CFG)
        served[pos] = np.asarray(logits)
    assert cache.k.shape[0] == CFG.n_cache_entries == 6
    assert np.asarray(cache.lengths).tolist() == [n_total] * rows

    def worst(ablate: str) -> float:
        want = [reference_logits(params, CFG, seq, ablate) for seq in seqs]
        return max(
            float(np.max(np.abs(got[r] - want[r][pos])))
            for r in range(rows) for pos, got in served.items()
        )

    assert worst("") <= LOGIT_TOLERANCE
    for ablate in reference.ABLATIONS:
        assert worst(ablate) >= ABLATED_AT_LEAST, ablate


def engine_of(model: str = MODEL, *, paged: bool = False, **kw):
    if paged:
        kw = dict(kv_block=16, auto_prefix=True, **kw)
    return InferenceEngine(
        model, tokenizer=ByteTokenizer(), n_slots=2, max_len=128,
        prefill_chunk=16, window_k=4, pipeline_depth=1, **kw,
    )


@pytest.fixture(scope="module")
def engines(params):
    """The looped model behind the engine's own programs, contiguous and
    paged (and a second paged one, for the block transfer)."""
    built = {
        "contiguous": engine_of(params=params),
        "paged": engine_of(params=params, paged=True),
        "paged-2": engine_of(params=params, paged=True),
    }
    for e in built.values():
        e.start_sync()
    yield built
    for e in built.values():
        e.close()


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_the_engines_programs_serve_the_references_log_probabilities(
    engines, layout,
):
    """Through submit -> chunked prefill (a 40-token prompt in chunks of 16)
    -> decode windows: the log-probability the engine reports for each
    greedy token is the reference's teacher-forced one; with any of the
    reference's five pieces removed it is not."""
    e = engines[layout]
    prompt = tokens_of(5, 40)
    result = e.generate_sync(
        prompt, max_new_tokens=12, temperature=0.0, stop_on_eos=False,
        timeout=300,
    )
    assert len(result.token_ids) == 12
    sequence = prompt + result.token_ids

    def worst(ablate: str) -> float:
        want = reference.reference_logprobs(e, [sequence], len(prompt), ablate)
        return max(abs(a - b) for a, b in zip(result.token_logprobs, want[0]))

    assert worst("") <= LOGIT_TOLERANCE
    for ablate in reference.ABLATIONS:
        assert worst(ablate) >= ABLATED_AT_LEAST, ablate


def test_the_cache_has_a_entry_a_layer_and_pass_and_says_its_bytes(engines):
    published = get_model("ouro-2.6b").config
    assert published.n_cache_entries == 4 * 48 == 192
    assert published.kv_bytes_per_token == 1_572_864
    assert get_model("mistral-7b").config.kv_bytes_per_token == 131_072
    for e in engines.values():
        assert e.cache.k.shape[0] == e.cache.v.shape[0] == 6
        # 6 entries x (k, v) x 4 kv heads x 16 x 4 B
        assert e.kv_bytes_per_token() == CFG.kv_bytes_per_token == 3_072
        details = e.health_check()["details"]
        assert details["kv_bytes_per_token"] == 3_072
    int8 = engine_of("llama-tiny", kv_quant="int8")
    # 2 entries x (k, v) x 2 kv heads x (32 B int8 + 8 x 4 B of scales)
    assert int8.kv_bytes_per_token() == 2 * 2 * 2 * (32 + 32)


def test_one_pass_without_the_sandwich_is_the_unlooped_model():
    """The defaults are the model as it always was: the same config, the
    same parameter tree, and programs with one scan over the layers and no
    pass machinery; T = 1 of a looped preset's sizes gives the unlooped
    model's logits bit for bit."""
    base = get_model("llama-tiny").config
    assert dataclasses.replace(
        base, n_passes=1, post_norm=False, exit_threshold=1.0
    ) == base
    plain = dataclasses.replace(CFG, n_passes=1, post_norm=False)
    tree = init_transformer(jax.random.PRNGKey(0), plain)
    assert set(tree) == {"embed", "layers", "final_norm", "lm_head"}
    assert not any("post_norm" in name for name in tree["layers"])
    cache = empty_cache("contiguous", 2, 64)._replace(
        k=jnp.zeros((2, 2, 4, 64, 16)), v=jnp.zeros((2, 2, 4, 64, 16))
    )
    jaxpr = str(jax.make_jaxpr(
        lambda p, t, c: transformer_decode_step(
            p, t, c, jnp.ones((2,), bool), plain
        )
    )(tree, jnp.zeros((2,), jnp.int32), cache))
    assert jaxpr.count("scan[") == 1 and "cond[" not in jaxpr
    # Cache entry 0 is pass 0's layer 0 in the looped program: the keys the
    # unlooped model writes there, bit for bit (no later pass touches them).
    looped = seeded_params()
    shared = {**looped, "layers": {
        k: v for k, v in looped["layers"].items() if "post_norm" not in k
    }}
    shared = {k: v for k, v in shared.items() if not k.startswith("exit_")}
    toks = jnp.asarray([tokens_of(3, 8)], jnp.int32)
    args = (jnp.arange(1, dtype=jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 8, jnp.int32))
    _, one = transformer_prefill_chunk(
        shared, toks, KVCache.create(2, 1, 16, 4, 16, jnp.float32), *args, plain
    )
    _, three = transformer_prefill_chunk(
        looped, toks, KVCache.create(6, 1, 16, 4, 16, jnp.float32), *args, CFG
    )
    assert np.array_equal(np.asarray(one.k[0]), np.asarray(three.k[0]))


def test_what_is_not_implemented_for_a_looped_stack_is_refused_at_boot():
    early = dataclasses.replace(CFG, exit_threshold=0.9)
    register_model(dataclasses.replace(
        get_model(MODEL), name="looped-tiny-early-exit", config=early
    ))
    with pytest.raises(ValueError, match="exit_threshold=0.9 < 1 is not served"):
        engine_of("looped-tiny-early-exit")
    with pytest.raises(ValueError, match="pipeline-parallel"):
        transformer_param_specs(CFG, pp=True)
    specs = transformer_param_specs(CFG)  # tp specs cover every leaf
    tree = jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0), CFG))
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)
    )


def test_blocks_of_a_looped_cache_move_between_engines_and_no_others_do(
    engines,
):
    """Export a prompt's blocks from one paged engine, import them into a
    second, decode the same tokens there; a payload cut for an unlooped
    geometry (fewer cache entries) is refused by its fingerprint."""
    src, dst = engines["paged"], engines["paged-2"]
    prompt = tokens_of(21, 48)  # three whole 16-token blocks
    want = src.generate_sync(
        prompt, max_new_tokens=8, temperature=0.0, stop_on_eos=False,
        timeout=300,
    )
    payload = src.export_cached(prompt, timeout_s=30.0)
    assert payload is not None and payload.n_blocks == 3
    assert payload.k.shape[0] == 6 and payload.geometry[0] == 6
    assert payload.compatible_with(dst.cache)

    def adopt(blocks) -> list:
        req = _GenRequest(
            prompt_ids=list(prompt), max_new_tokens=8, temperature=0.0,
            stop_on_eos=False,
        )
        verdict = dst.handoff_prefilled(req, blocks)
        toks = []
        while (tok := req.stream.get(timeout=300)) is not None:
            toks.append(tok)
        return [verdict, toks]

    hits = dst._prefix_hit_tokens
    assert adopt(payload) == ["imported", want.token_ids]
    assert dst._prefix_hit_tokens > hits  # the shipped blocks were aliased
    unlooped = dataclasses.replace(
        payload, k=payload.k[:2], v=payload.v[:2],
        geometry=(2, *payload.geometry[1:]),
    )
    assert not unlooped.compatible_with(dst.cache)
    assert adopt(unlooped) == ["fused", want.token_ids]
