"""A stacked all-held expert layer picks its product from the step's shape
(ISSUE 34): the sorted, grouped product in tiles where the step has rows
enough (a prefill step), the dense einsum elsewhere (a decode step, a mesh).

All on the CPU: ``moe-tiny`` (4 experts, 2 a token) in float32, and one
case at Mixtral's counts (8 experts, 2 a token) with weight-only int8
leaves. The einsum form on the same stacked leaves is the yardstick; both
forms share one router.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import faults
from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.metrics.exposition import render_prometheus
from gofr_tpu.models import transformer as T
from gofr_tpu.models.registry import get_model, register_model
from gofr_tpu.ops.kv_cache import KVCache
from gofr_tpu.ops.quant import Q8, quantize_params
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

TINY = dataclasses.replace(get_model("moe-tiny").config, dtype=jnp.float32)
MODEL = "moe-tiny-f32-tiled"
register_model(dataclasses.replace(get_model("moe-tiny"), name=MODEL, config=TINY))
# Mixtral's expert counts at a width the CPU multiplies in a moment.
EIGHT = dataclasses.replace(
    get_model("moe-tiny").config, n_experts=8, n_experts_active=2
)
MIXTRAL = get_model("mixtral-8x7b").config
TILE = T.EXPERT_ROW_TILE


def layer_of(params: dict, l: int) -> dict:
    return jax.tree.map(lambda a: a[l], params["layers"])


def both_forms(cfg, params, x, valid=None, l=1):
    """The layer's FFN on the same stacked leaves: (einsum, grouped, the
    grouped form's counts)."""
    lp = layer_of(params, l)
    dense, none = T._ffn_moe(x, lp, cfg)
    assert none is None
    grouped, counts = jax.jit(
        lambda x, layers: T._ffn_moe(x, lp, cfg, valid, (layers, jnp.int32(l)))
    )(x, params["layers"])
    return dense, grouped, counts


@pytest.fixture(scope="module")
def tiny_params():
    return T.init_transformer(jax.random.PRNGKey(0), TINY)


# ----------------------------------------------------------------------
# the rule: from shapes alone
# ----------------------------------------------------------------------


@pytest.mark.parametrize("rows,sharded,product", [
    (8 * 256, False, "tiles"),     # the [8, 256] prefill step
    (256, False, "einsum"),        # the [1, 256] rung: a tile an expert
    (64, False, "einsum"),         # the decode step, 64 slots
    (8 * 256, True, "einsum"),     # under a mesh GSPMD partitions the einsum
    (342, False, "tiles"),         # rows x 2 + 8 tiles < rows x 8 from here
    (341, False, "einsum"),
])
def test_the_rule_picks_the_product_from_the_steps_rows(rows, sharded, product):
    assert MIXTRAL.experts_stacked and not MIXTRAL.counts_routes
    assert MIXTRAL.expert_product(rows, sharded) == product
    # the same rule, the tiny preset's counts: 4 experts, 2 a token
    threshold = TINY.n_experts * TILE // (TINY.n_experts - TINY.n_experts_active)
    assert TINY.expert_product(threshold + 1) == "tiles"
    assert TINY.expert_product(threshold) == "einsum"
    assert TINY.expert_product(threshold + 1, sharded=True) == "einsum"


def test_a_share_is_ragged_at_every_shape_and_a_dense_model_has_no_product():
    share = get_model("mla-moe-tiny").config
    assert not share.experts_stacked and share.counts_routes
    assert {share.expert_product(r, s) for r in (1, 64, 2048) for s in (False, True)} == {"ragged"}
    dense = get_model("llama-tiny").config
    assert not dense.experts_stacked and not dense.counts_routes


# ----------------------------------------------------------------------
# the mathematics: the einsum form on the same leaves
# ----------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 256), (1, 1100), (64, 1)])
def test_the_grouped_form_is_the_einsum_form_in_float32(tiny_params, shape):
    x = jax.random.normal(jax.random.PRNGKey(1), (*shape, TINY.d_model), jnp.float32)
    dense, grouped, (rows, sizes) = both_forms(TINY, tiny_params, x)
    assert float(jnp.max(jnp.abs(dense - grouped))) < 1e-5
    assert float(jnp.mean(jnp.abs(dense))) > 1e-2
    k = TINY.n_experts_active
    assert rows.tolist() == [shape[1] * k] * shape[0]
    assert int(sizes.sum()) == shape[0] * shape[1] * k


def test_int8_leaves_give_the_einsums_own_scaled_product():
    """E 8, k 2, Q8 leaves: the grouped form reads the stacked int8 leaf and
    scales the output by the row's own expert's scale, as ``_wein`` does for
    the einsum: the two agree within bf16 rounding of the outputs (the
    same int8 values, exact in bf16, the same float32 accumulation)."""
    params = quantize_params(T.init_transformer(jax.random.PRNGKey(2), EIGHT))
    assert isinstance(params["layers"]["w_gate"], Q8)
    assert params["layers"]["w_gate"].q.shape[:2] == (2, 8)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 400, EIGHT.d_model), EIGHT.dtype)
    dense, grouped, (_, sizes) = both_forms(EIGHT, params, x)
    assert int(sizes.sum()) == 3 * 400 * 2 and sizes.shape == (8,)
    dense, grouped = (np.asarray(a, np.float32) for a in (dense, grouped))
    scale = float(np.mean(np.abs(dense)))
    assert scale > 1e-2
    # bf16 keeps 8 bits: the hidden product rounds by its magnitude / 256,
    # and where the two forms' float32 sums fall on either side of a
    # rounding the down projection carries the flips on (measured here: 1.4
    # roundings of the mean output on average, 11 at the worst element)
    bound = 32 * np.maximum(np.abs(dense), scale) / 256
    assert bool(np.all(np.abs(dense - grouped) <= bound))
    assert float(np.mean(np.abs(dense - grouped))) < 4 * scale / 256
    # float32 activations on the same int8 leaves take the rounding away:
    # the same values times the same scales, each row's its own expert's
    dense, grouped, _ = both_forms(EIGHT, params, x.astype(jnp.float32))
    assert float(jnp.max(jnp.abs(dense - grouped))) < 1e-5 * max(1.0, scale)


def test_every_row_on_the_same_two_experts_loses_no_route(tiny_params):
    """No capacity: the buffer holds every route, so experts 0 and 1 take
    all 1,200 rows (five tiles each) and the other two take none."""
    cfg, n = TINY, 1200
    x = jax.random.normal(jax.random.PRNGKey(4), (n, cfg.d_model), jnp.float32)
    idx = jnp.tile(jnp.array([[1, 0]], jnp.int32), (n, 1))
    gates = jnp.tile(jnp.array([[0.75, 0.25]], jnp.float32), (n, 1))
    out, sizes = jax.jit(lambda x, layers: T.moe_tiled_experts(
        x, idx, gates, jnp.ones((n,), bool), layers, jnp.int32(0), cfg
    ))(x, tiny_params["layers"])
    assert sizes.tolist() == [n, n, 0, 0]
    lp = layer_of(tiny_params, 0)
    want = sum(
        g * T._swiglu(x, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], cfg)
        for e, g in ((1, 0.75), (0, 0.25))
    )
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5


def test_rows_that_hold_no_token_are_in_no_run_and_change_no_valid_row(tiny_params):
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 256, TINY.d_model), jnp.float32)
    lens = jnp.array([256, 100, 0, 7])
    valid = jnp.arange(256)[None, :] < lens[:, None]
    dense, grouped, (rows, sizes) = both_forms(TINY, tiny_params, x, valid)
    k = TINY.n_experts_active
    assert int(sizes.sum()) == int(lens.sum()) * k  # the sorted runs: valid routes only
    assert rows.tolist() == (lens * k).tolist()
    keep = valid[..., None]
    assert float(jnp.max(jnp.abs(jnp.where(keep, dense - grouped, 0)))) < 1e-5
    # other values in the rows that hold nothing: the valid rows' outputs
    # are the same to the bit, and what held no token comes back as zero
    noise = jnp.where(keep, x, 1e3 * jax.random.normal(jax.random.PRNGKey(6), x.shape))
    _, again, (_, sizes2) = both_forms(TINY, tiny_params, noise, valid)
    assert bool(jnp.all(jnp.where(keep, again == grouped, True)))
    assert sizes2.tolist() == sizes.tolist()
    assert float(jnp.max(jnp.abs(jnp.where(keep, 0, grouped)))) == 0.0


def test_the_tiles_loop_slices_the_stack_and_multiplies_no_rows_x_experts(tiny_params):
    """The traced grouped step holds no array of ``rows x experts`` rows, and
    takes each expert's weights out of the stacked leaf inside the loop."""
    rows = 1100
    x = jnp.zeros((1, rows, TINY.d_model), jnp.float32)
    lp = layer_of(tiny_params, 0)
    text = str(jax.make_jaxpr(
        lambda x, layers: T._ffn_moe(x, lp, TINY, None, (layers, jnp.int32(0)))
    )(x, tiny_params["layers"]))
    E, F = TINY.n_experts, TINY.d_ff
    assert f"{rows},{E},{F}]" not in text and f"{E},{rows},{F}]" not in text
    assert f"f32[{TILE},{F}]" in text and "while" in text
    dense = str(jax.make_jaxpr(lambda x: T._ffn_moe(x, lp, TINY))(x))
    assert f"1,{rows},{E},{F}]" in dense and "while" not in dense


# ----------------------------------------------------------------------
# through the serving steps: one router in prefill and in decode
# ----------------------------------------------------------------------


def test_grouped_prefill_then_einsum_decode_gives_the_whole_forwards_logits(tiny_params):
    """A chunked prefill whose steps run grouped ([8, 128]: 1,024 rows),
    then decode steps that keep the einsum (9 slots), against
    ``transformer_forward`` on the whole sequences: one router, so a token's
    experts are the same in both."""
    cfg, rows, chunk, n_prompt, n_total = TINY, 8, 128, 128, 136
    assert cfg.expert_product(rows * chunk) == "tiles"
    assert cfg.expert_product(rows + 1) == "einsum"
    toks = jax.random.randint(jax.random.PRNGKey(7), (rows, n_total), 3, cfg.vocab_size)
    want = np.asarray(T.transformer_forward(tiny_params, toks, cfg))
    cache = KVCache.create(
        cfg.n_cache_entries, rows + 1, 256, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    )
    slots = jnp.arange(1, rows + 1, dtype=jnp.int32)
    # rows 6 and 7 are padding (duplicates of row 0, marked invalid) and row
    # 5 holds 40 tokens: what they compute is dropped, what the others
    # compute is the forward's
    lens = jnp.array([chunk] * 5 + [40, chunk, chunk], jnp.int32)
    row_valid = jnp.array([True] * 6 + [False] * 2)
    step_toks = toks[:, :chunk].at[6:].set(toks[0, :chunk])
    step_slots = slots.at[6:].set(slots[0])
    logits, cache, (held, ratio) = jax.jit(
        T.transformer_prefill_chunk, static_argnames=("cfg", "stats")
    )(tiny_params, step_toks, cache, step_slots, jnp.zeros((rows,), jnp.int32),
      lens, cfg=cfg, row_valid=row_valid, stats=True)
    for r in range(5):
        assert np.max(np.abs(np.asarray(logits[r]) - want[r, chunk - 1])) < 2e-4
    assert np.max(np.abs(np.asarray(logits[5]) - want[5, 39])) < 2e-4
    k, L = cfg.n_experts_active, cfg.n_layers
    assert held.tolist() == [chunk * k * L] * 5 + [40 * k * L, 0, 0]
    assert 1.0 <= float(ratio) <= cfg.n_experts
    # decode rows 0..4 from their full chunk (slot 6's 40 tokens stay behind)
    live = slots[:5]
    cache = cache._replace(lengths=cache.lengths.at[live].set(n_prompt))
    active = jnp.zeros((rows + 1,), bool).at[live].set(True)
    decode = jax.jit(T.transformer_decode_step, static_argnames="cfg")
    for pos in range(n_prompt, n_total):
        step = jnp.zeros((rows + 1,), jnp.int32).at[live].set(toks[:5, pos])
        logits, cache = decode(tiny_params, step, cache, active, cfg=cfg)
        assert np.max(np.abs(np.asarray(logits[1:6]) - want[:5, pos])) < 2e-4


def test_a_wide_decode_step_runs_grouped_and_skips_idle_slots(tiny_params):
    """The rule reads the decode step's slot count as it reads a prefill
    step's rows: 600 slots go grouped, and give the einsum's logits."""
    cfg, S = TINY, 600
    assert cfg.expert_product(S) == "tiles"
    cache = KVCache.create(
        cfg.n_cache_entries, S, 16, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    )
    toks = jax.random.randint(jax.random.PRNGKey(8), (S,), 3, cfg.vocab_size)
    active = jnp.arange(S) % 3 != 0
    decode = jax.jit(T.transformer_decode_step, static_argnames=("cfg", "sharded"))
    grouped, _ = decode(tiny_params, toks, cache, active, cfg=cfg)
    einsum, _ = decode(tiny_params, toks, cache, active, cfg=cfg, sharded=True)
    diff = jnp.where(active[:, None], grouped - einsum, 0)
    assert float(jnp.max(jnp.abs(diff))) < 2e-4


# ----------------------------------------------------------------------
# the engine: the counters
# ----------------------------------------------------------------------


@contextlib.contextmanager
def parked(engine: InferenceEngine):
    """The scheduler held at the top of a pass until the block ends: what is
    submitted inside waits together (tests/test_prefill_rungs.py)."""
    gate_in, gate_out = threading.Event(), threading.Event()

    def park(**fired):
        if fired.get("engine") is engine and not gate_out.is_set():
            gate_in.set()
            gate_out.wait(timeout=120)

    with faults.armed("scheduler.window", action=park):
        try:
            assert gate_in.wait(60), "the scheduler never reached a pass"
            yield
        finally:
            gate_out.set()


def series(metrics, name: str, **labels) -> float:
    from benchmark.harness import prom

    found = prom.parse(render_prometheus(metrics)).get(name, {})
    return sum(
        value for text, value in found.items()
        if all(f'{k}="{v}"' in text for k, v in labels.items())
    )


def test_the_product_counter_and_the_load_histogram_are_exported(tiny_params):
    """An engine whose 8-row rung goes grouped ([8, 128]) and whose one-row
    rung and decode window keep the einsum: every dispatch counts under the
    product its program ran, and the grouped steps record their expert load."""
    metrics = Container.create(MockConfig({"APP_NAME": "tiled-test"})).metrics
    engine = InferenceEngine(
        MODEL, tokenizer=ByteTokenizer(), params=tiny_params, metrics=metrics,
        n_slots=8, max_len=256, prefill_chunk=128, prefill_batch=8, window_k=4,
        pipeline_depth=1,
    )
    assert engine.moe_products == {
        ("prefill_chunk", 1): "einsum", ("prefill_chunk", 8): "grouped",
        ("decode_window", 8): "einsum",
    }
    assert engine._obs.model_attrs == {}
    engine.start_sync()
    try:
        rng = np.random.default_rng(9)
        prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (90, 60, 120)]
        alone = [
            engine.generate_sync(p, max_new_tokens=4, temperature=0.0,
                                 stop_on_eos=False, timeout=300).token_ids
            for p in prompts
        ]
        steps = lambda **kw: series(  # noqa: E731
            metrics, "app_tpu_moe_product_steps_total", model=MODEL, **kw)
        assert steps(product="grouped") == 0
        assert steps(product="einsum", program="prefill_chunk") == 3
        assert series(metrics, "app_tpu_moe_expert_load_ratio_count") == 0
        with parked(engine):
            requests = [
                engine.submit_generate(p, max_new_tokens=4, temperature=0.0,
                                       stop_on_eos=False)
                for p in prompts
            ]
        together = [r.future.result(timeout=300).token_ids for r in requests]
        # one router, both products: a prompt's tokens are the same whether
        # its prefill step ran the einsum (alone) or grouped (together)
        assert together == alone
        assert steps(product="grouped", program="prefill_chunk") == 1
        assert steps(product="grouped", program="decode_window") == 0
        assert steps(product="einsum", program="decode_window") >= 4
        deadline = time.monotonic() + 30
        while (series(metrics, "app_tpu_moe_expert_load_ratio_count") < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert series(metrics, "app_tpu_moe_expert_load_ratio_count", model=MODEL) == 1
        ratio = series(metrics, "app_tpu_moe_expert_load_ratio_sum", model=MODEL)
        assert 1.0 <= ratio <= TINY.n_experts
        # an all-held layer has no absent routes to tell from held ones
        assert series(metrics, "app_tpu_moe_routes_total") == 0
    finally:
        engine.close()


def test_weight_only_int8_passes_through_the_grouped_product():
    """``TPU_QUANT=int8`` is refused for a share's ``ragged_dot`` only: a
    stacked layer's engine boots with Q8 leaves and compiles both rungs."""
    engine = InferenceEngine(
        "moe-tiny", tokenizer=ByteTokenizer(), quant="int8", n_slots=8,
        max_len=256, prefill_chunk=128, prefill_batch=8, window_k=4,
    )
    try:
        assert isinstance(engine.params["layers"]["w_gate"], Q8)
        assert engine.moe_products["prefill_chunk", 8] == "grouped"
        assert engine.compile_stats()["programs"]["prefill_chunk"]["compiles"] == 2
    finally:
        engine.close()
