"""The latent cache's decode attention through the Pallas kernel
``latent_flash_decode``: the stacked plane read where it lies, each slot to
its own length.

Held here, on the CPU (the kernel in interpret mode): the kernel against the
dense ``latent_decode_attention`` (float32 to 1e-5; bfloat16 within its
rounding of the float32 mathematics), at the lengths where a block's edge
can go wrong, from a stacked plane whose entry is traced through a scan;
the decode step through the kernel against the decode step through the
dense read; the rule that chooses the kernel (a latent cache, on a TPU,
without a mesh; never a hybrid cache); and the host's read counter, which
counts a latent cache's reads by each slot's own blocks and a hybrid
cache's by the rung.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.models.registry import get_model
from gofr_tpu.models.transformer import init_transformer, transformer_decode_step
from gofr_tpu.ops.attention import (
    LATENT_DECODE_BLOCK,
    decode_read_index,
    decode_read_plan,
    decode_read_rungs,
    latent_decode_attention,
    latent_decode_block,
)
from gofr_tpu.ops.kv_cache import LatentKVCache
from gofr_tpu.ops.pallas.latent_decode import latent_flash_decode
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

# ``gofr_tpu.ops.attention`` the attribute is the function of that name.
attention_module = importlib.import_module("gofr_tpu.ops.attention")

RANK, ROPE, HEADS, ENTRIES = 96, 16, 8, 3
BLOCK, MAX_LEN = 128, 512
WIDTH = LatentKVCache.width_for(RANK + ROPE)  # 128: 16 lanes of zeros
# a slot that does not decode, first and between live ones; one position;
# each side of a block's edge; the last position a slot can hold
LENGTHS = (0, 1, BLOCK - 1, 0, BLOCK, BLOCK + 1, MAX_LEN - 1)
IDLE = [i for i, n in enumerate(LENGTHS) if n == 0]
LIVE = [i for i, n in enumerate(LENGTHS) if n > 0]
SCALE = 0.1
# bfloat16 inputs, float32 accumulation: the kernel's output rounds once,
# where the dense read also rounds its weights' sum and the merge; both stay
# within 2^-7 of the largest output of the float32 mathematics on the same
# inputs (2.6 here), the kernel at about half the dense read's distance.
BF16_ATOL = 0.02


def operands(dtype, key=0):
    """A stacked plane of distinct entries as the cache holds it (zeros
    beyond each row's content), the queries and the new rows."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    slots = len(LENGTHS)
    content = jax.random.normal(
        ks[0], (ENTRIES, slots, 1, MAX_LEN, RANK + ROPE)
    )
    plane = jnp.pad(content, [(0, 0)] * 4 + [(0, WIDTH - RANK - ROPE)])
    q = jax.random.normal(ks[1], (slots, HEADS, RANK + ROPE))
    row_new = jax.random.normal(ks[2], (slots, RANK + ROPE))
    return plane.astype(dtype), q.astype(dtype), row_new.astype(dtype)


def dense(q, plane, row_new, entry, lengths=LENGTHS):
    return latent_decode_attention(
        q, plane, jnp.asarray(lengths), row_new, rank=RANK, scale=SCALE,
        layer=jnp.int32(entry),
    )


def through_the_kernel(q, plane, row_new, lengths=LENGTHS):
    """Every entry's attention, the entry's index traced through a scan as
    the decode step's layer scan hands it in. [entries, slots, heads, rank]"""

    def per_entry(carry, entry):
        return carry, latent_decode_attention(
            q, plane, jnp.asarray(lengths), row_new, rank=RANK, scale=SCALE,
            layer=entry, block=BLOCK,
        )

    return jax.lax.scan(per_entry, None, jnp.arange(ENTRIES))[1]


# ----------------------------------------------------------------------
# the kernel against the dense read
# ----------------------------------------------------------------------


def test_the_kernel_is_the_dense_read_in_float32():
    plane, q, row_new = operands(jnp.float32)
    got = through_the_kernel(q, plane, row_new)
    assert got.shape == (ENTRIES, len(LENGTHS), HEADS, RANK)
    for entry in range(ENTRIES):
        np.testing.assert_allclose(
            got[entry], dense(q, plane, row_new, entry), atol=1e-5
        )


def test_the_kernel_reads_the_entry_it_is_given():
    """Each entry's output is its own: read against another entry's dense
    attention, every slot with context is far off."""
    plane, q, row_new = operands(jnp.float32)
    got = through_the_kernel(q, plane, row_new)
    for entry in range(ENTRIES):
        for other in range(ENTRIES):
            if other == entry:
                continue
            distance = jnp.abs(got[entry] - dense(q, plane, row_new, other))
            assert float(distance[jnp.array(LIVE)].max(axis=(1, 2)).min()) > 0.05


def test_the_kernel_stays_within_bfloat16s_rounding():
    plane, q, row_new = operands(jnp.bfloat16)
    got = through_the_kernel(q, plane, row_new)
    assert got.dtype == jnp.bfloat16
    f32 = [x.astype(jnp.float32) for x in (q, plane, row_new)]
    for entry in range(ENTRIES):
        exact = dense(*f32, entry)
        np.testing.assert_allclose(
            got[entry].astype(jnp.float32), exact, atol=BF16_ATOL
        )
        np.testing.assert_allclose(
            dense(q, plane, row_new, entry).astype(jnp.float32), exact,
            atol=BF16_ATOL,
        )


def test_a_slot_of_length_zero_attends_its_new_row_alone():
    """Its whole cache poisoned, it reads the new row's values, every head."""
    plane, q, row_new = operands(jnp.float32)
    for slot in IDLE:
        plane = plane.at[:, slot].set(1e4)
    got = through_the_kernel(q, plane, row_new)
    for slot in IDLE:
        want = jnp.broadcast_to(row_new[slot, :RANK], (ENTRIES, HEADS, RANK))
        np.testing.assert_allclose(got[:, slot], want, atol=1e-6)


def test_positions_past_each_slots_length_are_not_read():
    """NaN in every block past the one that holds a slot's last position
    (never brought in), and a large value in the rest of that block past
    its length (brought in, masked): the same output."""
    plane, q, row_new = operands(jnp.float32)
    pos = jnp.arange(MAX_LEN)[None, :]
    lengths = jnp.asarray(LENGTHS)[:, None]
    own_blocks = jnp.maximum(-(-lengths // BLOCK), 1) * BLOCK
    at = lambda mask: mask[None, :, None, :, None]  # noqa: E731
    poisoned = jnp.where(at(pos >= lengths), 1e4, plane)
    poisoned = jnp.where(at(pos >= own_blocks), jnp.nan, poisoned)
    got = through_the_kernel(q, poisoned, row_new)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(
        got, through_the_kernel(q, plane, row_new), atol=1e-6
    )


def test_the_lanes_beyond_the_rows_content_are_zero_and_count_for_nothing():
    """The plane holds zeros beyond ``rank + rope`` (the cache's lane tile),
    and the queries are padded with zeros there: whatever those lanes held,
    the scores and the weighted sum are the same."""
    plane, q, row_new = operands(jnp.float32)
    assert float(jnp.abs(plane[..., RANK + ROPE:]).max()) == 0.0
    filled = plane.at[..., RANK + ROPE:].set(3.0)
    np.testing.assert_allclose(
        through_the_kernel(q, filled, row_new),
        through_the_kernel(q, plane, row_new), atol=1e-6,
    )


def test_the_kernel_refuses_a_block_that_does_not_divide_the_cache():
    plane, q, row_new = operands(jnp.float32)
    with pytest.raises(ValueError, match="does not divide"):
        latent_flash_decode(
            q, plane, jnp.asarray(LENGTHS), row_new, layer=jnp.int32(0),
            rank=RANK, scale=SCALE, block=96, interpret=True,
        )


# ----------------------------------------------------------------------
# the rule
# ----------------------------------------------------------------------


def test_the_kernel_is_chosen_for_a_latent_cache_on_a_tpu_without_a_mesh(
    monkeypatch,
):
    latent = get_model("mla-moe-tiny").config.is_latent
    hybrid = get_model("sala-tiny").config
    assert latent and hybrid.is_hybrid and not hybrid.is_latent
    rungs = decode_read_rungs(8192)
    # the CPU: the dense bounded read, whatever the cache
    assert latent_decode_block(8192, latent=True, dense=False) == 0
    assert decode_read_plan(8192, latent=True) == rungs
    monkeypatch.setattr(attention_module, "_on_tpu", lambda: True)
    assert latent_decode_block(8192, latent=True, dense=False) == (
        LATENT_DECODE_BLOCK
    )
    assert decode_read_plan(8192, latent=True) == (8192,)
    # under a mesh the dense form, which XLA partitions
    assert latent_decode_block(8192, latent=True, dense=True) == 0
    assert decode_read_plan(8192, latent=True, kernel=False) == rungs
    # a hybrid cache, or any other, never
    assert latent_decode_block(8192, latent=hybrid.is_latent, dense=False) == 0
    assert decode_read_plan(8192, hybrid=True) == rungs
    # the block divides the cache: the plane cannot be padded
    assert latent_decode_block(384, latent=True, dense=False) == 128
    assert latent_decode_block(1280, latent=True, dense=False) == 256
    assert latent_decode_block(1536, latent=True, dense=False) == 512
    assert latent_decode_block(100, latent=True, dense=False) == 100


# ----------------------------------------------------------------------
# the decode step and the engine through the kernel
# ----------------------------------------------------------------------

MODEL = "mla-moe-tiny"
CFG = dataclasses.replace(get_model(MODEL).config, dtype=jnp.float32)


@pytest.fixture
def kernel_chosen(monkeypatch):
    """The rule as it reads on a TPU, at a block that splits a 128-position
    cache in four; the kernel itself runs interpreted (the backend is the
    CPU's)."""
    monkeypatch.setattr(attention_module, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention_module, "LATENT_DECODE_BLOCK", 32)


def test_the_decode_step_through_the_kernel_is_the_dense_step(kernel_chosen):
    """Four slots, one of them not decoding, at lengths on each side of a
    block's edge: the same logits and the same cache after the step."""
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    params = init_transformer(ks[0], CFG)
    cache = LatentKVCache.create(CFG.n_cache_entries, 4, 128, CFG.cache_row,
                                 CFG.dtype)
    content = jax.random.normal(ks[1], cache.k.shape[:-1] + (CFG.cache_row,))
    cache = cache._replace(
        k=cache.k.at[..., :CFG.cache_row].set(content),
        lengths=jnp.array([31, 32, 33, 70], jnp.int32),
    )
    tokens = jnp.array([5, 6, 7, 8], jnp.int32)
    active = jnp.array([True, True, False, True])
    assert latent_decode_block(128, latent=True, dense=False) == 32
    step = jax.jit(transformer_decode_step,
                   static_argnames=("cfg", "dense_attn"))
    logits, after = step(params, tokens, cache, active, cfg=CFG)
    want, want_after = step(params, tokens, cache, active, cfg=CFG,
                            dense_attn=True)
    live = np.asarray(active)
    np.testing.assert_allclose(logits[live], want[live], atol=1e-5)
    np.testing.assert_allclose(after.k, want_after.k, atol=1e-5)
    np.testing.assert_array_equal(after.lengths, [32, 33, 33, 71])


def _windows_spied(engine):
    """Each processed window's ``held`` (every live slot's cached positions
    when it was dispatched) and ``longest``."""
    seen: list = []
    process = engine._process_window

    def spy(emitted, snapshot, etops, held, longest):
        seen.append((list(held), longest))
        return process(emitted, snapshot, etops, held, longest)

    engine._process_window = spy
    return seen


def _read_ratio(metrics, model):
    inst = {i.name: i for i in metrics.instruments()}["app_tpu_decode_read_ratio"]
    for labels, (_counts, (total, n)) in inst.collect().items():
        if ("model", model) in labels:
            return n, total
    return 0, 0.0


def _served(model, metrics, prompt, n):
    engine = InferenceEngine(
        model, n_slots=2, max_len=128, prefill_chunk=16, window_k=4,
        pipeline_depth=1, tokenizer=ByteTokenizer(), metrics=metrics,
    )
    seen = _windows_spied(engine)
    engine.start_sync()
    try:
        result = engine.generate_sync(
            prompt, max_new_tokens=n, temperature=0.0, stop_on_eos=False,
            timeout=300,
        )
    finally:
        engine.close()
    assert len(result.token_ids) == n
    return engine, seen


def test_the_read_ratio_counts_a_latent_caches_own_blocks_and_a_hybrids_rung(
    kernel_chosen,
):
    """One record a processed window. A latent cache read through the
    kernel: each live slot's blocks up to its length at the window's last
    step, over slots x max_len. A hybrid cache, asked on the same (TPU)
    rule: the rung of the longest slot under its dense length, as before."""
    metrics = Container.create(MockConfig({"APP_NAME": "latent-read"})).metrics
    latent, seen = _served(MODEL, metrics, "x" * 50, 20)
    assert latent.decode_read_block == 32
    assert latent.decode_read_rungs == (128,)
    n, total = _read_ratio(metrics, MODEL)
    k = latent.window_k
    want = [
        sum(-(-(h + k - 1) // 32) * 32 for h in held) / (2 * 128)
        for held, _ in seen
    ]
    assert n == len(seen) >= 4
    assert total == pytest.approx(sum(want))
    # a lone slot of 50 to 70 positions: two blocks of the eight, then three
    assert set(want) == {64 / 256, 96 / 256}

    hybrid, seen = _served("sala-tiny", metrics, "x" * 20, 20)
    assert hybrid.decode_read_block == 0
    rungs = hybrid.decode_read_rungs
    assert rungs == decode_read_rungs(128)
    n, total = _read_ratio(metrics, "sala-tiny")
    want = [
        rungs[decode_read_index(rungs, longest + k - 1)] / 128
        for _, longest in seen
    ]
    assert n == len(seen) >= 4
    assert total == pytest.approx(sum(want))


# ----------------------------------------------------------------------
# the kernel compiled for the chip, at the latent share's real widths
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_the_v5e_at_the_latent_shares_widths(one_chip):
    """Mosaic takes the kernel at ``openpangu-ultra-moe-718b-ep16``'s shapes
    (32 slots of 8,192 positions, 5 entries, 128 heads, a 576-value row in
    640 lanes, rank 512) and the block it serves with, and the compiled
    program copies nothing of the plane: no temporaries."""
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    S, H, W, E, ML = 32, 128, 640, 5, 8192
    attend = jax.jit(lambda q, plane, lengths, row, layer: latent_flash_decode(
        q, plane, lengths, row, layer=layer, rank=512, scale=192**-0.5,
        block=LATENT_DECODE_BLOCK,
    ))
    compiled = attend.lower(
        shape((S, H, W), jnp.bfloat16), shape((E, S, 1, ML, W), jnp.bfloat16),
        shape((S,), jnp.int32), shape((S, W), jnp.bfloat16),
        shape((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
