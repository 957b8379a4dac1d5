"""The dense decode attention reads only the rung of the cache that holds
the longest live slot (ISSUE 31).

Three things are held here, all on the CPU at tiny sizes: the rule (which
prefixes a ``max_len`` has, and which of them a length takes), that the
bounded read is the whole read bit for bit for every slot the rung holds,
and that the decode step chooses its rung from the ACTIVE slots alone and
reads nothing beyond it (the cache is poisoned there).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.registry import get_model
from gofr_tpu.models.transformer import (
    init_transformer,
    transformer_decode_step,
)
from gofr_tpu.ops.attention import (
    decode_attention,
    decode_read_index,
    decode_read_plan,
    decode_read_rungs,
)
from gofr_tpu.ops.kv_cache import KVCache, quantize_kv

# ``gofr_tpu.ops.attention`` the attribute is the function of that name.
attention_module = importlib.import_module("gofr_tpu.ops.attention")


# ----------------------------------------------------------------------
# the rule
# ----------------------------------------------------------------------


@pytest.mark.parametrize("max_len,rungs", [
    (2048, (512, 1024, 1536, 2048)),
    (384, (128, 256, 384)),
    (8192, (2048, 4096, 6144, 8192)),
    (64, (64,)),
    (128, (128,)),
    (1000, (256, 512, 768, 1000)),
])
def test_the_rungs_are_max_lens_quarters_on_the_lane_tile(max_len, rungs):
    assert decode_read_rungs(max_len) == rungs


@pytest.mark.parametrize("longest,rung", [
    (0, 512), (1, 512), (511, 512), (512, 512), (513, 1024), (1024, 1024),
    (1025, 1536), (1536, 1536), (1537, 2048), (2047, 2048),
])
def test_a_length_takes_the_smallest_rung_that_holds_it(longest, rung):
    """``lengths`` counts the cached prefix without the step's own token,
    so a length equal to a rung fits it. The host (a Python int) and the
    device (a traced scalar) ask the same function."""
    rungs = decode_read_rungs(2048)
    assert rungs[decode_read_index(rungs, longest)] == rung
    on_device = jax.jit(lambda n: decode_read_index(rungs, n))(longest)
    assert rungs[int(on_device)] == rung


def test_a_cache_with_one_rung_keeps_the_whole_read():
    assert decode_read_index(decode_read_rungs(64), 63) == 0


@pytest.mark.parametrize("kw,rungs", [
    ({}, (128, 256)),
    ({"paged": True}, (256,)),
    ({"kernel": True}, (256,)),
    ({"window": 64, "kernel": False}, (128, 256)),
    ({"window": 4096}, (128, 256)),
], ids=["dense", "paged", "kernel", "binding-window-dense", "idle-window"])
def test_only_the_dense_path_over_a_contiguous_cache_is_bounded(kw, rungs):
    """What the engine's counter is told is what ``decode_attention``
    does: a paged pool and the kernel read as before (one rung)."""
    assert decode_read_plan(256, **kw) == rungs


def test_the_tpus_auto_choice_bounds_up_to_2048_and_leaves_the_kernel_above(
    monkeypatch,
):
    monkeypatch.setattr(attention_module, "_flash_decode_enabled", lambda: True)
    assert decode_read_plan(2048) == (512, 1024, 1536, 2048)
    assert decode_read_plan(4096) == (4096,)
    # A binding window takes the kernel, which reads the window's blocks.
    assert decode_read_plan(2048, window=1024) == (2048,)


# ----------------------------------------------------------------------
# bounded against whole, bit for bit
# ----------------------------------------------------------------------

MAX_LEN, ENTRIES, SLOTS, KV, HD = 512, 2, 3, 2, 16
RUNGS = decode_read_rungs(MAX_LEN)  # 128, 256, 384, 512


def planes(dtype, variant):
    """Stacked caches ``[entries, slots, kv, max_len, hd]`` and, for an
    int8 cache, their scale planes ``[entries, slots, kv, 8, max_len]``."""
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    shape = (ENTRIES, SLOTS, KV, MAX_LEN, HD)
    k, v = (jax.random.normal(key, shape, jnp.float32) for key in keys)
    if variant != "int8":
        return k.astype(dtype), v.astype(dtype), None, None

    def quantized(x):
        q, scale = quantize_kv(x)  # scale [entries, slots, kv, max_len]
        return q, jnp.broadcast_to(
            scale[:, :, :, None, :], (ENTRIES, SLOTS, KV, 8, MAX_LEN)
        )

    (k, k_s), (v, v_s) = quantized(k), quantized(v)
    return k, v, k_s, v_s


@pytest.mark.parametrize("variant", ["plain", "int8", "window"])
@pytest.mark.parametrize("split", [True, False], ids=["split", "written-in"])
@pytest.mark.parametrize("rep", [1, 4], ids=["ungrouped", "grouped"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_bounded_read_is_the_whole_read(dtype, rep, split, variant):
    """Every rung, with the longest slot one short of it, on it and one
    past it (which takes the next rung): the slots' outputs equal the
    whole read's to the last bit, from the stacked planes (the decode
    step's call) and from one entry's. A binding window stays on the dense
    path here (``kernel=False``, a mesh's choice)."""
    k, v, k_s, v_s = planes(dtype, variant)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (SLOTS, KV * rep, HD), dtype)
    new = {}
    if split:
        new = {
            "k_new": jax.random.normal(keys[1], (SLOTS, KV, HD), dtype),
            "v_new": jax.random.normal(keys[2], (SLOTS, KV, HD), dtype),
        }
    window = 100 if variant == "window" else 0
    entry = jnp.int32(1)

    @jax.jit
    def whole(lengths):
        return decode_attention(
            q, k[1], v[1], lengths, kernel=False, window=window,
            k_scale=None if k_s is None else k_s[1],
            v_scale=None if v_s is None else v_s[1], **new,
        )

    @jax.jit
    def bounded(lengths):
        read = decode_read_index(RUNGS, jnp.max(lengths))
        stacked = decode_attention(
            q, k, v, lengths, kernel=False, window=window, k_scale=k_s,
            v_scale=v_s, layer=entry, read=read, **new,
        )
        one_entry = decode_attention(
            q, k[1], v[1], lengths, kernel=False, window=window,
            k_scale=None if k_s is None else k_s[1],
            v_scale=None if v_s is None else v_s[1], read=read, **new,
        )
        return stacked, one_entry

    first = 0 if split else 1  # written-in: lengths include the token
    for rung in RUNGS:
        for longest in (rung - 1, rung, rung + 1):
            if longest >= MAX_LEN:
                continue
            lengths = jnp.array([longest, 37, first], jnp.int32)
            want = np.asarray(whole(lengths).astype(jnp.float32))
            for got in bounded(lengths):
                np.testing.assert_array_equal(
                    np.asarray(got.astype(jnp.float32)), want,
                    err_msg=f"longest {longest} at rung {rung}",
                )


def test_positions_beyond_the_rung_are_not_read():
    """NaN from the first rung on would reach the output through the
    weighted sum (0 x NaN) if the branch read it."""
    k, v, _, _ = planes(jnp.float32, "plain")
    v = v.at[:, :, :, RUNGS[0]:].set(jnp.nan)
    q = jax.random.normal(jax.random.PRNGKey(5), (SLOTS, KV, HD))
    lengths = jnp.array([RUNGS[0], 5, 0], jnp.int32)

    def attend(read):
        return decode_attention(
            q, k, v, lengths, kernel=False, layer=jnp.int32(0), read=read,
        )

    assert bool(jnp.all(jnp.isfinite(attend(jnp.int32(0)))))
    assert not bool(jnp.all(jnp.isfinite(attend(jnp.int32(1)))))
    assert not bool(jnp.all(jnp.isfinite(attend(None))))


# ----------------------------------------------------------------------
# the decode step chooses the rung from the active slots
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model("llama-tiny-f32").config  # max_len 256: rungs 128, 256
    return cfg, init_transformer(jax.random.PRNGKey(0), cfg)


def poisoned_cache(cfg, lengths, beyond: int) -> KVCache:
    """Random keys and values, NaN in the values from ``beyond`` on."""
    cache = KVCache.create(
        cfg.n_cache_entries, len(lengths), cfg.max_len, cfg.n_kv_heads,
        cfg.head_dim, dtype=cfg.dtype,
    )
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    k = jax.random.normal(keys[0], cache.k.shape, cfg.dtype)
    v = jax.random.normal(keys[1], cache.v.shape, cfg.dtype)
    return cache._replace(
        k=k, v=v.at[:, :, :, beyond:].set(jnp.nan),
        lengths=jnp.asarray(lengths, jnp.int32),
    )


@pytest.mark.parametrize("lengths,active,finite", [
    ((100, 250, 0), (True, False, True), True),
    ((128, 250, 250), (True, False, False), True),
    ((100, 129, 0), (True, True, False), False),
], ids=["stale-long-inactive", "length-on-the-rung", "long-active"])
def test_the_step_reads_the_rung_of_the_longest_active_slot(
    tiny, lengths, active, finite,
):
    """An inactive slot's stale length (a retired request's, or a prompt
    mid-way through its chunks) does not widen the read: with the cache
    poisoned from the first rung on, the live slots' logits are finite
    exactly when the first rung was the one read."""
    cfg, params = tiny
    cache = poisoned_cache(cfg, lengths, decode_read_rungs(cfg.max_len)[0])
    active = jnp.asarray(active)
    logits, after = jax.jit(
        lambda c: transformer_decode_step(
            params, jnp.array([5, 6, 7], jnp.int32), c, active, cfg
        )
    )(cache)
    live = np.asarray(active) & (np.asarray(lengths) <= 128)
    assert bool(np.all(np.isfinite(np.asarray(logits)[live]))) is finite
    np.testing.assert_array_equal(
        np.asarray(after.lengths), np.asarray(lengths) + np.asarray(active)
    )


@pytest.mark.parametrize("model", ["llama-tiny-f32", "moe-tiny", "looped-tiny"])
def test_the_bounded_step_is_the_whole_step(model):
    """Logits and the committed cache, bit for bit, against the step that
    keeps the whole read (``bound_read=False``, a context-parallel
    cache's), while the lengths grow across a rung."""
    cfg = get_model(model).config
    params = init_transformer(jax.random.PRNGKey(1), cfg)
    cache = poisoned_cache(cfg, (126, 40, 250), cfg.max_len)  # no poison
    active = jnp.array([True, True, False])

    def steps(bound_read):
        def run(cache):
            out = []
            tokens = jnp.array([3, 4, 5], jnp.int32)
            for _ in range(4):  # 126 -> 130: over the 128 rung
                logits, cache = transformer_decode_step(
                    params, tokens, cache, active, cfg,
                    bound_read=bound_read,
                )
                tokens = jnp.argmax(logits, -1).astype(jnp.int32)
                out.append(logits)
            # The inactive row is computed and discarded: its logits go
            # nowhere, its keys and values park at max_len - 1.
            return jnp.stack(out)[:, :2], cache.k[:, :2], cache.v[:, :2]
        return jax.jit(run)(cache)

    for got, want in zip(steps(True), steps(False)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)
        )
