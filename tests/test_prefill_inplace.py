"""The dense prefill chunk step holds the K and V planes once: they ride the
layer scan's carry, each layer writes its chunk into its own cache entry in
place, and the attention reads that entry where it lies (the flash chunk
kernel takes the stacked plane and the entry's index).

On the CPU, in float32: ``transformer_prefill_chunk`` against a per-layer
reference of the former form, kept here (the planes handed to the scan as
xs, each layer's entry handed back as ys and stacked again), for a plain, an
expert (both products, padding rows in the grouped one) and a looped stack;
the scan's structure in the jaxpr; the compiled step's temporaries; and
through the engine, a prompt alone and beside seven others.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.config import MockConfig
from gofr_tpu.container import Container
from gofr_tpu.models import transformer as T
from gofr_tpu.models.registry import get_model, register_model
from gofr_tpu.ops.attention import cache_chunk_attention
from gofr_tpu.ops.kv_cache import KVCache, PagedKVCache, quantize_kv
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from tests.test_prefill_rungs import serve_together, tokens_of


def f32(name: str, **over) -> T.TransformerConfig:
    return dataclasses.replace(get_model(name).config, dtype=jnp.float32, **over)


CONFIGS = {
    "llama": f32("llama-tiny"),
    "moe": f32("moe-tiny"),
    "looped": f32("looped-tiny", n_passes=4),
}
# The engine's twins, under names of this file's own.
for _name, _cfg in (("llama-tiny", CONFIGS["llama"]), ("moe-tiny", CONFIGS["moe"]),
                    ("looped-tiny", CONFIGS["looped"])):
    register_model(dataclasses.replace(
        get_model(_name), name=_name + "-f32-inplace", config=_cfg
    ))


def xs_ys_prefill_chunk(params, tokens, cache, slots, starts, lens, cfg,
                        row_valid=None):
    """The former form of the dense chunk step: each layer's entry of the
    planes sliced out as the scan's xs, written, attended, handed back as
    ys and stacked into fresh planes. Returns (logits, cache)."""
    P, c = tokens.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = starts[:, None] + jnp.arange(c)[None, :]
    x = T._embed(params, tokens, cfg, positions)
    cos, sin = T.rope_frequencies(cfg.rope_dims, cache.max_len, cfg.rope_theta)
    paged = isinstance(cache, PagedKVCache)
    tiled = cfg.expert_product(P * c, False) == "tiles"
    counted = None
    if cfg.counts_routes or tiled:
        counted = jnp.arange(c)[None, :] < lens[:, None]
        if row_valid is not None:
            counted &= row_valid[:, None]
    write_pos = positions
    if tiled and row_valid is not None:
        write_pos = jnp.where(row_valid[:, None], positions, cache.max_len)
    idx_kv = jnp.arange(KV)[None, :, None]
    s_kv = jnp.arange(KV)[None, :, None, None]
    s_sub = jnp.arange(8)[None, None, :, None]
    if paged:
        B = cache.block
        bt_rows = cache.block_table[slots]
        blk = jnp.take_along_axis(
            bt_rows, jnp.minimum(write_pos // B, bt_rows.shape[1] - 1), axis=1
        )
        in_range = write_pos < cache.max_len
        blk = jnp.where(in_range, blk, 0)
        off = jnp.where(in_range, write_pos % B, B - 1)
        idx_row, idx_pos = blk[:, None, :], off[:, None, :]
        s_row, s_pos = blk[:, None, None, :], off[:, None, None, :]
    else:
        idx_row, idx_pos = slots[:, None, None], write_pos[:, None, :]
        s_row, s_pos = slots[:, None, None, None], write_pos[:, None, None, :]

    def body(x, scanned):
        lp, ck, cv, cks, cvs, entry = scanned  # one entry's planes
        h = T._norm(x, lp["attn_norm"], cfg, lp.get("attn_norm_b"))
        q, k, v = T._qkv(h, lp, "pcd,dh->pch", H, KV, hd, P, c)
        q = T.apply_rope(q, cos, sin, positions)
        k = T.apply_rope(k, cos, sin, positions)
        if cks is not None:
            k, k_sc = quantize_kv(k)
            v, v_sc = quantize_kv(v)
            cks = cks.at[s_row, s_kv, s_sub, s_pos].set(
                k_sc.transpose(0, 2, 1)[:, :, None, :])
            cvs = cvs.at[s_row, s_kv, s_sub, s_pos].set(
                v_sc.transpose(0, 2, 1)[:, :, None, :])
        ck = ck.at[idx_row, idx_kv, idx_pos].set(k.transpose(0, 2, 1, 3))
        cv = cv.at[idx_row, idx_kv, idx_pos].set(v.transpose(0, 2, 1, 3))
        one = None if cks is None else (cks[None], cvs[None])
        attn = cache_chunk_attention(
            q, ck[None], cv[None], slots, starts, lens, layer=0,
            k_scale=None if one is None else one[0],
            v_scale=None if one is None else one[1],
            block_table=cache.block_table if paged else None, kernel=False,
        )
        ao = attn.reshape(P, c, H * hd)
        attn_out = T._post_norm(
            T._wein("pch,hd->pcd", ao, lp["wo"]), lp, "attn_post_norm", cfg
        )
        mlp_in = x + attn_out
        h = T._norm(mlp_in, lp["mlp_norm"], cfg, lp.get("mlp_norm_b"))
        ffn, counts = T._ffn_any(
            h, lp, cfg, None, counted, params.get("experts"),
            T._expert_stack(params, cfg, entry if tiled else None),
        )
        return mlp_in + T._post_norm(ffn, lp, "mlp_post_norm", cfg), (
            ck, cv, cks, cvs, counts
        )

    x, (k, v, ks, vs, _) = T._scan_stack(
        body, x, params, cfg,
        (cache.k, cache.v, cache.k_s, cache.v_s, jnp.arange(cfg.n_cache_entries)),
    )
    cache = cache._replace(k=k, v=v, k_s=ks, v_s=vs)
    return T._chunk_logits(params, x, lens, cache, None, cfg, False)


def case_cache(cfg, S, max_len, quant="", block=0):
    """A cache whose every entry already holds random keys and values (the
    step must leave what it does not write as it was); a paged pool behind
    a table that hands each slot its blocks in reverse."""
    E, KV, hd = cfg.n_cache_entries, cfg.n_kv_heads, cfg.head_dim
    if block:
        cache = PagedKVCache.create(
            E, S, max_len, KV, hd, dtype=jnp.float32, block=block
        )
        n = cache.block_table.size
        cache = cache._replace(block_table=jnp.arange(n, 0, -1, dtype=jnp.int32)
                               .reshape(cache.block_table.shape))
    else:
        cache = KVCache.create(E, S, max_len, KV, hd, dtype=jnp.float32, quant=quant)
    key = jax.random.PRNGKey(3)
    k = jax.random.normal(key, cache.k.shape)
    v = jax.random.normal(jax.random.fold_in(key, 1), cache.k.shape)
    if quant:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        rep8 = lambda s: jnp.broadcast_to(  # noqa: E731
            s[..., None, :], s.shape[:-1] + (8, s.shape[-1]))
        return cache._replace(k=k, v=v, k_s=rep8(ks), v_s=rep8(vs))
    return cache._replace(k=k, v=v)


CASES = {
    # name: (config, rows P, chunk c, padding rows, cache kwargs)
    "llama": ("llama", 3, 16, 0, {}),
    "llama_int8_kv": ("llama", 3, 16, 0, {"quant": "int8"}),
    "llama_paged": ("llama", 3, 16, 0, {"block": 16}),
    "moe_einsum": ("moe", 1, 128, 0, {}),
    "moe_tiled_padding_rows": ("moe", 8, 128, 3, {}),
    "looped_4_passes": ("looped", 3, 16, 0, {}),
}


def case_step(name):
    cfg_name, P, c, pad, cache_kw = CASES[name]
    cfg = CONFIGS[cfg_name]
    S, max_len = 8, 256
    params = T.init_transformer(jax.random.PRNGKey(1), cfg)
    cache = case_cache(cfg, S, max_len, **cache_kw)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(3, 500, (P, c)), jnp.int32)
    live = P - pad
    slots = np.array([5, 0, 2, 7, 1, 3, 4, 6][:P])
    starts = np.array([0, 32, 96, 128, 16, 48, 64, 112][:P])
    lens = np.array([c, c, c // 2 + 1, c, 3, c, c - 5, c][:P])
    # Padding rows duplicate row 0, as the scheduler fills a rung.
    slots[live:], starts[live:], lens[live:] = slots[0], starts[0], lens[0]
    row_valid = jnp.arange(P) < live
    rows = (jnp.asarray(a, jnp.int32) for a in (slots, starts, lens))
    return cfg, (params, tokens, cache, *rows), row_valid


@pytest.mark.parametrize("name", list(CASES))
def test_the_carried_planes_give_the_former_forms_logits_and_planes(name):
    cfg, args, row_valid = case_step(name)
    assert (name == "moe_tiled_padding_rows") == (
        cfg.expert_product(args[1].size, False) == "tiles"
    )
    got_logits, got = jax.jit(
        lambda *a: T.transformer_prefill_chunk(*a, cfg, row_valid=row_valid)
    )(*args)
    want_logits, want = jax.jit(
        lambda *a: xs_ys_prefill_chunk(*a, cfg, row_valid=row_valid)
    )(*args)
    live = np.asarray(row_valid)
    np.testing.assert_allclose(
        np.asarray(got_logits)[live], np.asarray(want_logits)[live],
        atol=1e-5, rtol=1e-5,
    )
    for plane in ("k", "v", "k_s", "v_s"):
        if getattr(want, plane) is None:
            assert getattr(got, plane) is None
            continue
        np.testing.assert_allclose(
            np.asarray(getattr(got, plane), np.float32),
            np.asarray(getattr(want, plane), np.float32),
            atol=1e-5, rtol=1e-5, err_msg=plane,
        )
    # The step wrote something, and left the rest of the cache as it was.
    before = np.asarray(args[2].k, np.float32)
    changed = np.asarray(got.k, np.float32) != before
    assert changed.any() and not changed.all()


def plane_io(step, args, plane_shape):
    """(carried, xs, ys) counts of the layer scan's operands and results of
    the planes' shape (ys: the shape stacked, as a scan returns it)."""
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    layer_scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(layer_scans) == 1, [e.primitive.name for e in jaxpr.eqns]
    (eqn,) = layer_scans
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    shapes = [tuple(v.aval.shape) for v in eqn.invars]
    carried = shapes[n_consts:n_consts + n_carry]
    xs = [s[1:] for s in shapes[n_consts + n_carry:]]
    ys = [tuple(v.aval.shape) for v in eqn.outvars[n_carry:]]
    entry = plane_shape[1:]
    return (
        carried.count(plane_shape),
        xs.count(entry),
        sum(s in (plane_shape, entry) for s in ys),
    )


@pytest.mark.parametrize(
    "name", ["llama", "llama_int8_kv", "looped_4_passes", "moe_tiled_padding_rows"]
)
def test_the_scan_carries_the_planes_and_hands_none_back_as_ys(name):
    cfg, args, row_valid = case_step(name)
    plane = tuple(args[2].k.shape)
    carried, xs, ys = plane_io(
        lambda *a: T.transformer_prefill_chunk(*a, cfg, row_valid=row_valid),
        args, plane,
    )
    quant = args[2].k_s is not None
    assert (carried, xs, ys) == (2, 0, 0)
    if quant:  # the scale planes ride the carry too
        scale = tuple(args[2].k_s.shape)
        assert plane_io(
            lambda *a: T.transformer_prefill_chunk(*a, cfg, row_valid=row_valid),
            args, scale,
        ) == (2, 0, 0)
    # The former form: planes in as xs and out as ys, none carried.
    assert plane_io(
        lambda *a: xs_ys_prefill_chunk(*a, cfg, row_valid=row_valid), args, plane
    ) == (0, 2, 2)


@pytest.mark.parametrize("name,through", [
    ("llama", False), ("llama_int8_kv", False), ("llama_paged", False),
    ("moe_einsum", True), ("moe_tiled_padding_rows", True),
    ("looped_4_passes", False),
])
def test_the_cases_run_both_forms_of_the_write(name, through):
    """The comparison above holds each form of the write: in place in the
    stack, and through the entry's slice."""
    cfg, (_, tokens, cache, *_), _ = case_step(name)
    rows = tokens.size * cfg.n_kv_heads
    assert T.kv_write_through_slice(
        cache.k.shape, cache.k.dtype.itemsize, rows
    ) is through


@pytest.mark.parametrize("cell,shape,through", [
    # (entries, slots, kv heads, max_len, head_dim); [1, 256] and [8, 256]
    ("ouro-2.6b", (192, 8, 16, 384, 128), (True, True)),
    ("mistral-7b", (32, 14, 8, 2048, 128), (False, True)),
    ("mixtral-8x7b-d4", (4, 64, 8, 2048, 128), (False, False)),
])
def test_the_write_takes_the_form_the_chip_measured_faster(cell, shape, through):
    """Each dense cell's rungs write in the form that was faster on the v5e
    (PERF.md section 6): through the slice where a step writes a
    large share of a small entry, in place elsewhere."""
    got = tuple(
        T.kv_write_through_slice(shape, 2, rows * shape[2] * 256)
        for rows in (1, 8)
    )
    assert got == through, cell


# ----------------------------------------------------------------------
# through the engine
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "model", ["llama-tiny-f32-inplace", "moe-tiny-f32-inplace",
              "looped-tiny-f32-inplace"],
)
def test_a_prompt_alone_and_beside_seven_others(model):
    """The same greedy tokens for a prompt served alone (the one-row rung)
    and beside seven others in the eight-row rung (grouped experts in
    ``moe-tiny``'s); the compiled step's temporaries are exported and in
    health."""
    metrics = Container.create(MockConfig({"APP_NAME": "inplace-test"})).metrics
    engine = InferenceEngine(
        model, tokenizer=ByteTokenizer(), n_slots=8, max_len=256,
        prefill_chunk=128, window_k=4, pipeline_depth=1, metrics=metrics,
    )
    assert engine.prefill_rungs == (1, 8)
    engine.start_sync()
    try:
        prompt = tokens_of(41, 150)
        (alone,) = serve_together(engine, [prompt], new_tokens=6)
        others = [tokens_of(50 + i, 40 + 25 * i) for i in range(7)]
        together = serve_together(engine, [prompt, *others], new_tokens=6)
        assert together[0].token_ids == alone.token_ids
        assert len(alone.token_ids) == 6
        temp = engine.health_check()["details"]["prefill_temp_bytes"]
        assert temp > 0
        gauge = {i.name: i for i in metrics.instruments()}[
            "app_tpu_prefill_temp_bytes"
        ].collect()
        assert gauge == {(("model", model),): temp}
    finally:
        engine.close()
