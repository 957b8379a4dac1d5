"""Pallas kernel correctness vs the dense reference path.

Kernels run in interpret mode (CPU); the dense jnp implementations in
``gofr_tpu.ops.attention`` are the oracle. Mirrors the reference's
fake-backend test idiom (SURVEY §4: miniredis stands in for Redis; here the
interpreter stands in for the TPU).

``chip_smoke.py``'s kernel phase imports this module on the TPU, sets
:data:`INTERPRET` to False and runs :func:`serving_kernel_cases` at
:data:`MISTRAL_7B` geometry — the same cases the CPU suite runs
interpreted at :data:`TINY`, compiled through Mosaic.
"""

import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import (
    attention,
    cache_chunk_attention,
    decode_attention,
)
from gofr_tpu.ops.kv_cache import paged_view, quantize_kv
from gofr_tpu.ops.pallas import (
    flash_attention,
    flash_cache_attention,
    flash_decode,
)

# Every pallas_call in this module takes this one switch.
INTERPRET = True


def _qkv(key, b, s_q, s_kv, n_heads, n_kv, hd, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s_q, n_heads, hd), dtype)
    k = jax.random.normal(kk, (b, s_kv, n_kv, hd), dtype)
    v = jax.random.normal(kv, (b, s_kv, n_kv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,s_q,s_kv,n_heads,n_kv,hd,causal",
    [
        (1, 64, 64, 4, 4, 32, True),     # MHA causal
        (2, 64, 64, 4, 2, 32, True),     # GQA
        (1, 32, 128, 4, 2, 32, True),    # query is suffix of keys
        (2, 64, 64, 4, 2, 32, False),    # non-causal (encoder)
        (1, 50, 70, 4, 2, 32, True),     # ragged: padding both axes
    ],
)
def test_flash_attention_matches_dense(b, s_q, s_kv, n_heads, n_kv, hd, causal):
    q, k, v = _qkv(jax.random.PRNGKey(0), b, s_q, s_kv, n_heads, n_kv, hd)
    want = attention(q, k, v, causal=causal)
    got = flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=INTERPRET
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "b,s,lengths,causal",
    [
        (3, 64, [1, 33, 64], True),     # ragged right-padded rows, causal
        (2, 128, [100, 17], True),      # lengths off block boundaries
        (2, 64, [40, 64], False),       # non-causal (encoder-style)
    ],
)
def test_flash_attention_lengths_matches_dense(b, s, lengths, causal):
    """The serving-prefill case: per-row valid prefixes masked in-kernel
    (VERDICT r1 weak #3 — prefill must keep the kernel path)."""
    q, k, v = _qkv(jax.random.PRNGKey(3), b, s, s, 4, 2, 32)
    lens = jnp.asarray(lengths, dtype=jnp.int32)
    want = attention(q, k, v, causal=causal, lengths=lens, kernel=False)
    got = flash_attention(
        q, k, v, lens, causal=causal, block_q=32, block_k=32, interpret=INTERPRET
    )
    # Rows at/after a row's own length are padding queries — the kernel
    # emits 0 there while the dense path emits uniform-softmax junk; only
    # compare valid rows.
    for i, ln in enumerate(lengths):
        np.testing.assert_allclose(
            np.asarray(got)[i, :ln], np.asarray(want)[i, :ln],
            atol=2e-5, rtol=2e-5,
        )


def test_attention_lengths_dispatches_kernel(monkeypatch):
    """attention(lengths=...) must keep the kernel path when flash is on."""
    import importlib

    # `import gofr_tpu.ops.attention as m` would bind the re-exported
    # FUNCTION (ops/__init__ shadows the submodule name); go via sys.modules.
    attn_mod = importlib.import_module("gofr_tpu.ops.attention")

    called = {}
    real = flash_attention

    def spy(q, k, v, lengths=None, **kw):
        called["lengths"] = lengths
        return real(q, k, v, lengths, **kw)

    monkeypatch.setattr(attn_mod, "_flash_enabled", lambda: True)
    monkeypatch.setattr(attn_mod, "_interpret", lambda: True)
    import gofr_tpu.ops.pallas as pallas_pkg

    monkeypatch.setattr(pallas_pkg, "flash_attention", spy)
    q, k, v = _qkv(jax.random.PRNGKey(4), 2, 32, 32, 4, 2, 32)
    lens = jnp.asarray([10, 32], dtype=jnp.int32)
    attn_mod.attention(q, k, v, causal=True, lengths=lens)
    assert called["lengths"] is lens


def test_flash_attention_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 64, 64, 4, 2, 64, jnp.bfloat16)
    want = attention(q, k, v, causal=True).astype(jnp.float32)
    got = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=INTERPRET
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize(
    "b,max_len,n_heads,n_kv,hd,lengths",
    [
        (4, 128, 4, 4, 32, [1, 7, 64, 128]),   # MHA, ragged lengths
        (2, 256, 8, 2, 32, [100, 256]),        # GQA
        (3, 96, 4, 2, 32, [5, 96, 33]),        # max_len not block-multiple
    ],
)
def test_flash_decode_matches_dense(b, max_len, n_heads, n_kv, hd, lengths):
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, n_heads, hd))
    k_cache = jax.random.normal(kk, (b, n_kv, max_len, hd))
    v_cache = jax.random.normal(kv, (b, n_kv, max_len, hd))
    lens = jnp.array(lengths, dtype=jnp.int32)

    want = decode_attention(q, k_cache, v_cache, lens)
    got = flash_decode(q, k_cache, v_cache, lens, block_k=64, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "b,max_len,n_heads,n_kv,hd,lengths",
    [
        (4, 128, 4, 4, 32, [0, 7, 64, 127]),   # incl. empty prefix
        (2, 256, 8, 2, 32, [100, 255]),        # GQA
    ],
)
def test_split_decode_matches_write_then_attend(
    b, max_len, n_heads, n_kv, hd, lengths
):
    """decode_attention(k_new=...) over the cache PREFIX must equal the
    old convention (token written at lengths-1, lengths includes it) —
    dense split vs dense written, and the kernel split path vs dense."""
    key = jax.random.PRNGKey(7)
    kq, kk, kv, kn, vn_key = jax.random.split(key, 5)
    q = jax.random.normal(kq, (b, n_heads, hd))
    k_cache = jax.random.normal(kk, (b, n_kv, max_len, hd))
    v_cache = jax.random.normal(kv, (b, n_kv, max_len, hd))
    k_new = jax.random.normal(kn, (b, n_kv, hd))
    v_new = jax.random.normal(vn_key, (b, n_kv, hd))
    prev = jnp.array(lengths, dtype=jnp.int32)

    # Old convention: write the new token at position prev, lengths+1.
    bi = jnp.arange(b)[:, None]
    ki = jnp.arange(n_kv)[None, :]
    kw = k_cache.at[bi, ki, prev[:, None]].set(k_new)
    vw = v_cache.at[bi, ki, prev[:, None]].set(v_new)
    want = decode_attention(kernel=False, q=q, k_cache=kw, v_cache=vw,
                            lengths=prev + 1)

    got = decode_attention(
        q, k_cache, v_cache, prev, k_new=k_new, v_new=v_new, kernel=False
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )

    got_kern = flash_decode(
        q, k_cache, v_cache, prev, k_new=k_new, v_new=v_new, block_k=64,
        interpret=INTERPRET,
    )
    np.testing.assert_allclose(
        np.asarray(got_kern), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_dispatch_and_grad(monkeypatch):
    # Force the kernel path off-TPU (interpret mode) and check both the
    # dispatch and the dense-recompute backward pass.
    import importlib

    att = importlib.import_module("gofr_tpu.ops.attention")
    monkeypatch.setattr(att, "_FLASH_ENV", "1")
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 32, 32, 4, 2, 32)

    got = att.attention(q, k, v, causal=True)
    want = att.attention(q, k, v, causal=True, kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def loss_kernel(q):
        return jnp.sum(att.attention(q, k, v, causal=True) ** 2)

    def loss_dense(q):
        return jnp.sum(att.attention(q, k, v, causal=True, kernel=False) ** 2)

    g_kernel = jax.grad(loss_kernel)(q)
    g_dense = jax.grad(loss_dense)(q)
    np.testing.assert_allclose(
        np.asarray(g_kernel), np.asarray(g_dense), atol=1e-4, rtol=1e-4
    )


def test_flash_decode_zero_length_slot_is_finite():
    # Empty slots (length 0) must not poison the batch with NaNs.
    b, max_len, n_kv, hd = 2, 64, 2, 32
    q = jnp.ones((b, 4, hd))
    k_cache = jnp.ones((b, n_kv, max_len, hd))
    v_cache = jnp.ones((b, n_kv, max_len, hd))
    lens = jnp.array([0, 10], dtype=jnp.int32)
    got = flash_decode(q, k_cache, v_cache, lens, block_k=64, interpret=INTERPRET)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got[0]), 0.0)


def test_flash_decode_env_override(monkeypatch):
    """GOFR_TPU_FLASH_DECODE overrides GOFR_TPU_FLASH for decode only —
    the bench's A/B knob for the kernel-vs-fused-dense decode trade."""
    import importlib

    att = importlib.import_module("gofr_tpu.ops.attention")
    monkeypatch.setattr(att, "_FLASH_ENV", "1")
    monkeypatch.setattr(att, "_FLASH_DECODE_ENV", "0")
    assert att._flash_enabled() is True
    assert att._flash_decode_enabled() is False
    monkeypatch.setattr(att, "_FLASH_DECODE_ENV", "1")
    assert att._flash_decode_enabled() is True
    monkeypatch.setattr(att, "_FLASH_DECODE_ENV", "")
    monkeypatch.setattr(att, "_FLASH_ENV", "0")
    assert att._flash_decode_enabled() is False

    # Both paths agree regardless of the knob.
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 32), jnp.float32)
    k_cache = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 64, 32), jnp.float32)
    v_cache = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 64, 32), jnp.float32)
    lens = jnp.asarray([5, 64], jnp.int32)
    dense = att.decode_attention(q, k_cache, v_cache, lens, kernel=False)
    kern = att.decode_attention(q, k_cache, v_cache, lens, kernel=True)
    np.testing.assert_allclose(
        np.asarray(kern), np.asarray(dense), atol=2e-5, rtol=2e-5
    )


def test_windowed_flash_attention_matches_dense():
    """Windowed full-sequence kernel == dense windowed math: suffix
    queries (s_kv > s_q offset), ragged lengths, and the differentiable
    wrapper's dense-recompute backward."""
    from gofr_tpu.ops.attention import attention

    b, s_kv, s_q, n_heads, n_kv, hd, w = 2, 192, 192, 4, 2, 32, 48
    key = jax.random.PRNGKey(31)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s_q, n_heads, hd))
    k = jax.random.normal(kk, (b, s_kv, n_kv, hd))
    v = jax.random.normal(kv_, (b, s_kv, n_kv, hd))

    want = attention(q, k, v, causal=True, window=w, kernel=False)
    got = flash_attention(
        q, k, v, causal=True, window=w, block_q=64, block_k=64,
        interpret=INTERPRET,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    full = attention(q, k, v, causal=True, kernel=False)
    assert not np.allclose(np.asarray(full), np.asarray(want), atol=1e-3)

    # Suffix-query case: the causal offset composes with the window.
    qs = q[:, -64:]
    want_s = attention(qs, k, v, causal=True, window=w, kernel=False)
    got_s = flash_attention(
        qs, k, v, causal=True, window=w, block_q=64, block_k=64,
        interpret=INTERPRET,
    )
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(want_s), atol=2e-5, rtol=2e-5
    )

    # Ragged lengths (serving prefill shape). Rows at positions past a
    # batch's valid length can have ZERO visible keys once the window
    # excludes the valid prefix — dense then emits uniform-softmax junk
    # while the kernel emits its guarded 0; serving reads neither, so
    # compare only the valid rows.
    lens = jnp.array([50, 192], dtype=jnp.int32)
    want_l = np.asarray(attention(
        q, k, v, causal=True, window=w, lengths=lens, kernel=False
    ))
    got_l = np.asarray(flash_attention(
        q, k, v, lens, causal=True, window=w, block_q=64, block_k=64,
        interpret=INTERPRET,
    ))
    for bi, ln in enumerate([50, 192]):
        np.testing.assert_allclose(
            got_l[bi, :ln], want_l[bi, :ln], atol=2e-5, rtol=2e-5
        )


def test_windowed_flash_attention_grad(monkeypatch):
    """Windowed kernel forward + dense-recompute backward == dense grad
    (windowed-model training path)."""
    import importlib

    att = importlib.import_module("gofr_tpu.ops.attention")
    monkeypatch.setattr(att, "_FLASH_ENV", "1")
    b, s, n_heads, n_kv, hd, w = 1, 64, 4, 2, 32, 16
    key = jax.random.PRNGKey(33)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n_heads, hd))
    k = jax.random.normal(kk, (b, s, n_kv, hd))
    v = jax.random.normal(kv_, (b, s, n_kv, hd))

    got = att.attention(q, k, v, causal=True, window=w)
    want = att.attention(q, k, v, causal=True, window=w, kernel=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )

    def loss_kernel(q):
        return jnp.sum(att.attention(q, k, v, causal=True, window=w) ** 2)

    def loss_dense(q):
        return jnp.sum(
            att.attention(q, k, v, causal=True, window=w, kernel=False) ** 2
        )

    gk = jax.grad(loss_kernel)(q)
    gd = jax.grad(loss_dense)(q)
    np.testing.assert_allclose(
        np.asarray(gk), np.asarray(gd), atol=1e-4, rtol=1e-4
    )


# ----------------------------------------------------------------------
# The serving-case matrix: one case list, two geometries
# ----------------------------------------------------------------------


class Geometry(NamedTuple):
    """Shapes one run of the matrix uses. ``window`` binds inside
    ``max_len`` in both; both keep mistral's 4 query heads per kv head
    (the q/out block height Mosaic sees)."""

    n_heads: int
    n_kv: int
    hd: int
    slots: int
    max_len: int      # cache capacity per slot
    block: int        # paged pool block (TPU_KV_BLOCK)
    window: int       # sliding window, < max_len so it binds
    chunk: int        # prefill chunk length (TPU_PREFILL_CHUNK)
    attn_sq: int      # flash_attention: windowed suffix queries ...
    attn_skv: int     # ... against this many keys (> window)
    attn_s: int       # flash_attention: unwindowed square length
    dtype: str        # activations (and K/V where not int8)


# mistral-7b head geometry at the smoke's paged boot: 32 q / 8 kv heads
# of 128, window 4096 inside an 8192 cache, pool block 32, chunk 256.
MISTRAL_7B = Geometry(
    n_heads=32, n_kv=8, hd=128, slots=4, max_len=8192, block=32,
    window=4096, chunk=256, attn_sq=512, attn_skv=4608, attn_s=1024,
    dtype="bfloat16",
)
# f32 on the CPU so the kernels' arithmetic is pinned tightly; the chip
# runs the dtype serving runs.
TINY = Geometry(
    n_heads=8, n_kv=2, hd=32, slots=4, max_len=256, block=32,
    window=96, chunk=8, attn_sq=64, attn_skv=192, attn_s=64,
    dtype="float32",
)


def serving_tolerance(case: "KernelCase", g: Geometry) -> float:
    """atol = rtol against the dense path, fixed from the dtypes. With
    bf16 anywhere (bf16 activations, or the int8 cache's bf16 V and
    probabilities) the kernel and the dense path round probabilities to
    bf16 at different points (before vs after normalisation) and outputs
    are O(1): a few bf16 ulps of 2^-8. All-f32 differs only in summation
    order."""
    return 3e-2 if (g.dtype == "bfloat16" or case.int8_kv) else 2e-5


class KernelCase(NamedTuple):
    kernel: str       # "decode" | "prefill_chunk" | "attention"
    int8_kv: bool
    paged: bool
    windowed: bool
    extra: bool       # decode: k_new/v_new split; attention: lengths
    rows: int = 3     # prefill_chunk: the step's row count (its rung)


def serving_kernel_cases() -> list[KernelCase]:
    """Every kernel variant the serving path can select: bf16 and
    int8-KV, contiguous and paged, window binding or not, decode with
    and without the ``k_new/v_new`` split, full attention with and
    without ``lengths``; the prefill chunk at 3, 2 and 1 rows (a step
    runs at the rung that holds the rows that wait)."""
    cases = [
        KernelCase("decode", q8, paged, win, new)
        for q8, paged, win, new in itertools.product((False, True), repeat=4)
    ]
    cases += [
        KernelCase("prefill_chunk", q8, paged, win, False, rows)
        for q8, paged, win in itertools.product((False, True), repeat=3)
        for rows in (3, 2, 1)
    ]
    cases += [
        KernelCase("attention", False, False, win, lens)
        for win, lens in itertools.product((False, True), repeat=2)
    ]
    return cases


def _rep8(scale, width):
    """[rows, KV, width] scales → the sublane-replicated
    [rows, KV, 8, width] f32 plane the caches store."""
    return jnp.broadcast_to(
        scale[:, :, None, :], scale.shape[:2] + (8, width)
    ).astype(jnp.float32)


def _case_cache(key, g: Geometry, case: KernelCase):
    """A slot cache for ``case``: contiguous ``[S, KV, max_len, hd]`` or
    a pool ``[1 + S*mb, KV, block, hd]`` behind a scrambled table (block
    0 parks, as in the engine), bf16 or int8 with scale planes. Returns
    ``(k, v, kwargs)`` twice: what the kernel takes, and the contiguous
    per-slot view the dense path takes."""
    kk, kv_, kt = jax.random.split(key, 3)
    if case.paged:
        mb = g.max_len // g.block
        rows, width = 1 + g.slots * mb, g.block
        table = (
            jax.random.permutation(kt, rows - 1) + 1
        ).reshape(g.slots, mb).astype(jnp.int32)
    else:
        rows, width, table = g.slots, g.max_len, None
    shape = (rows, g.n_kv, width, g.hd)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv_, shape, jnp.float32)
    ks = vs = None
    if case.int8_kv:
        k, ksc = quantize_kv(k)
        v, vsc = quantize_kv(v)
        ks, vs = _rep8(ksc, width), _rep8(vsc, width)
    else:
        k, v = k.astype(g.dtype), v.astype(g.dtype)
    kern = (k, v, dict(k_scale=ks, v_scale=vs, block_table=table))
    if case.paged:
        k, v, ks, vs = paged_view(
            table, k, v, jnp.arange(g.slots), ks, vs
        )
    return kern, (k, v, dict(k_scale=ks, v_scale=vs))


def run_serving_kernel_case(case: KernelCase, g: Geometry) -> float:
    """Run one case's kernel (``interpret=INTERPRET``) against the dense
    path in ``ops/attention.py`` (``kernel=False``); returns the max
    absolute difference over the rows serving reads, and asserts it is
    finite and within :func:`serving_tolerance` — and, for a windowed
    cache case, that the window really binds (the unwindowed dense
    result differs)."""
    key = jax.random.PRNGKey(97)
    kq, kc, kn, vn = jax.random.split(key, 4)
    w = g.window if case.windowed else 0
    bf = jnp.dtype(g.dtype)
    unwindowed = None

    def queries(shape):
        # 4× unit-normal queries give logits of std 4: the softmax peaks
        # on a few keys, outputs stay O(1) however long the row, and one
        # key wrongly seen or missed moves them far past the tolerance.
        # Unit queries over 8k random keys average everything to ~0.01.
        return (4 * jax.random.normal(kq, shape, jnp.float32)).astype(bf)

    if case.kernel != "attention":
        (k_c, v_c, kern), (k_d, v_d, dense) = _case_cache(kc, g, case)
    if case.kernel == "decode":
        q = queries((g.slots, g.n_heads, g.hd))
        # Empty, short, just past the window, and full slots.
        lens = jnp.array(
            [0, g.window // 3, g.window + g.block + 5, g.max_len - 1],
            jnp.int32,
        )[: g.slots]
        if not case.extra:
            lens = jnp.maximum(lens, 1)  # lengths include the query
        new = {}
        if case.extra:
            new = dict(
                k_new=jax.random.normal(kn, (g.slots, g.n_kv, g.hd), bf),
                v_new=jax.random.normal(vn, (g.slots, g.n_kv, g.hd), bf),
            )
        got = flash_decode(
            q, k_c, v_c, lens, window=w, interpret=INTERPRET, **kern, **new
        )
        want = decode_attention(
            q, k_d, v_d, lens, window=w, kernel=False, **dense, **new
        )
        if w:
            unwindowed = decode_attention(
                q, k_d, v_d, lens, kernel=False, **dense, **new
            )
        valid = np.ones(got.shape[:1], bool)
    elif case.kernel == "prefill_chunk":
        P, c = case.rows, g.chunk
        q = queries((P, c, g.n_heads, g.hd))
        # First chunk, a chunk straddling the window edge, a ragged
        # last chunk deep in the cache; fewer rows keep the last ones.
        slots = jnp.array([0, 3, 1][-P:], jnp.int32)
        starts = jnp.array(
            [0, g.window - c // 2, g.max_len - 2 * c][-P:], jnp.int32
        )
        lens = jnp.array([c, c, max(1, c // 2 + 1)][-P:], jnp.int32)
        got = flash_cache_attention(
            q, k_c, v_c, slots, starts, lens, window=w,
            interpret=INTERPRET, **kern,
        )

        def dense_chunk(window):
            return cache_chunk_attention(
                q, k_d, v_d, slots, starts, lens, window=window,
                kernel=False, **dense,
            )

        want = dense_chunk(w)
        if w:
            unwindowed = dense_chunk(0)
        valid = np.ones(got.shape[:2], bool)
    else:
        s_q, s_kv = (g.attn_sq, g.attn_skv) if w else (g.attn_s, g.attn_s)
        b = 2
        kk, kv_ = jax.random.split(kc)
        q = queries((b, s_q, g.n_heads, g.hd))
        k = jax.random.normal(kk, (b, s_kv, g.n_kv, g.hd), bf)
        v = jax.random.normal(kv_, (b, s_kv, g.n_kv, g.hd), bf)
        lens_list = [s_kv, s_kv - s_q // 3]
        lens = jnp.array(lens_list, jnp.int32) if case.extra else None
        got = flash_attention(
            q, k, v, lens, causal=True, window=w, interpret=INTERPRET
        )
        want = attention(
            q, k, v, causal=True, window=w, lengths=lens, kernel=False
        )
        # Query rows at or past a row's length are padding: the kernel
        # emits 0 there, the dense path uniform-softmax junk, and
        # serving reads neither.
        pos = (s_kv - s_q) + np.arange(s_q)
        valid = pos[None, :] < np.asarray(
            lens_list if case.extra else [s_kv, s_kv]
        )[:, None]
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    assert np.isfinite(got).all(), f"{case}: non-finite kernel output"
    tol = serving_tolerance(case, g)
    np.testing.assert_allclose(
        got[valid], want[valid], atol=tol, rtol=tol, err_msg=str(case)
    )
    if unwindowed is not None:
        assert not np.allclose(
            np.asarray(unwindowed.astype(jnp.float32)), want, atol=1e-3
        ), f"{case}: the window does not bind"
    return float(np.abs(got - want)[valid].max())


@pytest.mark.parametrize("case", serving_kernel_cases(), ids=str)
def test_serving_kernel_matrix(case):
    run_serving_kernel_case(case, TINY)
