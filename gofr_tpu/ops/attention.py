"""Attention ops: prefill (causal, full-sequence) and decode (one query token
against a KV cache slice).

Dense baseline implementations in pure jnp — static shapes, f32 softmax
accumulation, GQA via head-group broadcasting — with layouts chosen so the
pallas flash kernels (``gofr_tpu/ops/pallas/``) are drop-in replacements on
TPU. The dispatch helpers pick the kernel path when available.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

# GOFR_TPU_FLASH: "1" force kernels (interpret-mode off-TPU), "0" force
# dense, unset/"auto" → kernels on TPU backends only.
_FLASH_ENV = os.environ.get("GOFR_TPU_FLASH", "auto")
# GOFR_TPU_FLASH_DECODE: overrides GOFR_TPU_FLASH for DECODE attention
# only. The decode kernel launches grid (slots × kv_heads × kv_blocks)
# tiny programs per layer (length-skipping, O(true context) HBM reads);
# the dense path is two fused XLA ops reading one rung of every slot,
# the one that holds the longest live slot (``decode_read_rungs``).
# Which wins is a measured trade (per-program overhead vs reading every
# slot to the longest one's rung) — this knob lets the bench A/B it on
# hardware.
_FLASH_DECODE_ENV = os.environ.get("GOFR_TPU_FLASH_DECODE", "")
if _FLASH_DECODE_ENV not in ("", "0", "1"):
    raise ValueError(
        'GOFR_TPU_FLASH_DECODE must be "1", "0", or unset, got '
        f"{_FLASH_DECODE_ENV!r}"
    )
# GOFR_TPU_DECODE_BLOCK_K: kv block size for the decode kernel (default
# 256); bigger blocks → fewer grid programs, less length-skip precision.
try:
    _DECODE_BLOCK_K = int(os.environ.get("GOFR_TPU_DECODE_BLOCK_K", "256"))
    if _DECODE_BLOCK_K <= 0:
        raise ValueError
except ValueError:
    raise ValueError(
        "GOFR_TPU_DECODE_BLOCK_K must be a positive integer, got "
        f"{os.environ.get('GOFR_TPU_DECODE_BLOCK_K')!r}"
    ) from None


def _flash_enabled() -> bool:
    if _FLASH_ENV == "1":
        return True
    if _FLASH_ENV == "0":
        return False
    return jax.default_backend() == "tpu"


def _flash_decode_enabled() -> bool:
    if _FLASH_DECODE_ENV == "1":
        return True
    if _FLASH_DECODE_ENV == "0":
        return False
    return _flash_enabled()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode, for every kernel call in this module: never
    on a TPU backend, always off it. Off-TPU the kernel path is only
    taken when forced (``GOFR_TPU_FLASH=1`` / ``GOFR_TPU_FLASH_DECODE=1``,
    or a test passing ``kernel=True``), so serving on a TPU cannot reach
    the interpreter."""
    return jax.default_backend() != "tpu"


def _effective_window(window: int, positions: int, block_table) -> int:
    """0 when the sliding window cannot bind within the cache capacity.

    ``positions`` is the cache's position axis: contiguous caches are
    [b, KV, max_len, hd] (capacity = max_len); a paged pool is [n_blocks,
    KV, block, hd] where that axis is the BLOCK — capacity is the table's
    row length × block.
    """
    if not window:
        return 0
    if block_table is not None:
        positions *= block_table.shape[1]
    return 0 if window >= positions else window


def decode_read_rungs(max_len: int) -> tuple[int, ...]:
    """The prefixes of a ``max_len`` cache the dense decode path may read:
    its quarters, each rounded up to a multiple of 128 (the lane tile) and
    capped at ``max_len``. 2,048 gives 512 / 1,024 / 1,536 / 2,048, 384
    gives 128 / 256 / 384; a cache of 128 positions or fewer has the one
    rung, today's whole read."""
    return tuple(sorted({
        min(max_len, -(-(max_len * q) // (4 * 128)) * 128)
        for q in (1, 2, 3, 4)
    }))


def decode_read_index(rungs: tuple[int, ...], longest):
    """Index of the smallest rung that holds ``longest`` cached positions
    (a length equal to a rung fits it). ``longest`` is a Python int on the
    host or a traced scalar on the device: the same rule in both places."""
    return sum(longest > r for r in rungs[:-1])


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[b, s, kv_heads, hd] → [b, s, kv_heads*n_rep, hd] (GQA broadcast)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    mask: jnp.ndarray | None = None,
    lengths: jnp.ndarray | None = None,
    scale: float | None = None,
    kernel: bool | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Full-sequence attention (prefill / encoder).

    window: sliding-window attention (Mistral) — each query attends only
    the last ``window`` keys (positions in (q_pos-window, q_pos]); 0 =
    full. Honored on both paths (the kernel masks in-kernel and skips
    kv blocks wholly below the window).
    q: [b, s_q, n_heads, hd]; k, v: [b, s_kv, n_kv_heads, hd].
    mask: optional [b, s_q, s_kv] additive-validity bool mask (True = attend).
    lengths: optional [b] valid key-prefix lengths (right-padded batches) —
    unlike ``mask`` this KEEPS the flash-kernel path (the kernel masks and
    skips kv blocks per row in-kernel; serving prefill uses this).
    kernel: None → auto (pallas flash kernel on TPU when no custom mask);
    the kernel path is differentiable (backward recomputes densely).
    """
    if mask is not None and lengths is not None:
        raise ValueError("pass either mask or lengths, not both")
    if window and window >= k.shape[1]:
        window = 0  # cannot bind: plain causal
    if window and not causal:
        raise ValueError("window requires causal attention")
    if kernel is None:
        kernel = _flash_enabled() and mask is None
    if kernel and mask is None:
        if lengths is not None:
            # Serving prefill (no grad) — call the kernel directly.
            from gofr_tpu.ops.pallas import flash_attention

            return flash_attention(
                q, k, v, lengths, causal=causal, scale=scale,
                window=window, interpret=_interpret(),
            )
        return _flash_attention_ad(q, k, v, causal, scale, window)
    b, s_q, n_heads, hd = q.shape
    s_kv, n_kv = k.shape[1], k.shape[2]
    n_rep = n_heads // n_kv
    if scale is None:
        scale = hd**-0.5
    if lengths is not None:
        mask = jnp.broadcast_to(
            (jnp.arange(s_kv)[None, :] < lengths[:, None])[:, None, :],
            (b, s_q, s_kv),
        )

    # Grouped-head formulation: no materialized KV repeat (HBM-friendly) and
    # the kv-head axis keeps one consistent tp sharding end to end.
    qg = q.reshape(b, s_q, n_kv, n_rep, hd)
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32
    ) * scale  # [b, kv, rep, s_q, s_kv]

    if causal:
        # Offset so the last query attends to all keys (s_kv >= s_q case).
        q_pos = jnp.arange(s_q)[:, None] + (s_kv - s_q)
        causal_mask = jnp.arange(s_kv)[None, :] <= q_pos
        if window:
            causal_mask &= jnp.arange(s_kv)[None, :] > q_pos - window
        scores = jnp.where(causal_mask[None, None, None], scores, NEG_INF)
    elif window:
        raise ValueError("window requires causal attention")
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return out.reshape(b, s_q, n_heads, -1)  # v's width, where it is not q's


def _decode_takes_kernel(
    kernel: bool | None, max_len: int, paged: bool, window: int
) -> bool:
    """Whether decode attention runs the Pallas kernel; ``window`` is the
    effective (binding) one."""
    if kernel is not None:
        return kernel
    kernel = _flash_decode_enabled()
    if (
        kernel
        and _FLASH_DECODE_ENV == ""
        and _FLASH_ENV in ("", "auto")
        and not paged
        and not window
    ):
        # A contiguous cache of at most 2,048 positions takes the dense
        # path, which reads only the rung that holds the longest live
        # slot (``decode_read_rungs``). Measured on the v5e (PERF.md §6
        # PR 31, mistral-7b int8, 14 slots of 2,048, ~3 live): a layer's
        # attention costs 159 us dense over the whole cache, 81 us at
        # the 1,024 rung, and 344 us through the kernel, which skips by
        # each slot's own length but launches slots x kv_heads x
        # kv_blocks small programs (187 us) after the layer's K and V
        # planes were copied out of the stacked cache for it (2 x 79 us);
        # a decode window 125.0 / 105.6 / 174 ms, the median pace 18.3 /
        # 15.5 / 26.0 ms a token. The paged pool always takes the kernel
        # (its dense fallback must materialize a gather first), and so
        # does a binding window (the kernel reads only the window's
        # blocks).
        kernel = max_len > 2048
    return kernel


# Positions a grid step of ``latent_flash_decode`` reads of a slot. Measured
# on the v5e (PERF.md section 6), a layer of the latent share, 32
# slots of 8,192 positions: blocks of 256 / 512 / 1,024 read every slot at
# 8,092 positions in 1.041 / 0.745 / 0.597 ms and the ``longdoc`` cell's 7
# live slots in 0.272 / 0.185 / 0.152 ms (the dense read at its rung 2.52).
LATENT_DECODE_BLOCK = 1024


def latent_decode_block(max_len: int, *, latent: bool, dense: bool) -> int:
    """The block of positions in which a decode step's attention reads each
    slot through ``latent_flash_decode``, or 0 where the dense bounded path
    (``latent_decode_attention``'s rungs) runs. The kernel runs for a latent
    cache (``latent``; a hybrid cache's sparse layers keep their own dense
    read and gather), on a TPU backend, unless the caller asks for the dense
    form (``dense``: under a mesh, which XLA partitions and a Pallas kernel
    does not). No switch reaches it. The block is ``LATENT_DECODE_BLOCK``, or
    the largest of 512, 256 and 128 that divides ``max_len`` (the plane is
    the cache, so it cannot be padded), or the whole ``max_len``.
    The host's counter and the device's program ask this same function."""
    if not latent or dense or not _on_tpu():
        return 0
    return next(
        (b for b in (LATENT_DECODE_BLOCK, 512, 256, 128)
         if b <= max_len and max_len % b == 0),
        max_len,
    )


def decode_read_plan(
    max_len: int, *, paged: bool = False, window: int = 0,
    kernel: bool | None = None, latent: bool = False, hybrid: bool = False,
) -> tuple[int, ...]:
    """The prefixes ``decode_attention``'s ``read`` selects among for such
    a cache: ``decode_read_rungs`` where the dense path over a contiguous
    cache runs, the one whole read where the kernel or a paged pool does
    (the host's counter and the device's program ask the same function).
    A latent cache is read by ``latent_flash_decode`` where
    ``latent_decode_block`` says so (``kernel`` False: the dense form is
    asked for), each slot to its own length: the one whole read here; else
    on the dense bounded path at every ``max_len``. A hybrid cache's sparse
    layers are always read on the dense bounded path for the slots under
    their dense length."""
    if latent and latent_decode_block(
        max_len, latent=True, dense=kernel is False,
    ):
        return (max_len,)
    if latent or hybrid:
        return decode_read_rungs(max_len)
    window = _effective_window(window, max_len, None)
    if paged or _decode_takes_kernel(kernel, max_len, paged, window):
        return (max_len,)
    return decode_read_rungs(max_len)


def _entry_prefix(plane: jnp.ndarray, layer, n: int, axis: int):
    """The first ``n`` positions along ``axis`` of one cache entry:
    ``plane`` itself, or entry ``layer`` of a stacked ``[entries, ...]``
    plane, taken with one dynamic_slice so that it fuses into the op
    that reads it and the entry is never copied out whole."""
    if layer is None:
        return jax.lax.slice_in_dim(plane, 0, n, axis=axis)
    sizes = list(plane.shape)
    sizes[0], sizes[axis + 1] = 1, n
    starts = [layer] + [0] * (plane.ndim - 1)
    return jax.lax.dynamic_slice(plane, starts, sizes)[0]


def _dense_decode(
    qg, k_cache, v_cache, lengths, k_new, v_new, k_scale, v_scale,
    scale: float, window: int,
):
    """Dense decode attention over the positions handed in — the whole
    cache or a prefix of it that holds every kept slot's ``lengths``.
    qg: [b, kv, rep, hd]; caches [b, kv, n, hd]; scales [b, kv, 1, n]
    (int8) or None. Returns [b, kv, rep, hd]."""
    n = k_cache.shape[2]
    quant = k_scale is not None
    if quant:  # int8 cache: dequant via score/prob scaling, not the cache
        k_cache = k_cache.astype(qg.dtype)
        v_cache = v_cache.astype(qg.dtype)
    scores = jnp.einsum(
        "bgrd,bgkd->bgrk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale  # [b, kv, rep, n]
    if quant:
        scores = scores * k_scale

    valid = jnp.arange(n)[None, :] < lengths[:, None]  # [b, n]
    if window:
        # Query position: ``lengths`` (split path — the new token) or
        # ``lengths-1`` (already-written convention). Keys must sit in
        # (q_pos - window, q_pos].
        q_pos = lengths if k_new is not None else lengths - 1
        valid &= jnp.arange(n)[None, :] > (q_pos - window)[:, None]
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)

    if k_new is None:
        probs = jax.nn.softmax(scores, axis=-1)
        if quant:
            probs = probs * v_scale
        return jnp.einsum("bgrk,bgkd->bgrd", probs.astype(qg.dtype), v_cache)

    # Split path: merge the current token's (always-valid) score into the
    # cache-prefix softmax without writing it to the cache first.
    s_new = jnp.einsum(
        "bgrd,bgd->bgr", qg, k_new, preferred_element_type=jnp.float32
    ) * scale  # [b, kv, rep]
    m = jnp.maximum(jnp.max(scores, axis=-1), s_new)  # [b, kv, rep]
    e_c = jnp.exp(scores - m[..., None])  # [b, kv, rep, n]
    e_n = jnp.exp(s_new - m)  # [b, kv, rep]
    denom = jnp.sum(e_c, axis=-1) + e_n
    if quant:
        e_c = e_c * v_scale
    out = jnp.einsum("bgrk,bgkd->bgrd", e_c.astype(qg.dtype), v_cache)
    out = out + e_n[..., None].astype(qg.dtype) * v_new[:, :, None, :]
    return out / denom[..., None].astype(qg.dtype)


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_new: jnp.ndarray | None = None,
    v_new: jnp.ndarray | None = None,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    block_table: jnp.ndarray | None = None,
    scale: float | None = None,
    kernel: bool | None = None,
    window: int = 0,
    layer: jnp.ndarray | None = None,
    read: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Single-token decode attention against per-slot caches.

    window: sliding-window (Mistral) — the query attends only the last
    ``window`` positions including itself; 0 = full. Both paths honor
    it (the kernel masks in-kernel and skips out-of-window blocks).
    q: [b, n_heads, hd] (one query per sequence);
    k_cache, v_cache: [b, n_kv_heads, max_len, hd] (heads-major — the
    TPU-native cache layout, see ``ops/kv_cache.py``);
    lengths: [b] valid prefix length per slot. Two calling conventions:

    * ``k_new is None`` — the new token's K/V is already written in the
      cache at position lengths-1 (lengths INCLUDES it);
    * ``k_new``/``v_new`` given (``[b, n_kv, hd]``, same dtype as q) —
      the current token's K/V is attended SPLIT from the cache (online-
      softmax merge) and ``lengths`` counts only the cache prefix. This
      is the serving decode path: keeping the cache read-only inside the
      per-layer scan lets one scatter commit every layer's token per
      step, instead of the full cache round-tripping through scan ys
      (measured 11 ms/step of pure copy traffic on llama-1b at 32
      slots — scripts/tpu_probe.py).

    k_scale/v_scale: int8-cache mode — per-position absmax scales
    ``[b, n_kv, 8, max_len]`` (sublane-replicated, ``ops/kv_cache.py``);
    ``k_new``/``v_new`` stay bf16 (quantization happens at commit).
    kernel: None → auto (pallas flash-decode kernel on TPU; override with
    GOFR_TPU_FLASH_DECODE / GOFR_TPU_DECODE_BLOCK_K).
    layer: the caches and scales are the STACKED ``[entries, ...]`` planes
    and entry ``layer`` (a traced index) is the one attended: the decode
    step's layer scan closes over the planes and hands in its index, so
    the dense path slices the entry where it reads it.
    read: the dense path over a contiguous cache reads only the first
    ``decode_read_rungs(max_len)[read]`` positions of every slot (a traced
    index, ``decode_read_index`` of the longest length whose output is
    kept). Positions at or beyond a slot's length weigh exp(-1e30 - m)
    = 0 in the whole read, so a slot that fits the rung gets the same
    output; a slot that does not must be one whose output the caller
    discards. None, the kernel and the paged pool read as before (the
    kernel skips by each slot's own length).
    """
    if (k_new is None) != (v_new is None):
        raise ValueError("pass k_new and v_new together")
    # [.., n_kv, max_len, hd] in every layout (a paged pool: the block).
    max_len = k_cache.shape[-2]
    # A window that cannot bind is dropped (capacity-aware: a paged
    # pool's shape[-2] is the BLOCK axis, not capacity). A BINDING window
    # keeps the kernel path — flash_decode masks it in-kernel and skips
    # whole blocks below the window (O(window) HBM reads, vs the dense
    # paged fallback's per-step full gather).
    window = _effective_window(window, max_len, block_table)
    paged = block_table is not None
    kernel = _decode_takes_kernel(kernel, max_len, paged, window)
    bounded = read is not None and not kernel and not paged
    rungs = decode_read_rungs(max_len) if bounded else (max_len,)
    if layer is not None and len(rungs) < 2:
        # One read of the whole entry: index it as a scan over the
        # planes would have.
        k_cache, v_cache, k_scale, v_scale = (
            None if p is None
            else jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
            for p in (k_cache, v_cache, k_scale, v_scale)
        )
        layer = None
    if kernel:
        from gofr_tpu.ops.pallas import flash_decode

        return flash_decode(
            q, k_cache, v_cache, lengths, k_new=k_new, v_new=v_new,
            k_scale=k_scale, v_scale=v_scale, block_table=block_table,
            scale=scale, block_k=_DECODE_BLOCK_K, window=window,
            interpret=_interpret(),
        )
    if paged:
        # Paged pool + dense fallback: gather each row's blocks into a
        # contiguous view, then fall through to the regular dense math
        # (the kernel path above indexes the pool in place instead).
        from gofr_tpu.ops.kv_cache import paged_view

        k_cache, v_cache, k_scale, v_scale = paged_view(
            block_table, k_cache, v_cache, jnp.arange(q.shape[0]),
            k_scale, v_scale,
        )
    b, n_heads = q.shape[0], q.shape[1]
    n_kv = k_cache.shape[-3]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # Group query heads by their KV head: [b, kv, rep, hd].
    qg = q.reshape(b, n_kv, n_heads // n_kv, -1)

    def over(n: int):
        """The dense mathematics over the first ``n`` positions, each
        plane sliced here: inside its rung's branch."""
        # The scale planes are sublane-replicated: row 0 is the scale.
        ks, vs = (
            None if p is None else _entry_prefix(p, layer, n, 3)[:, :, :1]
            for p in (k_scale, v_scale)
        )
        return _dense_decode(
            qg, _entry_prefix(k_cache, layer, n, 2),
            _entry_prefix(v_cache, layer, n, 2), lengths, k_new, v_new,
            ks, vs, scale, window,
        )

    if len(rungs) < 2:
        out = over(k_cache.shape[-2])
    else:
        out = jax.lax.switch(
            read, [functools.partial(over, n) for n in rungs]
        )
    return out.reshape(b, n_heads, -1)


def pad_last(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` with zeros appended along its last axis up to ``width``."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def latent_decode_attention(
    q: jnp.ndarray,
    plane: jnp.ndarray,
    lengths: jnp.ndarray,
    row_new: jnp.ndarray,
    *,
    rank: int,
    scale: float,
    layer: jnp.ndarray | None = None,
    read: jnp.ndarray | None = None,
    block: int = 0,
) -> jnp.ndarray:
    """Absorbed-form decode attention over a latent cache, read as it lies.

    Every head attends the SAME row of a token: ``[latent | rotary key]``,
    ``rank`` + rope values, with the values the first ``rank`` of it. That
    is multi-query attention with one kv head whose value is a prefix of
    its key. ``block`` > 0 (``latent_decode_block``): the Pallas kernel
    ``latent_flash_decode`` reads each slot to its own length in blocks of
    that many positions, from the stacked plane (``layer`` required).
    Otherwise this is the dense split-softmax of ``_dense_decode`` with one
    group of ``n_heads`` queries, bounded by the read's rungs: the CPU's path,
    and the kernel's reference.

    q: [b, n_heads, rank + rope], the queries projected into the latent
    space beside their rotary part; plane: the stacked
    ``[entries, b, 1, max_len, width]`` cache (width >= rank + rope, zeros
    beyond the content) with ``layer`` the entry attended, or one entry
    ``[b, 1, max_len, width]``; lengths: [b]
    cached positions; row_new: [b, rank + rope], the current token's row,
    attended split from the cache as in ``decode_attention``; read: index
    into ``decode_read_rungs(max_len)`` (None: the whole read). Returns
    [b, n_heads, rank]: per head the weighted sum of latents, for the
    caller's value up-projection.
    """
    max_len = plane.shape[-2]
    # The plane is allocated in whole lane tiles: zeros beyond a row's
    # content, so zeros on the query there leave every score as it was.
    if block:
        from gofr_tpu.ops.pallas.latent_decode import latent_flash_decode

        return latent_flash_decode(
            pad_last(q, plane.shape[-1]), plane, lengths,
            pad_last(row_new, plane.shape[-1]), layer=layer, rank=rank,
            scale=scale, block=block, interpret=_interpret(),
        )
    rungs = decode_read_rungs(max_len) if read is not None else (max_len,)
    qg = pad_last(q, plane.shape[-1])[:, None]  # [b, 1, heads, width]
    k_new = pad_last(row_new, plane.shape[-1])[:, None]  # [b, 1, width]

    def over(n: int):
        rows = _entry_prefix(plane, layer, n, 2)  # [b, 1, n, row]
        return _dense_decode(
            qg, rows, rows[..., :rank], lengths, k_new, k_new[..., :rank],
            None, None, scale, 0,
        )

    if len(rungs) < 2:
        out = over(max_len)
    else:
        out = jax.lax.switch(
            read, [functools.partial(over, n) for n in rungs]
        )
    return out[:, 0]


# ---------------------------------------------------------------------------
# the chunk step's attention over blocks of positions, a row at a time
# ---------------------------------------------------------------------------


def chunk_block_counts(starts, lens, block: int):
    """[P]: how many blocks of ``block`` positions each row of a chunk step
    attends, those up to the one that holds ITS OWN last position; none for
    a row that holds no token. numpy in, numpy out (the scheduler's
    histogram); jnp in, jnp out (the loops' trip counts)."""
    return (lens > 0) * (-(-(starts + lens) // block))


def chunk_visit_ratio(starts, lens, block: int) -> float:
    """The block-steps a chunk step's blocked attention runs (the sum of its
    rows' own) over rows x the longest row's: what running every row to the
    longest row's block would have cost is 1."""
    counts = chunk_block_counts(np.asarray(starts), np.asarray(lens), block)
    return float(counts.sum()) / max(int(counts.max()) * len(counts), 1)


def _slot_block(plane: jnp.ndarray, layer, slot, i, block: int) -> jnp.ndarray:
    """[heads, block, width]: block ``i`` of one slot of the attended entry,
    one ``dynamic_slice`` of the plane where it lies. plane: the stacked
    ``[entries, S, heads, max_len, width]`` with ``layer`` the entry, or one
    entry ``[S, heads, max_len, width]`` (``layer`` None)."""
    at = (slot, 0, i * block, 0)
    sizes = (1, plane.shape[-3], block, plane.shape[-1])
    if layer is None:
        return jax.lax.dynamic_slice(plane, at, sizes)[0]
    return jax.lax.dynamic_slice(plane, (layer, *at), (1, *sizes))[0, 0]


def _row_block_softmax(block_terms, xs, counts, lead: tuple, width: int):
    """The running softmax of a chunk step's attention over blocks of
    positions, the step's rows in turn (``lax.map``), each over its own
    ``counts[p]`` blocks: the block-steps a step runs are the sum of its
    rows' own, not rows x the longest row's. (A ``vmap`` of the loop would
    run every row to the longest count under a select.)

    block_terms(x, i) -> (s, valid, weigh): row ``x`` (a slice of ``xs``
    along its leading axis P) against its block ``i``: the scaled float32
    scores ``[*lead, block]``, the mask of those that count (broadcastable
    to them) and ``weigh(e) -> [*lead, width]`` float32, the block's values
    summed under the weights ``e``. counts None: one step a row, block 0
    (a block that is the whole slot). Scores, maximum, sum and weighted sum
    are float32. Returns [P, *lead, width] float32, zeros where nothing
    counted."""

    def step(x, i, carry):
        m, l, acc = carry
        s, valid, weigh = block_terms(x, i)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # A query with nothing valid yet keeps m at NEG_INF: exp(s - m)
        # would be 1 there, so the mask is applied to the weights too.
        e = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(e, axis=-1)
        return m_new, l, acc * alpha[..., None] + weigh(e)

    def row(args):
        x, n = args
        init = (
            jnp.full(lead, NEG_INF, jnp.float32),
            jnp.zeros(lead, jnp.float32),
            jnp.zeros((*lead, width), jnp.float32),
        )
        if n is None:
            _, l, acc = step(x, 0, init)
        else:
            _, l, acc = jax.lax.fori_loop(
                0, n, functools.partial(step, x), init
            )
        return acc / jnp.maximum(l, 1e-30)[..., None]

    return jax.lax.map(row, (xs, counts))


# Positions a step of ``latent_chunk_attention``'s loop scores at once. The
# float32 scores of a row's block are [heads, chunk, block]: 128 x 256 x 256
# x 4 B = 34 MB, where the whole 8,192 positions would be 1.07 GB a row. On
# the v5e (PERF.md section 6, PR 36) 256 beat 128, 512 and 1,024: a block
# each visited key is expanded in, the size of the serving chunk, so a
# row's own chunk is one block and nothing beyond the diagonal is scored.
LATENT_CHUNK_BLOCK = 256


def latent_chunk_attention(
    q: jnp.ndarray,
    plane: jnp.ndarray,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    w_uk: jnp.ndarray,
    w_uv: jnp.ndarray,
    *,
    scale: float,
    layer: jnp.ndarray | None = None,
    block: int = LATENT_CHUNK_BLOCK,
) -> jnp.ndarray:
    """Chunked-prefill attention over a latent cache, expanded form, in
    blocks of positions with a running softmax, so that no array of
    heads x chunk x max_len is ever alive; a row of the step visits the
    blocks up to the one that holds its own last position and no more
    (``_row_block_softmax``), whatever ``max_len`` and whatever the step's
    other rows. The chunk's rows must already be written into the cache.

    q: [P, c, n_heads, nope + rope], ``[q_nope | q_rope]``; plane: the
    stacked ``[entries, S, 1, max_len, width]`` cache (width >= rank + rope,
    zeros beyond the content) with ``layer`` the entry, or one entry;
    slots/starts/lens: [P] as in ``cache_chunk_attention``; w_uk
    [rank, heads, nope], w_uv [rank, heads, vd]: each block's latents are
    expanded to per-head keys and values before they are scored. Returns
    [P, c, n_heads, vd]. ``block`` >= ``max_len`` is the unblocked
    mathematics (one step a row). Rows with t >= lens[p] return 0.
    """
    P, c, n_heads, _ = q.shape
    max_len = plane.shape[-2]
    block = min(block, max_len)
    if max_len % block:
        raise ValueError(f"max_len {max_len} is not a multiple of {block}")
    t = jnp.arange(c)
    pos = starts[:, None] + t[None, :]  # [P, c] global query positions
    live = t[None, :] < lens[:, None]  # [P, c]
    rank, nope = w_uk.shape[0], w_uk.shape[-1]
    rope = q.shape[-1] - nope

    def block_terms(x, i):
        q_nope, q_rope, slot, pos, live = x  # one row: [c, H, .], [c]
        rows = _slot_block(plane, layer, slot, i, block)[0]  # [block, row]
        latent = rows[:, :rank]
        k_nope = jnp.einsum("kr,rhn->khn", latent, w_uk)
        s = jnp.einsum(
            "chn,khn->hck", q_nope, k_nope,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "chr,kr->hck", q_rope, rows[:, rank:rank + rope],
            preferred_element_type=jnp.float32,
        )
        values = jnp.einsum("kr,rhv->khv", latent, w_uv)
        k_pos = i * block + jnp.arange(block)
        valid = (k_pos[None, :] <= pos[:, None]) & live[:, None]  # [c, block]
        return s * scale, valid[None], lambda e: jnp.einsum(
            "hck,khv->hcv", e.astype(q.dtype), values,
            preferred_element_type=jnp.float32,
        )

    out = _row_block_softmax(
        block_terms, (q[..., :nope], q[..., nope:], slots, pos, live),
        None if block == max_len else chunk_block_counts(starts, lens, block),
        (n_heads, c), w_uv.shape[-1],
    )  # [P, H, c, vd]
    out = jnp.where(live[:, None, :, None], out, 0.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [P, c, H, vd]


def cache_chunk_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    *,
    layer: jnp.ndarray,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    block_table: jnp.ndarray | None = None,
    scale: float | None = None,
    kernel: bool | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Chunked-prefill attention: a [P, c] chunk of queries per row attends
    to its slot's cache prefix [0, starts[p]+t] (causal at global
    positions). The chunk's K/V must already be written into the cache.

    q: [P, c, n_heads, hd]; caches: the STACKED [E, S, n_kv, max_len, hd]
    (heads-major) with ``layer`` (a traced index) the entry attended: the
    prefill step's layer scan carries the planes and hands in its index;
    slots/starts/lens: [P] int32 (lens = valid tokens in this chunk);
    k_scale/v_scale: int8-cache scales [E, S, n_kv, 8, max_len].
    block_table ([S, max_blocks] int32, paged): the caches are a pool
    [E, n_blocks, n_kv, block, hd]; the kernel indexes it through the
    table in place, while the dense path gathers each row's contiguous
    view (the CPU/tests fallback). Rows with t >= lens[p] return 0.
    kernel: None → auto (pallas on TPU).
    """
    window = _effective_window(window, k_cache.shape[3], block_table)
    if kernel is None:
        kernel = _flash_enabled()
    if kernel:
        from gofr_tpu.ops.pallas import flash_cache_attention

        return flash_cache_attention(
            q, k_cache, v_cache, slots, starts, lens, layer=layer,
            k_scale=k_scale, v_scale=v_scale, block_table=block_table,
            scale=scale, window=window, interpret=_interpret(),
        )
    if block_table is not None:
        from gofr_tpu.ops.kv_cache import paged_view

        # Views already per row: [P, KV, max_len, hd].
        ck, cv, ksl, vsl = paged_view(
            block_table, k_cache, v_cache, slots, k_scale, v_scale,
            layer=layer,
        )
    else:
        ck, cv = k_cache[layer, slots], v_cache[layer, slots]
        ksl, vsl = (
            None if p is None else p[layer, slots] for p in (k_scale, v_scale)
        )
    P, c, n_heads, hd = q.shape
    n_kv, max_len = ck.shape[1], ck.shape[2]
    rep = n_heads // n_kv
    if scale is None:
        scale = hd**-0.5
    quant = ksl is not None
    if quant:  # int8 cache: dequant via score/prob scaling, not the cache
        ck = ck.astype(q.dtype)
        cv = cv.astype(q.dtype)
    qg = q.reshape(P, c, n_kv, rep, hd)
    scores = jnp.einsum(
        "pcgrd,pgkd->pgrck", qg, ck, preferred_element_type=jnp.float32
    ) * scale  # [P, KV, rep, c, max_len]
    if quant:
        scores = scores * ksl[:, :, 0, :][:, :, None, None, :]
    t = jnp.arange(c)
    pos = starts[:, None] + t[None, :]  # [P, c] global query positions
    valid = jnp.arange(max_len)[None, None, :] <= pos[:, :, None]
    if window:
        valid &= (
            jnp.arange(max_len)[None, None, :]
            > (pos - window)[:, :, None]
        )
    valid = jnp.logical_and(valid, (t[None, :] < lens[:, None])[:, :, None])
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if quant:
        probs = probs * vsl[:, :, 0, :][:, :, None, None, :]
    out = jnp.einsum("pgrck,pgkd->pcgrd", probs.astype(q.dtype), cv)
    out = jnp.where(
        (t[None, :] < lens[:, None])[:, :, None, None, None], out, 0.0
    )
    return out.reshape(P, c, n_heads, hd)


# ---------------------------------------------------------------------------
# block-sparse attention selected per query (MiniCPM-SALA's ``minicpm4`` layers)
# ---------------------------------------------------------------------------

# Positions a step of ``sparse_chunk_attention``'s loop scores at once: the
# float32 scores of a row's [heads, chunk, positions] are 32 x 256 x 512 x
# 4 B = 17 MB, where all 32,768 positions of a slot would be 1.07 GB a row.
SPARSE_CHUNK_BLOCK = 512


def sparse_block_scores(
    q: jnp.ndarray,
    ck: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    kernel: int,
    stride: int,
    block: int,
    init_blocks: int,
    window: int,
    scale: float,
    new: tuple | None = None,
) -> jnp.ndarray:
    """The score of every block of keys for every query, one set a kv head:
    what the choice of blocks is made from.

    q: [P, c, n_heads, hd]; ck: [P, n_kv, M, hd] the compressed keys of each
    row's slot (row m the mean of keys [m stride, m stride + kernel)); pos:
    [P, c] the queries' positions. A window counts for a query at position t
    once it is complete within the context: m stride + kernel <= t + 1. Per
    query head, a softmax over the windows that count of ``q . ck * scale``;
    summed over the kv head's query heads; a block's score is the largest
    of the windows that overlap it; the first ``init_blocks`` blocks and the
    ``window // block`` blocks that end with the query's own score +inf, a
    block that no window that counts overlaps -inf. ``new``: (row [P, n_kv,
    hd], m [P], has [P] bool) a compressed key that completes with this
    token and is not in ``ck`` yet (the decode step's, which commits after
    its layers). Returns [P, n_kv, c, M * stride // block] float32.
    """
    P, c, n_heads, hd = q.shape
    n_kv, M = ck.shape[1], ck.shape[2]
    per_block = block // stride
    n_blocks = M // per_block
    m = jnp.arange(M)
    counts = (m * stride + kernel)[None, None, :] <= (pos + 1)[:, :, None]
    qg = q.reshape(P, c, n_kv, n_heads // n_kv, hd)

    def shared_by(qg, ck, new_rows):
        """qg [P, c, G, rep, hd], ck [P, G, M, hd], new_rows [P, G, hd] ->
        [P, G, c, M]: per query head a softmax over the windows that count,
        summed over each kv head's query heads."""
        s = jnp.einsum(
            "pcgrd,pgmd->pgrcm", qg, ck, preferred_element_type=jnp.float32
        )
        if new is not None:
            s_new = jnp.einsum(
                "pcgrd,pgd->pgrc", qg, new_rows,
                preferred_element_type=jnp.float32,
            )
            is_new = (m[None, :] == new[1][:, None]) & new[2][:, None]  # [P, M]
            s = jnp.where(is_new[:, None, None, None, :], s_new[..., None], s)
        s = jnp.where(counts[:, None, None], s * scale, NEG_INF)
        p = jnp.where(counts[:, None, None], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.sum(p, axis=2)

    new_rows = jnp.zeros((P, n_kv, hd), q.dtype) if new is None else new[0]
    if c == 1:  # a decode step: every kv head at once, the planes as they lie
        shared = shared_by(qg, ck, new_rows)
    else:  # a kv head at a time: [P, rep, c, M] float32 alive, not all heads'
        shared = jax.lax.map(
            lambda g: shared_by(
                jax.lax.dynamic_slice_in_dim(qg, g, 1, 2),
                jax.lax.dynamic_slice_in_dim(ck, g, 1, 1),
                jax.lax.dynamic_slice_in_dim(new_rows, g, 1, 1),
            )[:, 0],
            jnp.arange(n_kv),
        ).transpose(1, 0, 2, 3)  # [P, n_kv, c, M]
    shared = jnp.where(counts[:, None], shared, -jnp.inf)
    # Block b is overlapped by windows per_block * b - reach .. per_block *
    # (b + 1) - 1: pad ``reach`` windows before the first, then one window
    # of the reduction a block.
    reach = -(-kernel // stride) - 1
    padded = jnp.pad(
        shared, ((0, 0), (0, 0), (0, 0), (reach, n_blocks * per_block - M)),
        constant_values=-jnp.inf,
    )
    scores = jax.lax.reduce_window(
        padded, -jnp.inf, jax.lax.max, (1, 1, 1, per_block + reach),
        (1, 1, 1, per_block), "VALID",
    )  # [P, n_kv, c, n_blocks]
    b = jnp.arange(n_blocks)
    own = (pos // block)[:, :, None]  # [P, c, 1]
    forced = (b[None, None, :] < init_blocks) | (
        (b[None, None, :] <= own) & (b[None, None, :] > own - window // block)
    )
    return jnp.where(forced[:, None], jnp.inf, scores)


def sparse_chunk_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    allowed: jnp.ndarray | None,
    *,
    sel_block: int,
    scale: float | None = None,
    layer: jnp.ndarray | None = None,
    block: int = SPARSE_CHUNK_BLOCK,
) -> jnp.ndarray:
    """Chunked-prefill grouped-query attention over K and V planes in blocks
    of positions with a running softmax, each query restricted to the blocks
    of keys it is allowed: causal at global positions, and ``allowed`` [P,
    n_kv, c, max_len // sel_block] bool (None: every block) says which blocks
    of ``sel_block`` keys a query of a kv head attends at all. No array of
    heads x chunk x max_len is ever alive, and a row of the step visits the
    blocks up to the one that holds its own last position and no more
    (``_row_block_softmax``). The chunk's keys and values must already be
    written into the cache.

    q: [P, c, n_heads, hd]; k_cache, v_cache: the stacked ``[entries, S,
    n_kv, max_len, hd]`` planes with ``layer`` the entry attended, or one
    entry; slots/starts/lens: [P] as in ``cache_chunk_attention``. ``block``
    >= ``max_len`` is the unblocked mathematics (one step a row). Rows with
    t >= lens[p] return 0. Returns [P, c, n_heads, hd].
    """
    P, c, n_heads, hd = q.shape
    n_kv, max_len = k_cache.shape[-3], k_cache.shape[-2]
    block = min(block, max_len)
    if max_len % block or block % sel_block:
        raise ValueError(
            f"max_len {max_len}, the loop's block {block} and the selection's "
            f"block {sel_block} must divide each other in turn"
        )
    if scale is None:
        scale = hd**-0.5
    rep = n_heads // n_kv
    t = jnp.arange(c)
    pos = starts[:, None] + t[None, :]  # [P, c] global query positions
    live = t[None, :] < lens[:, None]  # [P, c]
    per_step = block // sel_block

    def block_terms(x, i):
        qg, slot, pos, live, mine = x  # one row: [c, KV, rep, hd], [c]
        s = jnp.einsum(
            "cgrd,gkd->grck", qg, _slot_block(k_cache, layer, slot, i, block),
            preferred_element_type=jnp.float32,
        )
        k_pos = i * block + jnp.arange(block)
        valid = (k_pos[None, :] <= pos[:, None]) & live[:, None]  # [c, block]
        valid = valid[None]
        if mine is not None:  # [KV, c, max_len // sel_block]
            mine = jax.lax.dynamic_slice_in_dim(
                mine, i * per_step, per_step, 2
            )
            valid = valid & jnp.repeat(mine, sel_block, axis=2)
        return s * scale, valid[:, None], lambda e: jnp.einsum(
            "grck,gkd->grcd", e.astype(q.dtype),
            _slot_block(v_cache, layer, slot, i, block),
            preferred_element_type=jnp.float32,
        )

    out = _row_block_softmax(
        block_terms,
        (q.reshape(P, c, n_kv, rep, hd), slots, pos, live, allowed),
        None if block == max_len else chunk_block_counts(starts, lens, block),
        (n_kv, rep, c), hd,
    )  # [P, KV, rep, c, hd]
    out = out.transpose(0, 3, 1, 2, 4)  # [P, c, KV, rep, hd]
    out = jnp.where(live[:, :, None, None, None], out, 0.0)
    return out.reshape(P, c, n_heads, hd).astype(q.dtype)


def sparse_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    chosen: jnp.ndarray,
    lengths: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    *,
    sel_block: int,
    layer: jnp.ndarray,
    scale: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token decode attention over the chosen blocks of keys only: a
    gather of ``chosen`` blocks a slot a kv head out of the stacked planes,
    in place of a read of the slot's whole prefix.

    q: [S, n_heads, hd]; k_cache, v_cache: the stacked ``[entries, S, n_kv,
    max_len, hd]`` planes, ``layer`` the entry; chosen: [S, n_kv, n] int32
    block indices (a block is ``sel_block`` positions; a chosen block beyond
    the context holds nothing valid); lengths: [S] cached positions, the
    current token's key and value (k_new, v_new [S, n_kv, hd]) attended split
    from the cache as in ``decode_attention``. Returns ([S, n_heads, hd],
    [S] int32 the positions attended through the choice, the current one
    among them, for the first kv head).
    """
    S, n_heads, hd = q.shape
    n_kv, max_len = k_cache.shape[2], k_cache.shape[3]
    n = chosen.shape[-1]
    if scale is None:
        scale = hd**-0.5
    s_idx = jnp.arange(S)[:, None, None]
    g_idx = jnp.arange(n_kv)[None, :, None]

    def gathered(plane):
        blocks = plane.reshape(
            plane.shape[0], S, n_kv, max_len // sel_block, sel_block, hd
        )
        return blocks[layer, s_idx, g_idx, chosen].reshape(
            S, n_kv, n * sel_block, hd
        )

    k_pos = (
        chosen[..., None] * sel_block + jnp.arange(sel_block)
    ).reshape(S, n_kv, n * sel_block)
    valid = k_pos < lengths[:, None, None]  # [S, KV, n * sel_block]
    qg = q.reshape(S, n_kv, n_heads // n_kv, hd)
    scores = jnp.einsum(
        "bgrd,bgkd->bgrk", qg, gathered(k_cache),
        preferred_element_type=jnp.float32,
    ) * scale
    scores = jnp.where(valid[:, :, None], scores, NEG_INF)
    s_new = jnp.einsum(
        "bgrd,bgd->bgr", qg, k_new, preferred_element_type=jnp.float32
    ) * scale
    m = jnp.maximum(jnp.max(scores, axis=-1), s_new)
    e_c = jnp.where(valid[:, :, None], jnp.exp(scores - m[..., None]), 0.0)
    e_n = jnp.exp(s_new - m)
    denom = jnp.sum(e_c, axis=-1) + e_n
    out = jnp.einsum(
        "bgrk,bgkd->bgrd", e_c.astype(q.dtype), gathered(v_cache),
        preferred_element_type=jnp.float32,
    )
    out = out + e_n[..., None] * v_new[:, :, None, :].astype(jnp.float32)
    out = (out / denom[..., None]).astype(q.dtype)
    attended = jnp.sum(valid[:, 0], axis=-1).astype(jnp.int32) + 1
    return out.reshape(S, n_heads, hd), attended


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_ad(q, k, v, causal, scale, window=0):
    """Flash forward, dense-recompute backward.

    pallas_call has no reverse-mode rule, so the VJP re-derives gradients
    from the dense formulation — training memory matches the dense path
    while inference (no grad) gets the O(s) kernel. ``window`` threads
    through both directions (windowed-model training stays exact).
    """
    from gofr_tpu.ops.pallas import flash_attention

    return flash_attention(
        q, k, v, causal=causal, scale=scale, window=window,
        interpret=_interpret(),
    )


def _flash_ad_fwd(q, k, v, causal, scale, window=0):
    return _flash_attention_ad(q, k, v, causal, scale, window), (q, k, v)


def _flash_ad_bwd(causal, scale, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: attention(
            q, k, v, causal=causal, scale=scale, kernel=False,
            window=window,
        ),
        q, k, v,
    )
    return vjp(g)


_flash_attention_ad.defvjp(_flash_ad_fwd, _flash_ad_bwd)
