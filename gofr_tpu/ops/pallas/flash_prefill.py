"""Flash chunk-prefill kernel (pallas TPU): a chunk of c query tokens per
row attends to its slot's KV-cache prefix IN PLACE.

This is the kernel behind chunked prefill (VERDICT r1 weak #9: a long
prompt's prefill must not stall every active decode stream): the engine
splits prompts into fixed-size chunks and interleaves one chunk step
between decode windows. The chunk length is static and arbitrary prompt
lengths are handled by the loop count, not the program; the row count is
one of the engine's two rungs (1 and ``prefill_batch``), so this kernel
is compiled once a rung, before the engine serves.

Contract (heads-major cache, ``ops/kv_cache.py``): the chunk's K/V must
already be written into the cache at positions ``starts[p] ..
starts[p]+lens[p]-1`` before the call. Queries are grouped kv-head-major
and token-major within the group: row ``r`` of the ``[c*rep, hd]`` q block
is token ``r // rep``, query-head ``(r % rep)`` of that kv head — so one
MXU matmul per (row-batch × kv block) serves all rep query heads of a kv
head, and the causal mask is computable from the row index alone.

Per-row scalars (slots, starts, lens) ride in SMEM via scalar prefetch;
kv blocks beyond ``starts[p]+lens[p]`` are skipped (clamped index maps →
the pipeline elides the DMA), so cost scales with the true context, not
``max_len``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _clamp_blk(ik, ctx_len, block_k, start=None, window=0):
    """kv block index clamped to the row's VISIBLE range. Windowed: the
    loosest lower bound over the chunk is the FIRST token's (global pos
    ``start``), so blocks wholly below ``start - window + 1`` re-fetch a
    visible block (DMA elided); exact per-token masking happens in the
    body."""
    hi = jnp.maximum(0, (ctx_len - 1) // block_k)
    if window:
        lo = jnp.maximum(0, start - window + 1) // block_k
        return jnp.clip(ik, jnp.minimum(lo, hi), hi)
    return jnp.minimum(ik, hi)


def _kernel(*refs, scale, rep, block_k, quant, paged, window):
    """Grid: (P, n_kv, kv_blocks); kv innermost (scratch carries state).

    quant (static): int8 cache mode — k/v scale refs follow v_ref
    ([8, block_k] sublane-replicated); see ``flash_decode._kernel``.
    paged (static): a 4th prefetched scalar (the block table) follows
    lens; it acts only through the index_maps — the body is unchanged.
    """
    refs = list(refs)
    slot_ref, start_ref, len_ref = refs[:3]
    refs = refs[3:]
    if paged:
        refs.pop(0)  # block table: consumed by the index_maps only
    q_ref, k_ref, v_ref = refs[:3]
    rest = refs[3:]
    if quant:
        k_s_ref, v_s_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    ip = pl.program_id(0)
    ik = pl.program_id(2)
    n_k = pl.num_programs(2)

    start = start_ref[ip]
    clen = len_ref[ip]
    ctx_len = start + clen  # keys visible to the chunk's LAST token

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    last_vis = jnp.clip((ctx_len - 1) // block_k, 0, n_k - 1)
    visible = ik <= last_vis
    if window:
        # Loosest chunk-wide lower bound (first token's window edge);
        # per-token exactness is in the mask below.
        lo_pos = jnp.maximum(0, start - window + 1)
        visible &= ik * block_k + block_k > lo_pos

    @pl.when(visible)
    def _body():
        q = q_ref[0, 0]  # [c*rep, hd]
        k = k_ref[0, 0]  # [block_k, hd]
        v = v_ref[0, 0]
        rows = q.shape[0]
        if quant:
            k = k.astype(q.dtype)
            v = v.astype(jnp.bfloat16)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [c*rep, block_k]
        if quant:
            s = s * k_s_ref[0, 0][0:1, :]

        row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
        t = row // rep  # chunk-token index of each q row
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1
        )
        # Causal vs the GLOBAL position start+t; rows past the row's own
        # chunk length are padding queries (fully masked → guarded 0 out).
        mask = jnp.logical_and(cols <= start + t, t < clen)
        if window:
            # Sliding window: keys must sit in (q_pos - window, q_pos].
            mask = jnp.logical_and(mask, cols > start + t - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        if quant:
            p = p * v_s_ref[0, 0][0:1, :]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * corr[:, :1] + pv

    @pl.when(ik == last_vis)
    def _finish():
        l = l_ref[:, :1]
        out = jnp.where(l > 0.0, acc_ref[:] / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "window", "interpret")
)
def flash_cache_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    slots: jnp.ndarray,
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    block_table: jnp.ndarray | None = None,
    scale: float | None = None,
    block_k: int = 256,
    window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunk attention against the slot cache.

    window (static): sliding-window attention — each query attends only
    keys in ``(start+t - window, start+t]``; 0 = full. Masked in-kernel;
    blocks wholly below the chunk's loosest window edge skip their body
    and their DMA.

    q: [P, c, n_heads, hd] — chunk queries (RoPE'd at positions
    starts[p]+t); k_cache, v_cache: [S, n_kv, max_len, hd] with the chunk's
    K/V already written; slots/starts/lens: [P] int32; k_scale/v_scale:
    int8-cache scales [S, n_kv, 8, max_len]. Rows with ``t >= lens[p]``
    return 0. block_table ([S, max_blocks] int32, paged mode): the caches
    are then a POOL [n_blocks, n_kv, block, hd] (scales
    [n_blocks, n_kv, 8, block]); logical kv block ``ik`` of row ``p``
    resolves to pool block ``block_table[slots[p], ik]`` inside the
    BlockSpec index_maps — no per-chunk gather of the whole view.
    Returns [P, c, n_heads, hd].
    """
    P, c, n_heads, hd = q.shape
    paged = block_table is not None
    n_kv = k_cache.shape[1]
    rep = n_heads // n_kv
    quant = k_scale is not None
    if scale is None:
        scale = hd**-0.5
    if paged:
        block_k = k_cache.shape[2]  # pool block size
        n_grid_blocks = block_table.shape[1]
    else:
        max_len = k_cache.shape[2]
        block_k = min(block_k, max_len)
        if max_len % block_k:
            # Persistent cache can't be padded per call; shrink to a
            # divisor.
            block_k = next(
                b for b in (128, 64, 32, 16, 8, 1) if max_len % b == 0
            )
        n_grid_blocks = max_len // block_k

    # [P, c, KV, rep, hd] → [P, KV, c*rep, hd], row = t*rep + head.
    qg = q.reshape(P, c, n_kv, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        P, n_kv, c * rep, hd
    )

    if paged:
        def kv_idx(ip, ig, ik, slots, starts, lens, bt, bk=block_k):
            blk = _clamp_blk(
                ik, starts[ip] + lens[ip], bk, starts[ip], window
            )
            return (bt[slots[ip], blk], ig, 0, 0)

        # Paged scale planes index exactly like K/V (pool block, head).
        scale_idx = kv_idx

        def row_idx(ip, ig, ik, slots, starts, lens, bt):
            return (ip, ig, 0, 0)
    else:
        def kv_idx(ip, ig, ik, slots, starts, lens, bk=block_k):
            blk = _clamp_blk(
                ik, starts[ip] + lens[ip], bk, starts[ip], window
            )
            return (slots[ip], ig, blk, 0)

        def scale_idx(ip, ig, ik, slots, starts, lens, bk=block_k):
            blk = _clamp_blk(
                ik, starts[ip] + lens[ip], bk, starts[ip], window
            )
            return (slots[ip], ig, 0, blk)

        def row_idx(ip, ig, ik, slots, starts, lens):
            return (ip, ig, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, c * rep, hd), row_idx),
        pl.BlockSpec((1, 1, block_k, hd), kv_idx),
        pl.BlockSpec((1, 1, block_k, hd), kv_idx),
    ]
    inputs = [
        slots.astype(jnp.int32), starts.astype(jnp.int32),
        lens.astype(jnp.int32),
    ]
    if paged:
        inputs.append(block_table.astype(jnp.int32))
    inputs += [qg, k_cache, v_cache]
    if quant:
        scale_spec = pl.BlockSpec((1, 1, 8, block_k), scale_idx)
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if paged else 3,
        grid=(P, n_kv, n_grid_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, c * rep, hd), row_idx),
        scratch_shapes=[
            pltpu.VMEM((c * rep, hd), jnp.float32),
            pltpu.VMEM((c * rep, 128), jnp.float32),
            pltpu.VMEM((c * rep, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, rep=rep, block_k=block_k, quant=quant,
            paged=paged, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, n_kv, c * rep, hd), q.dtype),
        interpret=interpret,
    )(*inputs)
    # [P, KV, c*rep, hd] → [P, c, H, hd]
    return out.reshape(P, n_kv, c, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        P, c, n_heads, hd
    )
