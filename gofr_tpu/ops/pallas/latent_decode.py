"""Latent flash-decode kernel (pallas TPU): the absorbed decode attention of
a latent cache, read from the stacked plane where it lies.

A latent cache holds ONE row a token a layer, ``[latent | rotary key]``,
allocated 640 lanes wide for 576 values (``ops/kv_cache.LatentKVCache``).
Every query head attends the same row, and a head's value is the row's
first ``rank`` lanes: multi-query attention with one kv head whose value is
a prefix of its key. So each ``[block, width]`` tile of a slot's rows is
brought into VMEM once and serves both products of every head: the scores
``[heads, width] x [width, block]`` and the weighted sum
``[heads, block] x [block, rank]``. Only the online softmax's running
maximum, running sum and ``[heads, rank]`` accumulator are kept (VMEM
scratch); no score tensor reaches HBM.

The kernel takes the STACKED ``[entries, slots, 1, max_len, width]`` plane
and the entry it attends as a prefetched scalar (as ``flash_cache_attention``
does): the index maps pick the entry's blocks where they lie, so the decode
step's layer scan closes over the plane and no entry is copied out for a
custom call that cannot take a slice. Each slot's length is prefetched too:
the grid is ``(slots, position blocks)`` and a block past a slot's length is
clamped in the index map to the slot's last visible block (the pipeline
elides a DMA of the block it already holds) and skips its body; a slot of
length 0 names, at every step, the block the grid step before it holds (the
last block of the nearest live slot before it), so a step reads each live
slot's own context and nothing else. The current token's row is merged at
the slot's last step, as ``flash_decode`` merges its ``k_new``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.pallas.flash_decode import NEG_INF


def _kernel(len_ref, src_ref, layer_ref, q_ref, kv_ref, new_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, block, rank):
    """Grid: (slots, position blocks); blocks innermost, state in scratch.
    ``src_ref`` and ``layer_ref`` act through the index maps only."""
    del src_ref, layer_ref
    ib = pl.program_id(0)
    ik = pl.program_id(1)
    length = len_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    col0 = ik * block

    @pl.when(col0 < length)
    def _body():
        q = q_ref[0]  # [heads, width]
        kv = kv_ref[...]  # [block, width]: keys, and values in the first rank
        heads = q.shape[0]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [heads, block]
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (heads, block), 1)
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]  # [heads, 128] (value replicated over lanes)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [heads, rank]
        acc_ref[:] = acc_ref[:] * corr[:, :1] + pv

    @pl.when(ik == jnp.maximum(0, (length - 1) // block))  # its last block
    def _finish():
        # Merge the current token's row (always valid) into the running
        # softmax, then normalise; with an empty prefix (m = NEG_INF, l = 0)
        # this is the new row alone. float32 throughout: the row's ref is
        # float32 (Mosaic rejects mixed-dtype broadcasts in this tail).
        q = q_ref[0].astype(jnp.float32)
        new = new_ref[0][0:1, :]  # [1, width], row 0 of the 8-replica
        s_n = jax.lax.dot_general(
            q, new, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [heads, 1]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s_n)
        corr = jnp.exp(m_prev - m_new)
        e_n = jnp.exp(s_n - m_new)
        l = l_ref[:, :1] * corr + e_n
        acc = acc_ref[:] * corr + e_n * new[:, :rank]
        o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("rank", "scale", "block", "interpret")
)
def latent_flash_decode(
    q: jnp.ndarray,
    plane: jnp.ndarray,
    lengths: jnp.ndarray,
    row_new: jnp.ndarray,
    *,
    layer: jnp.ndarray,
    rank: int,
    scale: float,
    block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Same contract as ``ops.attention.latent_decode_attention``:

    q: [b, heads, width], the absorbed queries zero-padded to the plane's
    width; plane: the stacked ``[entries, b, 1, max_len, width]`` cache
    (zeros beyond each row's content) with ``layer`` (a traced int32) the
    entry attended; lengths: [b] cached positions, the current token not
    among them (0: the slot attends its new row alone); row_new: [b, width],
    the current token's row, zero-padded likewise; block: positions a grid
    step reads, a divisor of ``max_len``. Returns [b, heads, rank]: per head
    the weighted sum of latents."""
    b, heads, width = q.shape
    max_len = plane.shape[-2]
    if max_len % block:
        raise ValueError(f"block {block} does not divide max_len {max_len}")

    def last_blk(n):
        """The block that holds a slot's last cached position (0: none)."""
        return jnp.maximum(0, (n - 1) // block)

    def row_idx(ib, ik, lens, src, layer):
        return (ib, 0, 0)

    def kv_idx(ib, ik, lens, src, layer):
        # src[ib]: ib itself if it is live, else the nearest live slot
        # before it, whose last block the grid step before holds already.
        at = src[ib]
        blk = jnp.where(
            lens[ib] > 0, jnp.minimum(ik, last_blk(lens[ib])),
            last_blk(lens[at]),
        )
        return (layer[0], at, 0, blk, 0)

    src = jax.lax.cummax(jnp.where(lengths > 0, jnp.arange(b), 0))

    # [b, width] -> sublane-replicated [b, 8, width] float32, so that the
    # row's block tiles VMEM (a [1, width] block cannot).
    new = jnp.broadcast_to(
        row_new.astype(jnp.float32)[:, None, :], (b, 8, width)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, max_len // block),
        in_specs=[
            pl.BlockSpec((1, heads, width), row_idx),
            # The entry's, the slot's and the head's axes are squeezed: the
            # body sees one [block, width] tile.
            pl.BlockSpec((None, None, None, block, width), kv_idx),
            pl.BlockSpec((1, 8, width), row_idx),
        ],
        out_specs=pl.BlockSpec((1, heads, rank), row_idx),
        scratch_shapes=[
            pltpu.VMEM((heads, rank), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), q.dtype),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32), src.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32), q, plane, new,
    )
