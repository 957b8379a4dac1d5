"""Slot-based KV cache for autoregressive decode (net-new; SURVEY §7 hard
part #3: persistent device state across requests).

Layout: ``[n_entries, n_slots, n_kv_heads, max_len, head_dim]`` — heads-major,
the TPU-native choice: the flash-decode kernel's per-head blocks
``[block_k, head_dim]`` tile directly onto the (8, 128) VMEM layout (a
heads-minor cache would need 1-sized blocks on the second-to-last dim,
which pallas cannot tile). ``n_entries`` is the model's
``n_cache_entries``: one entry a layer, or one a layer and pass for a looped
stack. The slot axis is the decode batch axis (decode
runs over ALL slots each step — static shapes, no gather/scatter), per-step
writes are position-local scatters, and the kv_heads axis shards over the
tensor-parallel mesh axis without resharding between prefill and decode.

**Int8 mode** (``quant="int8"``): K/V store as int8 with one f32 absmax
scale per (layer, slot, head, position) — decode streams the cache from
HBM at half the bytes and the cache footprint stops bounding slot count
at ``max_len × n_slots`` bf16 (VERDICT r2 next #9: an 8B model's bf16
cache is ~2 GB/slot at 8k context; int8 + scales is ~1.2 GB). Scale
layout is ``[n_entries, n_slots, n_kv_heads, 8, max_len]`` — the scale
vector a kernel needs per kv block is positions-along-lanes, and the
8-wide replicated sublane axis makes the block ``(8, block_k)``, an
exact f32 VMEM tile (a bare ``[block_k]`` vector block cannot tile).

The cache is a functional pytree; the model's prefill/decode steps return
updated buffers which XLA aliases in place when the jitted step donates them
(``gofr_tpu/serving/engine.py`` does).
"""

from __future__ import annotations

import json
import struct
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class KVCache(NamedTuple):
    k: jnp.ndarray  # [layers, slots, kv_heads, max_len, head_dim]
    v: jnp.ndarray
    lengths: jnp.ndarray  # [slots] int32 — tokens currently in each slot
    # int8 mode only: per-position absmax scales, sublane-replicated ×8
    # ([layers, slots, kv_heads, 8, max_len] f32); None in bf16 mode.
    k_s: Optional[jnp.ndarray] = None
    v_s: Optional[jnp.ndarray] = None

    @classmethod
    def create(
        cls,
        n_entries: int,
        n_slots: int,
        max_len: int,
        n_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        quant: str = "",
    ) -> "KVCache":
        shape = (n_entries, n_slots, n_kv_heads, max_len, head_dim)
        if (quant or "").lower() == "int8":
            sshape = (n_entries, n_slots, n_kv_heads, 8, max_len)
            return cls(
                k=jnp.zeros(shape, dtype=jnp.int8),
                v=jnp.zeros(shape, dtype=jnp.int8),
                lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
                k_s=jnp.ones(sshape, dtype=jnp.float32),
                v_s=jnp.ones(sshape, dtype=jnp.float32),
            )
        if quant:
            raise ValueError(f"unsupported KV quant mode {quant!r} (int8 only)")
        return cls(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
        )

    @property
    def quantized(self) -> bool:
        return self.k_s is not None

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    def hbm_bytes(self) -> int:
        total = self.k.size * self.k.dtype.itemsize * 2
        if self.k_s is not None:
            total += self.k_s.size * self.k_s.dtype.itemsize * 2
        return int(total)

    def bytes_per_token(self) -> int:
        return _bytes_per_position(self, self.hbm_bytes())

    state_bytes_per_slot = 0


def _bytes_per_position(cache: Any, token_bytes: int) -> int:
    """What one token position holds of ``token_bytes``, the bytes of a
    cache's planes that grow with tokens, from the arrays as allocated: over
    its slots (or pool blocks) x positions (or a block's). The one place
    every cache's ``bytes_per_token`` comes from, and through it health's
    ``kv_bytes_per_token`` and the gauge."""
    # k: [entries, slots | blocks, kv_heads | 1, max_len | block, width]
    return token_bytes // (cache.k.shape[1] * cache.k.shape[3])


class LatentKVCache(NamedTuple):
    """The contiguous cache of a latent-attention model: ONE row a token a
    layer, ``[n_entries, n_slots, 1, max_len, width]``. A row's content is
    ``cfg.cache_row`` values (the normed latent, then the rotary key
    values) and there is no V plane: the values are the first
    ``kv_lora_rank`` of the same row, read by the absorbed attention as
    they lie. ``width`` is the content rounded up to the 128-lane tile
    (576 -> 640, zeros beyond the content): the chip stores a minor
    dimension in whole tiles whatever its logical size, and with a
    576-wide plane XLA's layout assignment turned the whole cache round
    (positions minor, to save the padding) before every decode step's
    layer loop and back for the commit's scatter, two copies of 1.5 GB a
    step (compiled for the v5e, PERF.md section 6, PR 33); at a lane
    multiple it reads the plane where it lies and the commit is in place.
    The axes keep ``KVCache``'s places (a kv-head axis of 1), so the slot
    discipline, ``lengths`` and every reader of ``k.shape[1]`` /
    ``k.shape[3]`` hold; a class of its own because ``KVCache`` is K and V
    planes by construction (its ``hbm_bytes`` counts two, and every
    consumer that writes ``_replace(k=..., v=...)``, the prefix pool, the
    KV export payloads and int8 scales among them, must fail here and not
    quietly fill a plane that nothing reads: the engine refuses those at
    boot)."""

    k: jnp.ndarray  # [entries, slots, 1, max_len, width]
    lengths: jnp.ndarray  # [slots] int32

    @staticmethod
    def width_for(row: int) -> int:
        """The plane's last axis for ``row`` values of content."""
        return -(-row // 128) * 128

    @classmethod
    def create(
        cls, n_entries: int, n_slots: int, max_len: int, row: int,
        dtype: Any = jnp.bfloat16,
    ) -> "LatentKVCache":
        shape = (n_entries, n_slots, 1, max_len, cls.width_for(row))
        return cls(
            k=jnp.zeros(shape, dtype=dtype),
            lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
        )

    # What the engine asks of any contiguous cache.
    v = k_s = v_s = None
    quantized = False

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    def hbm_bytes(self) -> int:
        return int(self.k.size * self.k.dtype.itemsize)

    def bytes_per_token(self) -> int:
        return _bytes_per_position(self, self.hbm_bytes())

    state_bytes_per_slot = 0


class HybridCache(NamedTuple):
    """The contiguous cache of a stack of two kinds of mixer (sparse
    attention and lightning linear attention layers,
    ``models/transformer.py`` ``layer_kinds``): two kinds of per-slot state
    in one object that the engine sizes, commits and hands to both programs.

    * ``k``, ``v``: ``[sparse layers, slots, kv_heads, max_len, head_dim]``,
      the sparse layers' keys and values, ``KVCache``'s layout and slot
      discipline (made safe by the slot's length);
    * ``ck``: ``[sparse layers, slots, kv_heads, max_len // stride,
      head_dim]``, the compressed keys that the choice of blocks is made
      from: row m the mean of keys ``[m stride, m stride + kernel)``, written
      when that window fills (by the prefill step for the windows that end
      in its chunk, by the decode step for the one its token completes) and
      never recomputed from the K plane; a row is read only once its window
      lies within the slot's length, so a former occupant's rows are never;
    * ``state``: ``[lightning layers, slots, heads, head_dim, head_dim]``
      float32, fixed in size whatever the slot's length. A length does NOT
      make it safe: a prompt's first chunk (start 0) reads zeros in its
      place, which is the reset of a slot admitted again, and a padding row
      or an inactive slot leaves it as it was.

    The axes of ``k`` keep ``KVCache``'s places, so ``lengths`` and every
    reader of ``k.shape[1]`` / ``k.shape[3]`` hold. A class of its own
    because what copies K and V rows (the prefix pool, the paged pool, KV
    export) would carry a slot without its state: the engine refuses those
    at boot."""

    k: jnp.ndarray
    v: jnp.ndarray
    ck: jnp.ndarray
    state: jnp.ndarray
    lengths: jnp.ndarray  # [slots] int32

    @classmethod
    def create(
        cls, n_sparse: int, n_lin: int, n_slots: int, max_len: int,
        n_kv_heads: int, head_dim: int, lin_heads: int, lin_head_dim: int,
        stride: int, dtype: Any = jnp.bfloat16,
    ) -> "HybridCache":
        kv = (n_sparse, n_slots, n_kv_heads, max_len, head_dim)
        return cls(
            k=jnp.zeros(kv, dtype=dtype),
            v=jnp.zeros(kv, dtype=dtype),
            ck=jnp.zeros(kv[:3] + (max_len // stride, head_dim), dtype=dtype),
            state=jnp.zeros(
                (n_lin, n_slots, lin_heads, lin_head_dim, lin_head_dim),
                dtype=jnp.float32,
            ),
            lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
        )

    @classmethod
    def for_config(cls, cfg: Any, n_slots: int, max_len: int) -> "HybridCache":
        """The cache of ``cfg`` (a ``TransformerConfig`` with
        ``layer_kinds``): its sparse and lightning layers' planes."""
        return cls.create(
            cfg.n_sparse_layers, cfg.n_lin_layers, n_slots, max_len,
            cfg.n_kv_heads, cfg.head_dim, cfg.lin_heads, cfg.lin_head_dim,
            cfg.sparse_stride, cfg.dtype,
        )

    # What the engine asks of any contiguous cache.
    k_s = v_s = None
    quantized = False

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    def hbm_bytes(self) -> int:
        return int(sum(
            p.size * p.dtype.itemsize
            for p in (self.k, self.v, self.ck, self.state)
        ))

    def bytes_per_token(self) -> int:
        """K, V and the compressed keys: what grows with tokens."""
        return _bytes_per_position(self, sum(
            p.size * p.dtype.itemsize for p in (self.k, self.v, self.ck)
        ))

    @property
    def state_bytes_per_slot(self) -> int:
        """What a slot holds whatever its length."""
        return int(self.state.size * self.state.dtype.itemsize) // self.n_slots


class PagedKVCache(NamedTuple):
    """Block-pool KV cache (the vLLM idea, TPU-shaped).

    The slot cache reserves ``n_slots × max_len`` HBM whether or not the
    sequences are long; the paged cache reserves a POOL of fixed-size
    blocks and maps each slot's logical positions onto pool blocks via a
    block table, so HBM scales with the tokens actually resident:

    * ``k``/``v``: ``[L, n_blocks, KV, block, hd]`` — block as the
      second-to-last axis keeps per-(block, head) tiles ``[block, hd]``,
      the same VMEM-tileable layout the slot cache uses, so the pallas
      decode kernel only changes its index_map (pool block id from the
      prefetched table instead of ``ik``);
    * ``block_table``: ``[S, max_blocks] int32`` — pool block id for
      each slot's j-th logical block (entries past the allocated count
      are 0; the allocator guarantees allocation stays ahead of the
      pipelined windows' overshoot, see engine admission);
    * ``lengths``: ``[S]`` valid logical prefix per slot;
    * ``k_s``/``v_s``: int8 mode — ``[L, n_blocks, KV, 8, block]``
      sublane-replicated scale planes, mirroring the slot cache's.

    Block 0 is a reserved PARKING block: inactive-slot writes and
    rejected-draft history land there, so it is never handed out by the
    allocator and garbage in it is never attended (table entries of
    unallocated logical blocks also point at it).
    """

    k: jnp.ndarray
    v: jnp.ndarray
    block_table: jnp.ndarray
    lengths: jnp.ndarray
    k_s: Optional[jnp.ndarray] = None
    v_s: Optional[jnp.ndarray] = None

    @classmethod
    def create(
        cls,
        n_entries: int,
        n_slots: int,
        max_len: int,
        n_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        quant: str = "",
        block: int = 128,
        n_blocks: int = 0,
    ) -> "PagedKVCache":
        """``max_len`` is the per-slot LOGICAL cap (table width);
        ``n_blocks`` the pool size (default: slots×max_len/block — same
        capacity as the slot cache; size it smaller to oversubscribe)."""
        if max_len % block:
            raise ValueError(f"max_len {max_len} not a multiple of block {block}")
        max_blocks = max_len // block
        if n_blocks <= 0:
            n_blocks = n_slots * max_blocks + 1  # +1: parking block 0
        shape = (n_entries, n_blocks, n_kv_heads, block, head_dim)
        table = jnp.zeros((n_slots, max_blocks), dtype=jnp.int32)
        if (quant or "").lower() == "int8":
            sshape = (n_entries, n_blocks, n_kv_heads, 8, block)
            return cls(
                k=jnp.zeros(shape, dtype=jnp.int8),
                v=jnp.zeros(shape, dtype=jnp.int8),
                block_table=table,
                lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
                k_s=jnp.ones(sshape, dtype=jnp.float32),
                v_s=jnp.ones(sshape, dtype=jnp.float32),
            )
        if quant:
            raise ValueError(f"unsupported KV quant mode {quant!r} (int8 only)")
        return cls(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            block_table=table,
            lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
        )

    @property
    def quantized(self) -> bool:
        return self.k_s is not None

    @property
    def n_slots(self) -> int:
        return self.block_table.shape[0]

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.block_table.shape[1] * self.k.shape[3]

    def hbm_bytes(self) -> int:
        total = self.k.size * self.k.dtype.itemsize * 2
        if self.k_s is not None:
            total += self.k_s.size * self.k_s.dtype.itemsize * 2
        return int(total)

    def bytes_per_token(self) -> int:
        return _bytes_per_position(self, self.hbm_bytes())

    state_bytes_per_slot = 0

    def block_bytes(self) -> int:
        """Global bytes of ONE pool block across every layer's K/V
        (and int8-scale) planes — the unit the HBM ledger converts the
        eviction watermark's byte fractions into block counts with."""
        return self.hbm_bytes() // self.n_blocks


class BlockAllocator:
    """Host-side refcounted allocator over the paged pool's physical
    blocks (block 0 is the reserved parking block and never handed out).

    The original paged allocator was a bare free list: every block
    belonged to exactly one slot and retirement returned it. Automatic
    prefix caching (serving/radix_cache.py) shares fully-filled prompt
    blocks across requests by block-table aliasing, so ownership becomes
    counted: a block's refcount is the number of live slot tables that
    reference it plus one if the radix index holds it. A block returns
    to the free list exactly when its refcount reaches zero.

    Thread safety: admission/retirement mutate from the scheduler
    thread, but ``RadixPrefixIndex.purge_aid`` decrefs from whichever
    thread calls ``load_lora``/``unload_lora``, so the count/free-list
    transitions hold an internal lock (host bookkeeping — contention is
    nil next to a device dispatch).
    """

    def __init__(self, n_blocks: int) -> None:
        import threading

        self.n_blocks = int(n_blocks)
        self._lock = threading.Lock()
        # Pop from the end → highest ids hand out first (the original
        # free-list order; tests and the soak script watch its length).
        self._free: list[int] = list(range(1, self.n_blocks))
        self._refs: list[int] = [0] * self.n_blocks

    @property
    def free_blocks(self) -> list[int]:
        """Free-list view (length == free blocks). Treat as read-only."""
        return self._free

    @property
    def n_free(self) -> int:
        return len(self._free)

    def refcount(self, bid: int) -> int:
        return self._refs[bid]

    def alloc(self) -> Optional[int]:
        """One free block with refcount 1, or None when the pool is dry
        (callers may evict unreferenced radix-cached blocks and retry).
        """
        with self._lock:
            if not self._free:
                return None
            bid = self._free.pop()
            self._refs[bid] = 1
            return bid

    def incref(self, bid: int) -> int:
        """Add a reference (block-table aliasing / radix adoption)."""
        with self._lock:
            if self._refs[bid] <= 0:
                raise ValueError(f"incref of free block {bid}")
            self._refs[bid] += 1
            return self._refs[bid]

    def decref(self, bid: int) -> bool:
        """Drop one reference; True when this freed the block (refcount
        hit zero and it returned to the free list)."""
        with self._lock:
            if self._refs[bid] <= 0:
                raise ValueError(f"decref of free block {bid}")
            self._refs[bid] -= 1
            if self._refs[bid] == 0:
                self._free.append(bid)
                return True
            return False


@partial(jax.jit, donate_argnums=(0,))
def paged_copy_block(
    cache: "PagedKVCache", src: Any, dst: Any
) -> "PagedKVCache":
    """Copy one physical block pool→pool across every layer (K, V and
    the int8 scale planes when present) — the copy-on-write step behind
    zero-copy prefix sharing: when a cached prefix covers a slot's
    ENTIRE prompt, the finalize chunk still re-writes the last prompt
    position, so the boundary block is duplicated first and the slot's
    table points at the private copy. ``src``/``dst`` are traced int32
    scalars, so this is ONE fixed-shape compile per cache geometry; the
    donated pool aliases in place."""
    new = cache._replace(
        k=cache.k.at[:, dst].set(cache.k[:, src]),
        v=cache.v.at[:, dst].set(cache.v[:, src]),
    )
    if cache.k_s is not None:
        new = new._replace(
            k_s=cache.k_s.at[:, dst].set(cache.k_s[:, src]),
            v_s=cache.v_s.at[:, dst].set(cache.v_s[:, src]),
        )
    return new


def paged_view(
    block_table: Any,
    layer_k: Any,
    layer_v: Any,
    rows: Any,
    layer_ks: Any = None,
    layer_vs: Any = None,
    layer: Any = None,
) -> tuple:
    """Dense-fallback view: gather ``rows``' blocks into contiguous
    per-row caches ``[R, KV, max_len, hd]`` (+ scale planes). Materializes
    a copy — the paged flash kernels index the pool in place instead;
    this exists for the CPU/dense path and tests.

    layer_k/layer_v: one layer's pool ``[n_blocks, KV, block, hd]``, or,
    with ``layer`` (a traced index) the entry gathered from, the stacked
    pools ``[E, n_blocks, KV, block, hd]``.
    """
    bt = block_table[rows]  # [R, max_blocks]
    R, MB = bt.shape
    KV, B, hd = layer_k.shape[-3], layer_k.shape[-2], layer_k.shape[-1]

    def blocks(pool: Any) -> Any:  # [R, MB, ...] of the entry's pool
        return pool[bt] if layer is None else pool[layer, bt]

    k = blocks(layer_k).transpose(0, 2, 1, 3, 4).reshape(R, KV, MB * B, hd)
    v = blocks(layer_v).transpose(0, 2, 1, 3, 4).reshape(R, KV, MB * B, hd)
    if layer_ks is None:
        return k, v, None, None
    ks = blocks(layer_ks).transpose(0, 2, 3, 1, 4).reshape(R, KV, 8, MB * B)
    vs = blocks(layer_vs).transpose(0, 2, 3, 1, 4).reshape(R, KV, 8, MB * B)
    return k, v, ks, vs


# ----------------------------------------------------------------------
# Cross-engine block shipping (disaggregated prefill/decode tiers)
# ----------------------------------------------------------------------


@dataclass(frozen=True, eq=False)  # identity eq: ndarray fields don't compare
class KVBlockPayload:
    """A batch of fully-written paged KV blocks lifted off one engine's
    pool so a sibling can alias the same content into its own — the
    transfer unit of the disaggregated prefill/decode tier
    (``service/replica_pool.py``).

    This is the HOST-BOUNCE form: the planes are numpy arrays pulled
    device→host on the exporting engine and re-uploaded block-by-block
    on the importer (one fixed-shape jitted scatter per block, so the
    importer pays no recompiles). A device-to-device path over a shared
    mesh can later replace the numpy legs without changing this seam —
    the content keys and validation travel the same either way.

    ``token_ids`` is the blocks' token content in prompt order (exactly
    ``len(blocks) × block`` ids): the importing engine inserts the
    blocks into its radix prefix index under these content keys, so the
    import IS a prefix-cache warm and admission aliases the blocks
    zero-copy — an evicted or rejected import degrades to a plain
    re-prefill, never to a wrong answer.

    ``checksum`` covers the raw plane bytes; a short or corrupt payload
    fails :meth:`verify` and the importer falls back to re-prefilling
    (the transfer failure matrix's "corrupt payload" row).
    """

    block: int
    token_ids: tuple[int, ...]
    k: np.ndarray  # [L, n, KV, block, hd] — gathered pool blocks
    v: np.ndarray
    k_s: Optional[np.ndarray] = None  # int8 mode: [L, n, KV, 8, block]
    v_s: Optional[np.ndarray] = None
    src: str = ""
    checksum: int = 0
    # Geometry fingerprint of the exporting cache; importers with a
    # different model/config/quant mode must reject, not alias garbage.
    geometry: tuple = field(default_factory=tuple)

    @property
    def n_blocks(self) -> int:
        return int(self.k.shape[1])

    def compatible_with(self, cache: "PagedKVCache") -> bool:
        """Geometry (version) match against the importing pool."""
        return (
            self.block == cache.block
            and self.geometry == cache_geometry(cache)
        )

    def nbytes(self) -> int:
        """Shipped bytes across every plane (the per-leg transfer-bytes
        counter's increment)."""
        total = int(self.k.nbytes) + int(self.v.nbytes)
        if self.k_s is not None:
            total += int(self.k_s.nbytes)
        if self.v_s is not None:
            total += int(self.v_s.nbytes)
        return total

    def verify(self) -> bool:
        """Payload integrity: the token chain covers the blocks exactly
        and the plane bytes hash to the exporter's checksum. The CRC
        verdict is memoized — a transfer retrying across decode targets
        re-verifies the SAME in-process memory, which cannot rot
        between attempts (the wire form will re-checksum on receipt
        instead)."""
        if len(self.token_ids) != self.n_blocks * self.block:
            return False
        cached = self.__dict__.get("_crc_ok")
        if cached is None:
            cached = payload_checksum(
                self.k, self.v, self.k_s, self.v_s
            ) == self.checksum
            object.__setattr__(self, "_crc_ok", cached)
        return bool(cached)


def cache_geometry(cache: "PagedKVCache") -> tuple:
    """The paged pool's compile-relevant shape signature — what must
    match exactly for a foreign block's bytes to mean the same thing
    here (layers, kv heads, block, head_dim, dtype, quant mode)."""
    L, _, KV, B, hd = cache.k.shape
    return (L, KV, B, hd, str(cache.k.dtype), cache.k_s is not None)


def payload_checksum(
    k: np.ndarray,
    v: np.ndarray,
    k_s: Optional[np.ndarray] = None,
    v_s: Optional[np.ndarray] = None,
) -> int:
    crc = zlib.crc32(np.ascontiguousarray(k).tobytes())
    crc = zlib.crc32(np.ascontiguousarray(v).tobytes(), crc)
    if k_s is not None:
        crc = zlib.crc32(np.ascontiguousarray(k_s).tobytes(), crc)
    if v_s is not None:
        crc = zlib.crc32(np.ascontiguousarray(v_s).tobytes(), crc)
    return crc


def export_blocks(
    cache: "PagedKVCache",
    block_ids: list[int],
    token_ids: list[int],
    src: str = "",
) -> KVBlockPayload:
    """Pull ``block_ids``' fully-written pool blocks to host as a
    shippable payload (one gather + one device→host copy per plane —
    the deliberate host bounce of the tier-transfer path, not a hot-
    path sync; the caller is the exporting scheduler at prefill
    finalize, where the blocks are immutable)."""
    idx = np.asarray(block_ids, dtype=np.int32)
    k = np.asarray(jax.device_get(cache.k[:, idx]))  # graftlint: disable=GL001 — the host bounce IS the transfer
    v = np.asarray(jax.device_get(cache.v[:, idx]))  # graftlint: disable=GL001 — the host bounce IS the transfer
    k_s = v_s = None
    if cache.k_s is not None:
        k_s = np.asarray(jax.device_get(cache.k_s[:, idx]))  # graftlint: disable=GL001 — the host bounce IS the transfer
        v_s = np.asarray(jax.device_get(cache.v_s[:, idx]))  # graftlint: disable=GL001 — the host bounce IS the transfer
    return KVBlockPayload(
        block=cache.block,
        token_ids=tuple(int(t) for t in token_ids),
        k=k, v=v, k_s=k_s, v_s=v_s, src=src,
        checksum=payload_checksum(k, v, k_s, v_s),
        geometry=cache_geometry(cache),
    )


@partial(jax.jit, donate_argnums=(0,))
def paged_insert_block(
    cache: "PagedKVCache",
    dst: Any,
    k_blk: Any,
    v_blk: Any,
    k_s_blk: Any = None,
    v_s_blk: Any = None,
) -> "PagedKVCache":
    """Write one imported block's planes into pool block ``dst`` (the
    import half of the transfer seam). ``dst`` is a traced int32
    scalar and the block operands are fixed ``[L, KV, block, hd]``
    shapes, so this is ONE compile per cache geometry no matter how
    many blocks an import carries; the donated pool aliases in place
    (same discipline as :func:`paged_copy_block`)."""
    new = cache._replace(
        k=cache.k.at[:, dst].set(k_blk),
        v=cache.v.at[:, dst].set(v_blk),
    )
    if cache.k_s is not None and k_s_blk is not None:
        new = new._replace(
            k_s=cache.k_s.at[:, dst].set(k_s_blk),
            v_s=cache.v_s.at[:, dst].set(v_s_blk),
        )
    return new


# ----------------------------------------------------------------------
# Device leg: pool→pool block shipping without the host bounce
# ----------------------------------------------------------------------


@dataclass(frozen=True, eq=False)  # identity eq: device-array fields
class DeviceKVPayload:
    """The device-leg twin of :class:`KVBlockPayload`: per-block cache
    planes extracted as DEVICE arrays (one fixed-shape jitted gather per
    block, :func:`paged_extract_block`) and written into the importing
    pool with :func:`paged_move_block` — the bytes move over ICI/DMA
    (or stay in place when both pools share a device), never through
    host memory. Only usable between engines in one process on a shared
    JAX runtime; the pool's transfer ladder falls back to the wire or
    host-bounce form for everything else.

    Content keys (``token_ids``), the geometry fingerprint, and all
    radix bookkeeping stay host-side and travel exactly like the
    host-bounce payload's. There is deliberately no byte checksum: the
    planes never leave device memory, where in-process bytes cannot rot
    between export and import, and computing a CRC would itself be the
    host pull this leg exists to remove.
    """

    block: int
    token_ids: tuple[int, ...]
    #: per-block device planes, each ``[L, KV, block, hd]`` on the
    #: EXPORTING engine's sharding (the importer re-places them).
    k_blocks: tuple[Any, ...]
    v_blocks: tuple[Any, ...]
    #: int8 mode: per-block scale planes ``[L, KV, 8, block]``.
    k_s_blocks: Optional[tuple[Any, ...]] = None
    v_s_blocks: Optional[tuple[Any, ...]] = None
    src: str = ""
    geometry: tuple = field(default_factory=tuple)

    @property
    def n_blocks(self) -> int:
        return len(self.k_blocks)

    def compatible_with(self, cache: "PagedKVCache") -> bool:
        """Geometry (version) match against the importing pool."""
        return (
            self.block == cache.block
            and self.geometry == cache_geometry(cache)
        )

    def verify(self) -> bool:
        """Structural integrity: the token chain covers the blocks
        exactly and the scale planes match the quant mode. No CRC leg —
        see the class docstring."""
        if len(self.token_ids) != self.n_blocks * self.block:
            return False
        if len(self.v_blocks) != self.n_blocks:
            return False
        quant = self.geometry[-1] if self.geometry else False
        if bool(quant) != (self.k_s_blocks is not None):
            return False
        return True

    def nbytes(self) -> int:
        """Shipped bytes, computed from shapes host-side (never pulls
        a plane)."""
        total = 0
        for group in (
            self.k_blocks, self.v_blocks,
            self.k_s_blocks or (), self.v_s_blocks or (),
        ):
            for blk in group:
                total += int(np.prod(blk.shape)) * blk.dtype.itemsize
        return total


@jax.jit
def paged_extract_block(
    cache: "PagedKVCache", src: Any
) -> tuple[Any, Any, Any, Any]:
    """Lift one physical block's planes out of the pool as fresh DEVICE
    arrays ``([L, KV, block, hd]×2, [L, KV, 8, block]×2 | None)`` — the
    export half of the device leg. ``src`` is a traced int32 scalar, so
    this is ONE fixed-shape compile per cache geometry no matter how
    many blocks a transfer carries; on a GSPMD-sharded pool the result
    keeps the pool's head-axis sharding, so nothing gathers."""
    k_blk = cache.k[:, src]
    v_blk = cache.v[:, src]
    if cache.k_s is not None:
        return k_blk, v_blk, cache.k_s[:, src], cache.v_s[:, src]
    return k_blk, v_blk, None, None


@partial(jax.jit, donate_argnums=(0,))
def paged_move_block(
    cache: "PagedKVCache",
    dst: Any,
    k_blk: Any,
    v_blk: Any,
    k_s_blk: Any = None,
    v_s_blk: Any = None,
) -> "PagedKVCache":
    """Write one DEVICE-resident block's planes into pool block ``dst``
    — the import half of the device leg. Identical donation/fixed-shape
    discipline to :func:`paged_insert_block`; the difference is the
    contract on the operands: they are already on the importing
    engine's devices (placed shard-to-shard with an explicit
    ``device_put`` when the pools' meshes differ), so the write never
    touches host memory. graftlint GL018 pins that contract: no
    ``device_get``/``np.asarray`` of cache planes may appear in
    ``paged_move*``/``*_device_leg`` code."""
    new = cache._replace(
        k=cache.k.at[:, dst].set(k_blk),
        v=cache.v.at[:, dst].set(v_blk),
    )
    if cache.k_s is not None and k_s_blk is not None:
        new = new._replace(
            k_s=cache.k_s.at[:, dst].set(k_s_blk),
            v_s=cache.v_s.at[:, dst].set(v_s_blk),
        )
    return new


# ----------------------------------------------------------------------
# Wire leg: length-prefixed binary codec for remote decode replicas
# ----------------------------------------------------------------------

#: Wire format magic/version. Bump on any framing change — the importer
#: rejects unknown magics instead of guessing.
WIRE_MAGIC = b"KVB1"


def _np_dtype(name: str) -> np.dtype:
    """``str(dtype)`` → dtype, including the ml_dtypes extras (bf16)
    numpy itself cannot name. Raises ``ValueError`` on anything else —
    the wire decoder's one rejection currency."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        dtype = getattr(ml_dtypes, name, None)
        if dtype is None:
            raise ValueError(f"unknown plane dtype {name!r}") from None
        return np.dtype(dtype)


def payload_to_wire(payload: KVBlockPayload) -> bytes:
    """Serialize a host-bounce payload for the wire leg: ``KVB1`` magic,
    a u32-length-prefixed JSON header (geometry fingerprint, content
    keys, crc32, plane shapes/dtypes), then each plane's raw bytes
    u64-length-prefixed in header order. The receiver re-checksums the
    planes on receipt (:func:`payload_from_wire` builds a fresh
    :class:`KVBlockPayload`, whose ``verify()`` recomputes the CRC), so
    a corrupt body degrades to fused serving, never a wrong answer."""
    planes: list[np.ndarray] = [payload.k, payload.v]
    names = ["k", "v"]
    if payload.k_s is not None and payload.v_s is not None:
        planes += [payload.k_s, payload.v_s]
        names += ["k_s", "v_s"]
    header = {
        "block": payload.block,
        "token_ids": list(payload.token_ids),
        "src": payload.src,
        "checksum": payload.checksum,
        "geometry": list(payload.geometry),
        "planes": [
            {
                "name": name,
                "shape": list(plane.shape),
                "dtype": str(plane.dtype),
            }
            for name, plane in zip(names, planes)
        ],
    }
    head = json.dumps(header).encode()
    parts = [WIRE_MAGIC, struct.pack(">I", len(head)), head]
    for plane in planes:
        raw = np.ascontiguousarray(plane).tobytes()
        parts.append(struct.pack(">Q", len(raw)))
        parts.append(raw)
    return b"".join(parts)


def payload_from_wire(data: bytes) -> KVBlockPayload:
    """Parse a wire-leg body back into a :class:`KVBlockPayload`.
    Raises ``ValueError`` on any framing violation (bad magic, short
    body, shape/byte-count mismatch) — the import endpoint maps that to
    a 400 ``rejected`` reply and the exporter degrades to the next
    rung. Byte-level corruption INSIDE a plane is caught later by
    ``verify()``'s CRC recomputation against the header checksum."""
    if len(data) < 8 or data[:4] != WIRE_MAGIC:
        raise ValueError("tier-import body lacks the KVB1 magic")
    (head_len,) = struct.unpack(">I", data[4:8])
    if len(data) < 8 + head_len:
        raise ValueError("tier-import header truncated")
    try:
        header = json.loads(data[8:8 + head_len].decode())
    except Exception as exc:
        raise ValueError(f"tier-import header unparseable: {exc}") from exc
    offset = 8 + head_len
    planes: dict[str, np.ndarray] = {}
    # Every malformed-header shape (missing keys, wrong types, bogus
    # dtypes) is the same rejection: the decoder's ONE exception
    # currency is ValueError, which the import endpoint maps to a 400
    # "rejected" — never a 5xx, whatever bytes arrive.
    try:
        for meta in header.get("planes", []):
            if len(data) < offset + 8:
                raise ValueError("tier-import plane length truncated")
            (nbytes,) = struct.unpack(">Q", data[offset:offset + 8])
            offset += 8
            if len(data) < offset + nbytes:
                raise ValueError(
                    f"tier-import plane {meta.get('name')!r} truncated"
                )
            dtype = _np_dtype(str(meta["dtype"]))
            shape = tuple(int(s) for s in meta["shape"])
            if int(np.prod(shape)) * dtype.itemsize != nbytes:
                raise ValueError(
                    f"tier-import plane {meta.get('name')!r} byte count "
                    f"does not match its declared shape"
                )
            planes[str(meta["name"])] = np.frombuffer(
                data, dtype=dtype, count=int(np.prod(shape)), offset=offset
            ).reshape(shape)
            offset += nbytes
        if "k" not in planes or "v" not in planes:
            raise ValueError("tier-import body is missing K/V planes")
        return KVBlockPayload(
            block=int(header["block"]),
            token_ids=tuple(int(t) for t in header.get("token_ids", ())),
            k=planes["k"],
            v=planes["v"],
            k_s=planes.get("k_s"),
            v_s=planes.get("v_s"),
            src=str(header.get("src", "")),
            checksum=int(header.get("checksum", 0)),
            geometry=tuple(header.get("geometry", ())),
        )
    except (KeyError, TypeError, AttributeError, OverflowError,
            struct.error) as exc:
        raise ValueError(
            f"tier-import header malformed: {exc!r}"
        ) from exc


# ----------------------------------------------------------------------
# DMA leg: handle-bearing wire variant (cross-process transfer server)
# ----------------------------------------------------------------------

#: Handle wire format magic/version. A ``KVH1`` body carries NO plane
#: bytes — only a claim ticket against the exporter's transfer server
#: (``service/dma.py``); the importer redeems it with a bounded fetch
#: and only then sees a full ``KVB1`` payload. Sharing the first-4-byte
#: dispatch with :data:`WIRE_MAGIC` lets ``POST /ops/tier-import``
#: accept either form on the same endpoint.
HANDLE_MAGIC = b"KVH1"


@dataclass(frozen=True)
class KVHandlePayload:
    """A CLAIM TICKET for KV blocks staged on the exporting process's
    transfer server — the ``dma`` leg's transfer unit. Where
    :class:`KVBlockPayload` ships the plane bytes inline, this ships
    only an (address, key) pair plus the content metadata the importer
    needs for admission decisions *before* paying for the fetch:
    geometry fingerprint, token chain, byte count, and the exporter's
    checksum (re-verified against the fetched bytes, so a transfer
    server handing back the wrong staging entry is caught as a stale
    handle, never aliased as garbage).

    The fields deliberately mirror the host-bounce payload's metadata
    so validation code (``compatible_with``/``n_blocks``) reads the
    same; only ``verify()`` differs — structurally true here, because
    integrity is proven after the fetch, on the real bytes."""

    address: str  # "host:port" of the exporter's DmaTransferServer
    key: str      # opaque staging key (single-use, TTL-bounded)
    block: int
    token_ids: tuple[int, ...]
    src: str = ""
    checksum: int = 0
    geometry: tuple = field(default_factory=tuple)
    nbytes_hint: int = 0  # staged wire-body size (flow-control budget)

    @property
    def n_blocks(self) -> int:
        return len(self.token_ids) // self.block if self.block else 0

    def compatible_with(self, cache: "PagedKVCache") -> bool:
        """Same geometry gate as the inline payload — a handle whose
        fingerprint can't match is rejected before any socket opens."""
        return (
            self.block == cache.block
            and self.geometry == cache_geometry(cache)
        )

    def nbytes(self) -> int:
        return int(self.nbytes_hint)

    def verify(self) -> bool:
        """Structural check only: the token chain must tile the blocks.
        Byte integrity is decided by the post-fetch CRC against
        ``checksum`` (``service/dma.py`` raises ``stale`` on mismatch)."""
        return (
            self.block > 0
            and len(self.token_ids) % self.block == 0
            and len(self.token_ids) > 0
        )


def handle_to_wire(handle: KVHandlePayload) -> bytes:
    """Serialize a transfer-server claim ticket: ``KVH1`` magic + a
    u32-length-prefixed JSON header, no plane bytes. Tiny by design —
    the dma leg's HTTP POST carries O(100) bytes however many blocks
    the staged payload holds."""
    header = {
        "address": handle.address,
        "key": handle.key,
        "block": handle.block,
        "token_ids": list(handle.token_ids),
        "src": handle.src,
        "checksum": handle.checksum,
        "geometry": list(handle.geometry),
        "nbytes": handle.nbytes_hint,
    }
    head = json.dumps(header).encode()
    return b"".join([HANDLE_MAGIC, struct.pack(">I", len(head)), head])


def handle_from_wire(data: bytes) -> KVHandlePayload:
    """Parse a ``KVH1`` body back into a :class:`KVHandlePayload`.
    Exactly :func:`payload_from_wire`'s contract: every malformed shape
    raises ``ValueError`` — the import endpoint's one rejection
    currency, mapped to a 400 ``rejected`` reply."""
    if len(data) < 8 or data[:4] != HANDLE_MAGIC:
        raise ValueError("tier-import body lacks the KVH1 magic")
    (head_len,) = struct.unpack(">I", data[4:8])
    if len(data) < 8 + head_len:
        raise ValueError("tier-import handle header truncated")
    try:
        header = json.loads(data[8:8 + head_len].decode())
        address = str(header["address"])
        if ":" not in address:
            raise ValueError(f"handle address {address!r} lacks a port")
        return KVHandlePayload(
            address=address,
            key=str(header["key"]),
            block=int(header["block"]),
            token_ids=tuple(int(t) for t in header.get("token_ids", ())),
            src=str(header.get("src", "")),
            checksum=int(header.get("checksum", 0)),
            geometry=tuple(header.get("geometry", ())),
            nbytes_hint=int(header.get("nbytes", 0)),
        )
    except ValueError:
        raise
    except (KeyError, TypeError, AttributeError, OverflowError,
            struct.error, UnicodeDecodeError) as exc:
        raise ValueError(
            f"tier-import handle header malformed: {exc!r}"
        ) from exc


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Absmax-int8 quantize K/V rows over the trailing head_dim axis.

    x: [..., head_dim] → (q int8 same shape, scale f32 [...]) — one scalar
    scale per (token, head) row, the standard KV-cache granularity.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def fake_quantize_kv(x: jnp.ndarray) -> jnp.ndarray:
    """Quantize → dequantize (same dtype out). The split decode/verify
    paths attend a token's K/V BEFORE it is committed to an int8 cache;
    running the fresh values through the quantizer first makes what is
    attended bit-identical to what later steps will read back — and
    re-quantizing the result at commit time reproduces the same int8
    (the max element maps to exactly ±127, so the absmax scale is a
    fixed point)."""
    q, scale = quantize_kv(x)
    return (q.astype(jnp.float32) * scale[..., None]).astype(x.dtype)
