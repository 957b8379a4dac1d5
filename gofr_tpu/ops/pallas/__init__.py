"""Pallas TPU kernels for the attention hot path.

Four kernels, all written grid-sequential in the canonical TPU style (the
kv axis is the innermost grid dimension; online-softmax state carries in
VMEM scratch across kv iterations):

* :func:`flash_attention` — causal prefill, O(s) memory, GQA-aware block
  index maps so KV blocks are fetched once per kv-head (not per q-head);
* :func:`flash_decode` — one query token per sequence against a paged slot
  KV cache with per-slot lengths prefetched to SMEM so fully-invalid KV
  blocks are skipped before their DMA cost is paid;
* :func:`flash_cache_attention` — chunked-prefill queries against the slot
  cache in place (one compile a row count serves every prompt length);
* :func:`latent_flash_decode` — a latent cache's absorbed decode attention
  over the stacked plane in place, each slot read to its own length.

All run under ``interpret=True`` on CPU, which is how the unit tests
exercise them without hardware.
"""

from gofr_tpu.ops.pallas.flash_attention import flash_attention
from gofr_tpu.ops.pallas.flash_decode import flash_decode
from gofr_tpu.ops.pallas.flash_prefill import flash_cache_attention
from gofr_tpu.ops.pallas.latent_decode import latent_flash_decode

__all__ = [
    "flash_attention", "flash_cache_attention", "flash_decode",
    "latent_flash_decode",
]
