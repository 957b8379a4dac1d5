"""Pallas TPU kernels for the attention hot path.

Two kernels, both written grid-sequential in the canonical TPU style (the
kv axis is the innermost grid dimension; online-softmax state carries in
VMEM scratch across kv iterations):

* :func:`flash_attention` — causal prefill, O(s) memory, GQA-aware block
  index maps so KV blocks are fetched once per kv-head (not per q-head);
* :func:`flash_decode` — one query token per sequence against a paged slot
  KV cache with per-slot lengths prefetched to SMEM so fully-invalid KV
  blocks are skipped before their DMA cost is paid;
* :func:`flash_cache_attention` — chunked-prefill queries against the slot
  cache in place (one compile a row count serves every prompt length).

All run under ``interpret=True`` on CPU, which is how the unit tests
exercise them without hardware.
"""

from gofr_tpu.ops.pallas.flash_attention import flash_attention
from gofr_tpu.ops.pallas.flash_decode import flash_decode
from gofr_tpu.ops.pallas.flash_prefill import flash_cache_attention

__all__ = ["flash_attention", "flash_cache_attention", "flash_decode"]
