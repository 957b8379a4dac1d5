"""Int8 weight-only quantization for serving.

Decode is HBM-bandwidth-bound on weight reads (every step streams the
full parameter set); storing matmul weights as int8 with per-output-channel
f32 scales halves that traffic. Dequantization happens inside the jitted
step — ``dequant = q.astype(bf16) * scale`` immediately feeding an einsum —
so XLA fuses it into the matmul loop and HBM sees only int8 bytes plus a
tiny scale vector.

Representation: a :class:`Q8` pytree node ``(q: int8, s: f32)`` replacing
the weight leaf. The model's einsum helper (``models/transformer.py
_wein``) dequantizes transparently, so the same forward serves bf16 and
int8 params. Embeddings stay bf16 (gathers only touch the rows they need);
norms/scales are tiny and stay bf16.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class Q8(NamedTuple):
    """Int8 weight + per-output-channel scale (broadcastable to q.shape)."""

    q: jnp.ndarray  # int8, same shape as the original weight
    s: jnp.ndarray  # f32, shape = 1s except the channel (last) axis

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):  # for code asking "what compute dtype is this"
        return jnp.bfloat16


class Q4(NamedTuple):
    """Int4 weight + group-wise scales (W4A16).

    ``q``: uint8 with TWO 4-bit values (two's-complement nibbles) packed
    along the contraction axis — ``[..., D/2, out]`` for an original
    ``[..., D, out]`` weight. Explicit nibble packing instead of XLA's
    native s4: same ½-byte/elem HBM footprint, but the arrays are plain
    uint8 everywhere outside the fused unpack — s4 support is emulated
    on most backends anyway.
    ``s``: f32 ``[..., G, 1, out]`` — one scale per ``group`` contraction
    rows per output channel (group-wise absmax keeps 4-bit quality;
    per-column int4 is too coarse for real weights). Weight HBM is ~¼ of
    bf16 — an 8B model stores in ~4 GB.
    """

    q: jnp.ndarray
    s: jnp.ndarray

    @property
    def shape(self):  # logical (unpacked) shape
        lead, (d2, o) = self.q.shape[:-2], self.q.shape[-2:]
        return (*lead, d2 * 2, o)

    @property
    def dtype(self):
        return jnp.bfloat16


def quantize_array(w: jnp.ndarray) -> Q8:
    """Absmax int8 quantization reducing ONLY the contraction axis.

    Every matmul weight in the model — stacked or not, dense or MoE —
    contracts its second-to-last axis (wq [L, D, H*hd], w_down [L, E, F, D],
    lm_head [D, V], …), so scales keep per-layer / per-expert / per-channel
    resolution with one rule: absmax over ``axis=-2``.
    """
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return Q8(q=q, s=scale.astype(jnp.float32))


def quantize_array4(w: jnp.ndarray, group: int = 128) -> Q4:
    """Group-wise absmax int4 over the contraction (-2) axis, nibble-
    packed into uint8 (two values per byte along that axis).

    ``group`` shrinks to the axis size when it doesn't divide it (tiny
    test models); real model dims are multiples of 128. The contraction
    axis must be even (every real transformer dim is).
    """
    D = w.shape[-2]
    if D % 2:
        raise ValueError(f"int4 nibble packing needs an even contraction "
                         f"axis, got {D}")
    if D % group:
        group = D
    G = D // group
    lead = w.shape[:-2]
    wf = w.astype(jnp.float32).reshape(*lead, G, group, w.shape[-1])
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)  # [.., G, 1, O]
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -7, 7).astype(jnp.int32)
    q = q.reshape(*lead, D, w.shape[-1])
    nib = jnp.where(q < 0, q + 16, q).astype(jnp.uint8)  # two's complement
    packed = (nib[..., 0::2, :] << 4) | nib[..., 1::2, :]
    return Q4(q=packed, s=scale.astype(jnp.float32))


def dequantize(w: Any, dtype=jnp.bfloat16) -> jnp.ndarray:
    if isinstance(w, Q8):
        return (w.q.astype(jnp.float32) * w.s).astype(dtype)
    if isinstance(w, Q4):
        lead, (D2, O) = w.q.shape[:-2], w.q.shape[-2:]
        D = D2 * 2
        # Unpack nibbles (hi = even rows, lo = odd) and sign-extend —
        # elementwise ops XLA fuses into the consuming matmul's read.
        hi = (w.q >> 4).astype(jnp.int32)
        lo = (w.q & 0xF).astype(jnp.int32)
        n = jnp.stack([hi, lo], axis=-2)  # [..., D/2, 2, O]
        n = jnp.where(n > 7, n - 16, n).reshape(*lead, D, O)
        G = w.s.shape[-3]
        wf = n.astype(jnp.float32).reshape(*lead, G, D // G, O) * w.s
        return wf.reshape(*lead, D, O).astype(dtype)
    return w


# Weight leaves worth quantizing: the big matmul weights. Embeddings
# (gather), norms (tiny), and the MoE router (tiny AND routing-sensitive:
# a flipped top-k from quantization error changes which experts run)
# stay in bf16.
_QUANT_KEYS = {
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"
}


def _quant_fn(mode: str):
    if mode == "int8":
        return quantize_array
    if mode == "int4":
        return quantize_array4
    raise ValueError(f"unsupported quant mode {mode!r} (int8 or int4)")


def quantize_params(params: dict, mode: str = "int8") -> dict:
    """Quantize a transformer param tree's matmul weights (Q8 or Q4)
    in place (returns a new tree; other leaves pass through untouched)."""
    quant = _quant_fn(mode)
    out = dict(params)
    out["layers"] = {
        k: (quant(v) if k in _QUANT_KEYS else v)
        for k, v in params["layers"].items()
    }
    if "lm_head" in params:
        out["lm_head"] = quant(params["lm_head"])
    return out


def q8_spec(spec) -> Q8:
    """The Q8 PartitionSpec pair for a weight whose bf16 spec is ``spec``.

    ``q`` keeps the weight's sharding (same shape). ``s`` has extent 1 on
    the contraction (-2) axis, so that entry must be unsharded; every other
    axis (leading layer/pp axes, the output-channel axis) keeps the
    weight's sharding — the scale vector shards WITH its output channels,
    which is what lets int8 compose with a tp mesh (VERDICT r2 next #2).
    """
    from jax.sharding import PartitionSpec as P

    entries = list(spec)
    if len(entries) >= 2:
        entries[-2] = None
    return Q8(q=spec, s=P(*entries))


def q4_spec(spec) -> Q4:
    """Q4 PartitionSpec pair: ``q`` keeps the weight's sharding; the
    group-wise scale ``[..., G, 1, out]`` replicates its G and unit axes
    (G may not divide tp for small models; scales are tiny) and keeps the
    output-channel sharding."""
    from jax.sharding import PartitionSpec as P

    entries = list(spec)
    return Q4(q=spec, s=P(*entries[:-2], None, None, entries[-1]))


def quantized_param_specs(specs: dict, mode: str = "int8") -> dict:
    """Map a bf16 param-spec tree (``transformer_param_specs``) to the spec
    tree of ``quantize_params(params, mode)``: quantized leaves become
    Q8/Q4 spec pairs, everything else passes through."""
    _quant_fn(mode)  # validate
    qspec = q8_spec if mode == "int8" else q4_spec
    out = dict(specs)
    out["layers"] = {
        k: (qspec(v) if k in _QUANT_KEYS else v)
        for k, v in specs["layers"].items()
    }
    if "lm_head" in specs:
        out["lm_head"] = qspec(specs["lm_head"])
    return out


def quantized_bytes(params: Any) -> int:
    """Total parameter bytes as stored (int8 → 1 B/elem; int4 leaves are
    nibble-packed uint8, so the generic itemsize path already counts
    them at ½ B per logical element)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return int(total)
