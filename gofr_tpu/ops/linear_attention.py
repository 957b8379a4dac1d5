"""Lightning linear attention: a decayed recurrence a head, no softmax.

A head ``h`` keeps a ``[head_dim, head_dim]`` float32 state ``S`` a slot and
no keys or values::

    S_t = lambda_h S_{t-1} + k_t^T v_t        o_t = (q_t * scale) S_t

that is ``o_t = sum_{j<=t} lambda_h^(t-j) (q_t . k_j * scale) v_j``: no
normaliser. ``lambda_h = exp(log_decay[h])`` is a constant of the layer
(``lightning_log_decay``). The prefill step computes a chunk of positions at
once in the chunk-wise form (inside the chunk a masked, decayed product of
the scores; the state before the chunk enters each position decayed, and
leaves decayed by the chunk's valid length); the decode step is one step of
the recurrence. State and decay are float32 throughout, and the products
that touch the state run at ``HIGHEST`` precision: on a TPU a float32
product is otherwise computed from bfloat16 roundings of its operands, and
the state of a 30,000-token slot is a sum over every one of its tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


def lightning_log_decay(
    n_heads: int, layers: list[int], published_layers: int
) -> jnp.ndarray:
    """``log lambda`` [len(layers), n_heads] float32, Lightning Attention's
    published rule: ``lambda = exp(-s_h c_l)`` with the head's slope ``s_h =
    2^(-8 h / n_heads)``, h = 1..n_heads, and the layer's factor ``c_l = 1 -
    l / (published_layers - 1) + 1e-5`` for the layer's index ``l`` in the
    PUBLISHED stack: early layers and low heads forget fastest."""
    slopes = 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32) / n_heads)
    factor = (
        1.0 - jnp.asarray(layers, jnp.float32) / max(published_layers - 1, 1)
        + 1e-5
    )
    return -(factor[:, None] * slopes[None, :])


def lightning_chunk(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, state: jnp.ndarray,
    log_decay: jnp.ndarray, lens: jnp.ndarray, scale: float,
    precision: jax.lax.Precision | None = _EXACT,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of positions a row, chunk-wise. q, k, v: [P, c, H, hd] (after
    their norms and rotary values); state: [P, H, hd, hd] float32, each row's
    state BEFORE the chunk; log_decay: [H] float32 (<= 0); lens: [P] valid
    positions of each row (the padded tail is left out of the outputs' sums
    and of the state: a row of ``lens`` 0 returns its state as it was).
    ``precision``: of the two products that touch the state (a probe asks
    for the default to price the highest). Returns (o [P, c, H, hd] in q's
    dtype, the state after the row's last valid position [P, H, hd, hd]
    float32)."""
    c = q.shape[1]
    i = jnp.arange(c)
    valid = i[None, :] < lens[:, None]  # [P, c]
    k = jnp.where(valid[:, :, None, None], k, 0)
    # Inside the chunk: scores decayed by the distance, causal.
    s = jnp.einsum(
        "pihd,pjhd->phij", q, k, preferred_element_type=jnp.float32
    ) * scale
    dist = i[:, None] - i[None, :]  # [c, c]
    decay = jnp.where(
        dist >= 0,
        jnp.exp(jnp.maximum(dist, 0)[None] * log_decay[:, None, None]), 0.0,
    )  # [H, c, c]
    o = jnp.einsum(
        "phij,pjhd->pihd", (s * decay[None]).astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    # The state before the chunk, decayed up to each position.
    into = jnp.exp((i + 1)[:, None] * log_decay[None, :])  # [c, H]
    o = o + jnp.einsum(
        "pihd,phde->pihe",
        q.astype(jnp.float32) * (scale * into)[None, :, :, None], state,
        precision=precision,
    )
    # The state after the row's last valid position.
    left = jnp.where(valid, lens[:, None] - 1 - i[None, :], 0)  # [P, c]
    out_of = jnp.exp(left[:, :, None] * log_decay[None, None, :])  # [P, c, H]
    new = state * jnp.exp(
        lens[:, None] * log_decay[None, :]
    )[:, :, None, None] + jnp.einsum(
        "pjhd,pjhe->phde", k.astype(jnp.float32) * out_of[..., None],
        v.astype(jnp.float32), precision=precision,
    )
    return o.astype(q.dtype), new


def lightning_step(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, state: jnp.ndarray,
    log_decay: jnp.ndarray, active: jnp.ndarray, scale: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the recurrence a slot. q, k, v: [S, H, hd]; state:
    [S, H, hd, hd] float32; active: [S] bool, an inactive slot's state stays
    as it was (a slot in the middle of its prefill is building it).
    Returns (o [S, H, hd] in q's dtype, the new state)."""
    new = state * jnp.exp(log_decay)[None, :, None, None] + (
        k.astype(jnp.float32)[..., :, None] * v.astype(jnp.float32)[..., None, :]
    )
    o = jnp.einsum(
        "shd,shde->she", q.astype(jnp.float32) * scale, new, precision=_EXACT
    )
    new = jnp.where(active[:, None, None, None], new, state)
    return o.astype(q.dtype), new
