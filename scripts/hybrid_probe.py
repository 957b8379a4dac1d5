"""Times the pieces of a hybrid stack's two serving steps alone, at
MiniCPM-SALA's geometry (ISSUE 35: XLA or a kernel is decided by a
measurement on the chip that PERF.md records):

    chiprun -- python3 scripts/hybrid_probe.py          # about two chip-minutes

One JSON line a piece, host clock around ``block_until_ready``, best of 5
after a warm-up call; every big array is a jit OPERAND (never closed over).
The prefill step's pieces at ``[8, 256]``: the three FFN products alone (the
yardstick: what the MXU gives this shape), a whole lightning layer and its
chunk-wise product alone (``ops/linear_attention.lightning_chunk``) at the
served precision and with its state products at the default one, a sparse
layer's block scores with the choice (``sparse_block_scores``, ``top_k``) and
its masked attention over blocks of positions (``sparse_chunk_attention``) by
the context it runs to, every row at the same depth; then with the step's
eight rows at eight depths, as the scheduler fills a step (a row a slot, each
at its own ``done``): eighths of the longest and the ``longctx`` cell's own
spread (2,400 to 19,000 positions) beside every row at the longest, the loop
in blocks of 256, 512 and 1,024 positions, with the block-steps it visits (PR
36: a row visits its own blocks only). The decode step's pieces at 16 slots: one recurrence
step over a layer's states, and a sparse layer's attention by the gather of
the chosen 64 blocks (``sparse_decode_attention``) against the bounded dense
read (``decode_attention``) at each rung of 32,768.

``--tiny`` is the CPU rehearsal (never a measurement).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models.registry import get_model
    from gofr_tpu.models.transformer import _ffn_dense
    from gofr_tpu.ops import linear_attention
    from gofr_tpu.ops.attention import (
        chunk_block_counts, decode_attention, decode_read_rungs,
        sparse_block_scores, sparse_chunk_attention, sparse_decode_attention,
    )

    cfg = get_model("sala-tiny" if args.tiny else "minicpm-sala").config
    P, c, S = (2, 16, 3) if args.tiny else (8, 256, 16)
    max_len = 128 if args.tiny else 32768
    D, F, H, KV, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hl, hl = cfg.lin_heads, cfg.lin_head_dim
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform, "kind": device.device_kind}))
    key = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def rnd(*shape, dtype=cfg.dtype, scale=1.0):
        return (jax.random.normal(next(key), shape, jnp.float32) * scale).astype(dtype)

    def timed(name, fn, *operands, **facts):
        run = jax.jit(fn)
        jax.block_until_ready(run(*operands))
        best = 1e9
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*operands))
            best = min(best, time.perf_counter() - t0)
        print(json.dumps({"piece": name, "ms": round(best * 1e3, 3), **facts}),
              flush=True)

    # -- the prefill step's pieces, [P, c] ---------------------------------
    x = rnd(P, c, D)
    ffn = {"w_gate": rnd(D, F, scale=D**-0.5), "w_up": rnd(D, F, scale=D**-0.5),
           "w_down": rnd(F, D, scale=F**-0.5)}
    timed("ffn_three_products", lambda x, w: _ffn_dense(x, w, cfg), x, ffn,
          tflop=round(6 * P * c * D * F / 1e12, 3))
    proj = [rnd(D, Hl * hl, scale=D**-0.5) for _ in range(5)]
    timed("five_4096_projections",
          lambda x, ws: sum(jnp.einsum("pcd,dh->pch", x, w) for w in ws),
          x, proj, tflop=round(10 * P * c * D * Hl * hl / 1e12, 3))
    q, k, v = (rnd(P, c, Hl, hl) for _ in range(3))
    state = rnd(P, Hl, hl, hl, dtype=jnp.float32)
    log_decay = linear_attention.lightning_log_decay(Hl, [16], 32)[0]
    lens = jnp.full((P,), c, jnp.int32)
    timed("lightning_chunk",
          lambda *a: linear_attention.lightning_chunk(*a, hl**-0.5),
          q, k, v, state, log_decay, lens)
    timed("lightning_chunk_default_precision",  # bf16 passes on the state
          lambda *a: linear_attention.lightning_chunk(
              *a, hl**-0.5, precision=None),
          q, k, v, state, log_decay, lens)

    sizes = dict(kernel=cfg.sparse_kernel, stride=cfg.sparse_stride,
                 block=cfg.sparse_block, init_blocks=cfg.sparse_init_blocks,
                 window=cfg.sparse_window, scale=hd**-0.5)
    qs = rnd(P, c, H, hd)
    k_pl, v_pl = rnd(1, S, KV, max_len, hd), rnd(1, S, KV, max_len, hd)
    ck = rnd(P, KV, max_len // cfg.sparse_stride, hd)
    slots = jnp.arange(P, dtype=jnp.int32) % S
    for start in ([32, 96] if args.tiny else [8192, 16384, 30464]):
        starts = jnp.full((P,), start, jnp.int32)
        pos = starts[:, None] + jnp.arange(c)[None, :]

        def choose(qs, ck, pos):
            scores = sparse_block_scores(qs, ck, pos, **sizes)
            top, at = jax.lax.top_k(scores, cfg.sparse_topk)
            b = jnp.arange(scores.shape[-1])
            return (scores > top[..., -1:]) | (
                (scores == top[..., -1:]) & (b <= at[..., -1:])
            )

        timed("sparse_block_scores_and_choice", choose, qs, ck, pos, start=start)
        allowed = jax.jit(choose)(qs, ck, pos)
        timed(
            "sparse_chunk_attention",
            lambda qs, k_pl, v_pl, allowed, starts: sparse_chunk_attention(
                qs, k_pl, v_pl, slots, starts, lens, allowed,
                sel_block=cfg.sparse_block, layer=jnp.int32(0),
            ),
            qs, k_pl, v_pl, allowed, starts, start=start,
            blocks_of_512=-(-(start + c) // 512),
        )

    # the step's rows at eight depths (chunk-aligned), every block allowed
    # but for the causal mask: the loop's cost is its block-steps
    longest = max_len - (2 if args.tiny else 9) * c  # 30,464 of 32,768
    apart = {
        "eighths": np.arange(1, P + 1) * (longest // P),
        "cell": np.linspace(0.079 * longest, 0.624 * longest, P),
        "all_at_longest": np.full((P,), 0.624 * longest),
    }
    for block in ([16, 32] if args.tiny else [256, 512, 1024]):
        for name, depths in apart.items():
            depths = depths.astype(np.int64) // c * c
            counts = chunk_block_counts(depths, np.full((P,), c), block)
            timed(
                "sparse_chunk_attention_rows_apart",
                lambda qs, k_pl, v_pl, starts, block=block: sparse_chunk_attention(
                    qs, k_pl, v_pl, slots, starts, lens, None,
                    sel_block=cfg.sparse_block, layer=jnp.int32(0), block=block,
                ),
                qs, k_pl, v_pl, jnp.asarray(depths, jnp.int32), depths=name,
                block=block, starts=depths.tolist(),
                block_steps=int(counts.sum()),
                rows_x_longest=int(counts.max()) * P,
            )

    # -- the decode step's pieces, S slots ----------------------------------
    q1, k1, v1 = (rnd(S, Hl, hl) for _ in range(3))
    states = rnd(S, Hl, hl, hl, dtype=jnp.float32)
    timed("lightning_step_one_layer",
          lambda *a: linear_attention.lightning_step(
              *a, jnp.ones((S,), bool), hl**-0.5),
          q1, k1, v1, states, log_decay,
          state_mb=round(states.size * 4 / 1e6, 1))
    qd, kn, vn = rnd(S, H, hd), rnd(S, KV, hd), rnd(S, KV, hd)
    chosen = jnp.broadcast_to(
        jnp.arange(cfg.sparse_topk, dtype=jnp.int32), (S, KV, cfg.sparse_topk)
    )
    rungs = decode_read_rungs(max_len)
    for i, rung in enumerate(rungs):
        lengths = jnp.full((S,), rung - 1, jnp.int32)
        timed(
            "sparse_decode_attention_gather",
            lambda qd, k_pl, v_pl, chosen, lengths, kn, vn: sparse_decode_attention(
                qd, k_pl, v_pl, chosen, lengths, kn, vn,
                sel_block=cfg.sparse_block, layer=jnp.int32(0),
            )[0],
            qd, k_pl, v_pl, chosen, lengths, kn, vn, context=rung,
        )
        timed(
            "dense_read_at_rung",
            lambda qd, k_pl, v_pl, lengths, kn, vn, i=i: decode_attention(
                qd, k_pl, v_pl, lengths, k_new=kn, v_new=vn, kernel=False,
                layer=jnp.int32(0), read=jnp.int32(i),
            ),
            qd, k_pl, v_pl, lengths, kn, vn, context=rung,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
