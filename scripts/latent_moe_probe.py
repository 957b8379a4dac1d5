"""On-chip measurements behind PR 33's choices (PERF.md section 6):

    chiprun -- python3 scripts/latent_moe_probe.py

at the sizes of ``openpangu-ultra-moe-718b-ep16`` (seeded random weights,
32 slots of 8,192 positions):

* the prefill chunk step ``[8, 256]`` and ``[1, 256]`` at several prefix
  lengths, every row at the same one; then the ``[8, 256]`` step with its
  eight rows at eight depths, as the scheduler fills a step (a row a slot,
  each at its own ``done``): eighths of the longest, and the ``longdoc``
  cell's own spread (700 to 5,700 positions), beside every row at the
  longest, with the block-steps the attention's loop visits (PR 36: a row
  visits its own blocks only);
* ``latent_chunk_attention`` alone at those depths, its loop in blocks of
  256, 512 and 1,024 positions;
* one decode step with every slot live at several lengths: the bounded
  read's rungs at 8,192 positions;
* one expert layer's held part by the sorted, grouped product
  (``jax.lax.ragged_dot``) against the dense einsum over the held experts,
  at a prefill step's 2,048 rows and a decode step's 32: ROADMAP S5's
  measurement;
* the three ``ragged_dot`` s of that product alone over a sorted buffer of
  ``rows x 8`` rows of which none, a sixteenth, a half and all belong to a
  held expert: whether the product's cost follows the routes that land here
  or the buffer.

Times are host clock around ``block_until_ready``, the best of three after a
warm-up call. ``--model mla-moe-tiny --slots 4 --max-len 256 --chunk 32``
rehearses on the CPU (never a measurement).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default=os.path.join(
        CHECKOUT, "benchmark", "configs", "openpangu-ultra-moe-718b-ep16.json"))
    parser.add_argument("--model", default="")
    parser.add_argument("--slots", type=int, default=32)
    parser.add_argument("--max-len", type=int, default=8192)
    parser.add_argument("--chunk", type=int, default=256)
    parser.add_argument("--only", default="", help="of prefill, attention, decode, experts: one, or several with commas")
    args = parser.parse_args()
    only = {name for name in args.only.split(",") if name}
    asked = lambda name: not only or name in only  # noqa: E731

    import jax
    import jax.numpy as jnp

    import numpy as np

    from gofr_tpu.models import transformer as T
    from gofr_tpu.models.registry import get_model
    from gofr_tpu.ops.attention import (
        LATENT_CHUNK_BLOCK, chunk_block_counts, latent_chunk_attention,
    )
    from gofr_tpu.ops.kv_cache import LatentKVCache

    if args.model:
        cfg = get_model(args.model).config
    else:
        with open(args.config) as fh:
            config = json.load(fh)
        cfg = dataclasses.replace(
            get_model(config["base"]).config, **config["overrides"]
        )
    device = jax.devices()[0]
    out = lambda **kw: print(json.dumps(kw), flush=True)  # noqa: E731
    out(device=device.platform, kind=device.device_kind)
    S, ML, c = args.slots, args.max_len, args.chunk
    # the attention alone needs no weights but its own two (a boot draws
    # 9.84 GB in a minute or two)
    params = (T.init_transformer(jax.random.PRNGKey(0), cfg)
              if only != {"attention"} else None)
    cache = LatentKVCache.create(
        cfg.n_cache_entries, S, ML, cfg.cache_row, cfg.dtype
    )
    key = jax.random.PRNGKey(1)

    def best(fn, *a):
        jax.block_until_ready(fn(*a))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return min(times) * 1e3

    plane = [cache.k]  # donated to every step and handed back, as served

    def best_on_cache(fn, *a):
        def call():
            result, plane[0] = fn(*a[:2], plane[0], *a[2:])
            return result

        return best(call)

    # 1. the prefill chunk step
    def mixed_depths(longest):
        """[8] chunk-aligned starts: eighths of the longest, the cell's
        own spread (an eighth to three quarters of it, as ``longdoc``'s 700
        to 5,700 of 7,680), and every row at the longest."""
        eighths = np.arange(1, 9) * (longest // 8)
        spread = np.linspace(longest * 0.09, longest * 0.74, 8)
        return {
            "eighths": eighths // c * c,
            "cell": spread.astype(np.int64) // c * c,
            "all_at_longest": np.full((8,), int(spread[-1]) // c * c),
        }

    def block_steps(depths, block):
        counts = chunk_block_counts(
            np.asarray(depths), np.full((len(depths),), c), block)
        return {"block_steps": int(counts.sum()),
                "rows_x_longest": int(counts.max()) * len(counts)}

    starts = sorted({0, ML // 4, ML // 2, ML - 2 * c})
    if asked("prefill"):
        for rows in (8, 1):

            def step(p, tok, k, starts, rows=rows):
                logits, cache = T.transformer_prefill_chunk(
                    p, tok, LatentKVCache(k, jnp.zeros((S,), jnp.int32)),
                    jnp.arange(rows, dtype=jnp.int32), starts,
                    jnp.full((rows,), c, jnp.int32), cfg,
                )
                return logits, cache.k

            step = jax.jit(step, donate_argnums=(2,))
            tok = jax.random.randint(key, (rows, c), 3, cfg.vocab_size)
            for start in starts:
                out(what="prefill_chunk_step", rows=rows, start=start,
                    ms=best_on_cache(
                        step, params, tok, jnp.full((rows,), start, jnp.int32)))
            for name, depths in mixed_depths(ML - 2 * c).items() if rows == 8 else ():
                out(what="prefill_chunk_step_rows_apart", depths=name,
                    starts=depths.tolist(),
                    **block_steps(depths, min(LATENT_CHUNK_BLOCK, ML)),
                    ms=best_on_cache(
                        step, params, tok, jnp.asarray(depths, jnp.int32)))

    # 1b. the attention alone, its loop in blocks of 128, 256, 512 and 1,024
    # positions
    if asked("attention"):
        H, C = cfg.n_heads, cfg.kv_lora_rank
        ks = jax.random.split(key, 3)
        q = jax.random.normal(
            ks[0], (8, c, H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), cfg.dtype)
        w_uk = (jax.random.normal(ks[1], (C, H, cfg.qk_nope_head_dim)) * C**-0.5
                ).astype(cfg.dtype)
        w_uv = (jax.random.normal(ks[2], (C, H, cfg.v_head_dim)) * C**-0.5
                ).astype(cfg.dtype)
        for block in sorted({min(b, ML) for b in (128, 256, 512, 1024)}):
            attend = jax.jit(lambda q, k, starts, w_uk, w_uv, block=block:
                             latent_chunk_attention(
                                 q, k, jnp.arange(8, dtype=jnp.int32), starts,
                                 jnp.full((8,), c, jnp.int32), w_uk, w_uv,
                                 scale=cfg.head_dim**-0.5, layer=jnp.int32(1),
                                 block=block))
            for name, depths in mixed_depths(ML - 2 * c).items():
                out(what="latent_chunk_attention", block=block, depths=name,
                    **block_steps(depths, block),
                    ms=best(attend, q, plane[0],
                            jnp.asarray(depths, jnp.int32), w_uk, w_uv))

    # 2. one decode step, every slot live
    def dec(p, tok, k, lengths):
        logits, cache = T.transformer_decode_step(
            p, tok, LatentKVCache(k, lengths), jnp.ones((S,), bool), cfg
        )
        return logits, cache.k

    dec = jax.jit(dec, donate_argnums=(2,))
    tok = jax.random.randint(key, (S,), 3, cfg.vocab_size)
    lengths = sorted({ML // 8, 3 * ML // 8, 5 * ML // 8, ML - 100})
    for length in lengths if asked("decode") else ():
        out(what="decode_step", length=length,
            ms=best_on_cache(dec, params, tok,
                             jnp.full((S,), length, jnp.int32)))

    # 3. the held experts' part of one expert layer (the weights are
    # operands: closed over, they would be compiled in as constants)
    if params is None:
        return 0
    lp = {"router": params["layers"]["router"][0], **params["experts"][0]}
    lo, hi = cfg.held_range

    def grouped(x, lp):
        idx, gates = T.moe_route(x, lp["router"], cfg)
        return T.moe_grouped_experts(x, idx, gates, [lp], cfg)[0]

    def einsum(x, lp):
        idx, gates = T.moe_route(x, lp["router"], cfg)
        held = (idx >= lo) & (idx < hi)
        w = jnp.zeros((x.shape[0], hi - lo + 1), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], jnp.where(held, idx - lo, hi - lo)
        ].add(jnp.where(held, gates, 0.0))[:, : hi - lo]
        h = jax.nn.silu(jnp.einsum("td,edf->tef", x, lp["w_gate"])) * (
            jnp.einsum("td,edf->tef", x, lp["w_up"])
        )
        y = jnp.einsum("tef,efd->ted", h, lp["w_down"])
        return jnp.einsum("ted,te->td", y, w.astype(x.dtype))

    grouped, einsum = jax.jit(grouped), jax.jit(einsum)
    for n_rows in (8 * c, S) if asked("experts") else ():
        x = jax.random.normal(key, (n_rows, cfg.d_model), cfg.dtype)
        a, b = grouped(x, lp), einsum(x, lp)
        out(what="held_experts", rows=n_rows,
            grouped_ms=best(grouped, x, lp), einsum_ms=best(einsum, x, lp),
            max_abs_diff=float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            mean_abs=float(jnp.mean(jnp.abs(b.astype(jnp.float32)))))
    # 4. the product alone, by how much of the sorted buffer is held routes
    def product(rows, w, sizes):
        hidden = jax.nn.silu(
            jax.lax.ragged_dot(rows, w["w_gate"], sizes)
        ) * jax.lax.ragged_dot(rows, w["w_up"], sizes)
        return jax.lax.ragged_dot(hidden, w["w_down"], sizes)

    product = jax.jit(product)
    n_held, k = hi - lo, cfg.n_experts_active
    for n_rows in (8 * c, S) if asked("experts") else ():
        M = n_rows * k
        rows = jax.random.normal(key, (M, cfg.d_model), cfg.dtype)
        for held_routes in (0, M // 16, M // 2, M):
            sizes = jnp.full((n_held,), held_routes // n_held, jnp.int32)
            out(what="ragged_product", buffer_rows=M,
                held_routes=int(sizes.sum()),
                ms=best(product, rows, params["experts"][0], sizes))
    stats = device.memory_stats() or {}
    out(peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
