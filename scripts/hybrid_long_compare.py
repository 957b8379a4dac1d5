"""A long prompt through the hybrid stack's own serving functions, against the
plain reference's full forward pass (ISSUE 35, part 8).

The benchmark's probe is 9,216 + 8 tokens; the cell's prompts run to 30,720.
So, outside ``benchmark/``:

    chiprun --timeout 3000 -- python3 scripts/hybrid_long_compare.py

builds the configuration ``benchmark/configs/minicpm-sala-d16.json`` names
(seeded random weights), sends a 24,576-token prompt through
``transformer_prefill_chunk`` in [1, 256] chunks into a ``HybridCache`` of
32,768 positions (the first 32 chunks dense, the other 64 through the choice
of blocks; 96 chunk-wise updates of the 12 lightning states), then 16
teacher-forced tokens through ``transformer_decode_step`` (the body of the
decode window: the gather of the chosen blocks, one step of the recurrence),
and compares the 17 rows of logits (the prompt's last position and the 16
decode steps) with ``benchmark/reference/hybrid_sparse_linear.py``'s float32
full forward over the whole 24,592-token sequence, as log-probabilities over
the vocabulary.

The tolerance and its reason: the program computes in bfloat16 (weights,
activations, K, V, compressed keys; float32 accumulation, softmax, state and
decay), the reference in float32 at highest precision on the same weights. A
row reads ``median over the vocabulary of |log p_program - log p_reference|``;
the comparison passes when the MEDIAN of the 17 rows is at most
``--tolerance`` (0.056) and the worst row at most five times that. On the v5e
(PERF.md section 6, PR 35) the median row read 0.0400 and the worst 0.0606,
13 of 17 top tokens the same: what bfloat16 arithmetic leaves of a float32
forward through 16 layers at 24,592 positions (the reference itself with one
bfloat16 pass a matmul reads 0.0393 against itself in float32). The limit
lies between two readings, with 1.4 times of room on either side: the served
program's, and the reference in the next precision down (int8 matmul weights
by ``ops/quant.quantize_array``) against itself in float32, 0.0800, which
must FAIL it, as must the reference with each of its candidate pieces removed
(the least, ``causal``, reads 0.0640: a query past the dense length sees of
the future only the rest of its own block; the others 0.37 to 0.93, and
``logit_scale`` 57). One control decides nothing: the reference with one
bfloat16 pass a matmul, which reads what the served program reads.

``--model sala-tiny --prompt 96 --max-len 128 --chunk 16`` is the CPU
rehearsal (never a measurement).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default=os.path.join(
        CHECKOUT, "benchmark", "configs", "minicpm-sala-d16.json"))
    parser.add_argument("--model", default="",
                        help="a registry entry as it is, in place of --config")
    parser.add_argument("--prompt", type=int, default=24576)
    parser.add_argument("--decode", type=int, default=16)
    parser.add_argument("--max-len", type=int, default=32768)
    parser.add_argument("--chunk", type=int, default=256)
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--seed", type=int, default=35)
    parser.add_argument("--tolerance", type=float, default=0.056)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.cells import load_file
    from gofr_tpu.models.registry import get_model
    from gofr_tpu.models.transformer import (
        init_transformer, transformer_decode_step, transformer_prefill_chunk,
    )
    from gofr_tpu.ops.kv_cache import HybridCache
    from gofr_tpu.ops.quant import quantize_array

    if args.model:
        cfg = get_model(args.model).config
    else:
        with open(args.config) as fh:
            config = json.load(fh)
        cfg = dataclasses.replace(
            get_model(config["base"]).config, **config["overrides"]
        )
    reference = load_file("hybrid_reference", os.path.join(
        CHECKOUT, "benchmark", "reference", "hybrid_sparse_linear.py"))
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform, "kind": device.device_kind}))

    t0 = time.time()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(args.seed)
    n, c = args.prompt, args.chunk
    tokens = rng.integers(3, cfg.vocab_size, size=n + args.decode)
    cache = HybridCache.for_config(cfg, args.slots, args.max_len)
    slot = 1
    chunk = jax.jit(
        lambda p, tok, cache, start, length: transformer_prefill_chunk(
            p, tok, cache, jnp.full((1,), slot, jnp.int32), start[None],
            length[None], cfg,
        ), donate_argnums=(2,),
    )
    step = jax.jit(
        lambda p, tok, cache, active: transformer_decode_step(
            p, tok, cache, active, cfg
        ), donate_argnums=(2,),
    )
    rows = []
    for start in range(0, n, c):
        length = min(c, n - start)
        tok = np.zeros((1, c), np.int32)
        tok[0, :length] = tokens[start:start + length]
        logits, cache = chunk(
            params, jnp.asarray(tok), cache, jnp.int32(start), jnp.int32(length)
        )
    rows.append(np.asarray(logits[0]))
    cache = cache._replace(lengths=cache.lengths.at[slot].set(n))
    active = jnp.zeros((args.slots,), bool).at[slot].set(True)
    for t in range(n, n + args.decode):
        tok = jnp.zeros((args.slots,), jnp.int32).at[slot].set(int(tokens[t]))
        logits, cache = step(params, tok, cache, active)
        rows.append(np.asarray(logits[slot]))
    served = jax.nn.log_softmax(jnp.asarray(np.stack(rows)), axis=-1)
    print(json.dumps({"served_s": round(time.time() - t0, 1),
                      "cached": int(cache.lengths[slot])}))

    shape = reference.shape_of(cfg)
    last = args.decode + 1

    def reading(got: Any, want: Any) -> dict:
        """Two [rows, vocab] sets of log-probabilities, a row at a time."""
        diff = jnp.abs(got - want)
        per_row = jnp.median(diff, axis=-1)
        return {"worst_row_median": float(jnp.max(per_row)),
                "median_row_median": float(jnp.median(per_row)),
                "worst_value": float(jnp.max(diff)),
                "same_top_token": int(jnp.sum(
                    jnp.argmax(got, -1) == jnp.argmax(want, -1)))}

    def within(found: dict) -> bool:
        return (found["median_row_median"] <= args.tolerance
                and found["worst_row_median"] <= 5 * args.tolerance)

    def reference_rows(ablate: str = "", **kw: Any) -> Any:
        return jax.nn.log_softmax(reference.full_logits(
            params, shape, tokens[None, :], ablate, last=last, **kw
        )[0], axis=-1)

    exact = reference_rows()
    plain = reading(served, exact)
    print(json.dumps({"compared": "", **plain, "tolerance": args.tolerance}))
    ok = within(plain)
    for ablate in reference.CANDIDATES:
        found = reading(served, reference_rows(ablate))
        fails = not within(found)
        ok = ok and fails
        print(json.dumps({"compared": ablate, **found, "fails": fails}))

    def control(name: str, **kw: Any) -> bool:
        """The REFERENCE computed in a lower precision against itself at
        float32, by this script's limits."""
        found = reading(reference_rows(**kw), exact)
        print(json.dumps({
            "control": name, **found, "passes_this_comparison": within(found),
        }))
        return within(found)

    # float32 weights and activations, one bfloat16 pass a matmul: what the
    # chip does with a float32 product unless told otherwise
    # (decides nothing)
    control("reference_bfloat16_matmuls", precision="bfloat16")
    # the nearest precision below the configuration's: int8 matmul weights by
    # the repository's own rule (ops/quant.quantize_array: absmax over the
    # contraction axis; router, embedding and norms kept), float32 arithmetic
    fake_int8 = jax.jit(
        lambda w: (lambda q: q.q.astype(jnp.float32) * q.s)(
            quantize_array(w)).astype(w.dtype),
        donate_argnums=0,
    )
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: fake_int8(w) if str(
            getattr(path[-1], "key", "")
        ).startswith(("w", "lm_head")) else w,
        params,
    )
    # ... and it must FAIL: the comparison tells bfloat16 from int8
    ok = not control("reference_int8_weights") and ok
    print(json.dumps({"ok": bool(ok), "seconds": round(time.time() - t0, 1)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
