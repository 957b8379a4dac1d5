"""On-chip check that holds the served grouped prefill step to its mathematics
(PERF.md section 6, PR 34; the benchmark's probe sends its requests one at a
time and reaches only the one-row rung, the einsum):

    chiprun -- python3 scripts/moe_chunk_step_check.py

Boots ``mixtral-8x7b`` cut to 4 layers, as ``mixtral-8x7b-d4.batch`` serves it
(weight-only int8, the engine's own leaves), and runs ``transformer_prefill_chunk`` on the same
``[8, 256]`` inputs twice, each form over a cache of its own: once as the
rule picks (``expert_product`` "tiles": the sorted, grouped product), once
with ``sharded=True``, which keeps the einsum. The step is laid out as the
scheduler lays it out: rows 0-4 hold a whole chunk, row 5 a part of one, rows
6 and 7 are padding (duplicates of row 0, ``row_valid`` False). A second step
continues every row from where its first ended, so the attention reads what
the first step wrote. This is where the skipped rows, the padding rows' K/V
writes sent past ``max_len``, the real router and the ``dynamic_slice`` of
the Q8 stack inside the layer scan meet.

Prints, a seed and a step: over the rows that hold tokens, the largest
difference of the two forms' logits beside their mean magnitude, the share of
rows whose first choice agrees, and the difference of the first choice's
log-probability in nats, a row and the median (the quantity whose median the
benchmark's probe limits at 0.08); a layer, the largest difference of the K
and V the two forms wrote at the positions that hold a token, and how many of
those positions hold a value more than 0.25 apart (and the largest difference
among the rest: the two kinds lie far apart). Layer 0 reads the same
input in both forms (0.0) and layer 1 differs by the expert product's rounding
alone; from layer 2 on a few tokens in a thousand differ wholly, because on
seeded random weights a rounding in an earlier layer tips a router's second
choice to another expert. That is the model's, not the product's: the
yardstick, the einsum at ``[8, 256]`` against the einsum a row at a time
(``[1, 256]``, the rung the probe's requests run), has as many. The expert
rows counted a row; whether the slot no row names stayed zero. ``ok`` false
(exit 1): the rule did not pick the tiles, layer 0 differs, a token is off at
layer 1, a later layer has more than twice the yardstick's tokens off (and 8),
the median of the log-probability differences reaches 0.08 nats, a count is
wrong, or the spare slot was written.

``--model moe-tiny --layers 0 --quant "" --chunk 128 --max-len 256`` rehearses
on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from functools import partial

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

NATS_LIMIT = 0.08  # benchmark/harness/probe.py's, on the same quantity
OFF = 0.25  # a cached value this far from the einsum's is no bf16 rounding


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--model", default="mixtral-8x7b")
    parser.add_argument("--layers", type=int, default=4, help="0: the model's own")
    parser.add_argument("--quant", default="int8")
    parser.add_argument("--rows", type=int, default=8)
    parser.add_argument("--chunk", type=int, default=256)
    parser.add_argument("--max-len", type=int, default=2048)
    parser.add_argument("--seeds", default="0,1,2")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models.registry import get_model, register_model
    from gofr_tpu.models.transformer import transformer_prefill_chunk
    from gofr_tpu.ops.kv_cache import KVCache
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    out = lambda **kw: print(json.dumps(kw), flush=True)  # noqa: E731
    R, c = args.rows, args.chunk
    model = args.model
    if args.layers:  # the cut the cell's configuration makes, under its name
        base, model = get_model(model), f"{model}-d{args.layers}"
        register_model(dataclasses.replace(
            base, name=model,
            config=dataclasses.replace(base.config, n_layers=args.layers),
        ))
    engine = InferenceEngine(
        model, tokenizer=ByteTokenizer(), quant=args.quant,
        n_slots=R + 1, max_len=args.max_len, prefill_chunk=c,
    )
    cfg, params = engine.cfg, engine.params
    device = jax.devices()[0]
    out(device=device.platform, kind=device.device_kind, model=model,
        quant=args.quant, step=[R, c],
        product=cfg.expert_product(R * c),
        product_a_row=cfg.expert_product(c),
        product_sharded=cfg.expert_product(R * c, sharded=True))

    def step_fn(**kw):
        return jax.jit(partial(transformer_prefill_chunk, cfg=cfg, **kw))

    tiles, einsum = step_fn(stats=True), step_fn(sharded=True)

    def fresh():
        return KVCache.create(
            cfg.n_cache_entries, R + 1, args.max_len, cfg.n_kv_heads,
            cfg.head_dim, cfg.dtype,
        )

    # The scheduler's layout: slot 0 is no row's; the last two rows are
    # padding, duplicates of row 0 marked invalid; the last valid row holds
    # a part of a chunk.
    held = R - 2
    slots = np.arange(1, R + 1, dtype=np.int32)
    slots[held:] = slots[0]
    row_valid = np.arange(R) < held
    logp = lambda x: np.asarray(jax.nn.log_softmax(jnp.asarray(x), axis=-1))  # noqa: E731

    def against_einsum(name, logits, caches, wrote):
        """One form's logits and cache beside the [R, c] einsum's."""
        want = np.asarray(logits["einsum"].astype(jnp.float32))[:held]
        got = np.asarray(logits[name].astype(jnp.float32))[:held]
        first = want.argmax(-1)[:, None]
        nats = np.abs(
            np.take_along_axis(logp(got), first, 1)
            - np.take_along_axis(logp(want), first, 1)
        )[:, 0]
        line = dict(
            logits_max_abs_diff=float(np.max(np.abs(got - want))),
            logits_mean_abs=float(np.mean(np.abs(want))),
            first_choice_agrees=float(np.mean(got.argmax(-1) == first[:, 0])),
            first_choice_logprob_diff_nats_median=float(np.median(nats)),
            first_choice_logprob_diff_nats_a_row=[round(float(n), 5) for n in nats],
            spare_slot_zero=True,
        )
        for plane in ("k", "v"):
            a = np.asarray(getattr(caches[name], plane).astype(jnp.float32))
            b = np.asarray(getattr(caches["einsum"], plane).astype(jnp.float32))
            diff = np.abs(a - b)[:, 1:held + 1]  # [L, held, KV, max_len, hd]
            diff = np.where(wrote[None, :, None, :, None], diff, 0.0)
            line[f"{plane}_max_abs_diff_a_layer"] = diff.max(axis=(1, 2, 3, 4)).tolist()
            token = diff.max(axis=(2, 4))  # [L, held, max_len]
            line[f"{plane}_tokens_off_a_layer"] = (token > OFF).sum(axis=(1, 2)).tolist()
            line[f"{plane}_max_abs_diff_of_the_rest_a_layer"] = np.where(
                token > OFF, 0.0, token
            ).max(axis=(1, 2)).tolist()
            line["spare_slot_zero"] &= not np.any(a[:, 0])
        return line

    ok = cfg.expert_product(R * c) == "tiles"
    for seed in map(int, args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        starts = np.zeros((R,), np.int32)
        caches = {"tiles": fresh(), "einsum": fresh(), "einsum_a_row": fresh()}
        for step in range(2):
            tokens = rng.integers(3, cfg.vocab_size, (R, c)).astype(np.int32)
            lens = np.full((R,), c, np.int32)
            if step == 0:
                lens[held - 1] = max(1, c * 2 // 5)
            tokens[held:], lens[held:], starts[held:] = tokens[0], lens[0], starts[0]
            ops = tuple(map(jnp.asarray, (tokens, slots, starts, lens)))
            logits = {}
            logits["tiles"], caches["tiles"], (routes, load_ratio) = tiles(
                params, ops[0], caches["tiles"], *ops[1:],
                row_valid=jnp.asarray(row_valid),
            )
            logits["einsum"], caches["einsum"] = einsum(
                params, ops[0], caches["einsum"], *ops[1:]
            )
            rows = []
            for r in range(held):  # the yardstick: the einsum at [1, c]
                one = tuple(a[r:r + 1] for a in ops)
                row, caches["einsum_a_row"] = einsum(
                    params, one[0], caches["einsum_a_row"], *one[1:]
                )
                rows.append(row[0])
            logits["einsum_a_row"] = jnp.stack(rows)

            # positions that hold a token, a slot of a valid row
            ends = starts[:held] + lens[:held]
            at = np.arange(args.max_len)[None, :]
            wrote = (at >= starts[:held, None]) & (at < ends[:, None])
            got, yard = (
                against_einsum(name, logits, caches, wrote)
                for name in ("tiles", "einsum_a_row")
            )
            counted = np.asarray(routes).astype(np.int64).tolist()
            expected = (
                lens * row_valid * cfg.n_experts_active * cfg.n_moe_layers
            ).tolist()
            for plane in ("k", "v"):
                off, yard_off = (
                    line[f"{plane}_tokens_off_a_layer"] for line in (got, yard)
                )
                ok &= got[f"{plane}_max_abs_diff_a_layer"][0] == 0.0
                ok &= not any(off[:2])  # one expert layer in: rounding only
                ok &= all(n <= 2 * m + 8 for n, m in zip(off, yard_off))
            ok &= got["spare_slot_zero"] and counted == expected
            ok &= got["first_choice_logprob_diff_nats_median"] < NATS_LIMIT
            common = dict(seed=seed, step=step, rows_with_tokens=held,
                          tokens=int(lens[:held].sum()))
            out(what="tiles_against_einsum", **common, **got)
            out(what="einsum_a_row_against_einsum", **common, **yard)
            out(what="tiles_counts", **common, routes_a_row=counted,
                expected=expected, load_ratio=float(load_ratio))
            starts[:held] = ends
    out(ok=bool(ok))
    engine.close()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
