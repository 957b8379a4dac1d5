"""Serving soak: continuous mixed load across the full feature matrix.

One engine (paged KV + penalties + top_logprobs + multi-LoRA +
sliding window) takes wave after wave of requests churning
seeds, penalties, logit_bias, top_logprobs, stop sequences, adapters,
and mid-flight cancellations, with adapters loaded/unloaded between
waves. After every wave the engine must return to VERIFIED IDLE: all
slots free, every paged KV block back in the pool, no pending queue,
futures all resolved. Exit code 1 on any invariant break.

Usage: [SOAK_SECONDS=300] python scripts/soak.py
(CPU by default — set nothing; on a live chip prefix with the usual
env. The r4 close-out ran 600 s ≈ 27k requests with zero leaks.)
"""

from __future__ import annotations

import json
import os
import random
import resource
import sys
import time
from concurrent.futures import CancelledError

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    import jax

    from gofr_tpu.models.registry import get_model
    from gofr_tpu.models.transformer import lora_dims
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    import dataclasses

    from gofr_tpu.models.registry import ModelSpec, register_model

    seconds = float(os.environ.get("SOAK_SECONDS", "300"))
    # llama-tiny with an ACTIVE sliding window (32 < max_len 256): the
    # claimed feature matrix includes window masking, and in particular
    # the paged+window decode combination (kv_block below) — llama-tiny
    # itself has sliding_window=0 and would never exercise it.
    tiny = get_model("llama-tiny")
    cfg = dataclasses.replace(tiny.config, sliding_window=32)
    register_model(dataclasses.replace(tiny, name="soak-swa-tiny", config=cfg))
    eng = InferenceEngine(
        "soak-swa-tiny", n_slots=8, max_len=256, window_k=4,
        enable_penalties=True, top_logprobs=2, kv_block=32,
        tokenizer=ByteTokenizer(), lora_slots=2, lora_rank=4,
    )
    eng.start_sync()
    rng = random.Random(0)

    def rand_adapter(seed: int) -> dict:
        leaves = {}
        for ti, t in enumerate(("wq", "wv")):
            d_in, d_out = lora_dims(cfg, t)
            k1, k2 = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(seed), ti)
            )
            leaves[t] = (
                0.3 * jax.random.normal(k1, (cfg.n_layers, d_in, 4)),
                0.3 * jax.random.normal(k2, (cfg.n_layers, 4, d_out)),
            )
        return leaves

    eng.load_lora("a", rand_adapter(1))
    free_blocks_full = len(eng._free_blocks)
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    waves = requests = cancels = errors = adapter_races = 0
    t_end = time.time() + seconds
    # Compile-cache growth tripwire (r4 VERDICT weak #9 → next #6): the
    # program-variant caches are BOUNDED by construction — the only
    # static compile switches are use_bias (2 variants per program) and
    # the engine-level feature flags; penalties/seeds/top_logprobs ride
    # as dynamic operands. Measured: 12 churn waves hold jit cache sizes
    # at two a program with RSS flat at 454 MB. The r4 soak's
    # 0.27→0.52 GB was first-touch compile warmup, not monotonic growth.
    # This assertion makes any regression (a new static arg minting
    # per-request variants) fail the soak loudly: peak RSS after the
    # warmup third must not grow more than SOAK_RSS_CEILING_MB.
    warmup_until = time.time() + seconds / 3
    rss_warm = None
    rss_ceiling_mb = float(os.environ.get("SOAK_RSS_CEILING_MB", "192"))
    try:
        while time.time() < t_end:
            reqs = []
            for i in range(rng.randint(8, 16)):
                kw: dict = {
                    "max_new_tokens": rng.choice([4, 9, 17, 30]),
                    "temperature": rng.choice([0.0, 0.0, 0.9]),
                    "stop_on_eos": False,
                }
                adapters = [""] + eng.lora_names()
                kw["adapter"] = rng.choice(adapters)
                if rng.random() < 0.3:
                    kw["seed"] = rng.randint(0, 2**31 - 1)
                if rng.random() < 0.3:
                    kw["frequency_penalty"] = 1.0
                if rng.random() < 0.2:
                    kw["logit_bias"] = {rng.randint(0, 511): -100}
                if rng.random() < 0.3:
                    kw["top_logprobs"] = 2
                if rng.random() < 0.2:
                    kw["stop"] = [chr(97 + rng.randint(0, 25))]
                reqs.append(eng.submit_generate(f"wave {waves} req {i}", **kw))
                requests += 1
            # Adapter churn WHILE the wave's requests are live — this is
            # the load_lora/unload_lora "safe while serving" path the
            # soak exists to exercise (an idle-time swap would prove
            # nothing).
            if waves % 8 == 3:
                eng.load_lora("b", rand_adapter(100 + waves))
            elif waves % 8 == 7 and "b" in eng.lora_names():
                eng.unload_lora("b")
            # Cancel ~20% mid-flight (future.cancel() is the public
            # cancellation seam; False = already finished).
            for r in reqs:
                if rng.random() < 0.2 and r.future.cancel():
                    cancels += 1
            for r in reqs:
                try:
                    r.future.result(timeout=180)
                except CancelledError:
                    pass
                except RuntimeError as exc:
                    if "LoRA adapter" in str(exc):
                        # Designed outcome: churn invalidated a queued/
                        # in-flight adapter request (a completion must
                        # never mix weight sets) — count, don't fail.
                        adapter_races += 1
                    else:
                        errors += 1
                        print(f"wave {waves}: request failed: {exc!r}")
                except Exception as exc:  # noqa: BLE001
                    # A real request failure is exactly what the soak
                    # must surface, not swallow.
                    errors += 1
                    print(f"wave {waves}: request failed: {exc!r}")
            # Verified idle.
            deadline = time.time() + 30
            while time.time() < deadline:
                if (
                    all(s is None for s in eng._slots)
                    and not eng._prefilling
                    and eng._pending.empty()
                    and len(eng._free_blocks) == free_blocks_full
                ):
                    break
                time.sleep(0.05)
            else:
                print(json.dumps({
                    "soak": "FAIL", "wave": waves,
                    "slots_busy": sum(
                        1 for s in eng._slots if s is not None
                    ),
                    "blocks_leaked": free_blocks_full - len(eng._free_blocks),
                }))
                return 1
            waves += 1
            if rss_warm is None and time.time() >= warmup_until:
                rss_warm = resource.getrusage(
                    resource.RUSAGE_SELF
                ).ru_maxrss
    finally:
        eng.stop_sync()
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    rss_flat = True
    if rss_warm is not None:
        grew_mb = (rss1 - rss_warm) / 1024
        rss_flat = grew_mb <= rss_ceiling_mb
        if not rss_flat:
            print(f"RSS grew {grew_mb:.0f} MB past the post-warmup "
                  f"ceiling ({rss_ceiling_mb:.0f} MB) — a compile-cache "
                  f"or buffer leak regression")
    print(json.dumps({
        "soak": "OK" if errors == 0 and rss_flat else "FAIL",
        "seconds": seconds, "waves": waves,
        "requests": requests, "cancels": cancels, "errors": errors,
        "adapter_races": adapter_races,
        "rss_mb_start_to_peak": [round(rss0 / 1024), round(rss1 / 1024)],
        "rss_post_warmup_flat": rss_flat,
    }))
    return 0 if errors == 0 and rss_flat else 1


if __name__ == "__main__":
    sys.exit(main())
