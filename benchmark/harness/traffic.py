"""The one general traffic generator: a mix file's parameters and a seed
in, the requests of a run out.

A mix (``benchmark/traffic/<mix>.json``) names its ``kind``
(``benchmark/traffic_kinds/<kind>.py``: who sends when) and gives the
lengths every kind draws the same way:

    "prompt_tokens": {"median": 256, "sigma": 0.8, "min": 32, "max": 1024}
    "output_tokens": {"median": 96,  "sigma": 0.6, "min": 16, "max": 384}
    "temperature": 0.7
    "shared_prefix": {"groups": 8, "tokens": 512, "share": 0.8}   (optional)
    "pool_seed": 1

The (prompt, output) lengths and their order come from ``pool_seed``, which
is part of the mix; ``--seed`` draws the token ids, the sampling seeds and
which requests share which prefix. So every seed offers the same sizes at the
same times with other contents, and two seeds differ by no more than two runs
of one seed: a seed that reordered the work would move a tail by which long
prompt met which burst, and the check would read that as noise. Another
order of the same work is another mix file with another ``pool_seed``.
"""

from __future__ import annotations

import dataclasses
import math
import random
from typing import Any, Optional

# The byte tokenizer's BOS/EOS/PAD, and the low ids registry models use
# for EOS: never put in a prompt.
RESERVED = (256, 257, 258)
FIRST_TOKEN = 3


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: tuple[int, ...]
    max_tokens: int
    temperature: float
    seed: int

    def body(self) -> dict:
        return {
            "prompt": list(self.prompt), "max_tokens": self.max_tokens,
            "temperature": self.temperature, "seed": self.seed,
            "stream": True, "stream_options": {"include_tokens": True},
        }


def clipped_lognormal(rng: random.Random, spec: dict) -> int:
    """One draw of exp(N(ln median, sigma)), clipped to [min, max]."""
    value = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    return int(min(max(round(value), spec["min"]), spec["max"]))


def length_pool(mix: dict, n: int) -> list[tuple[int, int]]:
    """The mix's own ``n`` (prompt, output) lengths: a function of the mix
    and ``n`` alone."""
    rng = random.Random(mix.get("pool_seed", 0))
    return [
        (clipped_lognormal(rng, mix["prompt_tokens"]),
         clipped_lognormal(rng, mix["output_tokens"]))
        for _ in range(n)
    ]


def token_ids(rng: random.Random, n: int, vocab: int) -> list[int]:
    ids: list[int] = []
    while len(ids) < n:
        token = rng.randrange(FIRST_TOKEN, vocab)
        if token not in RESERVED:
            ids.append(token)
    return ids


def requests_for(mix: dict, n: int, seed: int, vocab: int) -> list[Request]:
    """``n`` requests: the pool's lengths in the pool's order, with this
    seed's token ids, sampling seeds and (where the mix shares prefixes)
    this seed's prefixes."""
    rng = random.Random(seed)
    pool = length_pool(mix, n)
    shared: Optional[dict[str, Any]] = mix.get("shared_prefix")
    prefixes = [
        token_ids(rng, shared["tokens"], vocab)
        for _ in range(shared["groups"])
    ] if shared else []
    out = []
    for index, (n_prompt, n_out) in enumerate(pool):
        head: list[int] = []
        if shared and rng.random() < shared["share"]:
            head = prefixes[rng.randrange(len(prefixes))][:n_prompt - 1]
        prompt = head + token_ids(rng, n_prompt - len(head), vocab)
        out.append(Request(
            index=index, prompt=tuple(prompt), max_tokens=n_out,
            temperature=float(mix.get("temperature", 0.0)),
            seed=rng.randrange(2**31),
        ))
    return out


def warmup_requests(mix: dict, n: int, seed: int, vocab: int) -> list[Request]:
    """Warm-up: the cell's own shapes and no others. One request at the
    mix's longest prompt (every prefill chunk position), the rest as the
    mix draws them, each long enough for several decode windows."""
    drawn = requests_for(mix, n, seed, vocab)
    longest = token_ids(
        random.Random(seed), mix["prompt_tokens"]["max"], vocab
    )
    return [
        dataclasses.replace(
            r, prompt=tuple(longest) if i == 0 else r.prompt,
            max_tokens=min(r.max_tokens, 40),
        )
        for i, r in enumerate(drawn)
    ]


def describe(requests: list[Request]) -> dict:
    """The lengths drawn, for the line the run prints before its result."""
    def spread(values: list[int]) -> dict:
        ordered = sorted(values)
        return {
            "min": ordered[0], "median": ordered[len(ordered) // 2],
            "max": ordered[-1], "sum": sum(ordered),
        }
    return {
        "requests": len(requests),
        "prompt_tokens": spread([len(r.prompt) for r in requests]),
        "output_tokens": spread([r.max_tokens for r in requests]),
    }
