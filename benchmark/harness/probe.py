"""The correctness probe: a few greedy requests through the served path,
compared with the plain reference as log-probabilities.

After the window the parent sends ``PROBES`` seeded greedy requests
(``PROMPT_TOKENS`` in, ``NEW_TOKENS`` out, ``logprobs: true``) through
``/v1/completions`` — prefill, then decoding through the cache and the
decode window — and posts prompt + emitted tokens to ``/bench/reference``,
which returns the teacher-forced log-probability of each emitted token
under the plain reference that the configuration's file names.
Log-probabilities and not tokens: with random weights the largest logit
changes on rounding. Which pieces the comparison must be shown to catch is
the reference module's to say (its ``ABLATIONS``, asked for over ``GET
/bench/reference``): nothing here names one.
"""

from __future__ import annotations

import json
import random

from benchmark.harness.server import Server, check
from benchmark.harness.stats import percentile
from benchmark.harness.traffic import token_ids

PROBES = 4
PROMPT_TOKENS = 96
NEW_TOKENS = 8

# |served - reference| per emitted token's log-probability, in nats.
# The served path computes in bfloat16 activations (8 bits of mantissa)
# through every layer, the dense or kernel attention path and a bfloat16
# cache; the reference computes float32 at highest precision on the same
# int8 weights. On the v5e (PR 24, my chip runs; PERF.md section 6) the
# dense model's worst token of 32 differed by 0.05. A mixture of experts is
# not continuous: where a token's second and third router probabilities are
# nearly equal, bfloat16 rounding picks another expert than float32 does,
# and that token (and, through its keys and values, the tokens after it)
# moves by a nat or more while the mathematics is right. So agreement is
# judged over the probe's tokens, not on the worst one:
#   * their median difference is at most MEDIAN_TOLERANCE, and
#   * at least AGREEING_SHARE of them are within TOKEN_TOLERANCE;
# and the comparison proves its own teeth in every run: the reference with
# each of its pieces removed in turn (the causal mask, the binding window,
# one expert, for the shared decoder) must fail the same two conditions.
# A piece whose removal changes nothing at the probe's length (a window
# longer than the probe) is skipped; a probe in which every piece was
# skipped has shown no teeth and does not agree.
TOKEN_TOLERANCE = 0.25
MEDIAN_TOLERANCE = 0.08
AGREEING_SHARE = 0.75


def differences(served: list, reference: list) -> list:
    return [abs(a - b) for s, r in zip(served, reference) for a, b in zip(s, r)]


def summary(diffs: list) -> dict:
    return {
        "median": percentile(diffs, 50), "max": max(diffs),
        "share_within_token_tolerance":
            sum(d <= TOKEN_TOLERANCE for d in diffs) / len(diffs),
    }


def agrees(diffs: list) -> bool:
    found = summary(diffs)
    return (found["median"] <= MEDIAN_TOLERANCE
            and found["share_within_token_tolerance"] >= AGREEING_SHARE)


def probe_reference(server: Server, config: dict, seed: int, vocab: int) -> dict:
    sizes = config.get("probe", {})
    n_prompt = int(sizes.get("prompt_tokens", PROMPT_TOKENS))
    n_new = int(sizes.get("new_tokens", NEW_TOKENS))
    rng = random.Random(seed ^ 0x9E3779B9)
    sequences, served = [], []
    for _ in range(PROBES):
        prompt = token_ids(rng, n_prompt, vocab)
        greedy = {"prompt": prompt, "max_tokens": n_new, "temperature": 0}
        # The unary reply carries the log-probabilities and no token ids;
        # the same greedy request streamed carries the ids, token for token.
        reply = server.post_json(
            "/v1/completions", {**greedy, "logprobs": True}
        )
        lps = reply["choices"][0]["logprobs"]["token_logprobs"]
        ids = streamed_ids(server, greedy)
        check(
            len(ids) == len(lps) >= 1,
            f"probe: {len(lps)} log-probabilities for {len(ids)} streamed ids",
        )
        served.append(lps)
        sequences.append(prompt + ids)

    def reference(ablate: str) -> list:
        return server.post_json("/bench/reference", {
            "sequences": sequences, "n_prompt": n_prompt, "ablate": ablate,
        })["logprobs"]

    diffs = differences(served, reference(""))
    ablated = {}
    for ablate in server.get_json("/bench/reference")["ablations"]:
        got = reference(ablate)
        if all(g is not None for g in got):  # else it changes nothing here
            found = differences(served, got)
            ablated[ablate] = {**summary(found), "agrees": agrees(found)}
    return {
        "agrees": agrees(diffs) and bool(ablated) and not any(
            found["agrees"] for found in ablated.values()
        ),
        **summary(diffs),
        "tolerances": {"token": TOKEN_TOLERANCE, "median": MEDIAN_TOLERANCE,
                       "agreeing_share": AGREEING_SHARE},
        "ablated": ablated, "abs_diffs": sorted(diffs),
        "sequences": len(sequences),
    }


def compared(probe: dict) -> dict:
    """Each number of the probe that decides ``correct``, beside its limit."""
    ablated = probe["ablated"]
    out = {
        "probe_median_nats": {
            "value": probe["median"], "limit": MEDIAN_TOLERANCE, "rule": "<="},
        "probe_share_within_token_tolerance": {
            "value": probe["share_within_token_tolerance"],
            "limit": AGREEING_SHARE, "rule": ">="},
        "ablations_applied": {"value": len(ablated), "limit": 1, "rule": ">="},
        "ablations_still_agreeing": {
            "value": sum(found["agrees"] for found in ablated.values()),
            "limit": 0, "rule": "=="},
    }
    for name, found in ablated.items():  # what "still agreeing" was read from
        out[f"ablated_{name}_median_nats"] = {
            "value": found["median"], "limit": MEDIAN_TOLERANCE,
            "rule": "> or the share below"}
        out[f"ablated_{name}_share_within_token_tolerance"] = {
            "value": found["share_within_token_tolerance"],
            "limit": AGREEING_SHARE, "rule": "< or the median above"}
    return out


def streamed_ids(server: Server, greedy: dict) -> list:
    status, raw = server.request("POST", "/v1/completions", {
        **greedy, "stream": True, "stream_options": {"include_tokens": True},
    }, timeout=600.0)
    check(status == 200, f"probe: streamed /v1/completions -> {status}")
    ids: list = []
    for line in raw.decode("utf-8").splitlines():
        if line.startswith("data: ") and line != "data: [DONE]":
            for choice in json.loads(line[6:]).get("choices", []):
                ids += choice.get("token_ids", [])
    return ids
