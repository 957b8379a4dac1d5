"""A reference with other mathematics than the shared decoder's, as a file
of its own: the same pre-norm decoder with a GELU gate (GeGLU, the
tanh-approximate GELU of ``TransformerConfig(act="gelu")``) where the
shared one computes SiLU. It is the worked example of what a
configuration with a new mechanism brings: the pieces it can remove, one
of them its own, and the two names the harness asks for.
"""

from __future__ import annotations

from typing import Any

import jax

from benchmark.reference import decoder

# "act" puts SiLU back: the probe must tell the two gates apart.
ABLATIONS = ("causal", "act")


@jax.jit
def geglu(x: Any, w_gate: Any, w_up: Any, w_down: Any) -> Any:
    return (jax.nn.gelu(x @ w_gate, approximate=True) * (x @ w_up)) @ w_down


def reference_logprobs(
    engine: Any, sequences: list, n_prompt: int, ablate: str = "",
) -> list:
    if ablate not in ("", *ABLATIONS):
        raise ValueError(f"unknown ablation {ablate!r}; known: {ABLATIONS}")
    shape = decoder.shape_of(engine.cfg)
    weights = decoder.EngineWeights(engine.params)
    kept = {} if ablate == "act" else {"gated": geglu}
    return [
        decoder.teacher_forced_logprobs(
            weights, shape, list(seq), n_prompt,
            "causal" if ablate == "causal" else "", **kept,
        )
        for seq in sequences
    ]
