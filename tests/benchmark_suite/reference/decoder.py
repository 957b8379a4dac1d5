"""The benchmark's shared decoder under the tests' own directory: a
configuration's reference is looked for beside its ``configs/``, and the
tests' tiny dense and MoE models have the mathematics of the benchmark's
two."""

from benchmark.reference.decoder import ABLATIONS, reference_logprobs

__all__ = ["ABLATIONS", "reference_logprobs"]
