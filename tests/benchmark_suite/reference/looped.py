"""The benchmark's looped decoder under the tests' own directory: a
configuration's reference is looked for beside its ``configs/``, and the
tests' tiny looped model has the mathematics of ``ouro-2.6b``."""

from benchmark.reference.looped import ABLATIONS, reference_logprobs

__all__ = ["ABLATIONS", "reference_logprobs"]
