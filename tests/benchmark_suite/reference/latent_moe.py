"""The benchmark's latent-attention expert-share decoder under the tests' own
directory: a configuration's reference is looked for beside its ``configs/``,
and the tests' tiny share has the mathematics of
``openpangu-ultra-moe-718b-ep16``."""

from benchmark.reference.latent_moe import ABLATIONS, reference_logprobs

__all__ = ["ABLATIONS", "reference_logprobs"]
