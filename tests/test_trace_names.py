"""The names the profiler's trace carries (ISSUE 25, part 3).

Two halves, both on the CPU at tiny size:

* the model's ops: the lowered text of the serving programs names every
  op by the ``jax.named_scope`` it ran under — the fixed vocabulary
  ``embed attn kv_commit ffn moe_router moe_experts lm_head sample`` —
  so a device op in a capture says which part of the model it belongs
  to (its ``tf_op`` is this ``op_name``);
* the host's phases: a ``ProfilerCapture`` around a few scheduler passes
  holds ``loop/<phase>`` and ``window_fetch`` events on a host line of
  the same ``.xplane.pb``, read back with ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import collections
import glob
import os
import re

import jax
import numpy as np
import pytest

from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.loop_profiler import PHASES
from gofr_tpu.serving.profiler_capture import ProfilerCapture
from gofr_tpu.serving.tokenizer import ByteTokenizer

# ``draft`` and ``verify`` named the spec window's ops until PR 30: counted
# here so that a fork that brings them back shows in ``absent`` below.
VOCABULARY = {
    "embed", "attn", "kv_commit", "ffn", "moe_router", "moe_experts",
    "lm_head", "sample", "draft", "verify",
    # PR 33: latent attention's projections, and a grouped expert layer's
    # sort-and-group and shared expert
    "mla_q", "mla_kv", "moe_dispatch", "moe_shared",
    # PR 35: a lightning layer's projections, its chunk-wise product or
    # recurrence step with the state's update, its norm, gate and wo; a
    # sparse layer's compressed-key scores and choice, and the decode step's
    # gather of the chosen blocks
    "lin_qkv", "lin_scan", "lin_out", "sparse_index", "sparse_gather",
}
LATENT_MOE = {"mla_q", "mla_kv", "moe_dispatch", "moe_shared"}
HYBRID = {"lin_qkv", "lin_scan", "lin_out", "sparse_index", "sparse_gather"}


def engine_of(model: str) -> InferenceEngine:
    return InferenceEngine(
        model, tokenizer=ByteTokenizer(), n_slots=2, max_len=128,
        prefill_chunk=32,
    )


def scopes_in(lowered) -> collections.Counter:
    """How many ops of the lowered program ran under each scope of the
    vocabulary: every component of every op_name, e.g. both ``pass``
    and ``attn`` for ``pass/attn/dot_general``."""
    return collections.Counter(
        part for name in op_names(lowered) for part in name.split("/")
        if part in VOCABULARY
    )


def op_names(lowered) -> list[str]:
    """Every op's name stack in the lowered text (a scan's body is a
    function of its own there, so names inside it start at the scope:
    ``attn/pch,hd->pcd/dot_general``)."""
    locations = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    names = [n for n in locations if ".py" not in n and not n.startswith("<")]
    assert names, "the lowered text carries no op_name at all"
    return names


def lower_prefill_chunk(e: InferenceEngine):
    P, c = e.prefill_batch, e.prefill_chunk
    rows = lambda dtype, fill=0: e._up(np.full((P,), fill, dtype=dtype))  # noqa: E731
    args = [
        e.params, e.cache, e._up(np.ones((P, c), np.int32)), rows(np.int32),
        rows(np.int32), rows(np.int32, c), rows(bool, True), rows(bool, True),
        rows(np.float32, 1), rows(bool, True), rows(np.float32, 1),
        e._seeds_dev, e._tokens_dev, e._logps_dev, e._pcounts_dev,
        e._nsteps_dev, e._bidx_dev, e._bval_dev, e._topi_dev, e._topl_dev,
        e._aids_dev, e._noff_dev,
    ]
    return e._prefill_chunk_step.__wrapped__.lower(*args, use_bias=False)


def lower_window(e: InferenceEngine):
    jnp = e._jnp
    S = e.n_slots
    active, ones = jnp.ones((S,), bool), jnp.ones((S,), jnp.float32)
    return e._decode_window.__wrapped__.lower(
        e.params, e._tokens_dev, e._logps_dev, e.cache, active,
        e._nsteps_dev, ones, active, ones, e._fpen_dev, e._ppen_dev,
        e._pcounts_dev, e._seeds_dev, e._bidx_dev, e._bval_dev,
        e._topi_dev, e._topl_dev, e._aids_dev,
        k=e.window_k, use_bias=False,
    )


@pytest.mark.parametrize("model,ffn_scopes,absent", [
    ("llama-tiny", {"ffn"},
     {"moe_router", "moe_experts", "draft", "verify"} | LATENT_MOE | HYBRID),
    ("moe-tiny", {"moe_router", "moe_experts"},
     {"ffn", "draft", "verify"} | LATENT_MOE | HYBRID),
    # two kinds of mixer: every scope of a lightning layer and of a sparse
    # one (the gather of chosen blocks is the decode step's alone)
    ("sala-tiny", {"ffn"} | HYBRID - {"sparse_gather"},
     {"moe_router", "moe_experts", "draft", "verify"} | LATENT_MOE),
    # a leading dense layer (ffn), then expert layers by the grouped product
    # over a latent cache: every scope of the model's vocabulary
    ("mla-moe-tiny", {"ffn", "moe_router", "moe_experts"} | LATENT_MOE,
     {"draft", "verify"} | HYBRID),
])
def test_lowered_programs_name_their_ops_by_scope(model, ffn_scopes, absent):
    e = engine_of(model)  # built, never started: nothing runs
    lowered_window = lower_window(e)
    prefill = scopes_in(lower_prefill_chunk(e))
    window = scopes_in(lowered_window)
    everywhere = {"embed", "attn", "kv_commit", "lm_head", "sample"} | ffn_scopes
    assert everywhere <= set(prefill), sorted(prefill)
    assert everywhere <= set(window), sorted(window)
    assert not absent & (set(prefill) | set(window))
    if model == "sala-tiny":
        assert "sparse_gather" in window and "sparse_gather" not in prefill
    # The matrix products carry a name: the attention and FFN einsums are
    # the device's time, and an unnamed one is what this PR is against.
    dots = [n for n in op_names(lowered_window) if n.endswith("dot_general")]
    assert dots and all(
        any(part in VOCABULARY for part in name.split("/")) for name in dots
    ), sorted(set(dots))


def test_a_looped_stack_names_its_passes():
    """A looped model's layer ops read ``pass/attn/...``, ``pass/ffn/...``,
    the norm that ends a pass ``pass/pass_norm/...``; an unlooped model's
    programs carry neither name."""
    e = engine_of("looped-tiny")
    for lowered in (lower_prefill_chunk(e), lower_window(e)):
        names = op_names(lowered)
        in_layers = [
            n for n in names
            if {"attn", "ffn"} & set(n.split("/")) and "dot_general" in n
        ]
        assert in_layers and all(n.startswith("pass/") for n in in_layers), (
            sorted(set(in_layers))
        )
        assert any("pass_norm" in n.split("/") for n in names)
        # embedding, head and sampling are outside the loop (the prefill
        # chunk commits its keys inside each layer, the decode step after)
        outside = [n for n in names
                   if {"embed", "lm_head", "sample"} & set(n.split("/"))]
        assert outside and not any("pass" in n.split("/") for n in outside)
    plain = engine_of("llama-tiny")
    for lowered in (lower_prefill_chunk(plain), lower_window(plain)):
        assert not any(
            {"pass", "pass_norm"} & set(n.split("/")) for n in op_names(lowered)
        )


def test_scopes_change_no_program(monkeypatch):
    """Compile-time metadata only: with every scope turned into a no-op
    the lowered programs are the same text, locations aside."""
    import contextlib

    e = engine_of("llama-tiny")
    named = [lower_prefill_chunk(e).as_text(), lower_window(e).as_text()]

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    # Decorated functions captured the real scope at import; the with-
    # blocks and a rebuilt engine's closures take the no-op. Enough to
    # show the text does not depend on it.
    bare_engine = engine_of("llama-tiny")
    bare = [
        lower_prefill_chunk(bare_engine).as_text(),
        lower_window(bare_engine).as_text(),
    ]
    assert named == bare


def test_capture_holds_the_loops_phases_on_a_host_line():
    e = engine_of("llama-tiny")
    e.start_sync()
    try:
        e.generate_sync(  # compile outside the capture
            "warm the loop", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False, timeout=120,
        )
        capture = ProfilerCapture()
        capture.start_trace()
        try:
            e.generate_sync(
                "a few scheduler passes", max_new_tokens=24, temperature=0.0,
                stop_on_eos=False, timeout=120,
            )
        finally:
            capture.stop_trace()
    finally:
        e.stop_sync()
    found = glob.glob(os.path.join(
        capture.trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    ))
    assert len(found) == 1
    data = jax.profiler.ProfileData.from_file(found[0])
    on_lines = collections.defaultdict(collections.Counter)
    for plane in data.planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("loop/") or event.name == "window_fetch":
                    on_lines[(plane.name, line.name)][event.name] += 1
    # One host thread, the scheduler's, holds them all.
    assert len(on_lines) == 1, sorted(on_lines)
    (plane_name, _line), names = next(iter(on_lines.items()))
    assert plane_name.startswith("/host:")
    assert names["loop/device_window"] >= 1 and names["window_fetch"] >= 1
    assert {"loop/reap", "loop/prefill", "loop/emit_flush", "loop/dispatch"} <= set(names)
    assert {n[len("loop/"):] for n in names if n.startswith("loop/")} <= set(PHASES)
