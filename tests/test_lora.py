"""Multi-LoRA serving: per-request adapters batched into one program.

The oracle is weight merging: serving with adapter slot a must equal
serving a model whose weights were merged W' = W + A_a @ B_a offline
(f32 tiny model, greedy). Batch isolation: concurrent requests on
different adapters must reproduce their solo outputs exactly — the
per-slot gather cannot leak across rows.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.registry import get_model
from gofr_tpu.models.transformer import (
    TransformerConfig,
    init_lora,
    init_transformer,
    lora_dims,
    transformer_forward,
)
from gofr_tpu.serving.engine import InferenceEngine
from gofr_tpu.serving.tokenizer import ByteTokenizer

CFG: TransformerConfig = get_model("llama-tiny-f32").config
TARGETS = ("wq", "wk", "wv", "wo")


def _rand_adapter(seed: int, rank: int = 4, scale: float = 0.5) -> dict:
    """{target: (a, b)} random leaves in the engine's load_lora form."""
    key = jax.random.PRNGKey(seed)
    leaves = {}
    for t in TARGETS:
        d_in, d_out = lora_dims(CFG, t)
        key, k1, k2 = jax.random.split(key, 3)
        leaves[t] = (
            scale * jax.random.normal(k1, (CFG.n_layers, d_in, rank)),
            scale * jax.random.normal(k2, (CFG.n_layers, rank, d_out)),
        )
    return leaves


def _merged_params(params: dict, leaves: dict) -> dict:
    merged = {**params, "layers": dict(params["layers"])}
    for t, (a, b) in leaves.items():
        delta = jnp.einsum("ldr,lro->ldo", a, b).astype(
            merged["layers"][t].dtype
        )
        merged["layers"][t] = merged["layers"][t] + delta
    return merged


def _engine(**kw):
    eng = InferenceEngine(
        "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
        tokenizer=ByteTokenizer(), lora_slots=2, lora_rank=4, **kw,
    )
    eng.start_sync()
    return eng


def _gen(eng, prompt, n=10, **kw):
    return eng.generate_sync(
        prompt, max_new_tokens=n, temperature=0.0, stop_on_eos=False,
        timeout=120, **kw,
    ).token_ids


def test_forward_adapter_matches_merged_weights():
    """transformer_forward with aids == forward on merged weights; rows
    with aid 0 are untouched base rows."""
    params = init_transformer(jax.random.PRNGKey(0), CFG)
    leaves = _rand_adapter(7)
    lora = init_lora(CFG, 3, 4, TARGETS)
    for t, (a, b) in leaves.items():
        lora[t + "_lora_a"] = lora[t + "_lora_a"].at[:, 2].set(a)
        lora[t + "_lora_b"] = lora[t + "_lora_b"].at[:, 2].set(b)
    p_lora = {**params, "layers": {**params["layers"], **lora}}
    tokens = jnp.array([[1, 5, 9, 2], [3, 8, 4, 6]], dtype=jnp.int32)
    out = np.asarray(transformer_forward(
        p_lora, tokens, CFG, aids=jnp.array([0, 2], dtype=jnp.int32)
    ))
    base = np.asarray(transformer_forward(params, tokens, CFG))
    merged = np.asarray(transformer_forward(
        _merged_params(params, leaves), tokens, CFG
    ))
    np.testing.assert_allclose(out[0], base[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[1], merged[1], atol=1e-4, rtol=1e-4)
    assert not np.allclose(out[1], base[1], atol=1e-2)


def test_engine_adapter_matches_merged_engine():
    """Greedy generation with adapter == generation on an engine booted
    from the merged checkpoint."""
    leaves = _rand_adapter(11)
    eng = _engine()
    try:
        base = _gen(eng, "hello")
        eng.load_lora("tuned", leaves)
        tuned = _gen(eng, "hello", adapter="tuned")
        base_params = init_transformer(
            jax.random.PRNGKey(0), CFG
        )  # engine seed=0 default
        merged_eng = InferenceEngine(
            "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
            tokenizer=ByteTokenizer(),
            params=_merged_params(eng.params, leaves),
        )
        merged_eng.start_sync()
        try:
            want = _gen(merged_eng, "hello")
        finally:
            merged_eng.stop_sync()
        assert tuned == want
        assert tuned != base
        assert _gen(eng, "hello") == base  # base unaffected
        del base_params
    finally:
        eng.stop_sync()


def test_concurrent_adapters_batch_isolation():
    """Requests on base + two adapters running CONCURRENTLY in one
    engine reproduce their solo outputs token for token."""
    a1, a2 = _rand_adapter(21), _rand_adapter(22)
    eng = _engine()
    try:
        eng.load_lora("a1", a1)
        eng.load_lora("a2", a2)
        solo = {
            "": _gen(eng, "hello"),
            "a1": _gen(eng, "hello", adapter="a1"),
            "a2": _gen(eng, "hello", adapter="a2"),
        }
        assert len({tuple(v) for v in solo.values()}) == 3
        reqs = [
            eng.submit_generate(
                "hello", max_new_tokens=10, temperature=0.0,
                stop_on_eos=False, adapter=name,
            )
            for name in ("", "a1", "a2", "a1")
        ]
        outs = [r.future.result(timeout=120).token_ids for r in reqs]
        assert outs[0] == solo[""]
        assert outs[1] == solo["a1"]
        assert outs[2] == solo["a2"]
        assert outs[3] == solo["a1"]
    finally:
        eng.stop_sync()


def test_ffn_targets_through_engine():
    """FFN LoRA targets (w_gate/w_up/w_down) apply on both serving paths,
    chunked prefill and decode, not just the full-sequence forward
    (regression: the inline layer bodies dropped aids on their _ffn_dense
    calls)."""
    all_targets = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    key = jax.random.PRNGKey(61)
    leaves = {}
    for t in all_targets:
        d_in, d_out = lora_dims(CFG, t)
        key, k1, k2 = jax.random.split(key, 3)
        leaves[t] = (
            0.5 * jax.random.normal(k1, (CFG.n_layers, d_in, 4)),
            0.5 * jax.random.normal(k2, (CFG.n_layers, 4, d_out)),
        )
    eng = InferenceEngine(
        "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
        tokenizer=ByteTokenizer(), lora_slots=1, lora_rank=4,
        lora_targets=",".join(all_targets),
    )
    eng.start_sync()
    try:
        eng.load_lora("full", leaves)
        got = _gen(eng, "hello", adapter="full")
        merged_eng = InferenceEngine(
            "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
            tokenizer=ByteTokenizer(),
            params=_merged_params(eng.params, leaves),
        )
        merged_eng.start_sync()
        try:
            assert got == _gen(merged_eng, "hello")
        finally:
            merged_eng.stop_sync()
    finally:
        eng.stop_sync()


def test_a_prompt_of_several_chunks_prefills_with_its_own_requests_adapter():
    """Every chunk step of a long prompt must run with the REQUEST's
    adapter, not the slot's previous occupant's (the aids plane uploads
    before the first dispatch after an admission)."""
    leaves = _rand_adapter(71)
    long_prompt = "abcdefgh" * 16  # 128 chars → 8 chunks of 16
    kw = dict(
        n_slots=2, max_len=256, window_k=4, tokenizer=ByteTokenizer(),
        prefill_chunk=16,
    )
    eng = InferenceEngine(
        "llama-tiny-f32", lora_slots=1, lora_rank=4, **kw
    )
    eng.start_sync()
    try:
        eng.load_lora("t", leaves)
        # Park the base request in slot 0 first so the adapter request
        # reuses a slot whose host aid was 0.
        base_out = _gen(eng, long_prompt)
        got = _gen(eng, long_prompt, adapter="t")
        merged_eng = InferenceEngine(
            "llama-tiny-f32",
            params=_merged_params(eng.params, leaves), **kw,
        )
        merged_eng.start_sync()
        try:
            want = _gen(merged_eng, long_prompt)
        finally:
            merged_eng.stop_sync()
        assert got == want
        assert got != base_out
    finally:
        eng.stop_sync()


def test_reload_with_fewer_targets_zeroes_stale_deltas():
    """Re-loading a name with fewer targets must clear the old version's
    other-target deltas (regression: load_lora wrote without zeroing)."""
    v1 = _rand_adapter(81)  # wq, wk, wv, wo
    v2 = {"wq": v1["wq"]}  # only wq survives
    eng = _engine()
    try:
        eng.load_lora("a", v1)
        eng.load_lora("a", v2)
        got = _gen(eng, "hello", adapter="a")
        merged_eng = InferenceEngine(
            "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
            tokenizer=ByteTokenizer(),
            params=_merged_params(eng.params, v2),
        )
        merged_eng.start_sync()
        try:
            assert got == _gen(merged_eng, "hello")
        finally:
            merged_eng.stop_sync()
    finally:
        eng.stop_sync()


def test_adapter_slot_management():
    eng = _engine()
    try:
        assert eng.lora_names() == []
        eng.load_lora("x", _rand_adapter(1))
        eng.load_lora("y", _rand_adapter(2))
        assert eng.lora_names() == ["x", "y"]
        with pytest.raises(RuntimeError, match="slots in use"):
            eng.load_lora("z", _rand_adapter(3))
        base = _gen(eng, "hi")
        x_out = _gen(eng, "hi", adapter="x")
        eng.unload_lora("x")
        assert eng.lora_names() == ["y"]
        with pytest.raises(Exception):
            _gen(eng, "hi", adapter="x")
        # Freed slot is reusable; zeroed slot serves base until then.
        eng.load_lora("z", _rand_adapter(3))
        assert eng.lora_names() == ["y", "z"]
        assert _gen(eng, "hi") == base
        assert x_out != base
    finally:
        eng.stop_sync()


def test_prefix_pool_per_adapter():
    """Prefix-KV reuse composes with LoRA: a prefix registered under an
    adapter is reused ONLY by same-adapter requests, outputs match the
    no-pool engines exactly, and unloading the adapter purges its
    pooled prefixes."""
    leaves = _rand_adapter(91)
    prefix = "system: answer briefly. "
    suffix = "hello there"
    kw = dict(
        n_slots=4, max_len=128, window_k=4, tokenizer=ByteTokenizer(),
        lora_slots=2, lora_rank=4,
    )
    eng = InferenceEngine("llama-tiny-f32", prefix_slots=2, **kw)
    eng.start_sync()
    try:
        eng.load_lora("t", leaves)
        eng.register_prefix_sync(prefix)
        eng.register_prefix_sync(prefix, adapter="t")
        assert len(eng._prefix_pool) == 2
        got_base = _gen(eng, prefix + suffix)
        got_tuned = _gen(eng, prefix + suffix, adapter="t")
        ref = InferenceEngine("llama-tiny-f32", **kw)
        ref.start_sync()
        try:
            ref.load_lora("t", leaves)
            assert got_base == _gen(ref, prefix + suffix)
            assert got_tuned == _gen(ref, prefix + suffix, adapter="t")
        finally:
            ref.stop_sync()
        assert got_base != got_tuned
        eng.unload_lora("t")
        assert len(eng._prefix_pool) == 1  # adapter prefix purged
    finally:
        eng.stop_sync()


def test_prefix_pool_purged_on_adapter_reload():
    """Re-loading an adapter name invalidates its pooled prefixes (the
    pooled K/V was computed under the old weights), and a prefix
    registration still in flight across the reload is dropped with -1
    instead of registering stale rows."""
    v1, v2 = _rand_adapter(95), _rand_adapter(96)
    kw = dict(
        n_slots=4, max_len=128, window_k=4, tokenizer=ByteTokenizer(),
        lora_slots=2, lora_rank=4, prefix_slots=2,
    )
    eng = InferenceEngine("llama-tiny-f32", **kw)
    eng.start_sync()
    try:
        eng.load_lora("t", v1)
        eng.register_prefix_sync("shared preamble. ", adapter="t")
        assert len(eng._prefix_pool) == 1
        eng.load_lora("t", v2)  # reload → v1-weight prefix must die
        assert len(eng._prefix_pool) == 0
        got = _gen(eng, "shared preamble. hi", adapter="t")
        ref = InferenceEngine(
            "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
            tokenizer=ByteTokenizer(),
            params=_merged_params(eng.params, v2),
        )
        ref.start_sync()
        try:
            assert got == _gen(ref, "shared preamble. hi")
        finally:
            ref.stop_sync()
    finally:
        eng.stop_sync()

    # In-flight registration racing a reload: whichever side wins, no
    # stale entry may survive — either the store is dropped (-1) or the
    # reload's purge removes the just-stored entry.
    eng = InferenceEngine("llama-tiny-f32", **kw)
    eng.start_sync()
    try:
        eng.load_lora("t", v1)
        req = eng.register_prefix("stale preamble. ", adapter="t")
        eng.load_lora("t", v2)
        res = req.future.result(timeout=120)
        assert res == -1 or len(eng._prefix_pool) == 0
        assert len(eng._prefix_pool) == 0
    finally:
        eng.stop_sync()


def test_adapter_churn_under_load():
    """load_lora/unload_lora while the engine is serving: in-flight base
    streams must be unaffected, every request must complete, and the
    engine must return to idle with all slots free."""
    import threading

    eng = _engine()
    try:
        expected = _gen(eng, "hello", n=24)
        stop = threading.Event()
        churn_err = []

        def churn():
            i = 0
            try:
                while not stop.is_set():
                    name = f"churn-{i % 2}"
                    eng.load_lora(name, _rand_adapter(100 + i % 3))
                    eng.unload_lora(name)
                    i += 1
            except Exception as exc:  # noqa: BLE001
                churn_err.append(exc)

        t = threading.Thread(target=churn)
        t.start()
        try:
            reqs = [
                eng.submit_generate(
                    "hello", max_new_tokens=24, temperature=0.0,
                    stop_on_eos=False,
                )
                for _ in range(8)
            ]
            outs = [r.future.result(timeout=120).token_ids for r in reqs]
        finally:
            stop.set()
            t.join(timeout=30)
        assert not churn_err, churn_err
        assert all(o == expected for o in outs)
        assert eng.lora_names() == []
        assert all(s is None for s in eng._slots)
    finally:
        eng.stop_sync()


def test_reload_fails_inflight_instead_of_mixing():
    """Overwriting a slot that live requests still route to must FAIL
    those requests — one completion must never mix tokens from two
    adapters (same-name reload), and a request queued across a reload
    fails at admission instead of running under the wrong weights."""
    import time as _time

    a1, a2 = _rand_adapter(31), _rand_adapter(32)
    eng = _engine()
    try:
        eng.load_lora("tuned", a1)
        req = eng.submit_generate(
            "hello", max_new_tokens=100, temperature=0.0,
            stop_on_eos=False, adapter="tuned",
        )
        deadline = _time.time() + 60
        while not req.token_ids and _time.time() < deadline:
            _time.sleep(0.002)
        assert req.token_ids, "request never started decoding"
        eng.load_lora("tuned", a2)
        with pytest.raises(RuntimeError, match="overwritten"):
            req.future.result(timeout=120)
        # The reloaded adapter serves fresh requests with the NEW weights.
        got = _gen(eng, "hello", adapter="tuned")
        ref = InferenceEngine(
            "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
            tokenizer=ByteTokenizer(),
            params=_merged_params(eng.params, a2),
        )
        ref.start_sync()
        try:
            assert got == _gen(ref, "hello")
        finally:
            ref.stop_sync()

        # Queued across a reload: fill every slot with long base runs so
        # the adapter request cannot be admitted before the reload lands.
        blockers = [
            eng.submit_generate(
                "hold", max_new_tokens=100, temperature=0.0,
                stop_on_eos=False,
            )
            for _ in range(4)
        ]
        queued = eng.submit_generate(
            "hello", max_new_tokens=4, temperature=0.0,
            stop_on_eos=False, adapter="tuned",
        )
        eng.load_lora("tuned", a1)
        with pytest.raises(RuntimeError, match="queued|overwritten"):
            queued.future.result(timeout=120)
        for b in blockers:
            b.future.result(timeout=120)
    finally:
        eng.stop_sync()


def test_fresh_load_prefers_idle_slot():
    """A fresh load after an unload picks the free slot with no live
    traffic, so requests finishing against base (documented unload
    semantics) are not silently switched onto the new adapter.

    White-box: the engine is never STARTED and the draining request is
    pinned into a slot directly — racing a real generation against
    unload_lora is timing-dependent (on a fast run the request finishes
    first and slot 1 is legitimately reused)."""
    from gofr_tpu.serving.types import _ActiveSeq, _GenRequest

    eng = InferenceEngine(
        "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
        tokenizer=ByteTokenizer(), lora_slots=2, lora_rank=4,
    )
    eng.load_lora("old", _rand_adapter(41))
    assert eng._lora_names["old"] == 1
    req = _GenRequest(
        prompt_ids=[1, 2], max_new_tokens=8, temperature=0.0,
        stop_on_eos=False, aid=1, lora_gen=eng._lora_gen[1],
    )
    eng._slots[0] = _ActiveSeq(request=req, last_token=-1)
    eng.unload_lora("old")  # in-flight finishes on base (documented)
    eng.load_lora("new", _rand_adapter(42))
    assert eng._lora_names["new"] == 2  # slot 1 still draining
    assert not req.future.done()  # the draining request was untouched
    # Forced reuse: with slot 2 also taken, a load MUST take slot 1 and
    # fail its draining request rather than mix weight sets.
    eng.load_lora("third", _rand_adapter(43))
    assert eng._lora_names["third"] == 1
    with pytest.raises(RuntimeError, match="overwritten"):
        req.future.result(timeout=5)


def test_engine_without_lora_rejects():
    eng = InferenceEngine(
        "llama-tiny-f32", n_slots=2, max_len=64,
        tokenizer=ByteTokenizer(),
    )
    try:
        with pytest.raises(RuntimeError, match="TPU_LORA_SLOTS"):
            eng.load_lora("x", _rand_adapter(1))
    finally:
        eng.close()


def test_peft_checkpoint_load(tmp_path):
    """HF PEFT format: adapter_config.json + safetensors, rank below the
    compiled rank (zero-pad), alpha scaling folded in — output equals
    the merged oracle with scale alpha/r."""
    from safetensors.numpy import save_file

    r, alpha = 2, 8.0
    rng = np.random.default_rng(5)
    tensors = {}
    leaves_scaled = {}
    for t in ("wq", "wv"):
        d_in, d_out = lora_dims(CFG, t)
        mod = {"wq": "q_proj", "wv": "v_proj"}[t]
        a = np.zeros((CFG.n_layers, d_in, 4), dtype=np.float32)
        b = np.zeros((CFG.n_layers, 4, d_out), dtype=np.float32)
        for i in range(CFG.n_layers):
            wa = rng.standard_normal((r, d_in)).astype(np.float32) * 0.5
            wb = rng.standard_normal((d_out, r)).astype(np.float32) * 0.5
            tensors[
                f"base_model.model.model.layers.{i}.self_attn.{mod}"
                f".lora_A.weight"
            ] = wa
            tensors[
                f"base_model.model.model.layers.{i}.self_attn.{mod}"
                f".lora_B.weight"
            ] = wb
            a[i, :, :r] = wa.T
            b[i, :r, :] = wb.T * (alpha / r)
        leaves_scaled[t] = (jnp.asarray(a), jnp.asarray(b))
    (tmp_path / "adapter_config.json").write_text(json.dumps({
        "r": r, "lora_alpha": alpha,
        "target_modules": ["q_proj", "v_proj"],
    }))
    save_file(tensors, str(tmp_path / "adapter_model.safetensors"))

    eng = _engine()
    try:
        eng.load_lora("peft", str(tmp_path))
        got = _gen(eng, "hello", adapter="peft")
        merged_eng = InferenceEngine(
            "llama-tiny-f32", n_slots=4, max_len=128, window_k=4,
            tokenizer=ByteTokenizer(),
            params=_merged_params(eng.params, leaves_scaled),
        )
        merged_eng.start_sync()
        try:
            assert got == _gen(merged_eng, "hello")
        finally:
            merged_eng.stop_sync()
    finally:
        eng.stop_sync()


def test_peft_rank_too_big_rejected(tmp_path):
    (tmp_path / "adapter_config.json").write_text(json.dumps({
        "r": 64, "lora_alpha": 64, "target_modules": ["q_proj"],
    }))
    eng = _engine()
    try:
        with pytest.raises(ValueError, match="TPU_LORA_RANK"):
            eng.load_lora("big", str(tmp_path))
    finally:
        eng.stop_sync()


def _memorize_tokens() -> list[int]:
    text = b"the quick brown fox jumps over the lazy dog. " * 3
    return np.frombuffer(text, dtype=np.uint8).astype(np.int32)[
        :128
    ].tolist()


def test_train_adapter_then_serve():
    """The train→serve loop: fine-tune LoRA factors on a frozen base
    (the base tree must come out bit-identical), load them into a
    serving engine, and the adapter stream must reproduce the memorized
    text while the base stream does not."""
    from gofr_tpu.parallel.sharding import make_lora_train_step

    base = init_transformer(jax.random.PRNGKey(0), CFG)
    base_flat = [np.asarray(x) for x in jax.tree_util.tree_leaves(base)]
    init_state, step = make_lora_train_step(
        CFG, base, rank=8, learning_rate=3e-3
    )
    lora, opt = init_state(jax.random.PRNGKey(1))
    toks = jnp.asarray(_memorize_tokens())[None, :]
    first = last = None
    for _ in range(60):
        loss, lora, opt = step(lora, opt, toks)
        first = float(loss) if first is None else first
        last = float(loss)
    assert last < first * 0.5
    for before, after in zip(
        base_flat, jax.tree_util.tree_leaves(base)
    ):
        np.testing.assert_array_equal(before, np.asarray(after))

    eng = InferenceEngine(
        "llama-tiny-f32", n_slots=2, max_len=160, window_k=4,
        tokenizer=ByteTokenizer(), params=base, lora_slots=1, lora_rank=8,
    )
    eng.start_sync()
    try:
        idx = eng.load_lora("memorized", {t: lora[t] for t in lora})
        assert idx == 1
        prompt = bytes(_memorize_tokens()[:20]).decode()
        cont = bytes(_memorize_tokens()[20:36]).decode()
        tuned = eng.generate_sync(
            prompt, max_new_tokens=16, temperature=0.0, stop_on_eos=False,
            adapter="memorized", timeout=120,
        )
        plain = eng.generate_sync(
            prompt, max_new_tokens=16, temperature=0.0, stop_on_eos=False,
            timeout=120,
        )
        assert tuned.text == cont  # memorization served through the engine
        assert plain.text != cont
    finally:
        eng.stop_sync()


def test_train_adapter_qlora_int8_base():
    """QLoRA shape: the frozen base is int8-quantized; training still
    converges (gradients flow only through the f32 factors)."""
    from gofr_tpu.ops.quant import Q8
    from gofr_tpu.parallel.sharding import make_lora_train_step
    from gofr_tpu.serving.engine import InferenceEngine as _E

    eng = _E(
        "llama-tiny", n_slots=2, max_len=64, tokenizer=ByteTokenizer(),
        quant="int8",
    )
    base = eng.params
    eng.close()
    assert isinstance(base["layers"]["wq"], Q8)
    cfg = get_model("llama-tiny").config
    init_state, step = make_lora_train_step(
        cfg, base, rank=4, learning_rate=3e-3
    )
    lora, opt = init_state(jax.random.PRNGKey(1))
    toks = jnp.asarray(_memorize_tokens())[None, :64]
    first = last = None
    for _ in range(30):
        loss, lora, opt = step(lora, opt, toks)
        first = float(loss) if first is None else first
        last = float(loss)
    assert last < first * 0.8


def test_train_adapter_on_mesh():
    """LoRA factors shard with their base projections (minus the adapter
    axis) over a dp×tp mesh; one step runs and the loss is finite."""
    from gofr_tpu.parallel import make_mesh
    from gofr_tpu.parallel.sharding import (
        make_lora_train_step,
        named_shardings,
        prune_specs,
    )
    from gofr_tpu.models.transformer import transformer_param_specs

    mesh = make_mesh({"dp": 2, "tp": 2})
    specs = prune_specs(transformer_param_specs(CFG), mesh)
    base = jax.jit(
        lambda k: init_transformer(k, CFG),
        out_shardings=named_shardings(specs, mesh),
    )(jax.random.PRNGKey(0))
    init_state, step = make_lora_train_step(
        CFG, base, rank=4, mesh=mesh, learning_rate=3e-3
    )
    lora, opt = init_state(jax.random.PRNGKey(1))
    assert "tp" in str(lora["wq"][1].sharding.spec)  # b shards out over tp
    toks = jnp.asarray(_memorize_tokens())[None, :64]
    toks = jnp.broadcast_to(toks, (2, 64))
    loss, lora, opt = step(lora, opt, toks)
    assert np.isfinite(float(loss))


def test_grpc_kwargs_pass_adapter():
    """Both gRPC surfaces (JSON + typed proto) forward the adapter."""
    from gofr_tpu.grpc import inference_pb2
    from gofr_tpu.grpc.inference import InferenceServicer
    from gofr_tpu.grpc.inference_typed import TypedInferenceServicer

    class _Eng:
        tokenizer = None

    kw = InferenceServicer(_Eng())._gen_kwargs(
        {"prompt": "x", "adapter": "tuned"}, False
    )
    assert kw["adapter"] == "tuned"
    kw2 = InferenceServicer(_Eng())._gen_kwargs({"prompt": "x"}, False)
    assert "adapter" not in kw2
    req = inference_pb2.GenerateRequest(prompt="x", adapter="tuned")
    _, tkw = TypedInferenceServicer(_Eng())._gen_kwargs(req)
    assert tkw["adapter"] == "tuned"


def test_openai_surface_routes_adapters():
    """The OpenAI surface serves adapters as model ids: /v1/models lists
    them, completions route by model name, unknown models still 404."""
    import asyncio
    import http.client
    import threading

    from gofr_tpu import App
    from gofr_tpu.config import MockConfig
    from gofr_tpu.serving.openai_compat import add_openai_routes

    app = App(config=MockConfig({
        "APP_NAME": "lora-test", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny-f32", "TPU_KV_SLOTS": "4",
        "TPU_MAX_LEN": "128", "TPU_LORA_SLOTS": "2", "TPU_LORA_RANK": "4",
    }))
    add_openai_routes(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    asyncio.run_coroutine_threadsafe(app.start(), loop).result(timeout=120)
    try:
        app.container.tpu.load_lora("tuned", _rand_adapter(51))

        def call(method, path, body=None):
            c = http.client.HTTPConnection(
                "127.0.0.1", app.http_port, timeout=120
            )
            c.request(
                method, path, body=json.dumps(body) if body else None
            )
            r = c.getresponse()
            return r.status, json.loads(r.read())

        _, models = call("GET", "/v1/models")
        ids = {m["id"] for m in models["data"]}
        assert "tuned" in ids
        body = {
            "model": "tuned", "prompt": "hello", "max_tokens": 6,
            "temperature": 0,
        }
        st, r_tuned = call("POST", "/v1/completions", body)
        assert st == 200
        st, r_base = call(
            "POST", "/v1/completions", {**body, "model": "llama-tiny-f32"}
        )
        assert st == 200
        assert r_tuned["choices"][0]["text"] != r_base["choices"][0]["text"]
        st, _ = call(
            "POST", "/v1/completions", {**body, "model": "missing"}
        )
        assert st == 404
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)


def test_boot_time_adapters_from_config(tmp_path):
    """TPU_LORA_ADAPTERS=name=path[,name2=p2] loads PEFT checkpoints at
    engine boot (the from_config seam); malformed entries fail loudly."""
    from safetensors.numpy import save_file

    from gofr_tpu.config import MockConfig

    rng = np.random.default_rng(9)
    tensors = {}
    for t, mod in (("wq", "q_proj"), ("wv", "v_proj")):
        d_in, d_out = lora_dims(CFG, t)
        for i in range(CFG.n_layers):
            tensors[
                f"base_model.model.model.layers.{i}.self_attn.{mod}"
                f".lora_A.weight"
            ] = rng.standard_normal((4, d_in)).astype(np.float32) * 0.5
            tensors[
                f"base_model.model.model.layers.{i}.self_attn.{mod}"
                f".lora_B.weight"
            ] = rng.standard_normal((d_out, 4)).astype(np.float32) * 0.5
    (tmp_path / "adapter_config.json").write_text(json.dumps({
        "r": 4, "lora_alpha": 4.0,
        "target_modules": ["q_proj", "v_proj"],
    }))
    save_file(tensors, str(tmp_path / "adapter_model.safetensors"))

    cfg = {
        "TPU_MODEL": "llama-tiny-f32", "TPU_KV_SLOTS": "2",
        "TPU_MAX_LEN": "128", "TPU_LORA_SLOTS": "2", "TPU_LORA_RANK": "4",
        "TPU_LORA_ADAPTERS": f"boot={tmp_path}",
    }
    eng = InferenceEngine.from_config(MockConfig(cfg))
    assert eng.lora_names() == ["boot"]
    eng.start_sync()
    try:
        base = eng.generate_sync(
            "hi", max_new_tokens=6, temperature=0.0, stop_on_eos=False,
            timeout=120,
        ).token_ids
        tuned = eng.generate_sync(
            "hi", max_new_tokens=6, temperature=0.0, stop_on_eos=False,
            timeout=120, adapter="boot",
        ).token_ids
        assert tuned != base  # the boot adapter actually loaded weights
    finally:
        eng.stop_sync()

    with pytest.raises(ValueError, match="name=path"):
        InferenceEngine.from_config(MockConfig({
            **cfg, "TPU_LORA_ADAPTERS": "not-an-assignment",
        }))
