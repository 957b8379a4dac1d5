"""OpenAI-compatible surface: /v1/completions, /v1/chat/completions
(non-stream + SSE streaming over chunked transfer), /v1/models — wire
shapes an off-the-shelf OpenAI SDK expects."""

from __future__ import annotations

import asyncio
import http.client
import json
import threading

import pytest

from gofr_tpu import App
from gofr_tpu.config import MockConfig
from gofr_tpu.serving.openai_compat import (
    add_openai_routes,
    default_chat_template,
)


@pytest.fixture(scope="module")
def oai_app():
    app = App(config=MockConfig({
        "APP_NAME": "oai-test", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2", "TPU_MAX_LEN": "128",
    }))
    add_openai_routes(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    asyncio.run_coroutine_threadsafe(app.start(), loop).result(timeout=60)
    yield app
    asyncio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
    loop.call_soon_threadsafe(loop.stop)


def _conn(app) -> http.client.HTTPConnection:
    return http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=120)


def test_completions_non_stream(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "model": "llama-tiny", "prompt": "hello", "max_tokens": 8,
        "temperature": 0,
    }))
    r = c.getresponse()
    assert r.status == 200  # OpenAI wire-compat: POST answers 200, not 201
    body = json.loads(r.read())
    assert body["object"] == "text_completion"
    assert body["id"].startswith("cmpl-")
    # Budget exhausted without eos → "length" (this model never emits eos
    # for this greedy prompt).
    assert body["choices"][0]["finish_reason"] == "length"
    assert isinstance(body["choices"][0]["text"], str)
    usage = body["usage"]
    assert usage["total_tokens"] == (
        usage["prompt_tokens"] + usage["completion_tokens"]
    )
    assert 1 <= usage["completion_tokens"] <= 8


def test_chat_completions_non_stream(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [
            {"role": "system", "content": "be brief"},
            {"role": "user", "content": "hi"},
        ],
        "max_tokens": 6, "temperature": 0,
    }))
    body = json.loads(c.getresponse().read())
    assert body["object"] == "chat.completion"
    msg = body["choices"][0]["message"]
    assert msg["role"] == "assistant"
    assert isinstance(msg["content"], str)


def test_completions_streaming_sse(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "stream me", "max_tokens": 6, "temperature": 0,
        "stream": True,
    }))
    r = c.getresponse()
    assert r.status == 200
    assert r.headers["Content-Type"].startswith("text/event-stream")
    raw = r.read().decode()  # http.client de-chunks transparently
    events = [
        line[len("data: "):]
        for line in raw.split("\n") if line.startswith("data: ")
    ]
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert all(ch["object"] == "text_completion" for ch in chunks)
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    text = "".join(ch["choices"][0]["text"] for ch in chunks)
    assert len(text) > 0


def test_chat_streaming_deltas(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [{"role": "user", "content": "go"}],
        "max_tokens": 4, "temperature": 0, "stream": True,
    }))
    raw = c.getresponse().read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.split("\n")
        if line.startswith("data: ") and not line.endswith("[DONE]")
    ]
    assert events[0]["choices"][0]["delta"]["role"] == "assistant"
    assert events[-1]["choices"][0]["finish_reason"] == "length"
    assert all(e["object"] == "chat.completion.chunk" for e in events)


def test_models_endpoint(oai_app):
    c = _conn(oai_app)
    c.request("GET", "/v1/models")
    body = json.loads(c.getresponse().read())
    assert body["object"] == "list"
    ids = {m["id"] for m in body["data"]}
    assert {"llama-tiny", "llama-3-8b", "llama-3-70b"} <= ids
    loaded = [m for m in body["data"] if m["loaded"]]
    assert [m["id"] for m in loaded] == ["llama-tiny"]


def test_bad_requests_are_400(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/chat/completions", body=b"{not json")
    r = c.getresponse()
    assert r.status == 400
    r.read()  # drain before reusing the keep-alive connection
    c.request("POST", "/v1/chat/completions", body=json.dumps({"messages": []}))
    r = c.getresponse()
    assert r.status == 400
    r.read()


def test_stream_text_matches_non_stream(oai_app):
    """Cumulative UTF-8-safe decode: the streamed deltas concatenate to
    exactly the non-streamed text (ByteTokenizer splits multi-byte
    chars across tokens, so per-token decode would corrupt this)."""
    payload = {"prompt": "match", "max_tokens": 10, "temperature": 0}
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps(payload))
    want = json.loads(c.getresponse().read())["choices"][0]["text"]
    c.request("POST", "/v1/completions",
              body=json.dumps({**payload, "stream": True}))
    raw = c.getresponse().read().decode()
    got = "".join(
        json.loads(line[len("data: "):])["choices"][0]["text"]
        for line in raw.split("\n")
        if line.startswith("data: ") and not line.endswith("[DONE]")
    )
    assert got == want


def test_null_params_and_token_id_prompt(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": [1, 5, 9],  # token-id array form
        "max_tokens": 4, "temperature": None,
    }))
    r = c.getresponse()
    assert r.status == 200
    body = json.loads(r.read())
    assert body["usage"]["prompt_tokens"] == 3


def test_batch_prompts_yield_indexed_choices(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": ["one", "two"], "max_tokens": 3, "temperature": 0,
    }))
    body = json.loads(c.getresponse().read())
    assert [ch["index"] for ch in body["choices"]] == [0, 1]
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": ["one", "two"], "max_tokens": 3, "stream": True,
    }))
    r = c.getresponse()
    assert r.status == 400  # streaming is single-prompt
    r.read()


def test_stream_overlong_prompt_fails_before_headers(oai_app):
    """Prompt validation happens BEFORE the SSE response starts — the
    client gets a real 413, not a dead 200 stream."""
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "x" * 500, "max_tokens": 4, "stream": True,
    }))
    r = c.getresponse()
    assert r.status == 413
    r.read()


def test_stop_sequences_and_finish_reason(oai_app):
    base = {"prompt": "det", "max_tokens": 10, "temperature": 0}
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps(base))
    first = json.loads(c.getresponse().read())["choices"][0]
    assert first["finish_reason"] == "length"  # budget exhausted, no eos
    full = first["text"]
    assert len(full) >= 2
    marker = full[1:3]  # greedy determinism → same text next time
    c.request("POST", "/v1/completions",
              body=json.dumps({**base, "stop": marker}))
    cut = json.loads(c.getresponse().read())["choices"][0]
    assert cut["finish_reason"] == "stop"
    assert cut["text"] == full[: full.find(marker)]
    assert marker not in cut["text"]
    # Streaming with the same stop cuts identically.
    c.request("POST", "/v1/completions",
              body=json.dumps({**base, "stop": marker, "stream": True}))
    raw = c.getresponse().read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.split("\n")
        if line.startswith("data: ") and not line.endswith("[DONE]")
    ]
    text = "".join(e["choices"][0]["text"] for e in events)
    assert text == cut["text"]
    assert events[-1]["choices"][0]["finish_reason"] == "stop"


def test_n_choices_and_logprobs(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "lp", "max_tokens": 4, "temperature": 0,
        "n": 2, "logprobs": 1,
    }))
    body = json.loads(c.getresponse().read())
    assert [ch["index"] for ch in body["choices"]] == [0, 1]
    lp = body["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 4
    assert all(isinstance(v, float) and v <= 0.0 for v in lp["token_logprobs"])
    assert len(lp["tokens"]) == 4
    assert body["usage"]["completion_tokens"] == 8  # 2 choices x 4

    c.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 3, "temperature": 0, "logprobs": True,
    }))
    chat = json.loads(c.getresponse().read())
    content_lp = chat["choices"][0]["logprobs"]["content"]
    assert len(content_lp) == 3
    assert all(e["logprob"] <= 0.0 for e in content_lp)


def test_engine_result_carries_logprobs(oai_app):
    eng = oai_app.container.tpu
    r = eng.generate_sync(
        "lp check", max_new_tokens=5, temperature=0.0, stop_on_eos=False,
        timeout=120,
    )
    assert len(r.token_logprobs) == len(r.token_ids) == 5
    assert all(lp <= 0.0 for lp in r.token_logprobs)


def test_param_validation_limits(oai_app):
    c = _conn(oai_app)

    def post(payload):
        c.request("POST", "/v1/completions", body=json.dumps(payload))
        r = c.getresponse()
        r.read()
        return r.status

    base = {"prompt": "x", "max_tokens": 2}
    assert post({**base, "n": 0}) == 400
    assert post({**base, "n": 1000}) == 400  # unbounded n is a DoS vector
    assert post({**base, "n": 2, "stream": True}) == 400
    assert post({**base, "stop": ""}) == 400  # empty stop matches everything
    assert post({**base, "stop": ["a", "b", "c", "d", "e"]}) == 400


def test_stop_trims_logprobs_aligned(oai_app):
    """Engine-level stop: token/logprob lists are trimmed WITH the text."""
    eng = oai_app.container.tpu
    full = eng.generate_sync(
        "align", max_new_tokens=10, temperature=0.0, stop_on_eos=False,
        timeout=120,
    )
    marker = full.text[2:4]
    cut = eng.generate_sync(
        "align", max_new_tokens=10, temperature=0.0, stop_on_eos=False,
        stop=[marker], timeout=120,
    )
    assert cut.finish_reason == "stop"
    assert cut.text == full.text[: full.text.find(marker)]
    assert len(cut.token_logprobs) == len(cut.token_ids)
    assert len(cut.token_ids) < len(full.token_ids)
    # Trimmed ids decode to a prefix of the kept text.
    assert eng.tokenizer.decode(cut.token_ids) == cut.text[
        : len(eng.tokenizer.decode(cut.token_ids))
    ]
    assert full.finish_reason == "length"


def test_default_chat_template():
    out = default_chat_template([
        {"role": "system", "content": "S"},
        {"role": "user", "content": "U"},
    ])
    assert out == "system: S\nuser: U\nassistant:"


def test_chat_uses_tokenizer_template_when_available():
    """An HF-style tokenizer's own chat template wins over the generic
    flattening; an explicit chat_template arg overrides both."""
    import asyncio as aio

    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    class TemplatedTokenizer(ByteTokenizer):
        def apply_chat_template(self, messages):
            return "<tmpl>" + messages[-1]["content"]

    eng = InferenceEngine(
        "llama-tiny", n_slots=2, max_len=128, tokenizer=TemplatedTokenizer()
    )
    eng.start_sync()
    seen = {}
    orig = eng.submit_generate

    def spy(prompt, **kw):
        seen["prompt"] = prompt
        return orig(prompt, **kw)

    eng.submit_generate = spy
    app = App(config=MockConfig({
        "APP_NAME": "tmpl", "HTTP_PORT": "0", "METRICS_PORT": "0",
    }))
    app.container.tpu = eng
    add_openai_routes(app)
    loop = aio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    aio.run_coroutine_threadsafe(app.start(), loop).result(timeout=30)
    try:
        c = http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=120)
        c.request("POST", "/v1/chat/completions", body=json.dumps({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 2, "temperature": 0,
        }))
        assert c.getresponse().status == 200
        assert seen["prompt"] == "<tmpl>hi"
    finally:
        aio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
        eng.stop_sync()


def test_embeddings_endpoint_with_secondary_encoder():
    """TPU_EMBED_MODEL wires a second (encoder) engine into the container;
    /v1/embeddings serves from it while the primary llm serves chat, and
    /v1/models marks both loaded."""
    app = App(config=MockConfig({
        "APP_NAME": "embed-test", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2", "TPU_MAX_LEN": "128",
        "TPU_EMBED_MODEL": "bert-tiny",
    }))
    add_openai_routes(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    asyncio.run_coroutine_threadsafe(app.start(), loop).result(timeout=60)
    try:
        assert app.container.tpu_embed is not None
        c = _conn(app)
        c.request("POST", "/v1/embeddings", body=json.dumps({
            "input": ["the cat sat", "on the mat"],
        }))
        r = c.getresponse()
        assert r.status == 200
        body = json.loads(r.read())
        assert body["object"] == "list"
        assert [d["index"] for d in body["data"]] == [0, 1]
        dims = {len(d["embedding"]) for d in body["data"]}
        assert len(dims) == 1 and dims.pop() > 0
        assert body["usage"]["prompt_tokens"] > 0

        c = _conn(app)
        c.request("GET", "/v1/models")
        models = json.loads(c.getresponse().read())["data"]
        loaded = {m["id"] for m in models if m["loaded"]}
        assert loaded == {"llama-tiny", "bert-tiny"}

        # Bad input shape → OpenAI-style 400.
        c = _conn(app)
        c.request("POST", "/v1/embeddings", body=json.dumps({"input": []}))
        assert c.getresponse().status == 400
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)


def test_unknown_model_gets_404(oai_app):
    """Naming a model that isn't the loaded one must 404 (OpenAI wire
    code), never silently serve the loaded model's output."""
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "model": "llama-3-8b", "prompt": "hello", "max_tokens": 4,
    }))
    r = c.getresponse()
    assert r.status == 404
    assert "not loaded" in json.loads(r.read())["error"]["message"]

    # The loaded name (and omitting model entirely) still works.
    c = _conn(oai_app)
    c.request("POST", "/v1/chat/completions", body=json.dumps({
        "model": "llama-tiny", "max_tokens": 2,
        "messages": [{"role": "user", "content": "hi"}],
    }))
    assert c.getresponse().status == 200


def test_top_p_zero_maps_to_greedy(oai_app):
    """OpenAI accepts top_p=0 (smallest nucleus = argmax) — it must work
    even on an engine compiled without the nucleus sampler, as greedy."""
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "greedy via top_p", "max_tokens": 4, "top_p": 0,
    }))
    r = c.getresponse()
    assert r.status == 200
    assert json.loads(r.read())["usage"]["completion_tokens"] >= 1


def test_completions_penalties(oai_app):
    # The engine behind oai_app is compiled WITHOUT TPU_PENALTIES: the
    # OpenAI-shaped error must say so (400), mirroring the top_p gate.
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "model": "llama-tiny", "prompt": "hello", "max_tokens": 4,
        "temperature": 0, "frequency_penalty": 0.8,
    }))
    r = c.getresponse()
    body = json.loads(r.read())
    assert r.status == 400
    assert "TPU_PENALTIES" in json.dumps(body)
    c.close()

    app = App(config=MockConfig({
        "APP_NAME": "oai-pen", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2",
        "TPU_MAX_LEN": "128", "TPU_PENALTIES": "true",
    }))
    add_openai_routes(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    asyncio.run_coroutine_threadsafe(app.start(), loop).result(timeout=60)
    try:
        c = _conn(app)
        c.request("POST", "/v1/completions", body=json.dumps({
            "model": "llama-tiny", "prompt": "hello", "max_tokens": 8,
            "temperature": 0, "frequency_penalty": 1.5,
        }))
        r = c.getresponse()
        assert r.status == 200
        out = json.loads(r.read())
        assert out["choices"][0]["text"]
        c.close()
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)


def test_completions_top_logprobs():
    app = App(config=MockConfig({
        "APP_NAME": "oai-lp", "HTTP_PORT": "0", "METRICS_PORT": "0",
        "TPU_MODEL": "llama-tiny", "TPU_KV_SLOTS": "2",
        "TPU_MAX_LEN": "128", "TPU_TOP_LOGPROBS": "4",
    }))
    add_openai_routes(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    asyncio.run_coroutine_threadsafe(app.start(), loop).result(timeout=60)
    try:
        c = _conn(app)
        # completions: logprobs=N → N alternatives per token.
        c.request("POST", "/v1/completions", body=json.dumps({
            "prompt": "hello", "max_tokens": 4, "temperature": 0,
            "logprobs": 3,
        }))
        r = c.getresponse()
        assert r.status == 200
        lp = json.loads(r.read())["choices"][0]["logprobs"]
        assert len(lp["top_logprobs"]) == 4
        # Keyed by decoded token STRING (the OpenAI completions schema):
        # distinct ids may decode identically and collapse, so <= 3.
        assert all(1 <= len(d) <= 3 for d in lp["top_logprobs"])
        # chat: logprobs=true + top_logprobs=N.
        c.request("POST", "/v1/chat/completions", body=json.dumps({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3, "temperature": 0,
            "logprobs": True, "top_logprobs": 2,
        }))
        r = c.getresponse()
        assert r.status == 200
        content = json.loads(r.read())["choices"][0]["logprobs"]["content"]
        assert len(content) == 3
        assert all(len(e["top_logprobs"]) == 2 for e in content)
        c.close()
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)


def test_completions_logprobs_backcompat_without_flag(oai_app):
    # logprobs=N on an engine WITHOUT TPU_TOP_LOGPROBS must stay a 200
    # with null alternatives (pre-flag behavior), never a 400.
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "hello", "max_tokens": 3, "temperature": 0,
        "logprobs": 2,
    }))
    r = c.getresponse()
    assert r.status == 200
    lp = json.loads(r.read())["choices"][0]["logprobs"]
    assert lp["top_logprobs"] is None
    assert len(lp["token_logprobs"]) == 3
    c.close()


def test_stream_options_include_usage(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "hi", "max_tokens": 4, "temperature": 0, "stream": True,
        "stream_options": {"include_usage": True},
    }))
    r = c.getresponse()
    assert r.status == 200
    raw = r.read().decode()
    chunks = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ") and line != "data: [DONE]"
    ]
    assert raw.rstrip().endswith("data: [DONE]")
    usage_chunks = [ch for ch in chunks if "usage" in ch]
    assert len(usage_chunks) == 1
    u = usage_chunks[0]
    assert u["choices"] == []
    assert u["usage"]["completion_tokens"] == 4
    assert u["usage"]["total_tokens"] == (
        u["usage"]["prompt_tokens"] + 4
    )
    c.close()


def test_chat_top_logprobs_backcompat_without_flag(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 3, "temperature": 0,
        "logprobs": True, "top_logprobs": 2,
    }))
    r = c.getresponse()
    assert r.status == 200
    content = json.loads(r.read())["choices"][0]["logprobs"]["content"]
    assert all(e["top_logprobs"] == [] for e in content)
    c.close()


def test_completions_echo(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "hello there", "max_tokens": 3, "temperature": 0,
        "echo": True,
    }))
    r = c.getresponse()
    assert r.status == 200
    text = json.loads(r.read())["choices"][0]["text"]
    assert text.startswith("hello there")
    assert len(text) > len("hello there")
    c.close()


TTFT_SPLIT = (
    "entry_s", "queue_wait_s", "prefill_wait_s", "prefill_dispatch_s",
    "first_token_wait_s", "delivery_s",
)


def _newest_flight_record(app) -> dict:
    flight = app.container.tpu.flight_records()
    return max(flight["records"] + flight["pinned"], key=lambda e: e["rid"])


@pytest.mark.parametrize("path,body", [
    ("/v1/completions", {"prompt": "split my first token"}),
    ("/v1/chat/completions", {"messages": [{"role": "user", "content": "go"}]}),
])
def test_streamed_request_splits_its_time_to_first_token(oai_app, path, body):
    """The handler stamps ``received`` before tokenisation and
    ``first_written`` after the first token's chunk went out: the
    request's flight record carries the six phases from socket to
    socket, and they are the whole of that time."""
    c = _conn(oai_app)
    c.request("POST", path, body=json.dumps({
        **body, "max_tokens": 48, "temperature": 0, "stream": True,
        "stream_options": {"include_tokens": True},
    }))
    r = c.getresponse()
    assert r.status == 200 and r.read().decode().rstrip().endswith("[DONE]")
    c.close()
    phases = _newest_flight_record(oai_app)["phases"]
    assert set(TTFT_SPLIT) <= set(phases)
    assert all(phases[k] >= 0 for k in TTFT_SPLIT)
    # entry + (submit → first token) + delivery; rounded to the µs in
    # the record, so to within a few of them.
    assert sum(phases[k] for k in TTFT_SPLIT) == pytest.approx(
        phases["entry_s"] + phases["ttft_s"] + phases["delivery_s"], abs=1e-5
    )
    assert phases["delivery_s"] < 1.0 and phases["entry_s"] < 1.0


def test_unstreamed_request_has_an_entry_phase_and_no_delivery(oai_app):
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "no stream", "max_tokens": 4, "temperature": 0,
    }))
    assert c.getresponse().status == 200
    c.close()
    phases = _newest_flight_record(oai_app)["phases"]
    assert [k for k in TTFT_SPLIT if k in phases] == list(TTFT_SPLIT[:5])


def test_streamed_request_times_its_hand_off_once_a_window(oai_app):
    """The scheduler stamps a stream once a window (the tokens in hand) and
    the SSE handler, back from a chunk's write, records the time since the
    newest stamp it has not recorded: one record a window, not a token."""
    hist = {i.name: i for i in oai_app.container.metrics.instruments()}[
        "app_tpu_token_handoff_seconds"
    ]

    def count() -> int:
        return sum(c for _, (_, c) in hist.collect().values())

    before = count()
    c = _conn(oai_app)
    c.request("POST", "/v1/completions", body=json.dumps({
        "prompt": "time my hand-off", "max_tokens": 40, "temperature": 0,
        "stream": True, "stream_options": {"include_tokens": True},
    }))
    r = c.getresponse()
    body = r.read().decode()
    c.close()
    tokens = sum(
        len(json.loads(line[6:])["choices"][0].get("token_ids", []))
        for line in body.splitlines()
        if line.startswith("data: {") and '"choices"' in line
    )
    k = oai_app.container.tpu.window_k
    records = count() - before
    assert tokens >= 2 * k
    # The first token comes from the prefill step's flush, the rest a
    # window at a time (the first window repeats that first token).
    assert 2 <= records <= 2 + (tokens - 1 + k - 1) // k < tokens
