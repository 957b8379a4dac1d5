"""The quickest proof that the serving path still starts on the chip.

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # one host with four chips

Drives the main path once, through the entry point a user calls
(``examples/openai-server/main.py``: ``App`` + ``add_openai_routes``,
configured from the process environment), at the published widths and
all 32 layers of ``mistral-7b`` with seeded random weights:

* ``devices``  — what JAX finds (platform, device kind, count, versions);
* ``kernels``  — the three Pallas kernels compiled through Mosaic
  (``interpret=False``) at mistral-7b head geometry against the dense
  path, the case matrix of ``tests/test_pallas_kernels.py``;
* ``default``  — the server a user gets from ``TPU_MODEL``, ``TPU_QUANT``,
  ``TPU_KV_SLOTS`` and ``TPU_MAX_LEN`` alone, answering completions over
  real HTTP (unary, streamed, greedy, seeded-sampled);
* ``paged``    — the same with the paged pool, the radix prefix cache and
  an 8k cache, with one prompt past the 4,096-token sliding window and a
  repeat that is a prefix hit.

With ``--chips 4``: ``tp4`` (bf16, ``TPU_TP=4``) and ``replicas4`` (int8,
``TPU_REPLICAS=4``, one engine per chip).

This process never imports jax: the chip has one owner at a time, so each
phase is a child that is started, spoken to over HTTP, and stopped before
the next one starts. Children run under ``JAX_PLATFORMS=tpu`` so JAX
raises when there is no TPU instead of falling back to the CPU. Any phase
that fails makes the exit code non-zero and nothing is printed to stdout.
On success stdout is two lines of JSON: first the report (versions, the
cache directory and per phase the set-up facts: compile seconds, peak HBM
— not speeds), also kept in ``chiprun_out/chip_smoke/report.json``; then,
last, the verdict a harness reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reported it.

``--rehearse-cpu`` runs the same phases on the CPU at a tiny size to
debug this script without a chip. It says so, and prints no result.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.util
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
ENTRY_POINT = os.path.join(REPO, "examples", "openai-server", "main.py")
KERNEL_TESTS = os.path.join(REPO, "tests", "test_pallas_kernels.py")
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

BOOT_TIMEOUT_S = 900.0      # engine init at 7B width, cold
REQUEST_TIMEOUT_S = 600.0   # the first request of a boot compiles
KERNELS_TIMEOUT_S = 900.0
STOP_TIMEOUT_S = 90.0


class SmokeFailure(Exception):
    """A phase did not do what it must; the message says which check."""


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------------
# children (the only code here that imports jax)
# ----------------------------------------------------------------------


def load_module(name: str, path: str) -> Any:
    """Import a repo file that is not on a package path."""
    spec = importlib.util.spec_from_file_location(name, path)
    assert spec is not None and spec.loader is not None
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def child_devices() -> None:
    """Print what JAX finds, as one JSON line."""
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    print(json.dumps({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": metadata.version("libtpu"),
    }), flush=True)


def child_kernels(rehearse: bool) -> None:
    """Run the serving-kernel case matrix against the dense path and
    print one JSON line. On the chip: compiled, at mistral-7b geometry."""
    from gofr_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    cases_mod = load_module("test_pallas_kernels", KERNEL_TESTS)
    cases_mod.INTERPRET = rehearse
    geometry = cases_mod.TINY if rehearse else cases_mod.MISTRAL_7B

    import jax

    check(
        rehearse or jax.default_backend() == "tpu",
        f"kernel phase needs a TPU, found {jax.default_backend()!r}",
    )
    worst: dict[str, float] = {}
    t0 = time.monotonic()
    for case in cases_mod.serving_kernel_cases():
        err = cases_mod.run_serving_kernel_case(case, geometry)
        log(f"kernels: {case} max|diff|={err:.2e}")
        worst[case.kernel] = max(worst.get(case.kernel, 0.0), err)
    print(json.dumps({
        "cases": len(cases_mod.serving_kernel_cases()),
        "interpret": bool(cases_mod.INTERPRET),
        "geometry": geometry._asdict(),
        "max_abs_diff": {k: round(v, 6) for k, v in worst.items()},
        "seconds": round(time.monotonic() - t0, 1),
    }), flush=True)


def child_serve() -> None:
    """The example server, exactly as ``python main.py`` builds it, plus
    one signal: SIGUSR1 arms the engines' warm-up fence
    (``mark_steady_state``), which has no HTTP surface."""
    app = load_module("openai_server", ENTRY_POINT).main()

    def arm_fence(signum: int, frame: Any) -> None:  # noqa: ARG001
        tpu = app.container.tpu
        engines = (
            [r.engine for r in tpu.replicas if hasattr(r, "engine")]
            if hasattr(tpu, "replicas") else [tpu]
        )
        for engine in engines:
            engine.mark_steady_state()

    signal.signal(signal.SIGUSR1, arm_fence)
    app.run()


# ----------------------------------------------------------------------
# orchestrator
# ----------------------------------------------------------------------


def child_env(rehearse: bool, cache_dir: str, extra: dict) -> dict:
    env = dict(os.environ)
    # JAX itself raises when the platform is missing: no CPU fallback.
    env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    # One cache for every child: the JAX variable if the machine came
    # with it, else the fixed checkout path — handed down as the variable
    # so no child sets a directory in code.
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env.update(extra)
    return env


def cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def cache_facts(cache_dir: str, entries_before: int) -> dict:
    """Warm = the phase found entries and had nothing left to add."""
    entries_after = cache_entries(cache_dir)
    return {
        "cache_entries_before": entries_before,
        "cache_entries_after": entries_after,
        "cache_warm": 0 < entries_before == entries_after,
    }


def run_child(
    kind: str, rehearse: bool, cache_dir: str, timeout_s: float,
    extra_env: Optional[dict] = None,
) -> dict:
    """Run a child to completion; its last stdout line is its JSON."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", kind]
    if rehearse:
        argv.append("--rehearse-cpu")
    proc = subprocess.run(
        argv, env=child_env(rehearse, cache_dir, extra_env or {}),
        stdout=subprocess.PIPE, timeout=timeout_s, cwd=REPO,
    )
    out = proc.stdout.decode("utf-8", "replace").strip()
    check(
        proc.returncode == 0,
        f"{kind} child exited {proc.returncode} (its stderr is above)",
    )
    return json.loads(out.splitlines()[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return int(s.getsockname()[1])


class Server:
    """One server child: started, spoken to over HTTP, stopped."""

    def __init__(
        self, name: str, config: dict, rehearse: bool, cache_dir: str
    ) -> None:
        self.name = name
        self.http_port, self.ops_port = free_port(), free_port()
        os.makedirs(LOG_DIR, exist_ok=True)
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        env = child_env(rehearse, cache_dir, {
            "APP_NAME": f"chip-smoke-{name}",
            "HTTP_PORT": str(self.http_port),
            "METRICS_PORT": str(self.ops_port),
            "LOG_LEVEL": "INFO",
            **config,
        })
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", "serve"],
            env=env, stdout=self._log, stderr=subprocess.STDOUT, cwd=REPO,
        )
        self.sent = 0
        self.succeeded = 0
        self._count_lock = threading.Lock()  # the burst posts from threads

    def log_tail(self, n: int = 60) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as fh:
            lines = fh.read().decode("utf-8", "replace").splitlines()
        return "\n".join(lines[-n:])

    def request(
        self, method: str, path: str, body: Any = None, *, ops: bool = False,
        timeout: float = 30.0,
    ) -> tuple[int, bytes]:
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.ops_port if ops else self.http_port,
            timeout=timeout,
        )
        try:
            payload = None if body is None else json.dumps(body).encode()
            headers = {"Content-Type": "application/json"} if payload else {}
            conn.request(method, path, body=payload, headers=headers)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path: str, *, ops: bool = False) -> dict:
        status, raw = self.request("GET", path, ops=ops)
        check(status == 200, f"{self.name}: GET {path} → {status}")
        return json.loads(raw)

    def tpu_health(self) -> dict:
        """``details.tpu`` of /.well-known/health. A server whose engine
        failed to initialise is still UP and simply has no such key —
        here that is a failure."""
        health = self.get_json("/.well-known/health")
        health = health.get("data", health)
        tpu = health.get("details", {}).get("tpu")
        check(
            tpu is not None,
            f"{self.name}: the app is up but container.tpu is None — the "
            f"engine failed to initialise; server log:\n{self.log_tail()}",
        )
        return tpu

    def wait_ready(self) -> float:
        t0 = time.monotonic()
        while True:
            check(
                self.proc.poll() is None,
                f"{self.name}: server exited {self.proc.returncode} during "
                f"boot; log:\n{self.log_tail()}",
            )
            check(
                time.monotonic() - t0 < BOOT_TIMEOUT_S,
                f"{self.name}: not serving after {BOOT_TIMEOUT_S:.0f}s; "
                f"log:\n{self.log_tail()}",
            )
            try:
                tpu = self.tpu_health()
            except (ConnectionError, socket.timeout, OSError):
                time.sleep(1.0)  # not listening yet
                continue
            if tpu.get("status") == "UP":
                return time.monotonic() - t0
            time.sleep(1.0)

    def complete(self, body: dict) -> dict:
        """POST /v1/completions; a 400 here is what a server without an
        engine answers, and any non-200 is a failure."""
        with self._count_lock:
            self.sent += 1
        status, raw = self.request(
            "POST", "/v1/completions", body, timeout=REQUEST_TIMEOUT_S
        )
        check(
            status == 200,
            f"{self.name}: /v1/completions → {status}: {raw[:400]!r}",
        )
        if body.get("stream"):
            out = parse_sse(raw.decode("utf-8"))
        else:
            out = json.loads(raw)
        with self._count_lock:
            self.succeeded += 1
        return out

    def arm_fence(self) -> None:
        self.proc.send_signal(signal.SIGUSR1)
        t0 = time.monotonic()
        while not all(c["warm"] for c in self.compile_stats()):
            check(
                time.monotonic() - t0 < 30,
                f"{self.name}: warm-up fence not armed after SIGUSR1",
            )
            time.sleep(0.2)

    def capacity(self) -> list[dict]:
        """/debug/capacity, one record per engine (a pool nests them)."""
        report = self.get_json("/debug/capacity", ops=True)["tpu"]
        if "replicas" in report:
            return list(report["replicas"].values())
        return [report]

    def compile_stats(self) -> list[dict]:
        return [c["compiles"] for c in self.capacity()]

    def metric_samples(self, name: str) -> dict[str, float]:
        """Samples of one metric from /metrics, keyed by label text."""
        status, raw = self.request("GET", "/metrics", ops=True)
        check(status == 200, f"{self.name}: GET /metrics → {status}")
        out: dict[str, float] = {}
        for line in raw.decode("utf-8").splitlines():
            if line.startswith(name) and line[len(name)] in "{ ":
                labels, _, value = line[len(name):].rpartition(" ")
                out[labels] = float(value)
        return out

    def stop(self) -> None:
        """SIGTERM is the graceful stop; the server must exit 0 on it."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name}: server ignored SIGTERM for "
                f"{STOP_TIMEOUT_S:.0f}s; log:\n{self.log_tail()}"
            ) from None
        check(
            code == 0,
            f"{self.name}: server exited {code} on SIGTERM; log:\n"
            f"{self.log_tail()}",
        )

    def close(self) -> None:
        """Nothing may outlive the phase, however it ended."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def parse_sse(text: str) -> dict:
    """Fold a completions SSE stream into {text, token_ids, finish_reason}."""
    events = [
        line[len("data: "):] for line in text.splitlines()
        if line.startswith("data: ")
    ]
    check(events and events[-1] == "[DONE]", "stream did not end with [DONE]")
    out: dict[str, Any] = {"text": "", "token_ids": [], "finish_reason": None}
    for raw in events[:-1]:
        event = json.loads(raw)
        check("error" not in event, f"stream error event: {event}")
        for choice in event["choices"]:
            out["text"] += choice.get("text", "")
            out["token_ids"] += choice.get("token_ids", [])
            if choice.get("finish_reason"):
                out["finish_reason"] = choice["finish_reason"]
            if "prompt_tokens" in choice:
                out["prompt_tokens"] = choice["prompt_tokens"]
    return out


def prompt_ids(rng: random.Random, n: int, vocab: int) -> list[int]:
    """Seeded token-id prompt; skips the byte tokenizer's BOS/EOS/PAD."""
    ids: list[int] = []
    while len(ids) < n:
        token = rng.randrange(3, vocab)
        if token not in (256, 257, 258):
            ids.append(token)
    return ids


def check_unary(name: str, reply: dict, n_prompt: int, n_new: int) -> list:
    """Counts are what was asked; every logprob is finite and <= 0.
    Random weights can emit EOS: then the reply says "stop" and is
    shorter, which is the server doing what was asked too."""
    choice = reply["choices"][0]
    usage = reply["usage"]
    lps = choice["logprobs"]["token_logprobs"]
    n_out = usage["completion_tokens"]
    check(
        usage["prompt_tokens"] == n_prompt and len(lps) == n_out
        and (n_out == n_new if choice["finish_reason"] == "length"
             else choice["finish_reason"] == "stop" and 1 <= n_out <= n_new),
        f"{name}: usage {usage}, finish {choice['finish_reason']!r}, "
        f"{len(lps)} logprobs; asked {n_prompt} prompt + {n_new} new",
    )
    check(
        all(isinstance(x, float) and math.isfinite(x) and x <= 0 for x in lps),
        f"{name}: token_logprobs not all finite and <= 0: {lps}",
    )
    return [choice["text"], lps, choice["finish_reason"]]


def twice_identical(
    server: Server, name: str, body: dict, n_prompt: int
) -> list:
    """The same request twice: byte-identical text and logprobs."""
    first = check_unary(
        name, server.complete(body), n_prompt, body["max_tokens"]
    )
    second = check_unary(
        name, server.complete(body), n_prompt, body["max_tokens"]
    )
    check(
        json.dumps(first) == json.dumps(second),
        f"{name}: repeat differs:\n  {first}\n  {second}",
    )
    return first


def request_round(
    server: Server, round_no: int, sizes: dict, *, long_prompt: bool,
    burst: int,
) -> dict:
    """One pass over every request shape. Fresh seeded prompts per round,
    so each round has its own cold serve and its own repeat."""
    rng = random.Random(1000 + round_no)
    vocab, n_new = sizes["vocab"], sizes["new_tokens"]
    facts: dict[str, Any] = {}

    ids = prompt_ids(rng, sizes["prompt"], vocab)
    greedy = {"prompt": ids, "max_tokens": n_new, "temperature": 0,
              "logprobs": True}
    unary = twice_identical(server, "greedy", greedy, len(ids))
    streamed = server.complete({
        "prompt": ids, "max_tokens": n_new, "temperature": 0, "stream": True,
        "stream_options": {"include_tokens": True},
    })
    check(
        [streamed["text"], len(streamed["token_ids"]),
         streamed["finish_reason"]] == [unary[0], len(unary[1]), unary[2]]
        and streamed.get("prompt_tokens") == len(ids),
        f"stream: {streamed} does not match the unary reply {unary}",
    )

    sampled = {"prompt": prompt_ids(rng, sizes["prompt"], vocab),
               "max_tokens": n_new, "temperature": 0.8, "seed": 7,
               "logprobs": True}
    twice_identical(server, "seeded-sampled", sampled, sizes["prompt"])

    if long_prompt:
        # Past the sliding window; the repeat is a radix prefix hit.
        long_ids = prompt_ids(rng, sizes["long_prompt"], vocab)
        before = server.tpu_health()["details"]["prefix_cache"]["hit_tokens"]
        twice_identical(
            server, "long-greedy",
            {"prompt": long_ids, "max_tokens": sizes["long_new_tokens"],
             "temperature": 0, "logprobs": True},
            len(long_ids),
        )
        hit = (
            server.tpu_health()["details"]["prefix_cache"]["hit_tokens"]
            - before
        )
        check(
            hit >= len(long_ids) // 2,
            f"long-greedy repeat hit only {hit} cached prompt tokens of "
            f"{len(long_ids)}",
        )
        facts["long_prompt_tokens"] = len(long_ids)
        facts["prefix_hit_tokens"] = hit

    if burst:
        # Several slots decoding at once (and, behind a pool, several
        # replicas): distinct prompts, so only per-request checks.
        bodies = [
            {"prompt": prompt_ids(rng, sizes["prompt"], vocab),
             "max_tokens": n_new, "temperature": 0, "logprobs": True}
            for _ in range(burst)
        ]
        with ThreadPoolExecutor(max_workers=burst) as pool:
            # map() re-raises a worker's failure here, on this thread.
            replies = list(pool.map(server.complete, bodies))
        for i, reply in enumerate(replies):
            check_unary(f"burst[{i}]", reply, sizes["prompt"], n_new)
    return facts


def serve_phase(
    name: str, config: dict, sizes: dict, rehearse: bool, cache_dir: str,
    *, platform: str, long_prompt: bool = False, burst: int = 3,
    fence: bool = True, expect_mesh: Optional[dict] = None,
    expect_replicas: int = 0,
) -> dict:
    t_phase = time.monotonic()
    entries_before = cache_entries(cache_dir)
    server = Server(name, config, rehearse, cache_dir)
    try:
        boot_s = server.wait_ready()
        details = server.tpu_health()["details"]
        log(f"{name}: serving after {boot_s:.0f}s")
        if expect_replicas:
            check(
                details["total"] == expect_replicas
                and details["serving"] == expect_replicas,
                f"{name}: {details['serving']}/{details['total']} replicas "
                f"serving, expected {expect_replicas}",
            )
        else:
            check(
                details["platform"] == platform,
                f"{name}: the engine is on {details['platform']!r} "
                f"({details['device_kind']!r}), not {platform!r}",
            )
        if expect_mesh is not None:
            check(
                details.get("mesh", {}).get("axes") == expect_mesh,
                f"{name}: mesh {details.get('mesh')} != axes {expect_mesh}",
            )

        # Round 0 compiles every program the plan touches; the fence
        # then makes any further compile a counted steady-state recompile.
        request_round(server, 0, sizes, long_prompt=long_prompt, burst=burst)
        compiles_warm = [c["total"] for c in server.compile_stats()]
        if fence:
            server.arm_fence()
        facts = request_round(
            server, 1, sizes, long_prompt=long_prompt, burst=burst
        )

        capacity = server.capacity()
        compiles = [c["compiles"] for c in capacity]
        if fence:
            recompiles = sum(c["steady_state_recompiles"] for c in compiles)
            exported = server.metric_samples(
                "app_tpu_steady_state_recompiles_total"
            )
            check(
                recompiles == 0 and not any(exported.values())
                and [c["total"] for c in compiles] == compiles_warm,
                f"{name}: compiles after the warm-up fence: {compiles} "
                f"(/metrics: {exported})",
            )
        # Per-device runtime accounting of each engine's own device(s).
        device_mem = [c["hbm"].get("device") or {} for c in capacity]
        if not rehearse:
            check(
                all(m.get("bytes_in_use", 0) > 2**30 for m in device_mem),
                f"{name}: an engine's device holds under 1 GiB: {device_mem}",
            )
        health = server.tpu_health()["details"]
        hbm = health.get("hbm", [])
        if expect_mesh is not None and not rehearse:
            used = [d["bytes_in_use"] for d in hbm]
            check(
                len(used) == math.prod(expect_mesh.values())
                and min(used) > 2**30 and max(used) < 2 * min(used),
                f"{name}: memory in use is not of one order on every mesh "
                f"device: {hbm}",
            )
        if expect_replicas and not rehearse:
            chips = server.metric_samples("app_tpu_hbm_used_bytes")
            check(
                len(chips) == expect_replicas
                and min(chips.values()) > 2**30,
                f"{name}: expected {expect_replicas} chips each holding an "
                f"engine, /metrics shows {chips}",
            )
        server.stop()
    finally:
        server.close()
    return {
        "wall_s": round(time.monotonic() - t_phase, 1),
        "boot_s": round(boot_s, 1),
        "compile_s": round(sum(
            p["seconds_total"]
            for c in compiles for p in c["programs"].values()
        ), 1),
        "compiles": sum(c["total"] for c in compiles),
        "steady_state_recompiles": sum(
            c["steady_state_recompiles"] for c in compiles
        ),
        **cache_facts(cache_dir, entries_before),
        "requests_sent": server.sent,
        "requests_succeeded": server.succeeded,
        "peak_hbm_bytes": [
            m.get("peak_bytes_in_use") for m in device_mem
        ],
        "hbm_bytes_in_use": [d["bytes_in_use"] for d in hbm],
        **facts,
    }


def orchestrate(chips: int, rehearse: bool) -> dict:
    for path in (ENTRY_POINT, KERNEL_TESTS, os.path.join(REPO, "gofr_tpu")):
        check(os.path.exists(path), f"not a checkout of the repo: no {path}")
    sys.path.insert(0, REPO)
    from gofr_tpu.compile_cache import resolve_compile_cache_dir

    cache_dir = resolve_compile_cache_dir()
    platform = "cpu" if rehearse else "tpu"
    extra = {}
    if rehearse:
        log("REHEARSAL on the CPU at tiny size: this is not a chip result")
        extra["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
        # Off-TPU the kernels run only when forced, interpreted.
        extra["GOFR_TPU_FLASH"] = "1"

    log(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries)")
    try:
        found = run_child("devices", rehearse, cache_dir, 120, extra)
    except SmokeFailure as exc:
        raise SmokeFailure(
            f"no {platform.upper()}: JAX could not initialize under "
            f"JAX_PLATFORMS={platform} ({exc})"
        ) from None
    log(f"devices: {found}")
    check(
        found["platform"] == platform and found["count"] == chips,
        f"need {chips} {platform} device(s); JAX found {found['count']} × "
        f"{found['platform']!r} ({found['device_kind']!r})",
    )

    if rehearse:
        model = {"TPU_MODEL": "llama-tiny", **extra}
        sizes = {"vocab": 250, "prompt": 40, "new_tokens": 6,
                 "long_prompt": 150, "long_new_tokens": 4}
        short_len, long_len = "128", "256"
    else:
        model = {"TPU_MODEL": "mistral-7b"}
        sizes = {"vocab": 32000, "prompt": 120, "new_tokens": 16,
                 "long_prompt": 4400, "long_new_tokens": 8}
        short_len, long_len = "1024", "8192"
    int8 = {**model, "TPU_QUANT": "int8", "TPU_KV_SLOTS": "4"}

    phases: dict[str, Any] = {}
    if chips == 1:
        t0 = time.monotonic()
        entries = cache_entries(cache_dir)
        kernels = run_child(
            "kernels", rehearse, cache_dir, KERNELS_TIMEOUT_S, extra
        )
        phases["kernels"] = {
            "wall_s": round(time.monotonic() - t0, 1),
            **cache_facts(cache_dir, entries), **kernels,
        }
        log(f"kernels: {phases['kernels']}")
        phases["default"] = serve_phase(
            "default", {**int8, "TPU_MAX_LEN": short_len}, sizes, rehearse,
            cache_dir, platform=platform,
        )
        log(f"default: {phases['default']}")
        phases["paged"] = serve_phase(
            "paged",
            {**int8, "TPU_MAX_LEN": long_len, "TPU_KV_BLOCK": "32",
             "TPU_AUTO_PREFIX": "true"},
            sizes, rehearse, cache_dir, platform=platform, long_prompt=True,
        )
        log(f"paged: {phases['paged']}")
    else:
        # llama-tiny (the rehearsal) has two kv heads to shard, not four.
        tp = 2 if rehearse else chips
        phases["tp4"] = serve_phase(
            "tp4",
            {**model, "TPU_TP": str(tp), "TPU_KV_SLOTS": "4",
             "TPU_MAX_LEN": short_len},
            sizes, rehearse, cache_dir, platform=platform,
            expect_mesh={"tp": tp},
        )
        log(f"tp4: {phases['tp4']}")
        # One engine per chip behind the pool. No fence: the router
        # decides which replicas the warm-up round reaches, so a replica
        # may compile for the first time in the second round.
        phases["replicas4"] = serve_phase(
            "replicas4",
            {**int8, "TPU_REPLICAS": str(chips), "TPU_MAX_LEN": short_len,
             "TPU_PROBE_INTERVAL_S": "0"},
            sizes, rehearse, cache_dir, platform=platform, burst=2 * chips,
            fence=False, expect_replicas=chips,
        )
        log(f"replicas4: {phases['replicas4']}")

    return {
        "device": {
            "platform": found["platform"],
            "kind": found["device_kind"],
            "count": found["count"],
        },
        "versions": {k: found[k] for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "phases": phases,
    }


def verdict_line(report: dict) -> str:
    """The last stdout line: these keys and no others."""
    return json.dumps({"ok": True, "device": report["device"]})


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="debug this script on the CPU at tiny size; prints no result",
    )
    parser.add_argument(
        "--child", choices=("devices", "kernels", "serve"),
        help=argparse.SUPPRESS,
    )
    args = parser.parse_args()
    if args.child:
        sys.path.insert(0, REPO)
        if args.child == "devices":
            child_devices()
        elif args.child == "kernels":
            child_kernels(args.rehearse_cpu)
        else:
            child_serve()
        return 0
    try:
        report = orchestrate(args.chips, args.rehearse_cpu)
    except (SmokeFailure, subprocess.TimeoutExpired) as exc:
        log(f"FAILED: {exc}")
        return 1
    if args.rehearse_cpu:
        log("rehearsal passed (no result printed: this was the CPU): "
            + json.dumps(report["phases"]))
        return 0
    os.makedirs(LOG_DIR, exist_ok=True)
    with open(os.path.join(LOG_DIR, "report.json"), "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"report": report}))
    print(verdict_line(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
