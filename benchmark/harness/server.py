"""One server child: started, spoken to over HTTP, stopped.

Copied from ``chip_smoke.py`` (``Server``, ``child_env``, ``free_port``,
the SIGUSR1 fence) so that later PRs may change the smoke and not the
yardstick. The parent that uses this module never imports jax: the chip
has one owner at a time, and that owner is the child.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Any, Optional

HARNESS = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = os.path.dirname(HARNESS)
CHECKOUT = os.path.dirname(BENCHMARK)
ENTRY_POINT = os.path.join(CHECKOUT, "examples", "openai-server", "main.py")
SERVE_CHILD = os.path.join(HARNESS, "serve_child.py")

BOOT_TIMEOUT_S = 900.0      # engine init at 7B width, cold
STOP_TIMEOUT_S = 90.0
READY_POLL_S = 0.25         # health poll; bounds what it adds to setup_s


class BenchFailure(Exception):
    """A phase did not do what it must; the message says which check."""


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if the machine came with it, else the
    fixed ``<checkout>/.jax_cache`` — the rule of ``gofr_tpu/compile_cache.py``,
    handed to the child as the variable so it sets no directory in code."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )


def child_env(rehearse: bool, extra: dict) -> dict:
    env = dict(os.environ)
    # JAX itself raises when the platform is missing: no CPU fallback.
    env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    # Persist every program, the sub-second ones too, so that a warm
    # set-up compiles nothing and repeats.
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env.pop("BENCH_RUN", None)  # the driver's own; the program never sees it
    env.update(extra)
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return int(s.getsockname()[1])


class Server:
    """The example server under one configuration file's environment."""

    def __init__(
        self, name: str, config_path: str, env: dict, rehearse: bool,
        log_dir: str,
    ) -> None:
        self.name = name
        self.http_port, self.ops_port = free_port(), free_port()
        os.makedirs(log_dir, exist_ok=True)
        self.log_path = os.path.join(log_dir, "server.log")
        self._log = open(self.log_path, "wb")
        full_env = child_env(rehearse, {
            "APP_NAME": f"benchmark-{name}",
            "HTTP_PORT": str(self.http_port),
            "METRICS_PORT": str(self.ops_port),
            "LOG_LEVEL": "INFO",
            "BENCH_CONFIG_FILE": config_path,
            **{k: str(v) for k, v in env.items()},
        })
        self.proc = subprocess.Popen(
            [sys.executable, SERVE_CHILD],
            env=full_env, stdout=self._log, stderr=subprocess.STDOUT,
            cwd=CHECKOUT,
        )

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

    def get_json(self, path: str, *, ops: bool = False,
                 timeout: float = 30.0) -> dict:
        status, raw = self.request("GET", path, ops=ops, timeout=timeout)
        check(status == 200, f"{self.name}: GET {path} -> {status}")
        return json.loads(raw)

    def post_json(self, path: str, body: Any, timeout: float = 600.0) -> dict:
        status, raw = self.request("POST", path, body, timeout=timeout)
        check(
            status in (200, 201),
            f"{self.name}: POST {path} -> {status}: {raw[:400]!r}",
        )
        return json.loads(raw)

    def metrics_text(self) -> str:
        status, raw = self.request("GET", "/metrics", ops=True)
        check(status == 200, f"{self.name}: GET /metrics -> {status}")
        return raw.decode("utf-8")

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
                time.sleep(READY_POLL_S)  # not listening yet
                continue
            if tpu.get("status") == "UP":
                return time.monotonic() - t0
            time.sleep(READY_POLL_S)

    def capacity(self) -> dict:
        return self.get_json("/debug/capacity", ops=True)["tpu"]

    def arm_fence(self) -> None:
        """SIGUSR1 arms ``mark_steady_state``: any later compile of a
        serving program is a counted steady-state recompile."""
        self.proc.send_signal(signal.SIGUSR1)
        t0 = time.monotonic()
        while not self.capacity()["compiles"]["warm"]:
            check(
                time.monotonic() - t0 < 30,
                f"{self.name}: warm-up fence not armed after SIGUSR1",
            )
            time.sleep(0.1)

    def stop(self) -> None:
        """SIGTERM is the graceful stop; the server must exit 0 on it."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise BenchFailure(
                f"{self.name}: server ignored SIGTERM for "
                f"{STOP_TIMEOUT_S:.0f}s; log:\n{self.log_tail()}"
            ) from None
        check(
            code == 0,
            f"{self.name}: server exited {code} on SIGTERM; log:\n"
            f"{self.log_tail()}",
        )

    def close(self) -> None:
        """Nothing may outlive the run, however it ended."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def device_of(server: Server, chips: int, platform: str) -> dict:
    """What JAX reports in the child; anything but ``chips`` devices of
    ``platform`` with the engine on one of them ends the run."""
    found = server.get_json("/bench/device")
    details = server.tpu_health()["details"]
    check(
        found["platform"] == platform and found["count"] >= chips
        and details["platform"] == platform,
        f"need {chips} {platform} device(s); JAX found {found['count']} x "
        f"{found['platform']!r} ({found['kind']!r}) and the engine is on "
        f"{details['platform']!r}",
    )
    return found


def peak_memory_bytes(server: Server) -> Optional[int]:
    return server.get_json("/bench/device").get("memory_peak_bytes")
