"""DCN tier: the non-no-op multi-host path of ``parallel/dcn.py``
exercised by two real processes on one machine (CPU backend, localhost
coordinator) — VERDICT r2 next #8. Each child initializes via
``initialize_multihost``, runs a cross-process allgather, and routes a
request across hosts through the service client + circuit breaker."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import pytest

from gofr_tpu.config import MockConfig
from gofr_tpu.parallel.dcn import initialize_multihost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_no_config_is_single_host_noop():
    assert initialize_multihost(MockConfig({})) is False


def test_two_process_dcn_runtime_and_service_hop():
    coord, http = _free_port(), _free_port()
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    tmpdir = os.path.join(REPO, ".pytest_cache", f"dcn-{coord}")
    os.makedirs(tmpdir, exist_ok=True)
    child = os.path.join(REPO, "tests", "dcn_child.py")
    procs = [
        subprocess.Popen(
            [sys.executable, "-u", child, str(pid), str(coord), str(http), tmpdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out.decode("utf-8", "replace"))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("DCN children timed out:\n" + "\n".join(
            p.stdout.read().decode("utf-8", "replace") for p in procs
        ))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"

    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("DCN_RESULT "):
                r = json.loads(line[len("DCN_RESULT "):])
                results[r["pid"]] = r
    assert set(results) == {0, 1}, outs
    for r in results.values():
        assert r["topo"]["process_count"] == 2
        assert r["allgather_sum"] == 3.0  # 1.0 + 2.0 across processes
    assert results[0]["served_peer"] is True
    assert results[1]["hop"]["process_count"] == 2

    # Multi-host serving: the tp=2-over-DCN engine generation must agree
    # BETWEEN processes (SPMD consistency) and WITH a single-process
    # engine at the same seed/geometry (the collectives changed the
    # placement, not the math).
    toks0, toks1 = results[0]["engine_tokens"], results[1]["engine_tokens"]
    assert toks0 == toks1 and len(toks0) == 16, (toks0, toks1)
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    ref = InferenceEngine(
        "llama-tiny", n_slots=2, max_len=128, window_k=4,
        tokenizer=ByteTokenizer(), seed=0,
    )
    ref.start_sync()
    try:
        base = ref.generate_sync(
            "dcn serving smoke", max_new_tokens=16, temperature=0.0,
            stop_on_eos=False,
        )
    finally:
        ref.stop_sync()
    assert toks0 == [int(t) for t in base.token_ids], (toks0, base.token_ids)

    # dp-over-processes × tp-within-process (DCN × ICI composed): same
    # SPMD-consistency + math-unchanged contract for the pod topology.
    dp0 = results[0]["engine_dp_tp_tokens"]
    dp1 = results[1]["engine_dp_tp_tokens"]
    assert dp0 == dp1 and len(dp0) == 16, (dp0, dp1)
    assert dp0 == [int(t) for t in base.token_ids], (dp0, base.token_ids)
