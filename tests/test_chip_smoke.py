"""chip_smoke.py's contract, as far as a box without a chip can pin it:
the orchestrator stays off jax, a missing TPU is a fast non-zero exit
with no result, and the compile cache has one resolvable home. No engine
is built here; the smoke itself runs on the chip (``chiprun``)."""

import glob
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def test_importing_chip_smoke_leaves_jax_unimported():
    """The orchestrating process must never hold the chip: a fresh
    interpreter that imports chip_smoke has no jax in sys.modules."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke, sys; assert 'jax' not in sys.modules"],
        cwd=REPO, capture_output=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()


@pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="a TPU is attached: the smoke would find it and run",
)
def test_no_tpu_is_a_fast_nonzero_exit_without_a_result():
    """Here JAX is held to the CPU; the smoke forces its children to
    JAX_PLATFORMS=tpu, so it must fail naming the missing TPU — quickly,
    and with nothing on stdout for a harness to mistake for a result."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, SMOKE], cwd=REPO, capture_output=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout == b""
    assert b"no TPU" in proc.stderr and b"FAILED" in proc.stderr
    assert time.monotonic() - t0 < 60


def test_verdict_line_has_the_contract_keys_and_no_others():
    """What a harness reads last: ok and the device as JAX reported it.
    The set-up facts go on the line before, never into this object."""
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    report = {"device": device, "versions": {}, "phases": {"default": {}}}
    line = chip_smoke.verdict_line(report)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}


def test_compile_cache_resolver(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins; unset, every call names the same
    directory inside the checkout — never a tempdir, a pid or the time."""
    from gofr_tpu.compile_cache import ENV_VAR, resolve_compile_cache_dir

    monkeypatch.setenv(ENV_VAR, "/x/cache")
    assert resolve_compile_cache_dir() == "/x/cache"
    monkeypatch.delenv(ENV_VAR)
    first = resolve_compile_cache_dir()
    assert first == resolve_compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")


def test_set_variable_means_no_directory_is_written_in_code(monkeypatch):
    """With the JAX variable set, enable_compile_cache leaves
    jax_compilation_cache_dir alone (JAX read the variable itself)."""
    import jax

    from gofr_tpu.compile_cache import ENV_VAR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, "/x/cache")
    assert enable_compile_cache() == "/x/cache"
    assert jax.config.jax_compilation_cache_dir == before
