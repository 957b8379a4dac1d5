"""Test harness configuration.

All tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(dp/tp/sp/ep meshes) is exercised without TPU hardware — the
`xla_force_host_platform_device_count` trick the driver also uses for the
multi-chip dry run.
"""

import os

# Before jax is imported: the CPU backend with 8 virtual devices, for
# this process and every subprocess a test starts.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# No persistent compilation cache in the suite: code under test that
# places one (gofr_tpu/compile_cache.py, at process entry points) must
# not make one test's compiles another run's cache hits. The one test
# that pins the cache turns it on for itself.
jax.config.update("jax_enable_compilation_cache", False)

import socket

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: subprocess/multi-minute chaos tests (their own named CI "
        "step runs them; the default tier-1 sweep filters -m 'not slow')",
    )


from gofr_tpu.analysis import lockcheck

if lockcheck.enabled():
    # Lock-discipline validation (TPU_LOCKCHECK=1, e.g. the CI
    # lockcheck-chaos step): every test starts with a fresh order graph
    # and must end with zero recorded violations — an order inversion or
    # a device sync under an instrumented lock anywhere in the test
    # fails THAT test, with the acquisition stacks in the message.
    @pytest.fixture(autouse=True)
    def _lockcheck_clean():
        lockcheck.reset()
        yield
        lockcheck.assert_clean()


@pytest.fixture
def free_port():
    def _get():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    return _get


@pytest.fixture
def mock_config():
    from gofr_tpu.config import MockConfig

    def _make(values=None):
        return MockConfig(values or {})

    return _make
