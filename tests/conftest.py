"""Test harness: run everything on a simulated 8-device CPU mesh.

Mirrors the survey's test strategy (SURVEY.md §4): the reference had no
working automated tests; here all "distributed" behavior is validated on
virtual CPU devices via ``--xla_force_host_platform_device_count`` so the
suite runs anywhere, including CI without TPUs.

Must set the env vars BEFORE jax is imported anywhere.
"""

import os

# overwrite, not setdefault: the suite is CPU-only whatever the shell presets
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end smokes (driver recipes)")
