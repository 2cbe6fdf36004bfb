import functools
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_in_subprocess(code: str, devices: int = 8, timeout: int = 600
                      ) -> subprocess.CompletedProcess:
    """Run `code` in a fresh python with N fake XLA host devices.

    Multi-device behaviours (shard_map collectives, pipelines, meshes)
    can't run in the main pytest process, which is pinned to 1 device.
    The child runs on the CPU: its devices are fake host devices, and a
    chip, if there is one, belongs to the parent.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@functools.lru_cache(maxsize=None)
def fake_devices_available(n: int = 8) -> bool:
    """Whether a subprocess can actually get `n` fake XLA host devices
    (some platforms ignore --xla_force_host_platform_device_count)."""
    r = run_in_subprocess(
        f"import jax; assert jax.device_count() >= {n}", devices=n,
        timeout=300)
    return r.returncode == 0


@pytest.fixture(scope="session")
def require_fake_devices():
    """Skip (not fail) multi-device tests on hosts that can't provide
    enough devices."""
    if not fake_devices_available(8):
        pytest.skip("insufficient jax devices (fake host devices "
                    "unavailable); multidevice tests need >= 8")


@pytest.fixture
def subproc():
    return run_in_subprocess


@pytest.fixture
def rng():
    return np.random.default_rng(0)
