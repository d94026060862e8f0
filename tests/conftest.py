import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
sys.path.insert(0, SRC)


def run_devices(code: str, n_devices: int = 8, x64: bool = False,
                timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with N fake devices (XLA_FLAGS must be
    set before jax initializes, so multi-device tests run out of process).
    Returns stdout; raises on nonzero exit."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout, cwd=REPO)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}")
    return res.stdout


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.RandomState(0)
