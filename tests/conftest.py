import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidevice(script: str, n_devices: int = 8, timeout: int = 600):
    """Run a snippet in a subprocess with N forced host devices.

    Tests that need >1 device use this so the main pytest process keeps the
    default single-device view (the dry-run owns the 512-device flag). The
    child is held to the CPU: the forced device count only applies there,
    and a parent may already hold the TPU library.
    """
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
               JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)
