import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
sys.path.insert(0, SRC)
sys.path.insert(0, REPO)


def run_with_devices(code: str, num_devices: int = 8, timeout: int = 560):
    """Run a python snippet in a subprocess with N fake host devices
    (the main test process must keep the default 1-device world).  The
    child is held to the CPU, so it never tries to take a chip that
    its parent may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={num_devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="session")
def small_graph():
    from repro.graphs import generators
    return generators.erdos_renyi(200, 8.0, seed=1)


@pytest.fixture(scope="session")
def incidence(small_graph):
    import jax
    from repro.core.rrr import sample_incidence_host
    X, theta = sample_incidence_host(small_graph, 512, jax.random.key(0),
                                     model="IC")
    return np.asarray(X), theta
