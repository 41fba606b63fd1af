import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidevice(code: str, n_devices: int = 8,
                    timeout: int = 560) -> str:
    """Run ``code`` in a subprocess with fake host devices.

    XLA device count is locked at first jax init, so multi-device tests
    must run out of process (the main test process stays at 1 device).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if res.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed:\n--- stdout ---\n"
            f"{res.stdout}\n--- stderr ---\n{res.stderr[-4000:]}")
    return res.stdout


@pytest.fixture(scope="session")
def repo_root():
    return REPO
