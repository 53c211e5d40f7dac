import os
import subprocess
import sys
import textwrap

import jax
import pytest

# Tests run single-device by default; multi-device tests spawn subprocesses
# with XLA_FLAGS so the main process's jax device count stays untouched.

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_py(code: str, devices: int = 8, timeout: int = 560,
           env_extra=None) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


@pytest.fixture
def subproc():
    def _run(code, devices=8, timeout=560, env_extra=None):
        r = run_py(code, devices=devices, timeout=timeout, env_extra=env_extra)
        assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
        return r.stdout
    return _run


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)

