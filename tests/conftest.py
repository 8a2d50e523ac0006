"""Shared fixtures.  Nothing here touches a device at import time."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]


def _run_cpu_child(code: str, *, devices: int = 8, env: dict = None,
                   timeout: float = 600) -> subprocess.CompletedProcess:
    """Run `code` in a fresh interpreter on the CPU backend with `devices`
    virtual devices, from the repo root, in this process's environment
    (plus `env`).  The child never loads an accelerator runtime."""
    child_env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.pathsep.join(
            p for p in (str(REPO_ROOT / "src"), os.environ.get("PYTHONPATH"))
            if p))
    child_env.update(env or {})
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=REPO_ROOT, env=child_env,
                          timeout=timeout)


@pytest.fixture
def cpu_child():
    """``cpu_child(code, devices=8, env=None)`` -> CompletedProcess."""
    return _run_cpu_child
