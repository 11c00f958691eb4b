"""Subprocess runner for tests needing an isolated interpreter.

Most multi-device tests run in-process on the suite's 4 simulated CPU
devices (see conftest.py).  Use this only when a test truly needs a fresh
jax runtime (e.g. different XLA flags than the suite's): child processes
doing XLA collectives schedule erratically under sandboxed kernels, so
prefer in-process.
"""
from __future__ import annotations

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_multidev(code: str, n_devices: int = 4, timeout: int = 240) -> str:
    """Run ``code`` in a subprocess with ``n_devices`` CPU devices.

    The snippet should print its own assertions' evidence; a non-zero exit
    (assertion/exception) fails the calling test with full output attached.
    The child is always killed on the way out — including when the caller
    is interrupted by a per-test timeout (pytest-timeout / conftest
    SIGALRM) — so a slow subprocess can never outlive its test and steal
    CPU from the rest of the suite.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    # the child runs on simulated CPU devices and must never try to load
    # the TPU library (its lock belongs to whichever process took it)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", code], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise AssertionError(
            f"multidev subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{stdout}\n--- stderr ---\n{stderr}")
    return stdout
