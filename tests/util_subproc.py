"""Run a python snippet in a subprocess with N fake XLA host devices.

Used by multi-device tests so the main pytest process keeps seeing exactly
one CPU device (required by the smoke tests and benchmarks).
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout


def popen_with_devices(code: str, n_devices: int = 8,
                       clean_faults: bool = True) -> subprocess.Popen:
    """Launch the snippet without waiting — for kill/crash tests.

    Same environment setup as ``run_with_devices`` but returns the live
    ``subprocess.Popen`` so the caller can SIGKILL it mid-run and inspect
    the on-disk state it left behind. ``clean_faults`` strips any ambient
    ``REPRO_FAULTS`` so determinism tests control injection explicitly.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if clean_faults:
        env.pop("REPRO_FAULTS", None)
    return subprocess.Popen(
        [sys.executable, "-c", code],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
