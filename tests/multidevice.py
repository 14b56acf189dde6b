"""Reusable multi-device subprocess runner.

Host-platform device multiplication (``--xla_force_host_platform_device_count``)
must be configured before jax initialises, so every test that needs more
than one device runs its body in a SUBPROCESS with ``XLA_FLAGS`` set —
the main pytest process keeps the default single CPU device (the
assignment note in ``tests/conftest.py``).

``run_multidevice`` runs a code string under N forced host devices and
returns its stdout; ``run_multidevice_json`` additionally parses the
LAST stdout line as JSON — the conventional way a subprocess test body
reports structured results (errors, counts) back to the asserting test.

Used by ``tests/test_launch.py`` (sharded-lowering / dry-run paths) and
``tests/test_sharded_buffer.py`` (pod-sharded ingest buffer parity).

CPU only: a parent that has touched JAX holds the accelerator, so it must
never spawn children that need the chip.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_HERE = os.path.dirname(__file__)
SRC = os.path.join(_HERE, "..", "src")
ROOT = os.path.join(_HERE, "..")


def run_multidevice(
    code: str, devices: int = 8, timeout: int = 900, check: bool = True
) -> str:
    """Runs ``code`` in a fresh interpreter seeing ``devices`` CPU devices.

    Returns the subprocess stdout; asserts a zero exit (tail of stderr in
    the failure message) unless ``check=False``.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + ROOT
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=timeout,
    )
    if check:
        assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def run_multidevice_json(code: str, devices: int = 8, timeout: int = 900):
    """As :func:`run_multidevice`; parses the last stdout line as JSON.

    The code string should end with ``print(json.dumps(result))``.
    """
    out = run_multidevice(code, devices=devices, timeout=timeout)
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    assert lines, f"subprocess printed nothing to parse:\n{out!r}"
    return json.loads(lines[-1])
