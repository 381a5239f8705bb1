"""Run an SPMD test body in a subprocess with N fake host (CPU) devices.

jax locks the platform device count at first init, so multi-device tests
cannot run inside the main pytest process (which must keep 1 device for
the smoke tests). Each SPMD test ships its body as a source string; the
subprocess prints one JSON line that the test asserts on.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_spmd(body: str, devices: int = 8, timeout: int = 600) -> dict:
    prog = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # fake host devices: the child must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", prog], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"SPMD subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    last = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert last, f"no JSON output:\n{proc.stdout}\n{proc.stderr}"
    return json.loads(last[-1])
