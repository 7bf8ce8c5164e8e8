"""Distributed-path equivalence, run in a subprocess with 8 placeholder
CPU devices (keeps the main pytest process at 1 device)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # subprocess + 8 placeholder devices; CI fast lane skips


def test_distributed_equivalence():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = os.path.join(os.path.dirname(__file__), "distributed_check.py")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "ALL OK" in out.stdout
