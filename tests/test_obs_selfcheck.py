"""Tier-1 wiring for the observability self-check:
``python -m deeplearning4j_tpu.obs.selfcheck`` must exit 0 — registry
lint, metric↔doc parity, a CPU cost_analysis smoke, a flight-recorder
dump round-trip and the loopback federation round-trip.
"""

import os
import subprocess
import sys

import deeplearning4j_tpu

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(
    deeplearning4j_tpu.__file__)))


def test_selfcheck_entry_point_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.obs.selfcheck"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "obs.selfcheck OK" in proc.stdout
