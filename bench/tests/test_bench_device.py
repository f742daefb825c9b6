"""The measurement path refuses anything but a TPU and prints no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]


def test_check_devices_refuses_the_cpu():
    with pytest.raises(harness.NoAccelerator):
        harness.check_devices(1)


def test_check_devices_refuses_too_few_chips():
    with pytest.raises(harness.NoAccelerator):
        harness.check_devices(4, require_tpu=False)


def test_run_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 3
    assert "not tpu" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
