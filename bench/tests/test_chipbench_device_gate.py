"""bench/run.py refuses to run without a TPU it knows the peaks of."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(harness.__file__).resolve().parents[1]


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_check_devices():
    peaks = harness.load_peaks()
    tpu = FakeDevice("tpu", "TPU v5 lite")
    assert harness.check_devices([tpu], 1, peaks)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_devices([FakeDevice("cpu", "cpu")], 1, peaks)
    with pytest.raises(harness.BenchError, match="peaks"):
        harness.check_devices([FakeDevice("tpu", "TPU v99")], 1, peaks)
    with pytest.raises(harness.BenchError, match="4 chip"):
        harness.check_devices([tpu], 4, peaks)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "protein-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    out = _run(ROOT)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
