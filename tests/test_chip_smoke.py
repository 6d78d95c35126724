"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusal to
report a result when JAX finds no TPU.

The script only runs for real on the chip; these tests make a broken main
path show up here first.
"""
import os
import pathlib
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_one_chip_phases_run_on_cpu():
    t0 = time.perf_counter()
    assert chip_smoke.one_chip(seed=0, epochs=2, scale=0.02) == []
    assert chip_smoke.compile_seconds(t0) > 0  # the compile events were seen


def test_phase_checks_fail_loudly():
    with pytest.raises(chip_smoke.SmokeFailure, match="bad thing"):
        chip_smoke.check(False, "bad thing")
    bad = lambda: chip_smoke.check(False, "bad thing")  # noqa: E731
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.run_phase("hard", bad)
    failed = []
    assert chip_smoke.run_phase("soft", bad, failed) is None
    assert failed == ["soft"]


def test_four_chip_phases_on_virtual_devices(multidevice_run):
    """The --chips 4 path on 4 of 8 virtual CPU devices."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "failed = chip_smoke.four_chips(seed=0, epochs=2,"
        " scale=0.05)\n"
        "print(json.dumps({'failed': failed}))\n")
    assert multidevice_run(code) == {"failed": []}


def test_entry_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=str(ROOT))
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits
    at a fixed path in the checkout. Checked in a child that compiles
    nothing, so no test process turns the cache on."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    want = str(ROOT / ".jax_cache")
    if env_dir is not None:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax\n"
            "from repro.utils.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]
