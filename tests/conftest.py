import itertools
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import settings

# A property's first example pays the jit compile of what it checks, which
# takes seconds and trips hypothesis's 200 ms default deadline; examples
# are bounded by each property's max_examples instead.
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")

# NOTE: no XLA_FLAGS here — tests must see the 1 real CPU device.
# Multi-device tests (marker ``multidevice``) run their sharded half in a
# subprocess whose environment carries MULTIDEVICE_XLA_FLAGS; the
# ``multidevice_run`` fixture below is the lane's entry point. That keeps
# the 8 virtual CPU devices OUT of this process (XLA reads the flag once,
# at backend init) while the lane still runs inside tier-1 on any host.

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
MULTIDEVICE_DEVICES = 8
MULTIDEVICE_XLA_FLAGS = (
    f"--xla_force_host_platform_device_count={MULTIDEVICE_DEVICES}")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def cg_loop():
    """Compiled HLO text -> (the CG ``while`` line, the lines of its
    condition computation). There must be exactly one CG loop."""
    def find(text):
        lines = text.splitlines()
        loops = [ln for ln in lines
                 if " while(" in ln and 'simplex.cg/while"' in ln]
        assert len(loops) == 1, loops
        cond = re.search(r"condition=(%[\w.\-]+)", loops[0]).group(1)
        start = next(i for i, ln in enumerate(lines)
                     if ln.startswith(cond + " "))
        body = itertools.takewhile(lambda ln: ln != "}", lines[start + 1:])
        return loops[0], list(body)
    return find


@pytest.fixture
def multidevice_run():
    """Run a python snippet under 8 virtual CPU devices; return its JSON.

    The snippet must print a single JSON object as its last stdout line.
    Existing XLA_FLAGS are preserved (the device-count flag is appended).
    """

    def run(code: str, timeout: int = 600) -> dict:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                            + MULTIDEVICE_XLA_FLAGS).strip()
        extra = env.get("PYTHONPATH")
        env["PYTHONPATH"] = SRC + (os.pathsep + extra if extra else "")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=timeout)
        assert out.returncode == 0, out.stderr[-3000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    return run


def pytest_addoption(parser):
    parser.addoption("--slow", action="store_true", default=False,
                     help="run slow integration tests")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--slow"):
        return
    skip = pytest.mark.skip(reason="slow; use --slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
