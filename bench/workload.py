"""What the drivers share: a configuration's data and model, seeds, spans.

A configuration file names one dataset of the paper's suite at its
published size; ``data_seed`` fixes the dataset of the deployment and the
run's ``--seed`` draws its 4/9-2/9-3/9 split, the estimator's keys and the
traffic, so every seed does the same amount of work.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.data import synthetic_uci
from bench.trace_reduce import SPAN_PREFIX


def split(config: dict, seed: int, scale: float = 1.0):
    """The configuration's dataset with the split drawn from ``seed``.
    ``scale`` < 1 subsamples it for tests on the CPU."""
    return synthetic_uci.load(config["dataset"], scale=scale,
                              seed=config["data_seed"],
                              split_seed=sub_seed(seed, "split"))


def sub_seed(seed: int, purpose: str) -> int:
    """A 32-bit seed for one purpose, derived from the run's seed."""
    words = [ord(ch) for ch in purpose]
    return int(np.random.SeedSequence([seed % 2**63, *words])
               .generate_state(1)[0])


def gp_model(config: dict):
    """The program's model for the configuration's settings."""
    from repro.gp import SimplexGP, SimplexGPConfig
    fields = {f.name for f in dataclasses.fields(SimplexGPConfig)}
    return SimplexGP(SimplexGPConfig(**{k: v for k, v in config["model"].items()
                                        if k in fields}))


@dataclasses.dataclass
class Spans:
    """Host-clock spans of the benchmark's own calls into the program,
    also written into the profiler's trace (as ``bench.<name>``) when one
    is recording."""
    items: list = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = 0.0) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.items if n == name and t0 >= since]


def rel_norm_gap(got, want) -> float:
    """Norm of the gap between two arrays over the norm of ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def leaf_gaps(got: dict, want: dict, skip=()) -> float:
    """Worst leaf's gap between two sets of leaf norms, each against the
    reference's norm of that leaf or the median leaf's, the larger."""
    med = float(np.median([want[k] for k in want]))
    gaps = [abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want if k not in skip]
    return max(gaps) if gaps else 0.0
