"""Synthetic stand-ins for the paper's UCI regression datasets (§5.3).

The container is offline, so the five UCI sets are replaced by generators
matched on (n, d) and on the *geometry* that drives the paper's results:
the lattice sparsity ratio m/L (Table 3) is controlled by how clustered the
inputs are, so each generator plants a cluster/manifold structure tuned to
land near the published ratio. Targets are a smooth random function
(random-feature GP sample) plus noise, standardized like the paper
(train-fit z-scoring, 4/9-2/9-3/9 split).

Benchmarks therefore reproduce the paper's *relationships* (sparsity <<1,
Simplex-GP ~ Exact >> SKIP, speedups growing with n) rather than the
published decimal values; see DESIGN.md §5.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import NamedTuple

import numpy as np

# name -> (n, d, n_clusters per unit volume proxy, cluster spread)
# spread tuned so m/L (Table 3) is qualitatively matched:
#   precipitation 0.003 (grid-like), protein 0.03, houseelectric 0.04,
#   keggdirected 0.12, elevators 0.69.
SPECS: dict[str, dict] = {
    "houseelectric": dict(n=2_049_280, d=11, structure="clustered",
                          clusters=64, spread=0.05, table3_m=1_000_190),
    "precipitation": dict(n=628_474, d=3, structure="grid",
                          grid=8, jitter=0.02, table3_m=480),
    "keggdirected": dict(n=48_827, d=20, structure="clustered",
                         clusters=256, spread=0.045, table3_m=122_755),
    "protein": dict(n=45_730, d=9, structure="clustered",
                    clusters=48, spread=0.08, table3_m=14_715),
    "elevators": dict(n=16_599, d=17, structure="lowrank",
                      intrinsic=6, noise=0.12, table3_m=204_761),
}


class Dataset(NamedTuple):
    name: str
    x_train: np.ndarray
    y_train: np.ndarray
    x_val: np.ndarray
    y_val: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray

    @property
    def n(self) -> int:
        return self.x_train.shape[0]

    @property
    def d(self) -> int:
        return self.x_train.shape[1]


def _inputs(rng: np.random.Generator, n: int, d: int, spec: dict) -> np.ndarray:
    kind = spec["structure"]
    if kind == "grid":
        # lat/lon/time-like gridded data -> extremely sparse lattice
        g = spec["grid"]
        cells = rng.integers(0, g, size=(n, d)).astype(np.float64)
        return cells / g + spec["jitter"] * rng.normal(size=(n, d))
    if kind == "clustered":
        k = spec["clusters"]
        centers = rng.normal(size=(k, d))
        assign = rng.integers(0, k, size=n)
        return centers[assign] + spec["spread"] * rng.normal(size=(n, d))
    # "lowrank": sensor-style data on a low-dim manifold in ambient d
    # (real elevators has correlated dims; m/L = 0.69 needs SOME vertex
    # sharing, which i.i.d. 17-D points never produce)
    z = rng.standard_t(df=4, size=(n, spec["intrinsic"]))
    mix = rng.normal(size=(spec["intrinsic"], d))
    return z @ mix + spec["noise"] * rng.normal(size=(n, d))


def _targets(rng: np.random.Generator, x: np.ndarray,
             num_features: int = 256, noise: float = 0.1) -> np.ndarray:
    """Sample from an RBF random-feature GP prior: smooth ground truth."""
    n, d = x.shape
    w = rng.normal(size=(d, num_features))
    b = rng.uniform(0, 2 * np.pi, size=num_features)
    amp = rng.normal(size=num_features) / np.sqrt(num_features)
    f = np.cos(x @ w + b) @ amp
    return f + noise * rng.normal(size=n)


def load(name: str, *, scale: float = 1.0, seed: int = 0) -> Dataset:
    """Generate the named dataset. ``scale`` subsamples n for CPU benches."""
    spec = SPECS[name]
    n = max(int(spec["n"] * scale), 64)
    d = spec["d"]
    # crc32, not hash(): str hashes are salted per process, so the same
    # seed would give different data in every run
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()))
    x = _inputs(rng, n, d, spec)
    y = _targets(rng, x)

    perm = rng.permutation(n)
    x, y = x[perm], y[perm]
    n_train = (4 * n) // 9
    n_val = (2 * n) // 9
    sl_train = slice(0, n_train)
    sl_val = slice(n_train, n_train + n_val)
    sl_test = slice(n_train + n_val, None)

    # standardize with train statistics (paper §5.3)
    mu_x, sd_x = x[sl_train].mean(0), x[sl_train].std(0) + 1e-8
    mu_y, sd_y = y[sl_train].mean(), y[sl_train].std() + 1e-8
    xs = (x - mu_x) / sd_x
    ys = (y - mu_y) / sd_y
    f32 = lambda a: np.ascontiguousarray(a, np.float32)
    return Dataset(name=name,
                   x_train=f32(xs[sl_train]), y_train=f32(ys[sl_train]),
                   x_val=f32(xs[sl_val]), y_val=f32(ys[sl_val]),
                   x_test=f32(xs[sl_test]), y_test=f32(ys[sl_test]))


def all_names() -> list[str]:
    return list(SPECS)
