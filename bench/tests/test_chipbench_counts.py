"""bench/counts.py against hand counts at a tiny size."""
import pytest

from bench import counts


def test_mvm_counts_by_hand():
    # n=2 points, d=1 (2 vertices each), m=3 vertices, c=1, r=1, symmetrised
    flops, nbytes = counts.mvm(2, 1, 3, 1)
    splat = slice_ = 2 * 2 * 2 * 1  # n (d+1) c multiply-adds
    blur = 2 * 2 * 2 * 3 * 3  # 2 orders x (d+1)=2 sweeps x 3 taps x m=3
    assert flops == splat + blur + 3 + slice_  # + m c to average the orders
    assert nbytes == 2 * 2 * 8 + 2 * 2 * 4 + 2 * 3 * 2 * 4


def test_mvm_counts_scale_with_occupied_vertices_not_cap():
    f1, b1 = counts.mvm(100, 9, 1000, 9)
    f2, b2 = counts.mvm(100, 9, 2000, 9)
    assert f2 > f1 and b2 > b1
    assert b2 - b1 == 10 * 1000 * 2 * 4  # neighbour indices only


def test_build_counts_by_hand():
    flops, nbytes = counts.build(1, 1, 2)
    # per point 3d + 5(d+1) + 2(d+1)^2 = 3 + 10 + 8; per vertex 2r (d+1)^2 = 8
    assert flops == 21 + 2 * 8
    assert nbytes == 4 + 2 * 8 + 2 * 2 * 4 + 2 * 2 * 2 * 4


def test_train_step_adds_its_terms():
    n, d, m, c, it = 50, 3, 40, 9, 10
    fb, bb = counts.build(n, d, m)
    fm, bm = counts.mvm(n, d, m, c)
    fg, bg = counts.mvm(n, d, m, 2 * c * (d + 1))
    f, b = counts.train_step(n, d, m, c, it)
    assert f == fb + it * (fm + 10 * n * c) + fm + fg
    assert b == bb + it * (bm + 7 * n * c * 4) + bm + bg


def test_least_seconds_takes_the_binding_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(100.0, 50.0, peak) == pytest.approx(5.0)
    assert counts.least_seconds(1000.0, 5.0, peak) == pytest.approx(10.0)
