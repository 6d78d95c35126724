"""Stencil discretization tests (paper §4.1, Eq. 9)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.kernels_math import PROFILES, get_profile
from repro.core.stencil import _coverage_curves, make_stencil, solve_spacing


@pytest.mark.parametrize("name", sorted(PROFILES))
@pytest.mark.parametrize("r", [1, 2, 3])
def test_coverage_balance_at_solution(name, r):
    """Eq. 9: spatial and spectral coverage cross at the solved spacing."""
    profile = get_profile(name)
    s = solve_spacing(profile, r)
    lhs, rhs = _coverage_curves(profile, r)
    assert abs(lhs(s) - rhs(s)) < 1e-6
    # monotonicity around the crossing
    assert lhs(s * 1.1) > lhs(s * 0.9)
    assert rhs(s * 1.1) < rhs(s * 0.9)


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_stencil_structure(name):
    st_ = make_stencil(name, r=2)
    w = np.asarray(st_.weights)
    assert w.shape == (5,)
    assert abs(w[2] - 1.0) < 1e-12  # center tap k(0) = 1
    assert np.all(w[:2] == w[:-3:-1])  # symmetric
    assert np.all(np.diff(w[2:]) <= 0)  # decaying

def test_spacing_shrinks_with_order():
    """More taps -> finer spacing (same coverage split over more points)."""
    s1 = make_stencil("rbf", 1).spacing
    s3 = make_stencil("rbf", 3).spacing
    assert s3 < s1


def test_rbf_derivative_stencil_is_minus_half_forward():
    """For RBF, k' = -k/2 exactly, so dweights == weights, dscale == -1/2."""
    st_ = make_stencil("rbf", 2)
    np.testing.assert_allclose(st_.dweights, st_.weights, rtol=1e-12)
    assert abs(st_.dscale + 0.5) < 1e-12


def test_matern12_gradient_disabled():
    """Matern-1/2 has a cusp at 0: derivative stencil must be disabled."""
    st_ = make_stencil("matern12", 1)
    assert st_.dscale == 0.0


@settings(max_examples=10, deadline=None)
@given(r=st.integers(1, 4),
       name=st.sampled_from(sorted(PROFILES)))
def test_property_weights_bounded(name, r):
    st_ = make_stencil(name, r)
    w = np.asarray(st_.weights)
    assert w.shape == (2 * r + 1,)
    assert np.all(w > 0) and np.all(w <= 1.0 + 1e-12)
    assert st_.spacing > 0
