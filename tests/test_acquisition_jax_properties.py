"""Property tests (hypothesis) for the device-resident acquisition engine:
the JAX EHVI/CEI/HVI ports match the numpy references across random fronts,
refs and degenerate cases, and the rank-1 Cholesky update in
``GP.condition_on`` matches a full refactorization."""
import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional test dep; pip install -e .[test]")
from hypothesis import given, settings, strategies as st

from repro.core import GP, cei, cei_jax, ehvi_mc, ehvi_mc_jax, hvi_2d, hvi_2d_jax, pareto_front
from repro.core.gp import _posterior_padded

points2d = st.lists(
    st.tuples(st.floats(0.01, 100.0, allow_nan=False), st.floats(0.01, 100.0, allow_nan=False)),
    min_size=1,
    max_size=16,
).map(lambda ps: np.array(ps, dtype=np.float64))


def _pad_front(front, extra):
    k0 = front.shape[0]
    fp = np.zeros((k0 + extra, 2))
    fm = np.zeros((k0 + extra,), bool)
    fp[:k0] = front
    fm[:k0] = True
    return fp, fm


@settings(max_examples=40, deadline=None)
@given(points2d, points2d, st.floats(-1.0, 1.0), st.floats(-1.0, 1.0), st.integers(0, 8))
def test_hvi_jax_matches_numpy(front_pts, pts, r0, r1, extra):
    ref = np.array([r0, r1])
    front = pareto_front(front_pts)
    want = hvi_2d(pts, front, ref)
    fp, fm = _pad_front(front, extra)
    with jax.enable_x64(True):
        got = np.asarray(hvi_2d_jax(pts, fp, fm, ref))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


class _FixedEps:
    def __init__(self, eps):
        self._eps = eps

    def standard_normal(self, shape):
        assert shape == self._eps.shape
        return self._eps


@settings(max_examples=25, deadline=None)
@given(points2d, st.integers(0, 2**31 - 1), st.integers(1, 3))
def test_ehvi_jax_matches_numpy(front_pts, seed, extra):
    rng = np.random.default_rng(seed)
    front = pareto_front(front_pts)
    ref = np.array([0.5, 0.5])
    c = 12
    mean = (rng.random((c, 2)) * 2).astype(np.float32).astype(np.float64)
    std = (rng.random((c, 2)) * 0.5 + 1e-3).astype(np.float32).astype(np.float64)
    eps = rng.standard_normal((16, c, 2))
    want = ehvi_mc(mean, std, front, ref, _FixedEps(eps), n_samples=16)
    fp, fm = _pad_front(front, extra)
    with jax.enable_x64(True):
        got = np.asarray(ehvi_mc_jax(mean, std, fp, fm, ref, eps))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.one_of(st.just(float("-inf")), st.floats(-2.0, 2.0)),
    st.floats(0.1, 1.5),
)
def test_cei_jax_matches_numpy(seed, best, rlim):
    rng = np.random.default_rng(seed)
    mean = rng.normal(0.0, 2.0, size=20)
    std = np.abs(rng.normal(0.0, 1.0, size=20)) + 1e-12
    mean_r = rng.random(20) * 1.5
    std_r = rng.random(20) * 0.2 + 1e-12
    want = cei(mean, std, mean_r, std_r, best, rlim)
    with jax.enable_x64(True):
        got = np.asarray(cei_jax(mean, std, mean_r, std_r, best, rlim))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(5, 40), st.integers(1, 6))
def test_rank1_cholesky_matches_full_refactorization(seed, n0, k):
    rng = np.random.default_rng(seed)
    X = rng.random((n0, 2))
    Y = np.stack([np.sin(3 * X[:, 0]), X[:, 1]], axis=1)
    gp = GP(seed=0, fit_steps=40).fit(X, Y)
    Xn = rng.random((k, 2))
    mean, _ = gp.predict(Xn)
    g2 = gp.condition_on(Xn, mean)
    s = g2.state
    chol_full, _ = _posterior_padded(s.params.log_ls, s.params.log_sf, s.params.log_noise, s.x, s.y, s.mask)
    np.testing.assert_allclose(np.asarray(s.chol), np.asarray(chol_full), atol=2e-4)
