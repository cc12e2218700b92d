"""Device-resident acquisition engine regression tests (no optional deps).

Covers the contracts of the fused jitted recommend path:
* JAX HVI / MC-EHVI / EI / CEI match the numpy references (including
  degenerate cases: empty fronts, points below the reference, padded-front
  invariance, infeasible CEI incumbents),
* the rank-1 bordered-Cholesky ``GP.condition_on`` matches a full
  refactorization (including growth across the PAD boundary),
* ``VDTuner(engine="jax")`` selects the same seeded configuration sequences
  as the numpy path for q=1 and q=4, rlim on and off — the headline
  argmax-equivalence guarantee (the numpy path itself is pinned to the
  pre-redesign loops by ``test_session.py``),
* GP warm starts: reduced-step refits, state threading, and bit-identical
  checkpoint/resume with ``warm_start=True``,
* bulk candidate generation consumes the RNG exactly like the legacy
  per-config loop and snaps to the identical encoded matrix.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import (
    GP,
    Param,
    SearchSpace,
    StopSession,
    TuningSession,
    VDTuner,
    cei,
    cei_jax,
    ehvi_mc,
    ehvi_mc_jax,
    ei,
    ei_jax,
    hvi_2d,
    hvi_2d_jax,
    non_dominated_mask,
    pareto_front,
)
from repro.core.gp import _posterior_padded

_FAST = dict(gp_fit_steps=24, n_candidates=48, mc_samples=16)


def _toy_objective(cfg):
    t = cfg["index_type"]
    k = cfg.get("ka", cfg.get("kb", 0.5))
    k = k / 8.0 if t == "A" else k
    sysq = 1.0 - (cfg["s1"] - 0.6) ** 2
    if t == "A":
        return {"speed": 80 * (1 - k) * sysq, "recall": 0.5 + 0.45 * k, "mem_gib": 1.0}
    return {"speed": 50 * (1 - k) * sysq, "recall": 0.6 + 0.39 * k, "mem_gib": 0.5}


def _toy_space():
    return SearchSpace(
        index_types={
            "A": [Param("ka", "grid", choices=(1, 2, 4, 8), default=2)],
            "B": [Param("kb", "float", 0.0, 1.0, default=0.5)],
        },
        system_params=[
            Param("s1", "float", 0.0, 1.0, default=0.5),
            Param("s2", "cat", choices=(False, True), default=False),
        ],
    )


def _pad_front(front, extra=4):
    k0 = front.shape[0]
    fp = np.zeros((k0 + extra, 2))
    fm = np.zeros((k0 + extra,), bool)
    fp[:k0] = front
    fm[:k0] = True
    return fp, fm


# ---------------------------------------------------------------------------
# JAX acquisition primitives vs numpy references
# ---------------------------------------------------------------------------
def test_hvi_jax_matches_numpy_random_fronts():
    rng = np.random.default_rng(0)
    for trial in range(20):
        k = int(rng.integers(1, 12))
        front = pareto_front(rng.random((k, 2)) * 10 - 1.0)
        ref = rng.normal(0.0, 1.0, size=2)
        pts = rng.random((64, 2)) * 12 - 2.0  # includes below-ref points
        want = hvi_2d(pts, front, ref)
        fp, fm = _pad_front(front)
        with jax.enable_x64(True):
            got = np.asarray(hvi_2d_jax(pts, fp, fm, ref))
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


def test_hvi_jax_padding_and_dominated_points_are_inert():
    rng = np.random.default_rng(1)
    front = pareto_front(rng.random((6, 2)) * 5)
    ref = np.zeros(2)
    pts = rng.random((32, 2)) * 6
    fp, fm = _pad_front(front, extra=9)
    # a dominated extra point must not change the staircase
    fp_dom, fm_dom = fp.copy(), fm.copy()
    fp_dom[len(front)] = front.min(axis=0) * 0.5
    fm_dom[len(front)] = True
    with jax.enable_x64(True):
        base = np.asarray(hvi_2d_jax(pts, fp, fm, ref))
        dom = np.asarray(hvi_2d_jax(pts, fp_dom, fm_dom, ref))
    np.testing.assert_allclose(dom, base, rtol=1e-12, atol=1e-12)


def test_hvi_jax_empty_front():
    pts = np.array([[2.0, 3.0], [-1.0, 5.0]])
    ref = np.zeros(2)
    fp = np.zeros((4, 2))
    fm = np.zeros((4,), bool)  # fully masked == empty front
    with jax.enable_x64(True):
        got = np.asarray(hvi_2d_jax(pts, fp, fm, ref))
    np.testing.assert_allclose(got, [6.0, 0.0], rtol=1e-12)


def test_ehvi_jax_matches_numpy_with_shared_draws():
    rng = np.random.default_rng(2)
    front = pareto_front(rng.random((8, 2)))
    ref = np.array([0.1, 0.1])
    mean = rng.random((40, 2)).astype(np.float32).astype(np.float64)
    std = (rng.random((40, 2)) * 0.3 + 0.01).astype(np.float32).astype(np.float64)
    eps = np.random.default_rng(3).standard_normal((64, 40, 2))
    want = ehvi_mc(mean, std, front, ref, _FixedEps(eps), n_samples=64)
    fp, fm = _pad_front(front)
    with jax.enable_x64(True):
        got = np.asarray(ehvi_mc_jax(mean, std, fp, fm, ref, eps))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


class _FixedEps:
    """Generator stand-in replaying fixed normal draws into ``ehvi_mc``."""

    def __init__(self, eps):
        self._eps = eps

    def standard_normal(self, shape):
        assert shape == self._eps.shape
        return self._eps


@pytest.mark.parametrize("best", [1.0, float("-inf")], ids=["feasible", "no-incumbent"])
def test_ei_cei_jax_match_numpy(best):
    rng = np.random.default_rng(4)
    mean = rng.normal(1.0, 2.0, size=50)
    std = np.abs(rng.normal(0.0, 1.0, size=50)) + 1e-13
    mean_r = rng.random(50)
    std_r = rng.random(50) * 0.1 + 1e-13
    with jax.enable_x64(True):
        got_ei = np.asarray(ei_jax(mean, std, 1.0))
        got_cei = np.asarray(cei_jax(mean, std, mean_r, std_r, best, 0.9))
    np.testing.assert_allclose(got_ei, ei(mean, std, 1.0), rtol=1e-9, atol=1e-15)
    np.testing.assert_allclose(got_cei, cei(mean, std, mean_r, std_r, best, 0.9), rtol=1e-9, atol=1e-15)


# ---------------------------------------------------------------------------
# rank-1 Cholesky conditioning vs full refactorization
# ---------------------------------------------------------------------------
def _full_refactorization(gp):
    s = gp.state
    return _posterior_padded(s.params.log_ls, s.params.log_sf, s.params.log_noise, s.x, s.y, s.mask)


@pytest.mark.parametrize("n0,k", [(20, 1), (20, 5), (30, 4), (32, 3)], ids=str)
def test_rank1_condition_matches_full_refactorization(n0, k):
    # (32, 3) crosses the PAD boundary: growth is an exact block extension
    rng = np.random.default_rng(n0 + k)
    X = rng.random((n0, 3))
    Y = np.stack([np.sin(3 * X[:, 0]), X[:, 1] - X[:, 2]], axis=1)
    gp = GP(seed=0).fit(X, Y)
    Xn = rng.random((k, 3))
    mean, _ = gp.predict(Xn)  # Kriging-believer-style (self-consistent) values
    g2 = gp.condition_on(Xn, mean)
    chol_full, alpha_full = _full_refactorization(g2)
    np.testing.assert_allclose(np.asarray(g2.state.chol), np.asarray(chol_full), atol=2e-4)
    # the posterior itself agrees tightly
    Xt = rng.random((16, 3))
    m1, s1 = g2.predict(Xt)
    g3 = GP(seed=0)
    g3.state = type(g2.state)(
        params=g2.state.params,
        x=g2.state.x,
        y=g2.state.y,
        mask=g2.state.mask,
        chol=chol_full,
        alpha=alpha_full,
        y_mean=g2.state.y_mean,
        y_std=g2.state.y_std,
    )
    m2, s2 = g3.predict(Xt)
    np.testing.assert_allclose(m1, m2, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(s1, s2, rtol=1e-3, atol=1e-4)


def test_with_capacity_is_exact_and_preserves_posterior():
    rng = np.random.default_rng(9)
    X = rng.random((32, 2))  # full PAD block
    Y = X[:, :1] * 2.0
    gp = GP(seed=0).fit(X, Y)
    big = gp.with_capacity(40)
    assert big.state.x.shape[0] == 64
    m0, s0 = gp.predict(X[:8])
    m1, s1 = big.predict(X[:8])
    np.testing.assert_array_equal(m0, m1)
    np.testing.assert_array_equal(s0, s1)


# ---------------------------------------------------------------------------
# fused engine == numpy engine (the seeded regression criterion)
# ---------------------------------------------------------------------------
def _run(engine, q, rlim, warm=False, n=12, seed=5):
    t = VDTuner(
        _toy_space(),
        _toy_objective,
        seed=seed,
        abandon_window=6,
        rlim=rlim,
        q=q,
        engine=engine,
        warm_start=warm,
        **_FAST,
    )
    return t.run(n)


@pytest.mark.parametrize("q", [1, 4], ids=["q1", "q4"])
@pytest.mark.parametrize("rlim", [None, 0.85], ids=["ehvi", "cei"])
def test_jax_engine_selects_same_configs_as_numpy(q, rlim):
    a = _run("numpy", q, rlim)
    b = _run("jax", q, rlim)
    assert [o.config for o in a.history] == [o.config for o in b.history]
    assert np.array_equal(a.Y, b.Y)


def test_jax_engine_matches_numpy_with_warm_start_too():
    a = _run("numpy", 4, None, warm=True)
    b = _run("jax", 4, None, warm=True)
    assert [o.config for o in a.history] == [o.config for o in b.history]


def test_engines_handle_q_larger_than_candidate_pool():
    kw = dict(_FAST, n_candidates=4)
    for engine in ("numpy", "jax"):
        t = VDTuner(_toy_space(), _toy_objective, seed=1, q=6, engine=engine, **kw)
        t._initial_sampling()
        cfgs = t.ask(6)
        assert len(cfgs) == 4  # clamped to the candidate pool
        assert len({tuple(sorted(c.items())) for c in cfgs}) == 4


def test_invalid_engine_rejected():
    with pytest.raises(ValueError):
        VDTuner(_toy_space(), _toy_objective, engine="fortran")


# ---------------------------------------------------------------------------
# warm-started GP refits
# ---------------------------------------------------------------------------
def test_warm_start_threads_state_and_checkpoints():
    tuner = VDTuner(_toy_space(), _toy_objective, seed=7, warm_start=True, **_FAST)
    tuner.run(5)
    state = tuner.state_dict()
    warm = state["extra"]["gp_warm"]
    assert warm is not None and set(warm) == {"log_ls", "log_sf", "log_noise"}
    fresh = VDTuner(_toy_space(), _toy_objective, seed=7, warm_start=True, **_FAST)
    fresh.load_state_dict(json.loads(json.dumps(state)))
    assert fresh._gp_warm.to_lists() == warm  # exact f32 round-trip through JSON


@pytest.mark.parametrize("q", [1, 4], ids=["q1", "q4"])
def test_warm_start_resume_is_bit_identical(q):
    def make():
        return VDTuner(_toy_space(), _toy_objective, seed=7, q=q, warm_start=True, **_FAST)

    full = make()
    TuningSession(full).run(9)

    def stopper(session, obs):
        if session.n_observations >= 5:
            raise StopSession

    part = make()
    session = TuningSession(part, callbacks=[stopper]).run(9)
    state = json.loads(json.dumps(session.state_dict()))
    fresh = make()
    TuningSession.restore(state, fresh).run(9)
    assert [o.config for o in fresh.history] == [o.config for o in full.history]
    assert np.array_equal(fresh.Y, full.Y)


def test_baseline_warm_start_threads_and_checkpoints():
    from repro.core import OtterTuneLike

    tuner = OtterTuneLike(_toy_space(), _toy_objective, seed=2, n_init=4, n_candidates=32, warm_start=True)
    tuner.run(7)
    assert tuner._gp_warm is not None
    state = json.loads(json.dumps(tuner.state_dict()))
    fresh = OtterTuneLike(_toy_space(), _toy_objective, seed=2, n_init=4, n_candidates=32, warm_start=True)
    fresh.load_state_dict(state)
    assert fresh._gp_warm.to_lists() == state["extra"]["gp_warm"]


def test_warm_fit_uses_reduced_steps_and_previous_params():
    rng = np.random.default_rng(0)
    X = rng.random((24, 2))
    Y = np.sin(4 * X[:, 0]) + X[:, 1]
    cold = GP(seed=0, fit_steps=120).fit(X, Y)
    warm = GP(seed=0, fit_steps=120, warm_fit_steps=0).fit(X, Y, init=cold.params)
    # 0 warm steps == the init itself: threading works end to end
    np.testing.assert_array_equal(np.asarray(warm.params.log_ls), np.asarray(cold.params.log_ls))
    # shape-mismatched init falls back to a cold fit instead of crashing
    other = GP(seed=0).fit(rng.random((10, 3)), rng.random(10), init=cold.params)
    assert other.state is not None


# ---------------------------------------------------------------------------
# bulk candidate generation
# ---------------------------------------------------------------------------
def _legacy_candidates(self, t):
    """Verbatim copy of the pre-bulk per-config candidate loop."""
    n_uniform = self.n_candidates // 2
    cands = self.space.sample(self.rng, n_uniform, index_type=t)
    ys = self.Y
    nd = non_dominated_mask(ys)
    seeds = [o.config for o, keep in zip(self.history, nd) if keep and o.index_type == t]
    if not seeds:
        mine = [o for o in self.history if o.index_type == t and not o.failed]
        if mine:
            seeds = [
                max(mine, key=lambda o: o.y[0]).config,
                max(mine, key=lambda o: o.y[1]).config,
            ]
    while len(cands) < self.n_candidates and seeds:
        base = seeds[len(cands) % len(seeds)]
        scale = float(self.rng.choice([0.05, 0.1, 0.2]))
        cands.append(self.space.perturb(self.rng, base, scale=scale))
    if len(cands) < self.n_candidates:
        cands += self.space.sample(self.rng, self.n_candidates - len(cands), index_type=t)
    return cands


def test_bulk_candidates_match_legacy_loop_and_rng_stream():
    a = VDTuner(_toy_space(), _toy_objective, seed=3, **_FAST).run(6)
    b = VDTuner(_toy_space(), _toy_objective, seed=3, **_FAST).run(6)
    for t in ("A", "B"):
        assert _legacy_candidates(a, t) == b._candidates(t)
        assert a.rng.bit_generator.state == b.rng.bit_generator.state


def test_snap_encoded_matches_scalar_roundtrip():
    tuner = VDTuner(_toy_space(), _toy_objective, seed=7, **_FAST).run(6)
    raw, Xc = tuner._candidates_encoded("A")
    want = np.stack([tuner.space.encode(tuner.space.decode(r, index_type="A")) for r in raw])
    np.testing.assert_array_equal(Xc, want)
