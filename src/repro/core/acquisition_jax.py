"""Device-resident acquisition engine.

JAX ports of the acquisition stack (2-D staircase HVI, MC-EHVI, EI,
constrained EI) plus a *fused* sequential-greedy batch selector: one jitted
call produces the whole q-batch — GP posterior prediction over the candidate
matrix, acquisition scoring, the availability-masked argmax, the
Kriging-believer fantasy (an exact rank-1 bordered-Cholesky append, reusing
the prediction solve), and the running front / feasible-incumbent update all
stay on device across the ``lax.scan`` over picks. The numpy implementations
in :mod:`.acquisition` / :mod:`.hypervolume` remain the references this
module is property-tested against.

Numerics
--------
The GP math runs in float32 with the same operation sequence as
``gp._predict_padded`` / ``gp._append_rows``; acquisition scores are then
computed in float64 (under a local ``jax.enable_x64(True)`` scope)
exactly like the numpy path, which does float64 scoring on the float32
posterior. Selected indices are argmax-equivalent to the numpy path up to
reduction-order rounding (~1e-12 relative on the scores) — seeded q=1/q=4
tuner runs select identical configuration sequences (regression-tested in
``tests/test_acquisition_jax.py``).

Shapes are jit-stable: training arrays use the GP's inert PAD rows
(pre-grown so all q fantasies fit), fronts are padded to multiples of
``FRONT_PAD`` with a validity mask (dominated/masked points never change the
staircase, so fantasies are appended without re-filtering).
"""
from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .gp import _JITTER, _NOISE_FLOOR, matern52

FRONT_PAD = 16

_SQRT2 = float(np.sqrt(2.0))


def _phi(z):
    return jnp.exp(-0.5 * z * z) / np.sqrt(2.0 * np.pi)


def _Phi(z):
    return 0.5 * (1.0 + jax.scipy.special.erf(z / _SQRT2))


# ---------------------------------------------------------------------------
# hypervolume improvement (2-D staircase, fixed padded front)
# ---------------------------------------------------------------------------
def hvi_2d_jax(points, front, front_mask, ref):
    """Exclusive HVI of each point w.r.t. the masked ``front`` (2-D).

    Mirrors ``hypervolume.hvi_2d``; masked-out (and below-ref) front rows are
    pinned to ``ref`` where they form zero-width segments, so a padded front
    gives bit-comparable results to the unpadded numpy staircase.
    """
    valid = front_mask & jnp.all(front > ref[None, :], axis=1)
    f1 = jnp.where(valid, front[:, 0], ref[0])
    f2 = jnp.where(valid, front[:, 1], ref[1])
    order = jnp.argsort(-f1, stable=True)  # f1 descending
    f1s = f1[order]
    f2s = f2[order]
    heights = jax.lax.cummax(f2s)  # max f2 among points with f1 >= f1s[i]
    xs = jnp.concatenate([ref[:1], f1s[::-1]])  # ascending breakpoints
    a = jnp.concatenate([xs[:-1], xs[-1:]])
    b = jnp.concatenate([xs[1:], jnp.full((1,), jnp.inf, xs.dtype)])
    h = jnp.concatenate([heights[::-1], ref[1:]])
    y1 = jnp.maximum(points[:, 0], ref[0])[:, None]
    y2 = points[:, 1][:, None]
    overlap = jnp.clip(jnp.minimum(y1, b[None, :]) - a[None, :], 0.0, None)
    gain = jnp.clip(y2 - jnp.maximum(h, ref[1])[None, :], 0.0, None)
    hvi = jnp.sum(overlap * gain, axis=1)
    return jnp.where(jnp.all(points > ref[None, :], axis=1), hvi, 0.0)


def ehvi_mc_jax(mean, std, front, front_mask, ref, eps):
    """MC-EHVI with externally supplied normal draws ``eps`` (S, c, 2) — the
    host draws them from the tuner's generator so RNG consumption matches
    the numpy path exactly."""
    samples = mean[None] + std[None] * eps  # (S, c, 2)
    flat = samples.reshape(-1, 2)
    hvi = hvi_2d_jax(flat, front, front_mask, ref).reshape(eps.shape[0], -1)
    return hvi.mean(axis=0)


def ei_jax(mean, std, best):
    """Closed-form expected improvement (maximization)."""
    std = jnp.maximum(std, 1e-12)
    z = (mean - best) / std
    return (mean - best) * _Phi(z) + std * _phi(z)


def cei_jax(mean_spd, std_spd, mean_rec, std_rec, best_feasible, rlim):
    """Constrained EI (paper Eq. 7): EI(speed) * Pr(recall > rlim)."""
    p_feas = 1.0 - _Phi((rlim - mean_rec) / jnp.maximum(std_rec, 1e-12))
    finite = jnp.isfinite(best_feasible)
    safe_best = jnp.where(finite, best_feasible, 0.0)
    return jnp.where(finite, ei_jax(mean_spd, std_spd, safe_best) * p_feas, p_feas)


# ---------------------------------------------------------------------------
# fused sequential-greedy selection
# ---------------------------------------------------------------------------
def _posterior_stats(log_ls, log_sf, x, mask, chol, alpha, Xc):
    """(mean, var, v) over candidates — same op sequence as
    ``gp._predict_padded`` (f32); ``v`` is reused for the rank-1 append."""
    ks = jax.vmap(lambda ls, sf: matern52(Xc, x, ls, sf))(log_ls, log_sf) * mask[None, None, :]
    mean = jax.vmap(lambda K, a: K @ a)(ks, alpha)  # (m, c)
    v = jax.vmap(lambda L, K: jax.scipy.linalg.solve_triangular(L, K.T, lower=True))(chol, ks)
    sf2 = jnp.exp(2.0 * log_sf)
    var = jnp.maximum(sf2[:, None] - jnp.sum(v * v, axis=1), 1e-10)
    return mean, var, v  # (m, c), (m, c), (m, n_pad, c)


def _greedy_scan(params, gp_arrays, Xc, y_mean, y_std, score_fn, update_fn, extra0, xs, q):
    """Shared scan over q picks: predict -> score -> masked argmax ->
    append fantasy (rank-1, exact). ``score_fn(mean64, std64, extra, inp)``
    returns f64 scores; ``update_fn(extra, fantasy64)`` folds the pick's
    fantasy into the incumbent state."""
    log_ls, log_sf, log_noise = params
    x0, y0, mask0, chol0, alpha0 = gp_arrays
    sf2 = jnp.exp(2.0 * log_sf)
    row_noise = sf2 * (_NOISE_FLOOR + _JITTER) + jnp.exp(2.0 * log_noise)  # (m,)
    kself = jax.vmap(
        lambda ls, sf: matern52(jnp.zeros((1, x0.shape[1]), x0.dtype),
                                jnp.zeros((1, x0.shape[1]), x0.dtype), ls, sf)[0, 0]
    )(log_ls, log_sf)

    def body(carry, inp):
        x, y, mask, chol, alpha, avail, extra = carry
        mean_s, var, v = _posterior_stats(log_ls, log_sf, x, mask, chol, alpha, Xc)
        # destandardize in f32 exactly like GP.predict, then score in f64
        mean32 = mean_s.T * y_std[None, :] + y_mean[None, :]  # (c, m)
        std32 = jnp.sqrt(var).T * y_std[None, :]
        mean64 = mean32.astype(jnp.float64)
        std64 = std32.astype(jnp.float64)
        acq = jnp.where(avail, score_fn(mean64, std64, extra, inp), -jnp.inf)
        i = jnp.argmax(acq)
        avail = avail.at[i].set(False)
        extra = update_fn(extra, mean64[i])
        # Kriging-believer fantasy: standardize the f32 posterior mean like
        # condition_on does, append as a bordered-Cholesky row (w = the
        # prediction solve's column i — no second triangular solve needed)
        y_new = (mean32[i] - y_mean) / y_std  # (m,) f32
        r = jnp.sum(mask).astype(jnp.int32)
        w = v[:, :, i]  # (m, n_pad); 0 at rows >= r (inert pads)
        l_rr = jnp.sqrt(jnp.maximum(kself + row_noise - jnp.sum(w * w, axis=1), 1e-10))
        chol = chol.at[:, r, :].set(w)
        chol = chol.at[:, r, r].set(l_rr)
        x = x.at[r].set(Xc[i])
        y = y.at[r].set(y_new)
        mask = mask.at[r].set(1.0)
        alpha = jax.vmap(
            lambda L, y_col: jax.scipy.linalg.cho_solve((L, True), y_col), in_axes=(0, 1)
        )(chol, y)
        return (x, y, mask, chol, alpha, avail, extra), i

    avail0 = jnp.ones((Xc.shape[0],), bool)
    carry0 = (x0, y0, mask0, chol0, alpha0, avail0, extra0)
    _, picks = jax.lax.scan(body, carry0, xs, length=q)
    return picks


@partial(jax.jit, static_argnames=("q",))
def _fused_qehvi(log_ls, log_sf, log_noise, x, y, mask, chol, alpha, y_mean, y_std,
                 Xc, front, front_mask, ref, eps, q: int):
    k0 = jnp.sum(front_mask).astype(jnp.int32)

    def score_fn(mean64, std64, extra, eps_j):
        fr, fm, _ = extra
        return ehvi_mc_jax(mean64, std64, fr, fm, ref, eps_j)

    def update_fn(extra, fantasy64):
        fr, fm, n_added = extra
        fr = fr.at[k0 + n_added].set(fantasy64)
        fm = fm.at[k0 + n_added].set(True)
        return (fr, fm, n_added + 1)

    extra0 = (front, front_mask, jnp.asarray(0, jnp.int32))
    return _greedy_scan(
        (log_ls, log_sf, log_noise), (x, y, mask, chol, alpha),
        Xc, y_mean, y_std, score_fn, update_fn, extra0, eps, q,
    )


@partial(jax.jit, static_argnames=("q",))
def _fused_cei(log_ls, log_sf, log_noise, x, y, mask, chol, alpha, y_mean, y_std,
               Xc, best_feasible, rlim_n, q: int):
    def score_fn(mean64, std64, extra, _inp):
        return cei_jax(mean64[:, 0], std64[:, 0], mean64[:, 1], std64[:, 1], extra, rlim_n)

    def update_fn(best, fantasy64):
        return jnp.where(fantasy64[1] >= rlim_n, jnp.maximum(best, fantasy64[0]), best)

    return _greedy_scan(
        (log_ls, log_sf, log_noise), (x, y, mask, chol, alpha),
        Xc, y_mean, y_std, score_fn, update_fn, best_feasible, None, q,
    )


def _gp_operands(gp, n_extra: int):
    """Pre-grow the GP so all fantasies fit (exact block extension), and
    unpack the device operands of the fused call."""
    g = gp.with_capacity(gp.n_real + n_extra)
    s = g.state
    return (
        s.params.log_ls, s.params.log_sf, s.params.log_noise,
        s.x, s.y, s.mask, s.chol, s.alpha, s.y_mean, s.y_std,
    )


def _padded_front(front: np.ndarray, q: int):
    k0 = front.shape[0]
    k_pad = int(np.ceil((k0 + q) / FRONT_PAD) * FRONT_PAD)
    fp = np.zeros((k_pad, 2), np.float64)
    fm = np.zeros((k_pad,), bool)
    fp[:k0] = front
    fm[:k0] = True
    return fp, fm


def fused_qehvi_select(gp, Xc: np.ndarray, front: np.ndarray, ref: np.ndarray,
                       rng: np.random.Generator, q: int, n_samples: int = 64) -> List[int]:
    """Device-resident sequential-greedy q-EHVI: one jitted call per round.

    Argmax-equivalent to ``acquisition.qehvi_sequential_greedy`` and consumes
    the generator identically (q draws of (n_samples, c, 2) normals).
    """
    q = min(int(q), Xc.shape[0])
    eps = np.stack([rng.standard_normal((n_samples, Xc.shape[0], 2)) for _ in range(q)])
    fp, fm = _padded_front(np.asarray(front, np.float64).reshape(-1, 2), q)
    ops = _gp_operands(gp, q)
    with jax.enable_x64(True):
        picks = _fused_qehvi(
            *ops, jnp.asarray(np.asarray(Xc, np.float32)), fp, fm,
            np.asarray(ref, np.float64), eps, q=q,
        )
        picks = np.asarray(picks)
    return [int(i) for i in picks]


def fused_cei_select(gp, Xc: np.ndarray, best_feasible: float, rlim_n: float,
                     q: int) -> List[int]:
    """Device-resident sequential-greedy constrained-EI batch selection."""
    q = min(int(q), Xc.shape[0])
    ops = _gp_operands(gp, q)
    with jax.enable_x64(True):
        picks = _fused_cei(
            *ops, jnp.asarray(np.asarray(Xc, np.float32)),
            np.float64(best_feasible), np.float64(rlim_n), q=q,
        )
        picks = np.asarray(picks)
    return [int(i) for i in picks]
