"""Shared benchmark harness utilities."""
from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from repro.core import (
    DefaultOnly, OpenTunerLike, OtterTuneLike, QEHVI, RandomLHS, TuningSession, VDTuner,
    hv_2d, pareto_front,
)
from repro.compile_cache import enable_compile_cache
from repro.vdms import VDMSTuningEnv, make_dataset

# every benchmark entry point imports this module: one compile cache for all
enable_compile_cache()

# benchmark scale knobs (FULL=1 reproduces paper-scale runs)
FULL = bool(int(os.environ.get("REPRO_BENCH_FULL", "0")))
N_VECTORS = 32768 if FULL else 6144
N_ITERS = 200 if FULL else 36
MODE = "wall" if FULL else "analytic"
DATASETS = ("glove_like", "keyword_like", "georadius_like")
RECALL_FLOORS = (0.85, 0.875, 0.9, 0.925, 0.95, 0.975, 0.99)


def make_env(dataset: str, seed: int = 0, mode: Optional[str] = None,
             n: Optional[int] = None) -> VDMSTuningEnv:
    n = n or N_VECTORS
    dim = None
    if dataset == "georadius_like":
        n = max(n // 4, 2048)
    ds = make_dataset(dataset, n=n, n_queries=128, k=10, seed=seed, dim=dim)
    return VDMSTuningEnv(ds, mode=mode or MODE, seed=seed)


def run_method(name: str, env, space, n_iters: int, seed: int = 0, executor=None, **kw):
    """Drive any tuner through the one ``TuningSession`` harness.

    Returns ``(tuner, wall_s, session)`` — the session carries the
    per-iteration recommend/eval ledger (``session.ledger_dict()``).
    """
    cls = {
        "vdtuner": VDTuner, "default": DefaultOnly, "random_lhs": RandomLHS,
        "ottertune": OtterTuneLike, "qehvi": QEHVI, "opentuner": OpenTunerLike,
    }[name]
    t0 = time.perf_counter()
    tuner = cls(space, env, seed=seed, **kw)
    session = TuningSession(tuner, executor=executor)
    session.run(n_iters)
    wall = time.perf_counter() - t0
    return tuner, wall, session


def norm_hv(tuner, ymax) -> float:
    return hv_2d(pareto_front(tuner.Y) / np.asarray(ymax), np.zeros(2))


def emit(name: str, us_per_call: float, derived: str):
    """CSV row in the required ``name,us_per_call,derived`` format."""
    print(f"{name},{us_per_call:.2f},{derived}")
