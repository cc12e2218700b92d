"""Sweep of offered rates for an open-loop cell, to find the knee once.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 20 --rates 200,400,600

One set-up, then one window of the cell's traffic at each offered rate. For
each rate it prints one JSON line: requests, served rate, calls and mean
batch, p50/p95/p99 latency, and whether the backlog grew (the mean latency
of the window's last quarter of requests over its first quarter). The knee
is the highest rate whose backlog does not grow; a cell's rate is fixed in
its traffic file from it, and ``PERF.md`` records the sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Offered-rate sweep of an open-loop cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = p.parse_args(argv)
    sys.path.insert(0, str(run.SRC))
    cell, config, mix = run.load_cell(args.workload, run.load_spec())
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")

    import jax

    from bench import traffic
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.require_chip(int(cell["chips"]))
    stage = run.set_up(config, mix, args.seed, None)
    for rate in (float(r) for r in args.rates.split(",")):
        log = traffic.drive({**mix, "rate_per_s": rate}, system=stage.system,
                            searcher=stage.searcher, dataset=stage.dataset, pool=stage.pool,
                            seconds=args.seconds, seed=args.seed)
        lat = np.asarray(log.done) - np.asarray(log.scheduled)
        quarter = max(lat.size // 4, 1)
        print(json.dumps({
            "offered_per_s": rate, "requests": int(lat.size),
            "served_per_s": lat.size / log.end, "calls": len(log.calls),
            "mean_batch": lat.size / len(log.calls),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "backlog_growth": float(lat[-quarter:].mean() / lat[:quarter].mean()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
