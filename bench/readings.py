"""Readings of the numbers compared, from which each limit is set.

    python bench/readings.py --workload <cell> --seeds 101-112 --seconds 5 \\
        [--system program|control|<fault of bench/faults.py>]

For each seed, in one process: the cell's set-up, one window of its own
traffic at its own load (``--seconds`` long enough to finish as many
requests as the check of a run compares), and the check. One JSON line per
seed. ``program`` gives the lower readings, ``control`` and the planted
faults (``bench/faults.py``) the upper ones; ``PERF.md`` records both and
the limits set between them. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import faults, run  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Readings of a cell's compared numbers.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="e.g. 101-112 or 5,9,3000000000")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--system", default="program",
                   choices=("program", "control", *faults.FAULTS))
    args = p.parse_args(argv)
    sys.path.insert(0, str(run.SRC))
    spec = run.load_spec()
    cell, config, mix = run.load_cell(args.workload, spec)

    import jax

    from bench import traffic
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.require_chip(int(cell["chips"]))
    program = run.Program(config["index"], run.shards_of(config))
    system = {"program": program, "control": faults.control(config)}.get(args.system)
    system = system or faults.Faulty(program, args.system)
    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        stage = run.set_up(config, mix, seed, system)
        log = traffic.drive(mix, system=stage.system, searcher=stage.searcher, dataset=stage.dataset,
                            pool=stage.pool, seconds=args.seconds, seed=seed)
        ev = run.answered(stage, mix, log, seed)
        stage.searcher = stage.warm_clusters = log = None
        numbers = run.check(stage, config, seed, ev)
        print(json.dumps({"workload": cell["name"], "system": args.system, "seed": seed,
                          "answers": int(len(ev.rows)), "seconds": time.perf_counter() - t0,
                          **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
