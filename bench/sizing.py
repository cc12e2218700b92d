"""Seconds and memory of the harness's own data and check at a
configuration's size, on the chips it is started on, with no program built:
what a cell on several chips is sized by before it is added.

    python bench/sizing.py --config <configuration .json> --seed 5 --check-queries 10000

In one process it runs the harness's own code: set-up's data step
(``run.host_data``: the corpus made on the chips and copied to the host), the
check's regeneration (``run.make_data``, compared with the host copy on a
sample of rows, then freed), and the check (``run.check``, which makes the
corpus again) over the first ``--check-queries`` pool queries as a window's
answers. One JSON line a phase: its seconds, the compile seconds within them,
each chip's ``peak_bytes_in_use`` so far (it never falls), the process's peak
resident set and the host's ``MemTotal``. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
import types
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def mem_total_bytes() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                return int(line.split()[1]) * 1024
    return -1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Time the harness's data and check at a configuration's size.")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--check-queries", type=int, required=True)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    from bench import run

    config = json.loads(Path(args.config).read_text())
    chips, k = run.shards_of(config), int(config["shape"]["k"])
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs) if event == run.COMPILE_EVENT else None)

    def phase(name, fn, **extra):
        del compiles[:]
        t = time.perf_counter()
        out = jax.block_until_ready(fn())
        seconds = time.perf_counter() - t
        print(json.dumps({"phase": name, "seconds": seconds, "compile_s": sum(compiles),
                          "peak_bytes_per_device": run.peak_bytes(chips),
                          "host_peak_rss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
                          "host_mem_total_bytes": mem_total_bytes(), **extra}), flush=True)
        return out

    _, _, corpus_host, pool_host = phase("data", lambda: run.host_data(config, args.seed))
    corpus, _ = phase("regenerate", lambda: run.make_data(config, args.seed))
    rows = np.random.default_rng(args.seed).choice(corpus_host.shape[0], 256, replace=False)
    print(json.dumps({"phase": "regenerated_equal",
                      "value": bool(np.array_equal(np.asarray(corpus[jnp.asarray(rows)]), corpus_host[rows]))}),
          flush=True)
    corpus.delete()
    del corpus
    q = min(args.check_queries, pool_host.shape[0])
    stage = types.SimpleNamespace(corpus=None, corpus_host=corpus_host, pool=pool_host)
    ev = types.SimpleNamespace(rows=np.arange(q), answers=np.tile(np.arange(k, dtype=np.int32), (q, 1)))
    phase("check", lambda: run.check(stage, config, args.seed, ev), queries=q)
    return 0


if __name__ == "__main__":
    sys.exit(main())
