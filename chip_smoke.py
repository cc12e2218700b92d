"""Chip smoke: the vector engine and the VDTuner loop on one TPU chip.

Drives the main path once through the entry points a user calls, at the
shape of ann-benchmarks ``glove-100-angular`` (1,183,514 x 100, angular,
k=10), generated from ``--seed``:

1. ``make_dataset`` builds the corpus and exact ground truth on the host;
2. the defaults of FLAT, IVF_SQ8 and IVF_PQ are built as ``VDMSInstance``s
   and answer the queries with the Pallas kernels (``impl="pallas"``); the
   result sets and recall must equal the same instances under
   ``impl="xla"``, and the compiled search must hold a Pallas kernel;
3. a ``TuningSession`` drives ``VDTuner(q=4)`` over those three families
   for 8 evaluations of a ``VDMSTuningEnv`` in wall mode; no evaluation may
   fail.

With ``--chips 4`` it runs only the sharded path: IVF_SQ8 placed on 4
shards under ``shard_map``, whose per-query (gid, score) sets must equal
the 1-shard engine's.

    python chip_smoke.py [--seed 0] [--chips 4]

Exits non-zero, without the result line, when JAX finds no TPU, when run
outside a checkout of this repository, or when any phase fails. Otherwise
the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

GLOVE_N, GLOVE_DIM, GLOVE_K = 1_183_514, 100, 10  # ann-benchmarks glove-100-angular
PUBLISHED_QUERIES = 10_000
N_QUERIES = 1_000
FAMILIES = ("FLAT", "IVF_SQ8", "IVF_PQ")
TUNER_EVALS = 8


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("bytes_in_use")


def differing_queries(a: np.ndarray, b: np.ndarray) -> int:
    """Queries whose returned id sets differ."""
    return sum(set(x[x >= 0].tolist()) != set(y[y >= 0].tolist()) for x, y in zip(a, b))


def run_families(ds, dev) -> None:
    """Phase 2: build the three defaults, search with Pallas, then with XLA."""
    from repro.kernels import ops
    from repro.vdms import VDMSInstance, engine, make_space, recall_at_k

    space = make_space(include=FAMILIES)
    q, k = ds.queries, ds.k
    built = {}
    for fam in FAMILIES:
        inst = VDMSInstance(ds, space.default_config(fam), seed=0)
        r = inst.measure(topk=k, repeats=3, mode="wall")
        ids = inst.search(q, k)
        args = (
            inst._chunked_queries(q), inst.bundle.arrays, inst.growing, inst.growing_gids,
            inst.bundle.kind, tuple(sorted(inst.bundle.static.items())), inst.k_seg, k,
            engine.get_search_pipeline() == "fused", inst._clamp_ok,
        )
        kernel = "tpu_custom_call" in engine._pipeline.lower(*args).compile().as_text()
        say(
            "search_pallas", family=fam, config=inst.config, n_segments=inst.plan.n_sealed,
            index_bytes=inst.bundle.memory_bytes(), device_bytes_in_use=device_bytes(dev),
            build_s=inst.build_time, compile_s=r["compile_time"],
            search_s=len(q) / r["speed"], qps=r["speed"], recall=r["recall"],
            tpu_custom_call=kernel,
        )
        if not kernel:
            raise RuntimeError(f"{fam}: the compiled search holds no Pallas kernel")
        built[fam] = (inst, ids, r["recall"])

    # the XLA reference on the same instances; it runs one query chunk per
    # call, since its candidate gathers do not fit the chip for the batch
    ops.set_default_impl("xla")
    try:
        for fam, (inst, ids_p, recall_p) in built.items():
            t0 = time.perf_counter()
            ids_x = np.concatenate(
                [inst.search(q[i : i + inst.batch], k) for i in range(0, len(q), inst.batch)]
            )
            recall_x = recall_at_k(ids_x[:, : ds.k], ds.ground_truth)
            differ = differing_queries(ids_p, ids_x)
            say(
                "search_xla", family=fam, seconds=time.perf_counter() - t0,
                recall=recall_x, queries_with_other_sets=differ,
            )
            if differ or recall_x != recall_p:
                raise RuntimeError(f"{fam}: Pallas and XLA results differ on {differ} queries")
    finally:
        ops.set_default_impl("pallas")
    flat, _, flat_recall = built["FLAT"]
    # FLAT is exact when graceful_time hides no part of the growing tail
    if flat.plan.growing_searched == flat.plan.growing_size and flat_recall < 0.999:
        raise RuntimeError(f"FLAT is exact but recalled {flat_recall}")


def run_tuner(ds, seed: int) -> None:
    """Phase 3: VDTuner's ask/tell loop against the wall-clock environment."""
    from repro.core import TuningSession, VDTuner
    from repro.vdms import VDMSTuningEnv, make_space

    env = VDMSTuningEnv(ds, mode="wall", seed=seed)
    tuner = VDTuner(make_space(include=FAMILIES), seed=seed, q=4)
    t0 = time.perf_counter()
    session = TuningSession(tuner, backend=env).run(TUNER_EVALS)
    for o in tuner.history:
        say(
            "tuner_eval", iteration=o.iteration, index_type=o.index_type, failed=o.failed,
            qps=o.raw.get("speed"), recall=o.raw.get("recall"), mem_gib=o.raw.get("mem_gib"),
            build_s=o.raw.get("build_time"), compile_s=o.raw.get("compile_time"),
            eval_s=o.eval_time, recommend_s=o.recommend_time,
        )
    totals = session.ledger_dict()["totals"]
    say("tuner", seconds=time.perf_counter() - t0, evaluations=len(tuner.history), **totals)
    failed = [o.config for o in tuner.history if o.failed]
    if failed or len(tuner.history) < TUNER_EVALS:
        raise RuntimeError(f"{len(failed)} failed evaluations of {len(tuner.history)}: {failed}")


def run_sharded(ds, n_shards: int) -> None:
    """``--chips 4``: IVF_SQ8 on n_shards devices under shard_map, compared
    with the single-device engine."""
    from repro.vdms import ShardedVDMS, VDMSInstance, make_space

    inst = VDMSInstance(ds, make_space(include=FAMILIES).default_config("IVF_SQ8"), seed=0)
    out = {}
    for n, dispatch in ((1, "direct"), (n_shards, "shard_map")):
        sh = ShardedVDMS.from_instance(inst, n_shards=n, dispatch=dispatch)
        t0 = time.perf_counter()
        sh.search(ds.queries, ds.k, mode="wall", return_scores=True)  # compile + warm
        compile_s = time.perf_counter() - t0
        ids, scores, elapsed = sh.search(ds.queries, ds.k, mode="wall", return_scores=True)
        out[n] = (ids, scores)
        say(
            "sharded", n_shards=n, dispatch=sh.dispatch, build_s=inst.build_time,
            compile_s=compile_s, search_s=elapsed, qps=len(ds.queries) / elapsed,
            per_shard_segments=sh.per_shard,
        )
    (i1, s1), (i4, s4) = out[1], out[n_shards]
    differ = sum(
        set(zip(a.tolist(), x.tolist())) != set(zip(b.tolist(), y.tolist()))
        for a, x, b, y in zip(i1, s1, i4, s4)
    )
    say("sharded_compare", shards=[1, n_shards], queries_with_other_sets=differ)
    if differ:
        raise RuntimeError(f"(gid, score) sets differ between 1 and {n_shards} shards")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0, help="seed of the generated corpus")
    p.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4 runs only the sharded phase, across four chips",
    )
    args = p.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "vdms").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found", file=sys.stderr)
        return 1
    say("device", platform=dev.platform, kind=dev.device_kind, count=len(devices))

    from repro.vdms import make_dataset

    try:
        t0 = time.perf_counter()
        ds = make_dataset(
            "glove_like", n=GLOVE_N, n_queries=N_QUERIES, k=GLOVE_K, seed=args.seed, dim=GLOVE_DIM
        )
        say(
            "dataset", source="ann-benchmarks glove-100-angular shape", n=ds.n, dim=ds.dim,
            k=ds.k, queries=N_QUERIES, published_queries=PUBLISHED_QUERIES,
            cut=f"{N_QUERIES} of {PUBLISHED_QUERIES} queries: exact ground truth in numpy",
            seconds=time.perf_counter() - t0,
        )
        if args.chips == 1:
            run_families(ds, dev)
            run_tuner(ds, args.seed)
        else:
            run_sharded(ds, args.chips)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(
        {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                "count": len(devices)}}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
