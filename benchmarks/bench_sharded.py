"""Sharded multi-device segment serving at realistic corpus size.

Builds one IVF_SQ8 corpus at ``n_base >= 1M`` (256+ sealed segments), places
it at increasing shard counts with :class:`~repro.vdms.sharded.ShardedVDMS`,
and measures per shard count:

* **QPS** in the deterministic analytic mode (the CI-gated number: leaf work
  charges the critical shard, the root merge charges the shard count) with
  wall-clock reported alongside;
* **recall** against the brute-force oracle — gated to match the unsharded
  engine *exactly* (sharding must never change what is returned);
* **(gid, score) result sets** — gated identical across every shard count;
* a **Poisson multi-stream replay** (``repro.vdms.replay_query_streams``)
  offered at ~70% of the measured analytic capacity: served QPS, sojourn
  percentiles, utilization, saturation flag.

``--check-invariants`` exits non-zero unless the recall/result-set
invariants hold AND the 1→4-shard analytic scaling clears
``MIN_QPS_SCALING_1_TO_4`` (when a 4-shard point is in the run). CI runs the
quick mode on a 4-device host-emulated mesh (``sharded-smoke``) and uploads
``BENCH_sharded.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.vdms import (
    ShardedVDMS,
    VDMSInstance,
    make_dataset,
    recall_at_k,
    replay_query_streams,
)
from repro.vdms.sharded import MIN_QPS_SCALING_1_TO_4

from .common import emit

SHARD_COUNTS = (1, 2, 4)


def _sizes(quick: bool):
    if quick:
        return dict(n_base=1_048_576, dim=64, n_queries=64, k=10)
    return dict(n_base=4_194_304, dim=64, n_queries=256, k=10)


def _config(quick: bool):
    return dict(
        index_type="IVF_SQ8",
        nlist=64,
        nprobe=8,
        kmeans_iters=4,
        segment_max_size=4096,
        seal_proportion=1.0,
        search_batch_size=32,
        graceful_time=0.2,
        topk_merge_width=32,
        storage_bf16=False,
    )


def _result_set(ids: np.ndarray, scores: np.ndarray):
    """Per-query frozenset of (gid, score-bits) — the shard-count invariant
    compares exact float bit patterns, not approximate equality."""
    bits = scores.view(np.int32)
    return [
        frozenset(
            (int(g), int(b)) for g, b in zip(row_i, row_b) if g >= 0
        )
        for row_i, row_b in zip(ids, bits)
    ]


def run(seed: int = 0, quick: bool = True, shard_counts=SHARD_COUNTS):
    sz = _sizes(quick)
    t0 = time.perf_counter()
    ds = make_dataset(
        "glove_like", n=sz["n_base"], n_queries=sz["n_queries"],
        dim=sz["dim"], k=sz["k"], seed=seed,
    )
    dataset_s = time.perf_counter() - t0
    cfg = _config(quick)
    t0 = time.perf_counter()
    inst = VDMSInstance(ds, cfg, seed=seed)
    build_s = time.perf_counter() - t0

    n_devices = len(jax.devices())
    out = {
        "n_base": sz["n_base"],
        "dim": sz["dim"],
        "n_queries": sz["n_queries"],
        "k": sz["k"],
        "n_sealed": int(inst.plan.n_sealed),
        "n_devices": n_devices,
        "dataset_s": dataset_s,
        "build_s": build_s,
        "min_qps_scaling_1_to_4": MIN_QPS_SCALING_1_TO_4,
        "shards": {},
    }

    baseline = None
    for n in shard_counts:
        dispatch = "auto" if n <= n_devices else "vmap"
        sharded = ShardedVDMS.from_instance(inst, n_shards=n, dispatch=dispatch)
        # one compiled warm pass, then the scored searches
        ids, scores, _ = sharded.search(
            ds.queries, sz["k"], mode="analytic", return_scores=True
        )
        _, analytic_s = sharded.search(ds.queries, sz["k"], mode="analytic")
        _, wall_s = sharded.search(ds.queries, sz["k"], mode="wall")
        qps = sz["n_queries"] / max(analytic_s, 1e-12)
        recall = float(recall_at_k(ids[:, : ds.k], ds.ground_truth))
        rec = {
            "dispatch": sharded.dispatch,
            "qps_analytic": float(qps),
            "qps_wall": float(sz["n_queries"] / max(wall_s, 1e-12)),
            "recall": recall,
            "mem_gib": float(sharded.memory_gib()),
            "stats": sharded.stats(),
        }
        if baseline is None:
            baseline = {
                "qps": qps,
                "recall": recall,
                "sets": _result_set(ids, scores),
                "ids": ids,
            }
            rec["qps_scaling_vs_1"] = 1.0
            rec["recall_matches_unsharded"] = True
            rec["result_sets_match"] = True
            rec["bitwise_identical"] = True
        else:
            rec["qps_scaling_vs_1"] = float(qps / baseline["qps"])
            rec["recall_matches_unsharded"] = bool(recall == baseline["recall"])
            rec["result_sets_match"] = bool(
                _result_set(ids, scores) == baseline["sets"]
            )
            rec["bitwise_identical"] = bool(np.array_equal(ids, baseline["ids"]))
        # Poisson multi-stream replay at ~70% of analytic capacity
        rec["poisson"] = replay_query_streams(
            sharded, ds.queries, rate=0.7 * qps, n_streams=8,
            n_per_stream=32 if quick else 64, topk=sz["k"], seed=seed,
        )
        out["shards"][str(n)] = rec
        emit(
            f"sharded/{n}",
            analytic_s / sz["n_queries"] * 1e6,
            f"qps={qps:.0f};scale={rec['qps_scaling_vs_1']:.2f};"
            f"recall={recall:.3f};dispatch={sharded.dispatch}",
        )
    return out


def check_invariants(out) -> list:
    """The CI gate: returns a list of violation strings (empty = pass)."""
    bad = []
    for n, rec in out["shards"].items():
        if not rec["recall_matches_unsharded"]:
            bad.append(f"{n} shards: recall diverged from the unsharded engine")
        if not rec["result_sets_match"]:
            bad.append(f"{n} shards: (gid, score) result sets changed")
    rec4 = out["shards"].get("4")
    if rec4 is not None:
        if rec4["qps_scaling_vs_1"] < out["min_qps_scaling_1_to_4"]:
            bad.append(
                f"1->4 shard scaling {rec4['qps_scaling_vs_1']:.2f}x below the "
                f"{out['min_qps_scaling_1_to_4']}x gate"
            )
    if out["n_base"] < 1_000_000:
        bad.append(f"n_base={out['n_base']} below the 1M-vector floor")
    return bad


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true", help="CI-sized corpus (1M vectors)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--shards", nargs="+", type=int, default=list(SHARD_COUNTS),
        help="shard counts to measure (shard counts beyond the device count run vmap-emulated)",
    )
    p.add_argument("--json", default=None, metavar="PATH", help="write results as JSON (CI artifact)")
    p.add_argument(
        "--check-invariants", action="store_true",
        help="exit 1 unless recall/result-set invariants hold and 1->4 "
             "scaling clears the gate",
    )
    args = p.parse_args(argv)

    out = run(seed=args.seed, quick=args.quick, shard_counts=tuple(args.shards))
    violations = check_invariants(out)
    out["invariant_violations"] = violations
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)

    for n, rec in out["shards"].items():
        po = rec["poisson"]
        print(
            f"{n} shards ({rec['dispatch']}): qps={rec['qps_analytic']:.0f} "
            f"(scale {rec['qps_scaling_vs_1']:.2f}x) recall={rec['recall']:.3f} "
            f"poisson served={po['served_qps']:.0f}/{po['offered_qps']:.0f} "
            f"p99={po['sojourn_p99_s'] * 1e3:.2f}ms util={po['utilization']:.2f}"
        )
    if violations:
        for v in violations:
            print(f"INVARIANT VIOLATION: {v}", file=sys.stderr)
    return 1 if (args.check_invariants and violations) else 0


if __name__ == "__main__":
    sys.exit(main())
