"""Kernel microbenchmarks (XLA path wall-time on this host + interpret-mode
correctness deltas), end-to-end fused-vs-composed search-pipeline rows, and
the dry-run roofline summary if artifacts exist.

The pipeline section builds one static ``VDMSInstance`` per hot family and
measures the SAME wall-clock search under both pipeline modes
(``set_search_pipeline``), so the reported speedup is exactly what the tuner's
wall-mode evaluations see. ``--check-speedup`` gates fused >= 2x composed QPS
on the hot families (IVF_SQ8, IVF_PQ) and verifies the composed fallback for
families without a fused hook; ``--json`` writes the per-family record
(``BENCH_fused.json`` in CI, rendered by ``roofline_table.py``).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.vdms import (
    VDMSInstance,
    get_family,
    get_search_pipeline,
    make_dataset,
    set_search_pipeline,
)
from repro.vdms.ivf_pqr import register as register_ivf_pqr

from .common import emit


def _time(fn, *args, repeats=5):
    fn(*args).block_until_ready() if hasattr(fn(*args), "block_until_ready") else None
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run():
    rng = np.random.default_rng(0)
    out = {}
    # distance
    q = jnp.asarray(rng.standard_normal((128, 96)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8192, 96)), jnp.float32)
    t = _time(lambda a, b: ops.batched_ip(a, b, impl="xla"), q, x)
    flops = 2 * 128 * 8192 * 96
    emit("kernel/distance_ip_128x8192x96", t * 1e6, f"gflops={flops/t/1e9:.1f}")
    out["distance"] = t
    # flash attention fwd
    qq = jnp.asarray(rng.standard_normal((1, 1024, 8, 64)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((1, 1024, 4, 64)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((1, 1024, 4, 64)), jnp.float32)
    t = _time(lambda a, b, c: ops.flash_attention(a, b, c, causal=True, impl="xla"), qq, kk, vv)
    emit("kernel/flash_fwd_b1_s1024_h8_d64", t * 1e6, "causal_gqa")
    out["flash"] = t
    # roofline summary from dry-run artifacts
    d = Path("experiments/dryrun")
    if d.exists():
        worst, bound_counts = None, {}
        for f in sorted(d.glob("*_256.json")):
            r = json.loads(f.read_text())
            if "skipped" in r or "bottleneck" not in r:
                continue
            bound_counts[r["bottleneck"]] = bound_counts.get(r["bottleneck"], 0) + 1
            frac = r.get("roofline_fraction", 0)
            if worst is None or frac < worst[1]:
                worst = (f.stem, frac)
        if worst:
            emit("roofline/summary", 0.0,
                 f"bounds={bound_counts};worst={worst[0]}@{worst[1]:.4f}")
    return out


# ---------------------------------------------------------------------------
# end-to-end search-pipeline rows (fused vs composed, per family)
# ---------------------------------------------------------------------------
#: families the >=2x fused-QPS gate applies to (the eval hot path)
GATED_FAMILIES = ("IVF_SQ8", "IVF_PQ")
#: a family registered WITHOUT a fused hook — exercises the composed fallback
FALLBACK_FAMILY = "IVF_FLAT"

_FAMILY_PARAMS = {
    "IVF_FLAT": {"nlist": 64, "nprobe": 8},
    "IVF_SQ8": {"nlist": 64, "nprobe": 8},
    "IVF_PQ": {"nlist": 64, "nprobe": 8, "m": 8, "nbits": 8},
    "IVF_PQR": {"nlist": 64, "nprobe": 8, "m": 8, "nbits": 8, "reorder_k": 64},
}


def run_pipelines(quick: bool = False, repeats: int = 5, check_speedup: bool = False):
    """Per-family end-to-end chunk pipeline: composed vs fused wall QPS.

    Builds each instance once, measures the identical query stream under both
    pipeline modes, and (optionally) enforces the fused >= 2x gate plus the
    fallback identity for hook-less families. Returns {family: record}.
    """
    register_ivf_pqr()
    n, seg = (4608, 2048) if quick else (9216, 4096)
    ds = make_dataset("glove_like", n=n, n_queries=128, k=10, seed=0)
    base = {
        "segment_max_size": seg, "seal_proportion": 0.75, "graceful_time": 0.2,
        "search_batch_size": 32, "topk_merge_width": 64, "kmeans_iters": 4,
        "storage_bf16": False,
    }
    records = {}
    prev = get_search_pipeline()
    try:
        for fam, params in _FAMILY_PARAMS.items():
            cfg = dict(base, index_type=fam, **params)
            inst = VDMSInstance(ds, cfg, seed=0)
            n_chunks = (ds.queries.shape[0] + inst.batch - 1) // inst.batch
            res = {}
            for mode in ("composed", "fused"):
                set_search_pipeline(mode)
                r = inst.measure(topk=10, repeats=repeats, mode="wall")
                ms_chunk = ds.queries.shape[0] / r["speed"] / n_chunks * 1e3
                res[mode] = dict(r, ms_chunk=ms_chunk)
                emit(
                    f"pipeline/{fam}_{mode}",
                    ms_chunk * 1e3,
                    f"qps={r['speed']:.0f};recall={r['recall']:.3f}",
                )
            speedup = res["fused"]["speed"] / res["composed"]["speed"]
            fused_hook = get_family(fam).fused_search is not None
            emit(
                f"pipeline/{fam}_speedup",
                0.0,
                f"x={speedup:.2f};fused_hook={int(fused_hook)}",
            )
            records[fam] = {
                "fused_hook": fused_hook,
                "composed_qps": res["composed"]["speed"],
                "fused_qps": res["fused"]["speed"],
                "composed_ms_chunk": res["composed"]["ms_chunk"],
                "fused_ms_chunk": res["fused"]["ms_chunk"],
                "speedup": speedup,
                "recall": res["fused"]["recall"],
            }
            if fused_hook:
                # result-set identity between the two modes on this instance
                set_search_pipeline("composed")
                a = inst.search(ds.queries[:32], 10)
                set_search_pipeline("fused")
                b = inst.search(ds.queries[:32], 10)
                same = all(
                    set(x[x >= 0]) == set(y[y >= 0]) for x, y in zip(a, b)
                )
                if not same:
                    raise AssertionError(f"{fam}: fused result set != composed")
        if check_speedup:
            if get_family(FALLBACK_FAMILY).fused_search is not None:
                raise AssertionError(
                    f"{FALLBACK_FAMILY} grew a fused hook; pick another fallback family"
                )
            fb = records[FALLBACK_FAMILY]["speedup"]
            if not 0.5 < fb < 2.0:
                raise AssertionError(
                    f"fallback family {FALLBACK_FAMILY} should be mode-invariant, "
                    f"got {fb:.2f}x"
                )
            for fam in GATED_FAMILIES:
                s = records[fam]["speedup"]
                if s < 2.0:
                    raise AssertionError(
                        f"fused pipeline gate: {fam} speedup {s:.2f}x < 2.0x"
                    )
            print("check-speedup OK: " + ", ".join(
                f"{f}={records[f]['speedup']:.2f}x" for f in GATED_FAMILIES))
    finally:
        set_search_pipeline(prev)
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="smaller corpus (CI smoke)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--json", metavar="PATH", help="write pipeline records as JSON")
    ap.add_argument(
        "--check-speedup", action="store_true",
        help="fail unless fused >= 2x composed QPS on the gated families",
    )
    ap.add_argument(
        "--ops-only", action="store_true", help="skip the pipeline section",
    )
    args = ap.parse_args(argv)
    run()
    if args.ops_only:
        return
    records = run_pipelines(
        quick=args.quick, repeats=args.repeats, check_speedup=args.check_speedup
    )
    if args.json:
        Path(args.json).write_text(json.dumps(records, indent=2, sort_keys=True))
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
