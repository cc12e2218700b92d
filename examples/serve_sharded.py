"""Sharded multi-device serving: place a sealed corpus on a mesh and drive it.

Walks the full sharded serving path on a host-emulated 4-device mesh (the
``XLA_FLAGS`` line below must run before JAX imports):

1. bulk-build a corpus with :class:`~repro.vdms.engine.VDMSInstance`;
2. place its sealed segments across 1/2/4 shards
   (:class:`~repro.vdms.sharded.ShardedVDMS`) and verify the shard-count
   invariants — identical recall, identical (gid, score) sets, >= trend
   analytic QPS scaling;
3. attach the serving metrics ledger (``attach_sharded``) and offer
   multi-stream Poisson load (:func:`~repro.vdms.replay_query_streams`);
4. snapshot a tombstoned :class:`~repro.vdms.engine.LiveVDMS` with
   ``from_live`` and confirm the 1-shard snapshot is bit-identical.

Run: PYTHONPATH=src python examples/serve_sharded.py
(CI runs this file in the api-smoke job; exits non-zero on failure.)
"""
from __future__ import annotations

import os
import sys

# emulate a 4-device mesh on one host BEFORE jax initializes
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

from repro.serving import attach_sharded, serving_ledger  # noqa: E402
from repro.vdms import (  # noqa: E402
    LiveVDMS,
    ShardedVDMS,
    VDMSInstance,
    make_dataset,
    recall_at_k,
    replay_query_streams,
)

CONFIG = dict(
    index_type="IVF_SQ8", nlist=32, nprobe=8, kmeans_iters=3,
    segment_max_size=2048, seal_proportion=1.0, search_batch_size=32,
    graceful_time=0.2, topk_merge_width=32, storage_bf16=False,
)


def main() -> int:
    import jax

    print(f"== mesh: {len(jax.devices())} devices ==")
    ds = make_dataset("glove_like", n=65536, n_queries=64, dim=64, k=10, seed=0)
    inst = VDMSInstance(ds, CONFIG, seed=0)
    print(f"   built {inst.plan.n_sealed} sealed segments over {ds.n} vectors")

    print("== shard-count invariants (1 -> 2 -> 4 shards) ==")
    results = {}
    for n in (1, 2, 4):
        # more shards than devices: emulate the mesh on one device, by name
        dispatch = "auto" if n <= len(jax.devices()) else "vmap"
        sharded = ShardedVDMS.from_instance(inst, n_shards=n, dispatch=dispatch)
        ids, elapsed = sharded.search(ds.queries, 10, mode="analytic")
        recall = recall_at_k(ids, ds.ground_truth)
        results[n] = (ids, elapsed, recall, sharded)
        print(
            f"   {n} shards ({sharded.dispatch}): qps={ds.queries.shape[0] / elapsed:.0f} "
            f"recall={recall:.3f}"
        )
    ids1 = results[1][0]
    assert all(np.array_equal(results[n][0], ids1) for n in (2, 4)), \
        "shard count changed the returned ids"
    assert len({results[n][2] for n in (1, 2, 4)}) == 1, "recall diverged"
    assert results[4][1] < results[1][1], "4 shards must be faster than 1 (analytic)"
    print("   invariants hold: identical ids, identical recall, QPS scales")

    print("== Poisson multi-stream serving with the metrics ledger ==")
    sharded = results[4][3]
    ledger = serving_ledger()
    attach_sharded(ledger, sharded)
    qps = ds.queries.shape[0] / results[4][1]
    rep = replay_query_streams(
        sharded, ds.queries, rate=0.5 * qps, n_streams=8, n_per_stream=16, topk=10,
    )
    print(
        f"   offered={rep['offered_qps']:.0f}/s served={rep['served_qps']:.0f}/s "
        f"p99={rep['sojourn_p99_s'] * 1e3:.2f}ms util={rep['utilization']:.2f}"
    )
    assert ledger.get("vdms_queries_total").value > 0, "ledger saw no queries"
    assert ledger.get("vdms_shards").value == 4.0
    print(f"   ledger: shards={ledger.get('vdms_shards').value:.0f} "
          f"queries={ledger.get('vdms_queries_total').value:.0f} "
          f"skew={ledger.get('vdms_shard_skew').value:.2f}")

    print("== live snapshot: tombstones + growing tail, sharded ==")
    live = LiveVDMS(CONFIG, dim=64, capacity=65536, seed=0)
    live.insert(ds.data[:20000])
    rng = np.random.default_rng(0)
    for g in rng.choice(16000, 800, replace=False):
        live.delete(int(g))
    live_ids, _ = live.search(ds.queries, 10)
    snap = ShardedVDMS.from_live(live, n_shards=1)
    snap_ids, _ = snap.search(ds.queries, 10, mode="analytic")
    assert np.array_equal(snap_ids, live_ids), "1-shard live snapshot must be bit-identical"
    snap4 = ShardedVDMS.from_live(
        live, n_shards=4, dispatch="auto" if len(jax.devices()) >= 4 else "vmap"
    )
    ids4, _ = snap4.search(ds.queries, 10, mode="analytic")
    assert np.array_equal(ids4, live_ids), "4-shard live snapshot changed results"
    st = snap4.stats()
    print(
        f"   live snapshot serves identically at 4 shards "
        f"(min shard coverage {st['min_shard_coverage']:.3f}, "
        f"tail {st['growing_size']} rows)"
    )
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
