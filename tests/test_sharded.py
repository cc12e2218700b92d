"""Sharded multi-device segment serving: merge extraction + shard invariants.

Four contract layers, weakest to strongest:

1. **merge regression** — the extracted ``repro.vdms.merge.merge_topk`` is
   bitwise-identical to verbatim copies of the pre-extraction engine merge
   code (``_pipeline_impl``'s static flavor and ``_live_chunk``'s tombstone
   flavor), on adversarial inputs: -1 padding, dead segments, empty tails,
   score ties.
2. **single shard** — ``ShardedVDMS`` at ``n_shards=1`` returns byte-identical
   ids to the unsharded engine (static and live, composed and fused).
3. **shard-count invariance** — a seeded randomized property sweep: for any
   corpus/shape/shard count, the per-query (gid, score) sets never change
   (hypothesis is not available in this environment; the sweep draws many
   cases from a fixed-seed rng instead).
4. **degenerate shapes** — more shards than sealed segments (dead padding
   shards), every segment on one shard fully tombstoned, and the Poisson
   multi-stream driver's bookkeeping.

Doc-sync tests at the bottom keep ``docs/SHARDING.md``'s generated tables
and the README links honest.
"""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.vdms as V
from repro.vdms.merge import merge_topk
from repro.vdms.sharded import SHARD_INVARIANTS, ShardedVDMS, shard_invariants_table
from repro.vdms.workload import make_query_streams, poisson_arrivals, replay_query_streams

BASE = {
    "segment_max_size": 512, "seal_proportion": 1.0, "graceful_time": 0.2,
    "search_batch_size": 16, "topk_merge_width": 32, "kmeans_iters": 3,
    "storage_bf16": False,
}


@pytest.fixture
def pipeline_guard():
    prev = V.get_search_pipeline()
    yield
    V.set_search_pipeline(prev)


def _dataset(n=4096, dim=32, nq=16, seed=0):
    return V.make_dataset("glove_like", n=n, n_queries=nq, dim=dim, k=10, seed=seed)


def _instance(ds, seed=0, **over):
    cfg = dict(BASE, index_type="IVF_SQ8", nlist=16, nprobe=8, **over)
    return V.VDMSInstance(ds, cfg, seed=seed)


# ---------------------------------------------------------------------------
# 1. merge_topk is bitwise the old engine merge (verbatim pre-extraction code)
# ---------------------------------------------------------------------------
def _old_static_merge(ids, sims, q, growing, growing_gids, topk):
    """Verbatim merge tail of the pre-extraction ``_pipeline_impl`` chunk_fn."""
    n_seg, b, ks = ids.shape
    ids2 = jnp.moveaxis(ids, 0, 1).reshape(b, n_seg * ks)
    sims2 = jnp.moveaxis(sims, 0, 1).reshape(b, n_seg * ks)
    if growing.shape[0] > 0:
        gs = jnp.dot(q, growing.T.astype(q.dtype), preferred_element_type=jnp.float32)
        gk = min(topk, growing.shape[0])
        gtop_s, gtop_i = jax.lax.top_k(gs, gk)
        ids2 = jnp.concatenate([ids2, growing_gids[gtop_i]], axis=1)
        sims2 = jnp.concatenate([sims2, gtop_s], axis=1)
    k = min(topk, sims2.shape[1])
    top_s, top_i = jax.lax.top_k(sims2, k)
    out = jnp.take_along_axis(ids2, top_i, axis=1)
    if k < topk:
        out = jnp.pad(out, ((0, 0), (0, topk - k)), constant_values=-1)
    return out


def _old_live_merge(ids, sims, q, alive_g, growing, growing_gids, topk):
    """Verbatim merge tail of the pre-extraction ``_live_chunk``."""
    sentinel = alive_g.shape[0] - 1
    n_seg, b, ks = ids.shape
    ids2 = jnp.moveaxis(ids, 0, 1).reshape(b, n_seg * ks)
    sims2 = jnp.moveaxis(sims, 0, 1).reshape(b, n_seg * ks)
    ok = alive_g[jnp.where(ids2 >= 0, ids2, sentinel)]
    sims2 = jnp.where(ok, sims2, -jnp.inf)
    if growing.shape[0] > 0:
        gs = jnp.dot(q, growing.T.astype(q.dtype), preferred_element_type=jnp.float32)
        gs = jnp.where(growing_gids[None, :] >= 0, gs, -jnp.inf)
        gk = min(topk, growing.shape[0])
        gtop_s, gtop_i = jax.lax.top_k(gs, gk)
        ids2 = jnp.concatenate([ids2, growing_gids[gtop_i]], axis=1)
        sims2 = jnp.concatenate([sims2, gtop_s], axis=1)
    k = min(topk, sims2.shape[1])
    top_s, top_i = jax.lax.top_k(sims2, k)
    out = jnp.take_along_axis(ids2, top_i, axis=1)
    out = jnp.where(jnp.isfinite(top_s), out, -1)
    if k < topk:
        out = jnp.pad(out, ((0, 0), (0, topk - k)), constant_values=-1)
    return out


def _random_merge_case(rng, n_seg, b, ks, dim, n_grow, n_gids, tie_prob=0.3):
    """Adversarial candidates: -1 pads, dead segments, duplicated (tied)
    scores, a tail with -1 (pad) gid rows."""
    ids = rng.integers(0, n_gids, size=(n_seg, b, ks)).astype(np.int32)
    dead = rng.random((n_seg, b, ks)) < 0.25
    ids = np.where(dead, -1, ids)
    sims = rng.standard_normal((n_seg, b, ks)).astype(np.float32)
    # force score ties so the lowest-flat-index tie-break is exercised
    ties = rng.random((n_seg, b, ks)) < tie_prob
    sims = np.where(ties, np.float32(0.5), sims)
    sims = np.where(dead, -np.inf, sims)
    q = rng.standard_normal((b, dim)).astype(np.float32)
    growing = rng.standard_normal((n_grow, dim)).astype(np.float32)
    ggids = rng.integers(0, n_gids, size=n_grow).astype(np.int32)
    ggids[rng.random(n_grow) < 0.3] = -1
    return ids, sims, q, growing, ggids


@pytest.mark.parametrize("n_grow", [0, 7, 32])
@pytest.mark.parametrize("topk", [4, 10, 64])
def test_merge_topk_matches_old_static_merge(n_grow, topk):
    rng = np.random.default_rng(hash(("static", n_grow, topk)) % 2**32)
    for _ in range(5):
        ids, sims, q, growing, ggids = _random_merge_case(
            rng, n_seg=4, b=3, ks=6, dim=8, n_grow=n_grow, n_gids=64
        )
        got = merge_topk(ids, sims, q, growing, ggids, topk)
        want = _old_static_merge(ids, sims, q, growing, ggids, topk)
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_grow", [0, 7, 32])
@pytest.mark.parametrize("topk", [4, 10, 64])
def test_merge_topk_matches_old_live_merge(n_grow, topk):
    rng = np.random.default_rng(hash(("live", n_grow, topk)) % 2**32)
    for _ in range(5):
        ids, sims, q, growing, ggids = _random_merge_case(
            rng, n_seg=4, b=3, ks=6, dim=8, n_grow=n_grow, n_gids=64
        )
        alive = rng.random(65) < 0.8
        alive[-1] = False  # the always-dead sentinel slot
        got = merge_topk(ids, sims, q, growing, ggids, topk, alive=jnp.asarray(alive))
        want = _old_live_merge(
            ids, sims, q, jnp.asarray(alive), growing, ggids, topk
        )
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_merge_topk_all_dead_returns_minus_one():
    ids = np.zeros((2, 3, 4), np.int32)
    sims = np.full((2, 3, 4), -np.inf, np.float32)
    q = np.zeros((3, 8), np.float32)
    growing = np.empty((0, 8), np.float32)
    ggids = np.empty((0,), np.int32)
    alive = jnp.zeros(11, bool)
    out = np.asarray(merge_topk(ids, sims, q, growing, ggids, 5, alive=alive))
    assert (out == -1).all()


# ---------------------------------------------------------------------------
# 2. single shard is byte-identical to the unsharded engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["composed", "fused"])
def test_one_shard_bitwise_equals_instance(mode, pipeline_guard):
    ds = _dataset()
    inst = _instance(ds)
    V.set_search_pipeline(mode)
    want = inst.search(ds.queries, 10)
    sharded = ShardedVDMS.from_instance(inst, n_shards=1)
    assert sharded.dispatch == "direct"
    got, elapsed = sharded.search(ds.queries, 10, mode="analytic")
    assert np.array_equal(got, want)
    assert elapsed > 0


@pytest.mark.parametrize("mode", ["composed", "fused"])
def test_one_shard_bitwise_equals_live(mode, pipeline_guard):
    ds = _dataset()
    cfg = dict(BASE, index_type="IVF_SQ8", nlist=16, nprobe=8)
    live = V.LiveVDMS(cfg, dim=ds.dim, capacity=ds.n, seed=0)
    live.insert(ds.data[:3000])
    rng = np.random.default_rng(0)
    for g in rng.choice(2500, 200, replace=False):
        live.delete(int(g))
    V.set_search_pipeline(mode)
    want, _ = live.search(ds.queries, 10)
    sharded = ShardedVDMS.from_live(live, n_shards=1)
    got, _ = sharded.search(ds.queries, 10, mode="analytic")
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# 3. property sweep: shard count never changes the (gid, score) sets
# ---------------------------------------------------------------------------
def _gid_score_sets(ids, scores):
    return [
        frozenset(
            (int(g), int(b)) for g, b in zip(ri, rb.view(np.int32)) if g >= 0
        )
        for ri, rb in zip(ids, scores)
    ]


@pytest.mark.parametrize("case", range(6))
def test_property_shard_count_invariant_result_sets(case, pipeline_guard):
    """Seeded randomized property (hypothesis is unavailable here): random
    corpus size / segment size / topk / pipeline, shard counts 1..4 via the
    vmap dispatch — the per-query (gid, score) sets must be identical, and
    on this XLA build the id arrays are bitwise identical too."""
    rng = np.random.default_rng(1000 + case)
    n = int(rng.integers(1536, 4096))
    seg = int(rng.choice([256, 512, 1024]))
    topk = int(rng.choice([5, 10, 17]))
    V.set_search_pipeline(str(rng.choice(["composed", "fused"])))
    ds = _dataset(n=n, nq=8, seed=case)
    inst = _instance(ds, segment_max_size=seg)
    ref = None
    for n_shards in (1, 2, 3, 4):
        sharded = ShardedVDMS.from_instance(
            inst, n_shards=n_shards, dispatch="vmap" if n_shards > 1 else "direct"
        )
        ids, scores, _ = sharded.search(
            ds.queries, topk, mode="analytic", return_scores=True
        )
        if ref is None:
            ref = (ids, _gid_score_sets(ids, scores))
        else:
            assert _gid_score_sets(ids, scores) == ref[1], (
                f"(gid, score) sets changed at n_shards={n_shards}"
            )
            assert np.array_equal(ids, ref[0])


# ---------------------------------------------------------------------------
# 4. degenerate shard shapes
# ---------------------------------------------------------------------------
def test_more_shards_than_segments(pipeline_guard):
    V.set_search_pipeline("fused")
    ds = _dataset(n=2048)
    inst = _instance(ds, segment_max_size=1024)  # 2 sealed segments
    want = inst.search(ds.queries, 10)
    sharded = ShardedVDMS.from_instance(inst, n_shards=4, dispatch="vmap")
    assert inst.plan.n_sealed == 2 and sharded.n_pad == 2
    got, _ = sharded.search(ds.queries, 10, mode="analytic")
    assert np.array_equal(got, want)
    segs = sharded.shard_segments()
    assert segs.tolist() == [1, 1, 0, 0]
    cov = sharded.shard_coverage()
    assert cov[2] == 0.0 and cov[3] == 0.0  # padding-only shards report honestly


def test_one_shard_fully_tombstoned(pipeline_guard):
    """Delete every vector of the segments landing on shard 0; results must
    equal the live engine's (which sees the same tombstones) and shard 0's
    coverage must read 0."""
    V.set_search_pipeline("composed")
    cfg = dict(BASE, index_type="IVF_SQ8", nlist=16, nprobe=8)
    ds = _dataset()
    live = V.LiveVDMS(cfg, dim=ds.dim, capacity=ds.n, seed=0)
    live.insert(ds.data[:3100])  # seals segments, leaves a tail
    n_shards = 2
    per = -(-live.n_sealed // n_shards)
    for z in range(min(per, live.n_sealed)):  # shard 0's segments
        for g in live.seg_gids[z]:
            if g >= 0 and live.alive[g]:
                live.delete(int(g))
    want, _ = live.search(ds.queries, 10)
    sharded = ShardedVDMS.from_live(live, n_shards=n_shards, dispatch="vmap")
    got, _ = sharded.search(ds.queries, 10, mode="analytic")
    assert np.array_equal(got, want)
    assert sharded.shard_coverage()[0] == 0.0
    assert sharded.stats()["min_shard_coverage"] == 0.0


def test_nothing_sealed_raises():
    cfg = dict(BASE, index_type="IVF_SQ8", nlist=16, nprobe=8)
    live = V.LiveVDMS(cfg, dim=16, capacity=1024, seed=0)
    live.insert(np.random.default_rng(0).standard_normal((8, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="nothing sealed"):
        ShardedVDMS.from_live(live, n_shards=2)


def test_more_shards_than_devices_raises_unless_vmap_by_name():
    inst = _instance(_dataset())
    n = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="dispatch='vmap'"):
        ShardedVDMS.from_instance(inst, n_shards=n)
    assert ShardedVDMS.from_instance(inst, n_shards=n, dispatch="vmap").dispatch == "vmap"


def test_analytic_model_reduces_to_engine_at_one_shard():
    ds = _dataset()
    inst = _instance(ds)
    sharded = ShardedVDMS.from_instance(inst, n_shards=1)
    assert sharded._analytic_seconds_per_chunk() == pytest.approx(
        inst._analytic_seconds_per_chunk()
    )
    s4 = ShardedVDMS.from_instance(inst, n_shards=4, dispatch="vmap")
    assert s4._analytic_seconds_per_chunk() < sharded._analytic_seconds_per_chunk()


def test_search_streams_splits_per_stream(pipeline_guard):
    V.set_search_pipeline("fused")
    ds = _dataset()
    inst = _instance(ds)
    sharded = ShardedVDMS.from_instance(inst, n_shards=2, dispatch="vmap")
    streams = [ds.queries[:5], ds.queries[5:8], ds.queries[8:16]]
    outs, elapsed = sharded.search_streams(streams, 10)
    assert [o.shape for o in outs] == [(5, 10), (3, 10), (8, 10)]
    whole, _ = sharded.search(ds.queries[:16], 10)
    assert np.array_equal(np.concatenate(outs), whole)


# ---------------------------------------------------------------------------
# Poisson multi-stream driver
# ---------------------------------------------------------------------------
def test_poisson_arrivals_properties():
    t = poisson_arrivals(100.0, 5000, seed=1)
    assert t.shape == (5000,) and (np.diff(t) > 0).all()
    assert np.mean(np.diff(t)) == pytest.approx(0.01, rel=0.1)
    with pytest.raises(ValueError, match="rate"):
        poisson_arrivals(0.0, 10)


def test_make_query_streams_superposition():
    q = np.zeros((32, 8), np.float32)
    streams = make_query_streams(q, 4, rate=80.0, n_per_stream=50, seed=0)
    assert len(streams) == 4
    rows = np.concatenate([r for _, r in streams])
    assert set(rows.tolist()) == set(range(32))  # round-robin covers the pool
    merged = np.sort(np.concatenate([t for t, _ in streams]))
    # superposed rate ~ aggregate
    assert 1.0 / np.mean(np.diff(merged)) == pytest.approx(80.0, rel=0.25)


def test_replay_query_streams_accounting(pipeline_guard):
    V.set_search_pipeline("fused")
    ds = _dataset()
    inst = _instance(ds)
    sharded = ShardedVDMS.from_instance(inst, n_shards=2, dispatch="vmap")
    qps = ds.queries.shape[0] / sharded.search(ds.queries, 10, mode="analytic")[1]
    rep = replay_query_streams(
        sharded, ds.queries, rate=0.5 * qps, n_streams=4, n_per_stream=16, topk=10
    )
    assert rep["n_queries"] == 64
    assert rep["min_stream_queries"] == 16
    assert rep["served_qps"] > 0 and rep["utilization"] <= 1.0 + 1e-9
    assert rep["sojourn_p50_s"] <= rep["sojourn_p95_s"] <= rep["sojourn_p99_s"]
    # overload: a rate far beyond capacity must flag saturation
    hot = replay_query_streams(
        sharded, ds.queries, rate=50 * qps, n_streams=4, n_per_stream=64, topk=10
    )
    assert hot["saturated"] == 1.0


def test_sharded_search_hooks_fire(pipeline_guard):
    V.set_search_pipeline("fused")
    ds = _dataset()
    inst = _instance(ds)
    sharded = ShardedVDMS.from_instance(inst, n_shards=2, dispatch="vmap")
    seen = []
    sharded.search_hooks.append(lambda nq, lat, el: seen.append((nq, lat.size, el)))
    sharded.search(ds.queries, 10, mode="analytic")
    assert seen and seen[0][0] == ds.queries.shape[0] == seen[0][1]


def test_sharded_ledger_attach(pipeline_guard):
    from repro.serving import attach_sharded, serving_ledger

    V.set_search_pipeline("fused")
    ds = _dataset()
    inst = _instance(ds)
    sharded = ShardedVDMS.from_instance(inst, n_shards=2, dispatch="vmap")
    led = serving_ledger()
    attach_sharded(led, sharded)
    sharded.search(ds.queries, 10, mode="analytic")
    assert led.get("vdms_shards").value == 2.0
    assert led.get("vdms_queries_total").value == ds.queries.shape[0]
    assert led.get("vdms_shard_min_coverage").value == 1.0


# ---------------------------------------------------------------------------
# docs stay in sync
# ---------------------------------------------------------------------------
def _repo_root():
    return pathlib.Path(__file__).resolve().parents[1]


def test_sharding_doc_invariants_table_in_sync():
    doc = (_repo_root() / "docs" / "SHARDING.md").read_text()
    begin, end = "<!-- shard-invariants:begin -->", "<!-- shard-invariants:end -->"
    assert begin in doc and end in doc, "SHARDING.md lost the shard-invariants markers"
    block = doc.split(begin)[1].split(end)[0].strip()
    assert block == shard_invariants_table().strip(), (
        "SHARDING.md invariants table is stale; regenerate with "
        "python -c \"from repro.vdms import shard_invariants_table; "
        "print(shard_invariants_table())\""
    )


def test_sharding_doc_pipeline_table_in_sync():
    from repro.vdms import ivf_pqr

    ivf_pqr.register()
    doc = (_repo_root() / "docs" / "SHARDING.md").read_text()
    begin, end = "<!-- shard-pipelines:begin -->", "<!-- shard-pipelines:end -->"
    assert begin in doc and end in doc, "SHARDING.md lost the shard-pipelines markers"
    block = doc.split(begin)[1].split(end)[0].strip()
    assert block == V.shard_pipeline_table().strip(), (
        "SHARDING.md shard-pipeline table is stale; regenerate with "
        "python -c \"from repro.vdms import shard_pipeline_table, ivf_pqr; "
        "ivf_pqr.register(); print(shard_pipeline_table())\""
    )


def test_sharding_doc_covers_contract():
    doc = (_repo_root() / "docs" / "SHARDING.md").read_text()
    for name, _, _ in SHARD_INVARIANTS:
        assert name in doc
    for needle in (
        "shard_map", "segment_placement", "make_shard_mesh", "partial_topk",
        "merge_flat", "xla_force_host_platform_device_count", "bench_sharded",
    ):
        assert needle in doc, f"SHARDING.md lost {needle!r}"


def test_architecture_doc_exists_and_maps_subsystems():
    doc = (_repo_root() / "docs" / "ARCHITECTURE.md").read_text()
    for needle in (
        "core", "registry", "kernels", "serving", "faults", "sharded",
        "ShardedVDMS", "LiveVDMS", "docs/SHARDING.md",
    ):
        assert needle in doc, f"ARCHITECTURE.md lost {needle!r}"


def test_readme_links_new_docs():
    text = (_repo_root() / "README.md").read_text()
    assert "docs/SHARDING.md" in text
    assert "docs/ARCHITECTURE.md" in text
    assert "bench_sharded" in text
