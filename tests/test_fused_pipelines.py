"""Fused-vs-composed search-pipeline equivalence (the CI ``fused-parity`` job).

The engine routes chunks through a family's registered ``fused_search`` hook
when ``set_search_pipeline("fused")`` (the default) — these tests pin the
contract that routing must be INVISIBLE in results: identical result sets
(bitwise-identical under the XLA impl for every case here) across static
instances, clamped static instances, partial-seal plans, live instances with
tombstones, fully-dead segments, and families without a hook (composed
fallback). Adversarial shapes cover sub-block segments, ``k_seg > n``, and
dead padding.
"""
import numpy as np
import pytest

import repro.vdms as V
from repro.vdms import engine, ivf_pqr

BASE = {
    "segment_max_size": 512, "seal_proportion": 0.75, "graceful_time": 0.2,
    "search_batch_size": 16, "topk_merge_width": 32, "kmeans_iters": 4,
    "storage_bf16": False,
}
FUSED_CONFIGS = {
    "IVF_SQ8": {"nlist": 8, "nprobe": 4},
    "IVF_PQ": {"nlist": 8, "nprobe": 4, "m": 8, "nbits": 4},
    "IVF_PQR": {"nlist": 8, "nprobe": 4, "m": 8, "nbits": 4, "reorder_k": 32},
}
FALLBACK_CONFIGS = {
    "IVF_FLAT": {"nlist": 8, "nprobe": 4},
    "AUTOINDEX": {},
}


@pytest.fixture(autouse=True)
def ivf_pqr_registered():
    """IVF_PQR is registered on demand. Register it around each test, not at
    import: another module's teardown on the same worker may unregister it
    (``test_registry_conformance``'s ``extra_families`` does)."""
    was_registered = ivf_pqr.FAMILY.name in V.registered_names()
    ivf_pqr.register()
    yield
    if not was_registered:
        V.unregister_family(ivf_pqr.FAMILY.name)


@pytest.fixture
def fused_mode():
    prev = V.get_search_pipeline()
    yield
    V.set_search_pipeline(prev)


def _search_both(inst, queries, topk):
    V.set_search_pipeline("composed")
    a = inst.search(queries, topk)
    V.set_search_pipeline("fused")
    b = inst.search(queries, topk)
    return a, b


def _sets_match(a, b):
    return all(set(x[x >= 0]) == set(y[y >= 0]) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# pipeline mode API
# ---------------------------------------------------------------------------
def test_pipeline_mode_api(fused_mode):
    assert V.get_search_pipeline() in ("fused", "composed")
    V.set_search_pipeline("composed")
    assert V.get_search_pipeline() == "composed"
    V.set_search_pipeline("fused")
    assert V.get_search_pipeline() == "fused"
    with pytest.raises(ValueError, match="unknown search pipeline"):
        V.set_search_pipeline("warp")


def test_fused_hooks_registered_where_expected():
    for fam in FUSED_CONFIGS:
        assert V.get_family(fam).fused_search is not None, fam
    for fam in ("FLAT", "IVF_FLAT", "HNSW", "SCANN", "AUTOINDEX"):
        assert V.get_family(fam).fused_search is None, fam


# ---------------------------------------------------------------------------
# static instances
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fam", sorted(FUSED_CONFIGS))
def test_static_fused_equals_composed(fam, fused_mode):
    # 1450 into 512-slot segments: the 426-vector remainder crosses the
    # seal threshold (0.75 * 512 = 384) -> partial trailing seal, so clamp is
    # disabled and dead (-1) padding is present in the last sealed segment
    ds = V.make_dataset("glove_like", n=1450, dim=64, n_queries=24, k=10, seed=0)
    inst = V.VDMSInstance(ds, dict(BASE, index_type=fam, **FUSED_CONFIGS[fam]), seed=0)
    assert not inst._clamp_ok  # the partial seal pads with -1 gids
    a, b = _search_both(inst, ds.queries, 10)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("fam", sorted(FUSED_CONFIGS))
def test_static_clamped_fused_equals_composed(fam, fused_mode):
    # 1280 = 2 full seals + 256 growing (< seal size) -> clamp active
    ds = V.make_dataset("glove_like", n=1280, dim=64, n_queries=24, k=10, seed=1)
    inst = V.VDMSInstance(ds, dict(BASE, index_type=fam, **FUSED_CONFIGS[fam]), seed=0)
    assert inst._clamp_ok
    a, b = _search_both(inst, ds.queries, 10)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("fam", sorted(FALLBACK_CONFIGS))
def test_fallback_family_mode_invariant(fam, fused_mode):
    """Families without a fused hook must run the identical composed program
    in both modes — the registry fallback the engine guarantees."""
    assert V.get_family(fam).fused_search is None
    ds = V.make_dataset("glove_like", n=1280, dim=64, n_queries=16, k=10, seed=2)
    inst = V.VDMSInstance(ds, dict(BASE, index_type=fam, **FALLBACK_CONFIGS[fam]), seed=0)
    a, b = _search_both(inst, ds.queries, 10)
    assert np.array_equal(a, b)


def test_adversarial_tiny_segment_kseg_gt_n(fused_mode):
    """k_seg (128) > segment size (64) and segments far below one kernel block."""
    ds = V.make_dataset("glove_like", n=200, dim=32, n_queries=8, k=5, seed=3)
    cfg = dict(BASE, segment_max_size=64, topk_merge_width=128,
               index_type="IVF_SQ8", nlist=4, nprobe=2)
    inst = V.VDMSInstance(ds, cfg, seed=0)
    assert inst.k_seg > inst.plan.seg_size
    a, b = _search_both(inst, ds.queries, 5)
    assert np.array_equal(a, b)


def test_fused_topk_wider_than_results(fused_mode):
    """topk larger than every candidate pool: both modes pad with -1."""
    ds = V.make_dataset("glove_like", n=300, dim=32, n_queries=6, k=5, seed=4)
    cfg = dict(BASE, segment_max_size=128, index_type="IVF_PQ",
               nlist=4, nprobe=1, m=4, nbits=4)
    inst = V.VDMSInstance(ds, cfg, seed=0)
    a, b = _search_both(inst, ds.queries, 200)
    assert np.array_equal(a, b)
    assert (a == -1).any()  # padding actually exercised


# ---------------------------------------------------------------------------
# live instances (tombstones, compaction padding, fully-dead segments)
# ---------------------------------------------------------------------------
def _live_pair(fam, deletes, compact_threshold=1.1, seed=5):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((1200, 48)).astype(np.float32)
    data /= np.linalg.norm(data, axis=1, keepdims=True)
    queries = rng.standard_normal((12, 48)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    cfg = dict(BASE, index_type=fam, **FUSED_CONFIGS[fam])
    outs = {}
    for mode in ("composed", "fused"):
        V.set_search_pipeline(mode)
        live = V.LiveVDMS(cfg, dim=48, capacity=2048, seed=0,
                          compact_threshold=compact_threshold)
        live.bootstrap(data)
        for g in deletes:
            live.delete(int(g))
        ids, _ = live.search(queries, 10, mode="analytic")
        outs[mode] = ids
    return outs["composed"], outs["fused"]


@pytest.mark.parametrize("fam", sorted(FUSED_CONFIGS))
def test_live_tombstones_fused_equals_composed(fam, fused_mode):
    a, b = _live_pair(fam, deletes=range(50, 500, 3))
    assert np.array_equal(a, b)


def test_live_fully_dead_segment(fused_mode):
    """Every vector of sealed segment 0 tombstoned (compaction disabled):
    the fused live merge must drop the whole segment exactly like composed."""
    seg = V.live_seg_size(BASE["segment_max_size"], BASE["seal_proportion"])
    a, b = _live_pair("IVF_SQ8", deletes=range(0, seg))
    assert np.array_equal(a, b)
    assert not set(range(seg)) & set(a[a >= 0].tolist())


def test_live_compaction_padding(fused_mode):
    """Deletes past the compact threshold rebuild a segment with -1 padding;
    live fused search never clamps, so the padded slots stay width-consuming
    and the two modes agree."""
    seg = V.live_seg_size(BASE["segment_max_size"], BASE["seal_proportion"])
    a, b = _live_pair("IVF_SQ8", deletes=range(0, seg // 2), compact_threshold=0.3)
    assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# engine internals: the clamp invariant
# ---------------------------------------------------------------------------
def test_clamp_ok_matches_plan():
    ds_full = V.make_dataset("glove_like", n=1280, dim=32, n_queries=4, k=5, seed=6)
    ds_part = V.make_dataset("glove_like", n=1450, dim=32, n_queries=4, k=5, seed=6)
    cfg = dict(BASE, index_type="IVF_SQ8", nlist=8, nprobe=4)
    full = V.VDMSInstance(ds_full, cfg, seed=0)
    part = V.VDMSInstance(ds_part, cfg, seed=0)
    assert full._clamp_ok
    assert not part._clamp_ok
    assert bool(np.all(part.plan.sealed_valid == part.plan.seg_size)) is False


def test_measure_wall_both_modes(fused_mode):
    """measure(mode='wall') runs under either pipeline and reports identical
    recall (same result sets)."""
    ds = V.make_dataset("glove_like", n=1280, dim=32, n_queries=16, k=5, seed=7)
    cfg = dict(BASE, index_type="IVF_SQ8", nlist=8, nprobe=4)
    inst = V.VDMSInstance(ds, cfg, seed=0)
    V.set_search_pipeline("composed")
    r_c = inst.measure(topk=5, repeats=1, mode="wall")
    V.set_search_pipeline("fused")
    r_f = inst.measure(topk=5, repeats=1, mode="wall")
    assert r_c["recall"] == pytest.approx(r_f["recall"])


# ---------------------------------------------------------------------------
# property-based round-trips (skipped when hypothesis isn't installed)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(min_value=150, max_value=900),
        topk=st.integers(min_value=1, max_value=40),
        nprobe=st.sampled_from([1, 2, 4, 8]),
        seed=st.integers(min_value=0, max_value=3),
    )
    def test_fused_equals_composed_random_shapes(n, topk, nprobe, seed):
        prev = V.get_search_pipeline()
        try:
            ds = V.make_dataset("glove_like", n=n, dim=32, n_queries=8, k=5, seed=seed)
            cfg = dict(BASE, segment_max_size=256, index_type="IVF_SQ8",
                       nlist=8, nprobe=nprobe)
            inst = V.VDMSInstance(ds, cfg, seed=seed)
            a, b = _search_both(inst, ds.queries, topk)
            assert np.array_equal(a, b)
        finally:
            V.set_search_pipeline(prev)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_fused_equals_composed_random_shapes():
        pass


# ---------------------------------------------------------------------------
# README doc-sync: the generated fused-pipeline table
# ---------------------------------------------------------------------------
def test_readme_fused_table_in_sync():
    import pathlib

    readme = pathlib.Path(__file__).resolve().parents[1] / "README.md"
    text = readme.read_text()
    begin, end = "<!-- fused-table:begin -->", "<!-- fused-table:end -->"
    assert begin in text and end in text
    block = text.split(begin)[1].split(end)[0].strip()
    assert block == V.fused_pipeline_table().strip(), (
        "README fused-pipeline table is stale; regenerate with "
        "python -c \"from repro.vdms import fused_pipeline_table, ivf_pqr; "
        "ivf_pqr.register(); print(fused_pipeline_table())\""
    )


def test_fused_table_marks_hooks():
    table = V.fused_pipeline_table()
    for fam, line in zip(
        [f.name for f in V.registered_families()],
        table.splitlines()[2:],
    ):
        fused = V.get_family(fam).fused_search is not None
        assert ("fused (composed fallback)" in line) == fused, line
        if fused:
            stages = getattr(V.get_family(fam).fused_search, "stages")
            assert stages in line
