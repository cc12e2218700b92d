"""Stage names (``repro.obs``): host spans, their collector, the device scopes
in the compiled search program, and the engine and session timings read
from them. CPU only; nothing here measures speed."""
import glob
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops
from repro.vdms import VDMSInstance, make_dataset

BASE = {
    "segment_max_size": 512, "seal_proportion": 0.75, "graceful_time": 0.2,
    "search_batch_size": 16, "topk_merge_width": 32, "kmeans_iters": 4,
    "storage_bf16": False,
}
FAMILIES = {
    "FLAT": {},
    "IVF_FLAT": {"nlist": 8, "nprobe": 4},
    "IVF_SQ8": {"nlist": 8, "nprobe": 4},
    "IVF_PQ": {"nlist": 8, "nprobe": 4, "m": 8, "nbits": 4},
}


@pytest.fixture(scope="module")
def dataset():
    return make_dataset("glove_like", n=1450, n_queries=40, k=10, seed=3)


def _instance(dataset, kind):
    return VDMSInstance(dataset, {**BASE, "index_type": kind, **FAMILIES[kind]}, seed=1)


# ---------------------------------------------------------------------------
# spans and their collector
# ---------------------------------------------------------------------------
def test_nested_spans_both_record():
    with obs.collect() as took:
        with obs.span("outer") as outer:
            with obs.span("inner") as inner:
                sum(range(1000))
            with obs.span("inner"):
                pass
    assert set(took) == {"outer", "inner"}
    assert inner.seconds > 0 and outer.seconds >= inner.seconds
    assert took["outer"] == outer.seconds and took["inner"] >= inner.seconds
    assert took["outer"] >= took["inner"]


def test_raising_span_records_and_reraises():
    with obs.collect() as took:
        with pytest.raises(KeyError):
            with obs.span("fails") as s:
                raise KeyError("x")
    assert s.seconds is not None and took == {"fails": s.seconds}


def test_span_outside_a_collector_only_times():
    with obs.collect() as took:
        pass
    with obs.span("alone") as s:
        pass
    assert s.seconds >= 0 and took == {}


def test_collectors_are_per_thread():
    """A collector sees only its own context's spans: threads that open their
    own, and one that opens none, leave each other's dicts alone."""
    barrier = threading.Barrier(3)
    seen = {}

    def work(name, collecting):
        if collecting:
            with obs.collect() as took:
                barrier.wait(timeout=30)
                with obs.span(name):
                    barrier.wait(timeout=30)
            seen[name] = took
        else:
            barrier.wait(timeout=30)
            with obs.span(name):
                barrier.wait(timeout=30)

    with obs.collect() as main:
        threads = [threading.Thread(target=work, args=("a", True)),
                   threading.Thread(target=work, args=("b", True)),
                   threading.Thread(target=work, args=("c", False))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert set(seen["a"]) == {"a"} and set(seen["b"]) == {"b"} and main == {}


# ---------------------------------------------------------------------------
# device scopes in the compiled search program
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "kind, impl, scopes",
    [
        ("FLAT", "xla", {"segment_topk", "gid_map", "merge"}),
        ("IVF_FLAT", "xla", {"probe", "segment_topk", "gid_map", "merge"}),
        ("IVF_SQ8", "xla", {"gid_map", "merge"}),  # the fused hook; its XLA scan holds the probe
        ("IVF_SQ8", "pallas_interpret", {"cluster_of", "gid_map", "merge"}),
    ],
)
def test_search_program_carries_the_path_scopes(dataset, kind, impl, scopes):
    before = ops.get_default_impl()
    ops.set_default_impl(impl)
    try:
        inst = _instance(dataset, kind)
        text = inst.search_program(40, 10).as_text()
    finally:
        ops.set_default_impl(before)
    found = {name for name in ("probe", "segment_topk", "gid_map", "merge", "cluster_of")
             if f"vdms.{name}/" in text}
    assert found == scopes


def test_search_program_is_what_search_runs(dataset):
    inst = _instance(dataset, "IVF_SQ8")
    compiled = inst.search_program(40, 10)
    qc, arrays, growing, growing_gids = inst._pipeline_args(dataset.queries, 10)[:4]  # the rest are static
    out = np.asarray(compiled(qc, arrays, growing, growing_gids))
    assert np.array_equal(out.reshape(-1, 10)[:40], inst.search(dataset.queries, 10))


@pytest.mark.parametrize("kind", ["FLAT", "IVF_SQ8"])
def test_search_is_identical_under_a_trace(dataset, kind, tmp_path):
    """A running ``jax.profiler`` trace changes no answer, and holds the
    search's host spans."""
    from jax.profiler import ProfileData

    inst = _instance(dataset, kind)
    plain = inst.search(dataset.queries, 10)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = inst.search(dataset.queries, 10)
    finally:
        jax.profiler.stop_trace()
    assert np.array_equal(plain, traced)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes for line in p.lines for e in line.events}
    assert {"vdms.search.prep", "vdms.search.dispatch", "vdms.search.fetch"} <= names


# ---------------------------------------------------------------------------
# build stages and the session's ledger
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "kind, stages",
    [
        ("FLAT", {"build.stack_sealed", "build.upload"}),
        ("IVF_FLAT", {"build.stack_sealed", "build.upload", "build.kmeans", "build.member_lists"}),
        ("IVF_SQ8", {"build.stack_sealed", "build.upload", "build.kmeans", "build.member_lists",
                     "build.encode"}),
        ("IVF_PQ", {"build.stack_sealed", "build.upload", "build.kmeans", "build.member_lists",
                    "build.encode"}),
    ],
)
def test_build_seconds_name_every_stage(dataset, kind, stages):
    inst = _instance(dataset, kind)
    assert set(inst.build_seconds) == stages
    assert all(s > 0 for s in inst.build_seconds.values())
    assert sum(inst.build_seconds.values()) <= inst.build_time


@pytest.mark.parametrize("executor", ["sequential", "batch", "threaded"])
def test_session_ledger_times_come_from_the_spans(executor, monkeypatch):
    """``ask_s`` and ``eval_s`` are the ``tuner.recommend`` and
    ``tuner.evaluate`` spans' own seconds."""
    from repro.core import Param, RandomLHS, SearchSpace, SequentialBatchMixin, TuningSession
    from repro.core import session as session_mod

    class Backend(SequentialBatchMixin):
        def __call__(self, cfg):
            return {"speed": 10.0 * cfg["s1"], "recall": 0.9, "mem_gib": 1.0}

    space = SearchSpace(index_types={"A": [Param("ka", "grid", choices=(1, 2), default=1)]},
                        system_params=[Param("s1", "float", 0.0, 1.0, default=0.5)])
    spans = []

    class Recorded(obs.span):
        __slots__ = ()

        def __exit__(self, *exc):
            out = super().__exit__(*exc)
            spans.append((self.name, self.seconds))
            return out

    monkeypatch.setattr(session_mod, "span", Recorded)
    session = TuningSession(RandomLHS(space, seed=0), backend=Backend(), executor=executor)
    session.run(4)
    rounds = session.ledger_dict()["rounds"]
    asks = [s for name, s in spans if name == "tuner.recommend"]
    evals = [s for name, s in spans if name == "tuner.evaluate"]
    assert [r["ask_s"] for r in rounds] == asks
    told = [e["eval_s"] for r in rounds for e in r["evals"]]
    assert len(told) == 4 and all(t > 0 for t in told)
    if executor == "batch":  # one span per batch, shared out over its configs
        assert sum(told) == pytest.approx(sum(evals))
    else:
        assert told == evals
