"""The reduction from trace to intervals, on hand-made intervals."""
import pytest

from bench import trace


def make(ops, spans):
    return trace.Trace(ops=sorted(ops, key=lambda o: o[1]), spans=sorted(spans, key=lambda s: s[1]))


def test_timeline_merges_and_clips():
    t = trace.Timeline([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert t.covered(0.0, 10.0) == pytest.approx(3.0)
    assert t.covered(1.5, 3.5) == pytest.approx(1.0)
    assert t.covered(2.0, 3.0) == 0.0
    assert t.gaps(0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


def test_idle_is_named_by_the_innermost_open_span():
    tr = make(
        ops=[("k", 1.0, 2.0), ("k", 3.0, 4.0), ("m", 3.5, 4.0), ("k", 5.0, 5.5)],
        spans=[("traced", 0.0, 6.0), ("search_call", 0.25, 4.25), ("batch_assembly", 2.0, 3.0),
               ("generator_wait", 4.25, 5.0)],
    )
    idle = dict(trace.idle_by_activity(tr, 0.0, 6.0))
    assert idle == pytest.approx({"search_call": 1.0, "batch_assembly": 1.0,
                                  "generator_wait": 1.0, "host": 0.5})
    assert tr.busy.covered(*tr.window()) == pytest.approx(2.5)
    assert trace.top_ops(tr, 0.0, 6.0) == [["k", 2.0], ["m", 0.5]]  # m runs inside a k: self time


def test_recorded_chip_trace():
    """Three 1,024-query IVF_SQ8 calls traced on a TPU v5 lite (PR 12). The
    kernel's total is the sum of its event durations as the profiler wrote
    them; the reduction must find the TPU plane, not the host or the
    ``/device:CUSTOM`` one, and name ops without their operands."""
    from pathlib import Path

    from bench import work

    tr = trace.load(str(Path(__file__).resolve().parents[1] / "testdata" / "sq8-closed-3calls.xplane.pb"))
    assert len(tr.ops) == 7917
    (_, a, b), = tr.spans_named("window")
    calls = tr.spans_named("search_call")
    assert len(calls) == 3 and all(a <= s <= e <= b for _, s, e in calls)
    kernel = tr.timeline(work.kernel_match("IVF_SQ8"))
    assert sum(kernel.covered(s, e) for _, s, e in calls) == pytest.approx(3.178972, abs=2e-6)
    assert tr.busy.covered(a, b) == pytest.approx(3.898420, abs=2e-6)
    top = trace.top_ops(tr, a, b, 2)
    assert [name for name, _ in top] == ["fused_ivf_sq8_topk_pallas.1", "fusion"]
    assert top[0][1] == pytest.approx(3.178972, abs=2e-6)
    assert dict(trace.idle_by_activity(tr, a, b))["search_call"] > 0
