"""The reduction from trace to intervals, on hand-made intervals and on
traces recorded on the chip."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"


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


def test_devices_are_averaged_and_summed():
    """Two devices: busy time and idle gaps are means over them, an op's
    time is summed, and each device keeps its own timeline."""
    tr = trace.Trace(
        ops=[("k", 1.0, 2.0, 0), ("m", 1.5, 2.0, 0), ("k", 1.0, 3.0, 1), ("c", 3.0, 3.5, 1)],
        spans=[("traced", 0.0, 4.0), ("search_call", 0.75, 3.6)], devices=2)
    assert [t.covered(0.0, 4.0) for t in tr.busy_per_device] == [1.0, 2.5]
    assert tr.busy.covered(0.0, 4.0) == pytest.approx(1.75)
    assert trace.top_ops(tr, 0.0, 4.0) == [["k", 2.5], ["m", 0.5], ["c", 0.5]]  # k: 0.5 self + 2.0
    idle = dict(trace.idle_by_activity(tr, 0.0, 4.0))
    assert idle == pytest.approx({"search_call": (2.0 + 0.0) / 2, "host": (1.0 + 1.5) / 2})
    is_k = lambda name: name == "k"  # noqa: E731
    assert [tr.timeline(is_k, d).covered(0.0, 4.0) for d in range(2)] == [1.0, 2.0]


def test_recorded_chip_trace_reads_as_before():
    """The one-chip recording reads to the bit what the reduction read before
    it counted devices; asking for four chips of it keeps its one plane."""
    for chips in (1, 4):
        tr = trace.load(str(DATA / "sq8-closed-3calls.xplane.pb"), chips)
        assert tr.devices == 1
        (_, a, b), = tr.spans_named("window")
        assert tr.busy.covered(a, b) == 3.898420129000001
        assert [tr.busy.covered(s, e) for _, s, e in tr.spans_named("search_call")] == [
            1.2994918820000003, 1.299347662, 1.2991977160000006]
        assert trace.top_ops(tr, a, b, 3) == [["fused_ivf_sq8_topk_pallas.1", 3.178971975],
                                              ["fusion", 0.6579210100000004], ["fusion.12", 0.04527703200000593]]
        assert trace.idle_by_activity(tr, a, b) == [["search_call", 0.007707150999999218],
                                                    ["host", 0.0004401849999998639]]


def test_recorded_four_chip_trace():
    """Glove-100 IVF_SQ8 on four shards, traced on four TPU v5 lite chips:
    after the cell's set-up, three 256-query search calls traced on their own
    (not a ``bench/run.py`` window of 1,024-query requests); the first two
    kept, op names cut to what ``op_name`` keeps. The reduction keeps the four device planes, each op
    tagged with its chip: busy time is their mean, an op's time their sum, and
    each chip has its own kernel timeline; the exchange between the chips
    (``all-to-all``) is among the ops."""
    from bench import work

    path = str(DATA / "sq8-sharded4-2calls.xplane.pb")
    tr = trace.load(path, 4)
    assert tr.devices == 4 and [sum(o[3] == d for o in tr.ops) for d in range(4)] == [12080] * 4
    (_, a, _), (_, _, b) = tr.spans_named("search_call")
    busy = [t.covered(a, b) for t in tr.busy_per_device]
    assert busy == pytest.approx([0.188328, 0.188318, 0.188331, 0.188316], abs=2e-6)
    assert tr.busy.covered(a, b) == pytest.approx(sum(busy) / 4)
    kernel = [tr.timeline(work.kernel_match("IVF_SQ8"), d).covered(a, b) for d in range(4)]
    assert kernel == pytest.approx([0.097668, 0.097680, 0.097724, 0.094018], abs=2e-6)
    top = dict(trace.top_ops(tr, a, b, 6))
    assert top["fused_ivf_sq8_topk_pallas.1"] == pytest.approx(sum(kernel))
    assert top["all-to-all"] == pytest.approx(0.004720, abs=2e-6)
    assert trace.idle_by_activity(tr, a, b) == [["search_call", pytest.approx((b - a) - sum(busy) / 4)]]
    one = trace.load(path, 1)
    assert one.devices == 1 and len(one.ops) == 12080
