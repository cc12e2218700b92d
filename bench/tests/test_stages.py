"""The reduction from the program's named stages to per-stage device time."""
import types

import pytest

from bench import run, stages, trace

HLO = """\
HloModule jit__pipeline_impl, entry_computation_layout={()->s32[2]}

%fused_computation.1 (param_0: s32[4]) -> s32[2] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %gather.1 = s32[2]{0} gather(%param_0), metadata={op_name="jit(_pipeline_impl)/vdms.gid_map/gather"}
}

ENTRY %main.1 () -> s32[2] {
  %cc.1 = s32[4]{0} custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/pallas_call"}
  %fusion.12 = s32[4]{0} fusion(%cc.1), kind=kLoop, metadata={op_name="jit(f)/vdms.merge/while/body/vdms.cluster_of/scatter" source_file="ops.py"}
  %sort.9 = s32[4]{0} sort(%fusion.12), metadata={op_name="jit(f)/while/body/closed_call/vdms.segment_topk/top_k"}
  %copy.3 = s32[4]{0} copy(%sort.9)
  ROOT %fusion = s32[2]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_pipeline_impl)/vdms.gid_map/gather"}
}
"""


def test_op_stages_reads_the_innermost_scope():
    assert stages.op_stages(HLO) == {
        "gather.1": "gid_map", "fusion.12": "cluster_of", "sort.9": "segment_topk", "fusion": "gid_map",
    }


class Program:
    def __init__(self, text):
        self.text, self.shapes = text, []

    def search_program(self, n_queries, topk):
        self.shapes.append((n_queries, topk))
        return types.SimpleNamespace(as_text=lambda: self.text)


def ctx_of(ops, spans, searcher, mix=None):
    tr = trace.Trace(ops=sorted(ops, key=lambda o: o[1]), spans=sorted(spans, key=lambda s: s[1]))
    mix = mix or {"op": "search", "loop": "closed", "request_queries": 1024, "topk": 10}
    return types.SimpleNamespace(trace=tr, searcher=searcher, mix=mix)


OPS = [("cc.1", 0.0, 1.0), ("fusion", 1.0, 1.2), ("fusion.12", 1.2, 1.3), ("copy.3", 1.3, 1.35),
       ("cc.1", 2.0, 3.0), ("fusion", 3.0, 3.4), ("fusion.12", 3.4, 3.5)]
SPANS = [("search_call", 0.0, 1.5), ("search_call", 2.0, 3.6)]


def test_stage_ms_is_the_mean_per_call():
    program = Program(HLO)
    ctx = ctx_of(OPS, SPANS, program)
    assert stages.stage_ms(ctx, "gid_map") == pytest.approx(300.0)  # (0.2 + 0.4) / 2 s
    assert stages.stage_ms(ctx, "cluster_of") == pytest.approx(100.0)
    assert stages.stage_ms(ctx, "segment_topk") == 0.0  # in the program, not in the trace
    assert stages.stage_ms(ctx, "probe") is None  # no op of the program has it
    assert program.shapes == [(1024, 10)]  # one program read for every stage of the run


def test_stage_ms_reads_the_open_loop_batch_width():
    program = Program(HLO)
    ctx = ctx_of(OPS, SPANS, program, {"op": "search", "loop": "open", "request_queries": 1,
                                        "max_batch": 32, "topk": 10})
    stages.stage_ms(ctx, "gid_map")
    assert program.shapes == [(32, 10)]


@pytest.mark.parametrize(
    "searcher, spans",
    [
        (object(), SPANS),  # a program without ``search_program``
        (Program("HloModule m\nENTRY %e () -> s32[] {\n  ROOT %c = s32[] constant(0)\n}\n"), SPANS),
        (Program(HLO), []),  # no search call traced
        (None, SPANS),  # a build cell has no searcher
    ],
)
def test_stage_ms_reads_nothing_where_nothing_is_named(searcher, spans):
    assert stages.stage_ms(ctx_of(OPS, spans, searcher), "gid_map") is None


def test_op_stages_on_a_cpu_compiled_pipeline():
    """The program's own scopes, compiled on the CPU at a tiny IVF_SQ8 size:
    the gather of the id mapping is an instruction under ``vdms.gid_map``."""
    from repro.vdms import VDMSInstance, make_dataset

    dataset = make_dataset("glove_like", n=1450, n_queries=40, k=10, seed=3)
    config = {"index_type": "IVF_SQ8", "nlist": 8, "nprobe": 4, "segment_max_size": 512,
              "seal_proportion": 0.75, "graceful_time": 0.2, "search_batch_size": 16,
              "topk_merge_width": 32, "kmeans_iters": 4, "storage_bf16": False}
    text = VDMSInstance(dataset, config).search_program(40, 10).as_text()
    found = stages.op_stages(text)
    gathers = {name for name, stage in found.items() if stage == "gid_map"
               and any(line.lstrip().startswith(("%" + name + " ", "ROOT %" + name + " ")) and "gather" in line
                       for line in text.splitlines())}
    assert gathers
    assert set(found.values()) == {"gid_map", "merge"}


@pytest.mark.parametrize("stage, name", [("encode", "build_encode_s"), ("upload", "build_upload_s"),
                                         ("member_lists", "build_member_lists_s")])
def test_build_stage_metrics_read_the_kept_build(stage, name):
    read = run.reader(name)
    kept = types.SimpleNamespace(build_seconds={f"build.{stage}": 1.25})
    assert read(types.SimpleNamespace(log=types.SimpleNamespace(kept=kept))) == 1.25
    old = types.SimpleNamespace()  # a program without build stages
    assert read(types.SimpleNamespace(log=types.SimpleNamespace(kept=old))) is None



def test_recorded_chip_trace_stages():
    """Three 1,024-query IVF_SQ8 calls traced on a TPU v5 lite, with the HLO
    text of the program that ran them: the ops under the program's
    scopes cover at least 90% of each call's device time outside the kernel
    (``pipeline_other_ms``), and the program's host spans sit in the trace."""
    from pathlib import Path

    from jax.profiler import ProfileData

    from bench import work

    data = Path(__file__).resolve().parents[1] / "testdata"
    xplane = str(data / "sq8-closed-scoped-3calls.xplane.pb")
    text = (data / "sq8-closed-scoped.hlo.txt").read_text()
    tr = trace.load(xplane)
    calls = tr.spans_named("search_call")
    assert len(calls) == 3
    kernel = tr.timeline(work.kernel_match("IVF_SQ8"))
    found = stages.op_stages(text)
    assert found["fusion"] == "gid_map" and found["fusion.12"] == "cluster_of"
    for _, a, b in calls:
        other = tr.busy.covered(a, b) - kernel.covered(a, b)
        named = sum(s for name, s in trace.top_ops(tr, a, b, len(tr.ops)) if name in found)
        assert named >= 0.9 * other, (named, other)
    ctx = ctx_of(tr.ops, tr.spans, Program(text))
    assert stages.stage_ms(ctx, "gid_map") == pytest.approx(220.321, abs=0.01)
    assert stages.stage_ms(ctx, "cluster_of") == pytest.approx(15.277, abs=0.01)
    host = {e.name for p in ProfileData.from_file(xplane).planes for line in p.lines for e in line.events}
    assert {"vdms.search.prep", "vdms.search.dispatch", "vdms.search.fetch"} <= host
