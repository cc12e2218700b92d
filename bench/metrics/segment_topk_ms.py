"""segment_topk_ms: per search call, the device time of the ops under the
program's ``vdms.segment_topk`` scope (each segment's ``lax.top_k`` of its
scored rows, with the mask before it); mean, in ms (``bench/stages.py``)."""
from bench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "segment_topk")
