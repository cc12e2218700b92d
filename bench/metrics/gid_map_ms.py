"""gid_map_ms: per search call, the device time of the ops under the program's
``vdms.gid_map`` scope (per-segment local ids to corpus ids, dead slots
masked); mean, in ms (``bench/stages.py``)."""
from bench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "gid_map")
