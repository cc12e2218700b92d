"""cluster_of_ms.<cells>: per search call, the device time of the ops under the
program's ``vdms.cluster_of`` scope (the member lists inverted to a row's
cluster, which the fused IVF kernels read); mean, in ms (``bench/stages.py``)."""
from bench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "cluster_of")
