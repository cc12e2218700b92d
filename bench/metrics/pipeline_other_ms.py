"""pipeline_other_ms: per search call, the device time of the search program
outside its Pallas kernels (probe scatter, top-k, id mapping, merge); mean, ms."""
from bench import work


def read(ctx):
    tr = ctx.trace
    calls = tr.spans_named("search_call") if tr is not None and tr.ops else []
    if not calls:
        return None
    kernel = tr.timeline(work.kernel_match(ctx.config["index"]["index_type"]))
    other = [tr.busy.covered(a, b) - kernel.covered(a, b) for _, a, b in calls]
    return 1e3 * sum(other) / len(other)
