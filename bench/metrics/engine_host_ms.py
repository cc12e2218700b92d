"""engine_host_ms.<cells>: per search call, the host span of
``VDMSInstance.search`` less the device's busy time inside it; mean, in ms."""


def read(ctx):
    tr = ctx.trace
    calls = tr.spans_named("search_call") if tr is not None and tr.ops else []
    if not calls:
        return None
    host = [(b - a) - tr.busy.covered(a, b) for _, a, b in calls]
    return 1e3 * sum(host) / len(host)
