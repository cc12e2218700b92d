"""build_device_s: device busy seconds per index build in the window."""


def read(ctx):
    tr = ctx.trace
    builds = tr.spans_named("build") if tr is not None and tr.ops else []
    if not builds:
        return None
    return sum(tr.busy.covered(a, b) for _, a, b in builds) / len(builds)
