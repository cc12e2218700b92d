"""flat_scan_roofline: the distance kernel's share of its roofline over the
traced window of a FLAT cell, in percent (rule and work in ``bench/work.py``)."""
from bench import work


def read(ctx):
    tr = ctx.trace
    calls = tr.spans_named("search_call") if tr is not None else []
    kernel = tr.timeline(work.kernel_match("FLAT")) if calls else None
    if not calls or not kernel.starts:
        return None
    peak = work.peaks(ctx.device_kind)
    n, d = int(ctx.config["shape"]["n"]), int(ctx.config["shape"]["dim"])
    least, bound, kernel_s = 0.0, {}, 0.0
    for (_, a, b), rows in zip(calls, ctx.log.rows):
        t, term = work.least_seconds(*work.flat_call(len(rows), n, d), peak)
        least += t
        bound[term] = bound.get(term, 0) + 1
        kernel_s += kernel.covered(a, b)
    ctx.say(f"flat_scan_roofline: least {least!r} s over kernel {kernel_s!r} s, bound by {bound}")
    return 100.0 * least / kernel_s
