"""sq8_scan_roofline: the fused IVF_SQ8 kernel's share of its roofline over
the traced window, in percent (rule and work in ``bench/work.py``)."""
from bench import work


def read(ctx):
    tr = ctx.trace
    calls = tr.spans_named("search_call") if tr is not None else []
    kernel = tr.timeline(work.kernel_match("IVF_SQ8")) if calls else None
    if not calls or not kernel.starts:
        return None
    arrays, peak = ctx.searcher.bundle.arrays, work.peaks(ctx.device_kind)
    nprobe, k = int(ctx.config["index"]["nprobe"]), int(ctx.config["index"]["topk_merge_width"])
    least, bound, kernel_s = 0.0, {}, 0.0
    for (_, a, b), rows in zip(calls, ctx.log.rows):
        flops, nbytes = work.sq8_call(ctx.pool[rows], arrays["centroids"], arrays["members"], nprobe, k)
        t, term = work.least_seconds(flops, nbytes, peak)
        least += t
        bound[term] = bound.get(term, 0) + 1
        kernel_s += kernel.covered(a, b)
    ctx.say(f"sq8_scan_roofline: least {least!r} s over kernel {kernel_s!r} s, bound by {bound}")
    return 100.0 * least / kernel_s
