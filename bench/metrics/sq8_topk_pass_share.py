"""sq8_topk_pass_share: of the top-k selection passes the fused IVF_SQ8 kernel
could run on the window's first search call (``k`` for each tile of each query
block of each segment), the share it ran, in percent.

The kernel counts the passes it runs per (segment, query block) in a third
output. After the window it runs once more, on the arguments the search
program gives it for that call's queries (``VDMSInstance._pipeline_args``, as
``repro.vdms.fused`` passes them on), the way ``sq8_scan_roofline`` recomputes
that call's work. A kernel that returns no count reads nothing.
"""
import inspect

import numpy as np


def read(ctx):
    searcher = ctx.searcher
    if searcher is None or not ctx.log.rows:
        return None
    from repro.kernels import ops
    from repro.kernels.fused_scan import fused_ivf_sq8_topk_pallas, stacked_layout

    qc, arrays, _, _, _, statics, k_seg, topk, _, clamp = searcher._pipeline_args(
        ctx.pool[ctx.log.rows[0]], int(ctx.mix["topk"]))
    q = qc.reshape(-1, qc.shape[-1])
    k = min(k_seg, topk) if clamp else k_seg
    n_seg, s = arrays["gids"].shape
    out = fused_ivf_sq8_topk_pallas(
        q, arrays["codes"], arrays["scale"], arrays["centroids"],
        ops._cluster_of(arrays["members"], s), arrays["gids"],
        nprobe=int(dict(statics)["nprobe"]), k=k, mask_dead=clamp,
    )
    if len(out) < 3:
        return None
    passes = np.asarray(out[2], np.int64)
    bn = inspect.signature(fused_ivf_sq8_topk_pallas).parameters["bn"].default
    _, _, bn, np_, _, _ = stacked_layout(q.shape[0], s, arrays["centroids"].shape[1], k, 8, bn)
    could = k * (np_ // bn) * passes.shape[1] * n_seg
    ctx.say(f"sq8_topk_pass_share: {int(passes.sum())} passes of {could} (k {k}, "
            f"{np_ // bn} tiles, {passes.shape[1]} query blocks, {n_seg} segments)")
    return 100.0 * float(passes.sum()) / could
