"""Fused IVF probe → quantized scan → in-kernel top-k (SQ8 int8 pipeline).

The eval hot path composes four XLA calls per sealed segment (centroid
probe, candidate gather, dequantized scoring, ``lax.top_k``); this module
fuses the whole per-segment pipeline so the score matrix never round-trips
HBM. Two implementations share one contract:

* :func:`fused_ivf_sq8_topk_xla` — the reference path (production on CPU):
  probes via ``lax.top_k``, scores the FULL segment with one dequantized
  int8 matmul, then gathers candidate scores — measured 2-4x faster than
  the composed path because the per-segment top-k width can be clamped and
  the matmul is batched over every chunk at once.
* :func:`fused_ivf_sq8_topk_pallas` — the TPU Pallas kernel. TPUs have no
  gather, so the candidate-list formulation is ADAPTED to a mask-scan: the
  probe runs in-kernel (iterative max-extraction into a cluster-mask VMEM
  scratch), each code tile is scored on the MXU against the resident query
  block, cluster membership is applied as a one-hot matmul mask, and each
  tile is folded into a running top-k scratch by threshold-gated selection
  (:func:`merge_tile_topk`): only tile scores strictly above a row's k-th
  running score can enter, so a tile runs as many passes as its most
  demanding row needs, at most ``k`` and often none, each pass inserting a
  row's best remaining tile score into the sorted running list behind every
  entry at least as high (a tie goes to the lower local id). The kernel
  also returns the passes it ran per (segment, query block).

Memory-layout contract (shared by every fused kernel in this repo)
------------------------------------------------------------------
* All operands are row-major. One kernel call covers a stack of segments:
  the grid is ``(segment, query block, segment tile)``, the segment axis is
  tiled by ``bn``, the queries by ``bq`` rows, and the per-segment
  centroids plus the scale stay VMEM-resident while a segment is scanned.
  The embedding dim rides along padded to a multiple of 128.
* Scoring and probe matmuls run at ``Precision.HIGHEST`` in the kernels and
  in the XLA references alike, so both score in f32 on the TPU (XLA's
  default there is one bf16 pass) and return the same result sets.
* Inputs are zero-padded to block multiples; the padding is masked via
  ``cluster_of == -1`` (padded rows belong to no cluster), NEVER by score
  sentinels written into the input arrays.
* Accumulation and scores are f32 (``preferred_element_type``) regardless
  of storage dtype; int8 codes are dequantized in-register per tile.
* Outputs are (B, k) local ids (-1 = empty slot) + scores (-inf = empty);
  ordering among tied scores is implementation-defined — parity tests
  compare score-sorted sets, not raw slot order. The Pallas kernels return
  their lists sorted (score descending, then local id) and, third, the
  selection passes of each (segment, query block), which ``ops`` drops.

Candidate semantics match the composed path exactly: a point is a candidate
iff it appears in the (capacity-bounded) member list of a probed cluster;
``members_to_cluster_of`` derives the inverse map from the member lists
themselves, so list-overflow drops carry over to the mask-scan formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import HIGHEST


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def members_to_cluster_of(members: jnp.ndarray, s: int) -> jnp.ndarray:
    """Invert one segment's (nlist, cap) member lists into a (s,) cluster id
    per point; points dropped by the capacity bound (or padded slots) map to
    -1 so the mask-scan sees exactly the composed path's candidate set."""
    nlist, cap = members.shape
    flat = members.reshape(-1)
    vals = jnp.repeat(jnp.arange(nlist, dtype=jnp.int32), cap)
    safe = jnp.where(flat >= 0, flat, s)  # park padding on a scratch slot
    return jnp.full((s + 1,), -1, jnp.int32).at[safe].set(vals)[:s]


# ---------------------------------------------------------------------------
# shared in-kernel stages (also used by fused_adc.py)
# ---------------------------------------------------------------------------
def probe_and_init(q_ref, c_ref, cmask_scr, vals_scr, lids_scr, *, nlist: int, nprobe: int):
    """Grid step 0: probe the top-``nprobe`` clusters per query into the
    cluster-mask scratch and reset the running top-k scratch.

    The probe is iterative max-extraction (ties → lowest cluster index),
    matching ``lax.top_k``'s stable tie-break in the XLA reference, so both
    impls probe identical cluster sets.
    """
    csim = jax.lax.dot_general(
        q_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (bq, Lp)
    col = jax.lax.broadcasted_iota(jnp.int32, csim.shape, 1)
    csim = jnp.where(col < nlist, csim, -jnp.inf)

    def body(_, carry):
        csim, cmask = carry
        m = jnp.max(csim, axis=1, keepdims=True)
        hit = (csim == m) & jnp.isfinite(m)
        idx = jnp.min(jnp.where(hit, col, csim.shape[1]), axis=1, keepdims=True)
        sel = (col == idx) & jnp.isfinite(m)
        cmask = jnp.where(sel, 1.0, cmask)
        csim = jnp.where(sel, -jnp.inf, csim)
        return csim, cmask

    _, cmask = jax.lax.fori_loop(0, nprobe, body, (csim, jnp.zeros_like(csim)))
    cmask_scr[...] = cmask
    vals_scr[...] = jnp.full(vals_scr.shape, -jnp.inf, jnp.float32)
    lids_scr[...] = jnp.full(lids_scr.shape, -1, jnp.int32)


def merge_tile_topk(
    scores, j, cl_ref, gid_ref, cmask_scr, vals_scr, lids_scr, passes_scr, *, k: int,
    mask_dead: bool,
):
    """Mask one scored tile by probed-cluster membership and fold it into the
    running top-k scratch by threshold-gated selection.

    The running list is kept sorted (score descending, ties by ascending local
    id), so its slot ``k - 1`` is each row's admission threshold (-inf while
    the list is not full). Only masked tile scores strictly above it can
    enter, at most ``k`` of them, so the tile runs ``P = max over rows of
    min(k, count above threshold)`` passes: ``P`` is 0 for a tile that cannot
    change the result. Each pass extracts every row's best remaining tile
    score (ties → lowest column; its local id is ``j * bn + column``) and
    inserts it after every running entry that scores at least as high, the
    entries behind it shifting one lane down; a score at or below the
    threshold lands past slot ``k - 1`` and drops out of the result. So a tie
    goes to the earlier slot, hence the lower local id, and the list equals
    the first ``k`` of every candidate ordered by (score descending, local
    id). ``P`` is added to ``passes_scr[0]``, which the first tile resets.

    ``mask_dead`` additionally drops gid<0 slots pre-top-k (the clamped static
    path); otherwise dead slots survive to the caller like the composed path's
    post-top-k masking."""
    bn = scores.shape[1]
    cl = cl_ref[...]  # (1, bn) cluster id per point, -1 = not a candidate
    lp = cmask_scr.shape[1]
    lio = jax.lax.broadcasted_iota(jnp.int32, (lp, bn), 0)
    onehot = (lio == cl).astype(jnp.float32)  # (Lp, bn)
    probed = jax.lax.dot_general(
        cmask_scr[...], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bq, bn)
    ok = (probed > 0.5) & (cl >= 0)
    if mask_dead:
        ok = ok & (gid_ref[...] >= 0)
    scores = jnp.where(ok, scores, -jnp.inf)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    vals, lids = vals_scr[...], lids_scr[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    thr = jnp.min(jnp.where(slot < k, vals, jnp.inf), axis=1, keepdims=True)
    above = jnp.sum((scores > thr).astype(jnp.int32), axis=1, keepdims=True)
    n_pass = jnp.max(jnp.minimum(above, k))

    def body(_, carry):
        scores, vals, lids = carry
        m = jnp.max(scores, axis=1, keepdims=True)
        idx = jnp.min(jnp.where(scores == m, col, bn), axis=1, keepdims=True)
        # m's place: after every entry >= m; kp (no change) for an exhausted row
        pos = jnp.sum((vals >= m).astype(jnp.int32), axis=1, keepdims=True)
        keep, here = slot < pos, slot == pos
        vals = jnp.where(keep, vals, jnp.where(here, m, pltpu.roll(vals, 1, 1)))
        lids = jnp.where(keep, lids, jnp.where(here, j * bn + idx, pltpu.roll(lids, 1, 1)))
        return jnp.where(col == idx, -jnp.inf, scores), vals, lids

    _, vals, lids = jax.lax.fori_loop(0, n_pass, body, (scores, vals, lids))
    vals_scr[...] = vals
    lids_scr[...] = lids
    passes_scr[0] = jnp.where(j == 0, 0, passes_scr[0]) + n_pass


# ---------------------------------------------------------------------------
# SQ8 kernel
# ---------------------------------------------------------------------------
def _fused_sq8_kernel(
    q_ref, c_ref, scale_ref, codes_ref, cl_ref, gid_ref, lid_out, sim_out, pass_out,
    cmask_scr, vals_scr, lids_scr, passes_scr, *, nlist, nprobe, k, n_steps, mask_dead,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        probe_and_init(q_ref, c_ref, cmask_scr, vals_scr, lids_scr, nlist=nlist, nprobe=nprobe)

    deq = codes_ref[...].astype(jnp.float32) * scale_ref[...]  # (bn, Dp) f32
    scores = jax.lax.dot_general(
        q_ref[...], deq, (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )  # (bq, bn)
    merge_tile_topk(
        scores, j, cl_ref, gid_ref, cmask_scr, vals_scr, lids_scr, passes_scr, k=k,
        mask_dead=mask_dead,
    )

    @pl.when(j == n_steps - 1)
    def _flush():
        flush_topk(lid_out, sim_out, pass_out, vals_scr, lids_scr, passes_scr)


def flush_topk(lid_out, sim_out, pass_out, vals_scr, lids_scr, passes_scr):
    """Last tile: write the running top-k and the block's selection-pass count."""
    lid_out[...] = lids_scr[...]
    sim_out[...] = vals_scr[...]
    pass_out[...] = jnp.full(pass_out.shape, passes_scr[0], jnp.int32)


def stacked_layout(b: int, s: int, nlist: int, k: int, bq: int, bn: int):
    """Block sizes shared by the fused kernels: ``(bq, bp, bn, np_, lp, kp)``
    — the query block and padded query rows, the segment tile and padded
    segment length, the padded cluster count and the padded top-k width."""
    bq = min(bq, _round_up(b, 8))
    bn = min(bn, _round_up(s, 128))
    return (
        bq, _round_up(b, bq), bn, _round_up(s, bn), _round_up(nlist, 128), _round_up(k, 128)
    )


def stacked_spec(block, index_map):
    """BlockSpec of one stacked per-segment operand: the segment axis is
    squeezed out of the block and picked by grid axis 0."""
    return pl.BlockSpec((None,) + tuple(block), index_map)


def topk_outputs(n_seg: int, bp: int, bq: int, lp: int, kp: int) -> dict:
    """``pallas_call`` outputs and scratch shared by the fused kernels: the
    running top-k (lids, sims), each (n_seg, bp, kp), and the selection
    passes of each (segment, query block), one lane row (n_seg, bp // bq, 1,
    128) apiece; scratch for the cluster mask, the running top-k and the
    pass count."""
    return dict(
        out_specs=[
            stacked_spec((bq, kp), lambda z, i, j: (z, i, 0)),
            stacked_spec((bq, kp), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((None, None, 1, 128), lambda z, i, j: (z, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, bp, kp), jnp.int32),
            jax.ShapeDtypeStruct((n_seg, bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((n_seg, bp // bq, 1, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, lp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )


@functools.partial(
    jax.jit, static_argnames=("nprobe", "k", "mask_dead", "bq", "bn", "interpret")
)
def fused_ivf_sq8_topk_pallas(
    q: jnp.ndarray,
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    centroids: jnp.ndarray,
    cluster_of: jnp.ndarray,
    gids: jnp.ndarray,
    *,
    nprobe: int,
    k: int,
    mask_dead: bool = False,
    bq: int = 128,
    bn: int = 256,
    interpret: bool = False,
):
    """Stacked segments in one kernel: q (B, d) f32, codes (n_seg, s, d) int8,
    scale (d,), centroids (n_seg, nlist, d), cluster_of (n_seg, s) from
    :func:`members_to_cluster_of`, gids (n_seg, s) -> (lids, sims) each
    (n_seg, B, k), and the selection passes run per (segment, query block),
    (n_seg, ceil(B / bq)) int32 (:func:`merge_tile_topk`).

    Grid ``(segment, query block, segment tile)``: one launch covers every
    segment, and VMEM holds one ``bq``-row query block, never the batch."""
    b, d = q.shape
    n_seg, s, _ = codes.shape
    nlist = centroids.shape[1]
    bq, bp, bn, np_, lp, kp = stacked_layout(b, s, nlist, k, bq, bn)
    dp = _round_up(d, 128)
    qp = jnp.pad(q.astype(jnp.float32), ((0, bp - b), (0, dp - d)))
    cp = jnp.pad(centroids.astype(jnp.float32), ((0, 0), (0, lp - nlist), (0, dp - d)))
    sp = jnp.pad(scale.astype(jnp.float32), (0, dp - d)).reshape(1, dp)
    codesp = jnp.pad(codes, ((0, 0), (0, np_ - s), (0, dp - d)))
    clp = jnp.pad(cluster_of.astype(jnp.int32), ((0, 0), (0, np_ - s)), constant_values=-1)
    gp = jnp.pad(gids.astype(jnp.int32), ((0, 0), (0, np_ - s)), constant_values=-1)
    n_steps = np_ // bn

    lids, sims, passes = pl.pallas_call(
        functools.partial(
            _fused_sq8_kernel,
            nlist=nlist,
            nprobe=min(nprobe, nlist),
            k=k,
            n_steps=n_steps,
            mask_dead=mask_dead,
        ),
        grid=(n_seg, bp // bq, n_steps),
        in_specs=[
            pl.BlockSpec((bq, dp), lambda z, i, j: (i, 0)),
            stacked_spec((lp, dp), lambda z, i, j: (z, 0, 0)),
            pl.BlockSpec((1, dp), lambda z, i, j: (0, 0)),
            stacked_spec((bn, dp), lambda z, i, j: (z, j, 0)),
            stacked_spec((1, bn), lambda z, i, j: (z, 0, j)),
            stacked_spec((1, bn), lambda z, i, j: (z, 0, j)),
        ],
        **topk_outputs(n_seg, bp, bq, lp, kp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qp, cp, sp, codesp, clp.reshape(n_seg, 1, np_), gp.reshape(n_seg, 1, np_))
    return lids[:, :b, :k], sims[:, :b, :k], passes[:, :, 0, 0]


# ---------------------------------------------------------------------------
# XLA reference (production path on CPU)
# ---------------------------------------------------------------------------
def probe_candidates(q, centroids, members, nprobe: int) -> jnp.ndarray:
    """Probe top-nprobe clusters and flatten their member lists: (B, P) local
    ids, -1 padded — identical to the composed path's candidate stage."""
    csim = jnp.dot(q, centroids.T, precision=HIGHEST, preferred_element_type=jnp.float32)
    _, probe = jax.lax.top_k(csim, min(nprobe, centroids.shape[0]))
    return members[probe].reshape(q.shape[0], -1)


def topk_candidates(cand, sims, gids, *, k: int, mask_dead: bool):
    """Shared epilogue: mask padded (and optionally dead-gid) candidates,
    take the top-k, and return (lids, sims) padded to width ``k``."""
    ok = cand >= 0
    if mask_dead:
        ok = ok & (gids[jnp.maximum(cand, 0)] >= 0)
    sims = jnp.where(ok, sims, -jnp.inf)
    kk = min(k, sims.shape[1])
    top_s, top_i = jax.lax.top_k(sims, kk)
    lids = jnp.take_along_axis(cand, top_i, axis=1)
    lids = jnp.where(jnp.isfinite(top_s), lids, -1)
    if kk < k:
        pad = k - kk
        lids = jnp.pad(lids, ((0, 0), (0, pad)), constant_values=-1)
        top_s = jnp.pad(top_s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    return lids, top_s


def fused_ivf_sq8_topk_xla(
    q, codes, scale, centroids, members, gids, *, nprobe: int, k: int, mask_dead: bool = False
):
    """One segment, XLA formulation: full-segment dequantized int8 matmul +
    candidate-score gather + clamped top-k. Scores match the composed path's
    per-element arithmetic (codes·scale dequant, f32 contraction over d)."""
    cand = probe_candidates(q, centroids, members, nprobe)  # (B, P)
    deq = codes.astype(jnp.float32) * scale[None, :]
    sall = jnp.dot(q, deq.T, precision=HIGHEST, preferred_element_type=jnp.float32)  # (B, s)
    sims = jnp.take_along_axis(sall, jnp.maximum(cand, 0), axis=1)
    return topk_candidates(cand, sims, gids, k=k, mask_dead=mask_dead)
