"""Fused IVF probe → PQ ADC scan → in-kernel top-k (the IVF_PQ pipeline).

Same fusion contract as :mod:`repro.kernels.fused_scan` (which contributes
the in-kernel probe and running-top-k stages); the scoring stage differs:

* :func:`fused_ivf_pq_topk_xla` — reference path: the per-subquantizer LUT
  lookup runs as ONE flat ``take_along_axis`` over the (B, m*c) LUT
  (measured 6-8x faster than the nested per-subquantizer gather the
  composed path uses), summed over m in the same order so scores are
  bit-identical to the composed scan.
* :func:`fused_ivf_pq_topk_pallas` — TPU kernel: no gather on TPU, so each
  code tile scores via m one-hot matmuls against the query block's LUT,
  then flows through the shared membership-mask + running-top-k stages.

Memory-layout contract
----------------------
* Codes are passed TRANSPOSED to the kernel — (n_seg, m, s) int32,
  row-major — so the tiled axis (s) is the lane axis; the LUT is padded per
  subquantizer to a 128-multiple code width and flattened to (B, m*cpad),
  zero-padded slots are never matched because codes < c.
* Everything else follows fused_scan: zero-pad to block multiples, padding
  masked via ``cluster_of == -1``, f32 accumulation, (B, k) outputs with
  -1/-inf empty slots and impl-defined tie ordering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_scan import (
    HIGHEST,
    _round_up,
    merge_tile_topk,
    probe_and_init,
    probe_candidates,
    stacked_layout,
    stacked_spec,
    topk_candidates,
)


def _fused_pq_kernel(
    q_ref, c_ref, lut_ref, codes_ref, cl_ref, gid_ref, lid_out, sim_out,
    cmask_scr, vals_scr, lids_scr, *, nlist, nprobe, k, m, cpad, n_steps, mask_dead,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        probe_and_init(q_ref, c_ref, cmask_scr, vals_scr, lids_scr, nlist=nlist, nprobe=nprobe)

    bq = lut_ref.shape[0]
    bn = codes_ref.shape[1]

    def body(mi, acc):
        crow = codes_ref[pl.ds(mi, 1), :]  # (1, bn) int32
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (cpad, bn), 0) == crow
        ).astype(jnp.float32)  # (cpad, bn)
        lutm = lut_ref[:, pl.ds(pl.multiple_of(mi * cpad, 128), cpad)]  # (bq, cpad)
        return acc + jax.lax.dot_general(
            lutm, onehot, (((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )

    scores = jax.lax.fori_loop(0, m, body, jnp.zeros((bq, bn), jnp.float32))
    merge_tile_topk(
        scores, j, cl_ref, gid_ref, cmask_scr, vals_scr, lids_scr, k=k, mask_dead=mask_dead
    )

    @pl.when(j == n_steps - 1)
    def _flush():
        lid_out[...] = lids_scr[...]
        sim_out[...] = vals_scr[...]


@functools.partial(
    jax.jit, static_argnames=("nprobe", "k", "mask_dead", "bq", "bn", "interpret")
)
def fused_ivf_pq_topk_pallas(
    q: jnp.ndarray,
    lut: jnp.ndarray,
    codes: jnp.ndarray,
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
    """Stacked segments in one kernel: q (B, d) f32, lut (B, m, c) f32, codes
    (n_seg, s, m) integer, centroids (n_seg, nlist, d), cluster_of
    (n_seg, s), gids (n_seg, s) -> (lids, sims) each (n_seg, B, k). Grid
    ``(segment, query block, segment tile)`` as in the SQ8 kernel."""
    b, d = q.shape
    _, m, c = lut.shape
    n_seg, s, _ = codes.shape
    nlist = centroids.shape[1]
    bq, bp, bn, np_, lp, kp = stacked_layout(b, s, nlist, k, bq, bn)
    dp, cpad = _round_up(d, 128), _round_up(c, 128)
    qp = jnp.pad(q.astype(jnp.float32), ((0, bp - b), (0, dp - d)))
    cp = jnp.pad(centroids.astype(jnp.float32), ((0, 0), (0, lp - nlist), (0, dp - d)))
    lutp = jnp.pad(lut.astype(jnp.float32), ((0, bp - b), (0, 0), (0, cpad - c)))
    lutp = lutp.reshape(bp, m * cpad)
    codes_t = jnp.pad(
        codes.astype(jnp.int32), ((0, 0), (0, np_ - s), (0, 0)), constant_values=-1
    ).transpose(0, 2, 1)  # (n_seg, m, np_)
    clp = jnp.pad(cluster_of.astype(jnp.int32), ((0, 0), (0, np_ - s)), constant_values=-1)
    gp = jnp.pad(gids.astype(jnp.int32), ((0, 0), (0, np_ - s)), constant_values=-1)
    n_steps = np_ // bn

    lids, sims = pl.pallas_call(
        functools.partial(
            _fused_pq_kernel,
            nlist=nlist,
            nprobe=min(nprobe, nlist),
            k=k,
            m=m,
            cpad=cpad,
            n_steps=n_steps,
            mask_dead=mask_dead,
        ),
        grid=(n_seg, bp // bq, n_steps),
        in_specs=[
            pl.BlockSpec((bq, dp), lambda z, i, j: (i, 0)),
            stacked_spec((lp, dp), lambda z, i, j: (z, 0, 0)),
            pl.BlockSpec((bq, m * cpad), lambda z, i, j: (i, 0)),
            stacked_spec((m, bn), lambda z, i, j: (z, 0, j)),
            stacked_spec((1, bn), lambda z, i, j: (z, 0, j)),
            stacked_spec((1, bn), lambda z, i, j: (z, 0, j)),
        ],
        out_specs=[
            stacked_spec((bq, kp), lambda z, i, j: (z, i, 0)),
            stacked_spec((bq, kp), lambda z, i, j: (z, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, bp, kp), jnp.int32),
            jax.ShapeDtypeStruct((n_seg, bp, kp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, lp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qp, cp, lutp, codes_t, clp.reshape(n_seg, 1, np_), gp.reshape(n_seg, 1, np_))
    return lids[:, :b, :k], sims[:, :b, :k]


# ---------------------------------------------------------------------------
# XLA reference (production path on CPU)
# ---------------------------------------------------------------------------
def fused_ivf_pq_topk_xla(
    q, lut, codes, centroids, members, gids, *, nprobe: int, k: int, mask_dead: bool = False
):
    """One segment, XLA formulation: probe + flat-LUT ADC over the candidate
    codes + clamped top-k. The flat (B, m*c) lookup sums over m in the same
    order as the composed nested gather, so scores are bit-identical."""
    b, m, c = lut.shape
    cand = probe_candidates(q, centroids, members, nprobe)  # (B, P)
    ccodes = codes[jnp.maximum(cand, 0)].astype(jnp.int32)  # (B, P, m)
    lutf = lut.reshape(b, m * c)
    idx = ccodes + (jnp.arange(m, dtype=jnp.int32) * c)[None, None, :]
    sims = jnp.take_along_axis(lutf, idx.reshape(b, -1), axis=1)
    sims = sims.reshape(b, -1, m).sum(axis=-1)  # (B, P)
    return topk_candidates(cand, sims, gids, k=k, mask_dead=mask_dead)
