"""IVF_PQR: a DiskANN-style index family registered through the PUBLIC hook.

PQ candidate generation + exact re-ranking: the scan walks the probed
clusters with the ADC lookup table (like IVF_PQ), keeps the best
``reorder_k`` candidates, and re-scores exactly those against the raw stored
vectors — the graph-less core of the DiskANN/Vamana serving recipe (compressed
codes decide *where* to look, full-precision vectors decide *what* to return).
The memory/recall trade sits between IVF_PQ (codes only) and SCANN (int8
codes): PQ compression for the scan plus one raw copy for the re-rank.

This module is deliberately NOT imported by ``repro.vdms`` — it exists to
prove the registry API: calling :func:`register` is the ONLY integration
step, after which ``make_space()`` exposes the family's parameters, the
engine builds/searches/seals it, and both static and streaming tuning runs
work end-to-end with zero edits to ``core/space.py``, ``tuning_env.py``, or
the session layer. The README "Extending" section walks through this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import obs
from ..core.space import Param
from .fused import fused_search_ivf_pqr, shard_search_ivf_pqr
from .indexes import (
    _NLIST,
    _NPROBE,
    IndexBundle,
    _build_cost_ivf_pq,
    _gather_candidates,
    _segment_topk,
    _storage,
    build_ivf_pq,
)
from .registry import REGISTRY, IndexFamily, register_family


def build_ivf_pqr(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    """PQ bundle (codes + shared codebooks, frozen-calibration reuse included)
    plus the raw vectors the re-rank stage scores against."""
    base = build_ivf_pq(key, segs, gids, params, sys, frozen=frozen)
    arrays = dict(base.arrays)
    arrays["data"] = _storage(segs, sys["storage_bf16"])
    static = dict(base.static)
    static["reorder_k"] = int(max(params["reorder_k"], 1))
    return IndexBundle(kind="IVF_PQR", arrays=arrays, static=static)


def search_ivf_pqr(q, arrays, *, k_seg: int, nprobe: int, m: int, c: int, reorder_k: int):
    b, d = q.shape
    dsub = d // m
    # ADC similarity LUT (higher is better), shared across segments
    lut = jnp.einsum("bmd,mcd->bmc", q.reshape(b, m, dsub), arrays["codebooks"])

    def per_seg(seg):
        codes, data, gids, cents, members = seg
        cand = _gather_candidates(q, cents, members, nprobe=nprobe)  # (B, P)
        safe = jnp.maximum(cand, 0)
        ccodes = codes[safe].astype(jnp.int32)  # (B, P, m)
        g = jnp.take_along_axis(lut[:, None, :, :], ccodes[..., None], axis=3)
        approx = jnp.sum(g[..., 0], axis=-1)
        approx = jnp.where(cand >= 0, approx, -jnp.inf)
        r = min(reorder_k, approx.shape[1])
        with obs.scope("segment_topk"):
            _, top_r = jax.lax.top_k(approx, r)  # (B, r)
        rcand = jnp.take_along_axis(cand, top_r, axis=1)
        rsafe = jnp.maximum(rcand, 0)
        exact = jnp.einsum("brd,bd->br", data[rsafe].astype(jnp.float32), q)
        return _segment_topk(exact, rcand, gids, k_seg)

    return jax.lax.map(
        per_seg,
        (
            arrays["codes"],
            arrays["data"],
            arrays["gids"],
            arrays["centroids"],
            arrays["members"],
        ),
    )


def _chunk_cost_ivf_pqr(st, arrays, n_sealed, seg_size, dim):
    """ADC scan (centroid probe + LUT + code adds) plus the exact re-rank."""
    nlist = arrays["centroids"].shape[1]
    cap = arrays["members"].shape[2]
    flops = n_sealed * (
        nlist * dim * 2
        + st["m"] * st["c"] * (dim // st["m"]) * 2
        + st["nprobe"] * cap * st["m"]
        + st["reorder_k"] * dim * 2
    )
    return flops, 0


FAMILY = IndexFamily(
    name="IVF_PQR",
    params=(
        Param("nlist", "grid", choices=_NLIST, default=128),
        Param("m", "grid", choices=(4, 8, 16, 32), default=8),
        Param("nbits", "grid", choices=(4, 6, 8), default=8),
        Param("nprobe", "grid", choices=_NPROBE, default=8),
        Param("reorder_k", "grid", choices=(32, 64, 128, 256, 512), default=64),
    ),
    build=build_ivf_pqr,
    search=search_ivf_pqr,
    shared_arrays=("codebooks",),
    fused_search=fused_search_ivf_pqr,
    shard_search=shard_search_ivf_pqr,
    supports_frozen=True,
    chunk_cost=_chunk_cost_ivf_pqr,
    build_cost=_build_cost_ivf_pq,  # re-rank stores raw vectors; build cost is PQ's
    description="DiskANN-style IVF: PQ candidate scan + exact re-rank (reorder_k)",
)


def register() -> IndexFamily:
    """Register IVF_PQR via the public hook (idempotent)."""
    if FAMILY.name not in REGISTRY:
        register_family(FAMILY)
    return FAMILY
