"""Fused per-family search pipelines (the registry ``fused_search`` hooks).

Each hook replaces one family's *entire* per-chunk hot path — IVF probe,
candidate scoring, per-segment top-k, global-id mapping, and the merge with
the growing tail — with a single call into the fused kernel layer
(:mod:`repro.kernels.fused_scan` / :mod:`repro.kernels.fused_adc` via the
``ops`` impl switch: XLA reference on CPU, Pallas on TPU). The engine
dispatches here whenever the family registered a hook and the session
pipeline mode is ``"fused"``; families without a hook transparently fall
back to their composed ``search`` callable.

Result contract (what the engine relies on):

* the returned ``(B, topk)`` global ids are SET-identical per query to the
  composed path's output — same candidates survive, same growing-tail merge,
  same -1 padding — with slot order among *tied* scores impl-defined;
* under the XLA impl the IVF_PQ and IVF_PQR scores are bit-identical to the
  composed scan (the flat-LUT lookup sums subquantizers in the same order),
  while IVF_SQ8 may differ in the last ulp (full-tile matmul vs gathered
  einsum associate the d-reduction differently);
* ``clamp=True`` (static instances whose sealed segments carry no ``-1``
  padding, see ``VDMSInstance._clamp_ok``) narrows the per-segment width to
  ``min(k_seg, topk)`` — exact because only ``topk`` results survive the
  merge and no dead slot can consume width; live searches never clamp;
* ``alive`` selects the merge flavor: ``None`` runs the static
  ``_pipeline_impl`` chunk merge, a mask runs ``_live_chunk``'s tombstone
  filtering (sentinel slot, masked growing gids, -1 on -inf) — both are the
  SAME code the engine calls (``repro.vdms.merge.merge_topk``), not copies.

The module also hosts the per-family **shard hooks** (``shard_search``): the
candidate-generation stage of the sharded engine's merge tree. A shard hook
runs the family's fused kernels over one shard's local segment stack and
returns per-segment ``(global ids, sims)`` with composed masking semantics
(dead slots stay -1/-inf and keep their width, never clamped) — the merge
itself stays in ``ShardedVDMS``, which feeds every shard's partial top-k
through the same ``repro.vdms.merge`` arithmetic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import obs
from ..kernels import ops
from .merge import merge_topk


def _map_gids(gids, lids, sims):
    """Map per-segment local ids (n_seg, B, k) to global ids via each
    segment's gid row, with dead-slot masking: empty slots (lid < 0) and
    padded ones (gid < 0) keep their width but turn -1/-inf, mirroring the
    composed post-top-k mask. Returns (ids, sims)."""
    with obs.scope("gid_map"):
        ids = jax.vmap(lambda g, l: g[jnp.maximum(l, 0)])(gids, lids)
        ids = jnp.where(lids >= 0, ids, -1)
        return ids, jnp.where(ids >= 0, sims, -jnp.inf)


def _rerank_topk(q, data, lids, k):
    """IVF_PQR's second stage: score the PQ candidates (n_seg, B, r) exactly
    against the raw vectors, keep each segment's best ``k`` (padded -1/-inf
    where ``r < k``). Returns (lids, sims), each (n_seg, B, k)."""

    def rerank(data_z, lids_z):
        vecs = data_z[jnp.maximum(lids_z, 0)].astype(jnp.float32)  # (B, r, d)
        exact = jnp.einsum("brd,bd->br", vecs, q)
        return jnp.where(lids_z >= 0, exact, -jnp.inf)

    exact = jax.vmap(rerank)(data, lids)  # (n_seg, B, r)
    kk = min(k, exact.shape[-1])
    with obs.scope("segment_topk"):
        top_s, top_i = jax.lax.top_k(exact, kk)
    lids = jnp.take_along_axis(lids, top_i, axis=2)
    if kk < k:
        pad = ((0, 0), (0, 0), (0, k - kk))
        lids = jnp.pad(lids, pad, constant_values=-1)
        top_s = jnp.pad(top_s, pad, constant_values=-jnp.inf)
    return lids, top_s


def _finish(lids, sims, gids, q, growing, growing_gids, alive, topk):
    """Shared epilogue: local→global ids with dead-slot masking, then the
    shared static/live merge (``repro.vdms.merge``)."""
    ids, sims = _map_gids(gids, lids, sims)
    return merge_topk(ids, sims, q, growing, growing_gids, topk, alive=alive)


# ---------------------------------------------------------------------------
# per-family hooks
# ---------------------------------------------------------------------------
def fused_search_ivf_sq8(
    q, arrays, growing, growing_gids, *, k_seg, topk, clamp=False, alive=None, nprobe
):
    """IVF_SQ8: fused probe → int8 dequant scan → in-kernel top-k."""
    clamped = clamp and alive is None
    k_eff = min(k_seg, topk) if clamped else k_seg
    lids, sims = ops.fused_ivf_sq8_topk(
        q,
        arrays["codes"],
        arrays["scale"],
        arrays["centroids"],
        arrays["members"],
        arrays["gids"],
        nprobe=nprobe,
        k=k_eff,
        mask_dead=clamped,
    )
    return _finish(lids, sims, arrays["gids"], q, growing, growing_gids, alive, topk)


fused_search_ivf_sq8.stages = "probe → int8 dequant scan → top-k"


def fused_search_ivf_pq(
    q, arrays, growing, growing_gids, *, k_seg, topk, clamp=False, alive=None, nprobe, m, c
):
    """IVF_PQ: fused probe → flat-LUT ADC scan → in-kernel top-k."""
    clamped = clamp and alive is None
    k_eff = min(k_seg, topk) if clamped else k_seg
    b, d = q.shape
    lut = jnp.einsum("bmd,mcd->bmc", q.reshape(b, m, d // m), arrays["codebooks"])
    lids, sims = ops.fused_ivf_pq_topk(
        q,
        lut,
        arrays["codes"],
        arrays["centroids"],
        arrays["members"],
        arrays["gids"],
        nprobe=nprobe,
        k=k_eff,
        mask_dead=clamped,
    )
    return _finish(lids, sims, arrays["gids"], q, growing, growing_gids, alive, topk)


fused_search_ivf_pq.stages = "probe → PQ ADC scan → top-k"


def fused_search_ivf_pqr(
    q,
    arrays,
    growing,
    growing_gids,
    *,
    k_seg,
    topk,
    clamp=False,
    alive=None,
    nprobe,
    m,
    c,
    reorder_k,
):
    """IVF_PQR: fused PQ candidate scan (width ``reorder_k``, never clamped —
    dead slots consume reorder width exactly as composed) → exact re-rank
    against the raw vectors → clamped per-segment top-k."""
    clamped = clamp and alive is None
    k_eff = min(k_seg, topk) if clamped else k_seg
    b, d = q.shape
    lut = jnp.einsum("bmd,mcd->bmc", q.reshape(b, m, d // m), arrays["codebooks"])
    lids, _ = ops.fused_ivf_pq_topk(
        q,
        lut,
        arrays["codes"],
        arrays["centroids"],
        arrays["members"],
        arrays["gids"],
        nprobe=nprobe,
        k=reorder_k,
        mask_dead=False,
    )  # (n_seg, B, r): the PQ stage only ranks; its scores are discarded
    lids, sims = _rerank_topk(q, arrays["data"], lids, k_eff)
    return _finish(lids, sims, arrays["gids"], q, growing, growing_gids, alive, topk)


fused_search_ivf_pqr.stages = "probe → PQ ADC scan → exact re-rank → top-k"


# ---------------------------------------------------------------------------
# per-family shard hooks (candidate stage of the sharded merge tree)
# ---------------------------------------------------------------------------
def shard_search_ivf_sq8(q, arrays, *, k_seg, nprobe):
    """IVF_SQ8 per-shard candidates via the fused kernel (composed masking:
    dead slots -1/-inf, full ``k_seg`` width)."""
    lids, sims = ops.fused_ivf_sq8_topk(
        q,
        arrays["codes"],
        arrays["scale"],
        arrays["centroids"],
        arrays["members"],
        arrays["gids"],
        nprobe=nprobe,
        k=k_seg,
        mask_dead=False,
    )
    return _map_gids(arrays["gids"], lids, sims)


shard_search_ivf_sq8.stages = "probe → int8 dequant scan → shard top-k"


def shard_search_ivf_pq(q, arrays, *, k_seg, nprobe, m, c):
    """IVF_PQ per-shard candidates via the fused ADC kernel."""
    b, d = q.shape
    lut = jnp.einsum("bmd,mcd->bmc", q.reshape(b, m, d // m), arrays["codebooks"])
    lids, sims = ops.fused_ivf_pq_topk(
        q,
        lut,
        arrays["codes"],
        arrays["centroids"],
        arrays["members"],
        arrays["gids"],
        nprobe=nprobe,
        k=k_seg,
        mask_dead=False,
    )
    return _map_gids(arrays["gids"], lids, sims)


shard_search_ivf_pq.stages = "probe → PQ ADC scan → shard top-k"


def shard_search_ivf_pqr(q, arrays, *, k_seg, nprobe, m, c, reorder_k):
    """IVF_PQR per-shard candidates: fused PQ scan picks ``reorder_k``
    candidates per segment, the exact re-rank scores them against the raw
    vectors, then the per-segment top-k (all inside the shard)."""
    b, d = q.shape
    lut = jnp.einsum("bmd,mcd->bmc", q.reshape(b, m, d // m), arrays["codebooks"])
    lids, _ = ops.fused_ivf_pq_topk(
        q,
        lut,
        arrays["codes"],
        arrays["centroids"],
        arrays["members"],
        arrays["gids"],
        nprobe=nprobe,
        k=reorder_k,
        mask_dead=False,
    )
    lids, sims = _rerank_topk(q, arrays["data"], lids, k_seg)
    return _map_gids(arrays["gids"], lids, sims)


shard_search_ivf_pqr.stages = "probe → PQ ADC scan → exact re-rank → shard top-k"
