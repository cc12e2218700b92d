"""Top-k merge arithmetic — the ONE implementation every search path shares.

Historically the engine's static chunk merge (``_pipeline_impl``), the live
tombstone merge (``_live_chunk``) and the fused hooks' epilogues
(``fused._merge_static`` / ``fused._merge_live``) each carried a line-for-line
copy of the same arithmetic. This module is the extraction: the composed and
fused pipelines call :func:`merge_topk`, and the sharded engine's two-level
merge tree is built from the same primitives (:func:`flatten_candidates`,
:func:`partial_topk`, :func:`merge_flat`), so a change to the merge semantics
lands everywhere at once — there is no second copy left to drift.

Semantics (unchanged from the original engine code, bitwise):

* per-segment candidates ``(n_seg, B, k_seg)`` flatten query-major to
  ``(B, n_seg * k_seg)`` — flat position = ``segment * k_seg + slot``, which
  is the tie-break order (``lax.top_k`` keeps the lowest index among equal
  scores);
* ``alive`` (live merge only) gates every candidate through the global alive
  mask; id ``-1`` maps to the always-dead sentinel slot ``alive[-1]``;
* the growing tail is brute-forced and its best ``min(topk, len)`` candidates
  are appended AFTER all segment candidates (ties lose to sealed results);
  the live flavor additionally masks tail pad rows (gid < 0) to ``-inf``;
* the final ``top_k`` keeps ``min(topk, width)`` winners; the live flavor
  reports ``-inf`` survivors as id ``-1``; missing width pads with ``-1``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import obs
from ..kernels import ops


def flatten_candidates(ids, sims, alive=None):
    """Flatten per-segment candidates (n_seg, B, k) to flat per-query lists
    (B, n_seg * k), optionally gating scores through the global ``alive``
    mask (id -1 hits the always-dead sentinel slot ``alive[-1]``)."""
    n_seg, b, ks = ids.shape
    with obs.scope("merge"):
        ids2 = jnp.moveaxis(ids, 0, 1).reshape(b, n_seg * ks)
        sims2 = jnp.moveaxis(sims, 0, 1).reshape(b, n_seg * ks)
        if alive is not None:
            sentinel = alive.shape[0] - 1
            ok = alive[jnp.where(ids2 >= 0, ids2, sentinel)]
            sims2 = jnp.where(ok, sims2, -jnp.inf)
    return ids2, sims2


def partial_topk(ids, sims, k, alive=None):
    """One leaf of the merge tree: flatten a shard's per-segment candidates
    and keep its best ``min(k, width)`` — scores included, so a root merge
    can finish the reduction. Tie-break and alive gating are identical to
    the full merge; prefiltering a flat list to its top-k preserves the
    global winners because at most ``k`` of them can come from one shard."""
    ids2, sims2 = flatten_candidates(ids, sims, alive=alive)
    with obs.scope("merge"):
        return ops.topk_by_score(ids2, sims2, min(k, sims2.shape[1]))


def merge_flat(ids2, sims2, q, growing, growing_gids, topk, *, live: bool,
               return_scores: bool = False):
    """Root of the merge: append the growing-tail candidates to flat
    per-query lists (B, W) and keep the global top-k. ``live`` selects the
    tombstone flavor (masked tail gids, -inf survivors become id -1)."""
    with obs.scope("merge"):
        if growing.shape[0] > 0:
            gs = jnp.dot(q, growing.T.astype(q.dtype), preferred_element_type=jnp.float32)
            if live:
                gs = jnp.where(growing_gids[None, :] >= 0, gs, -jnp.inf)
            gk = min(topk, growing.shape[0])
            gtop_s, gtop_i = jax.lax.top_k(gs, gk)
            ids2 = jnp.concatenate([ids2, growing_gids[gtop_i]], axis=1)
            sims2 = jnp.concatenate([sims2, gtop_s], axis=1)
        k = min(topk, sims2.shape[1])
        out, top_s = ops.topk_by_score(ids2, sims2, k)
        if live:
            out = jnp.where(jnp.isfinite(top_s), out, -1)
        if k < topk:
            out = jnp.pad(out, ((0, 0), (0, topk - k)), constant_values=-1)
            if return_scores:
                top_s = jnp.pad(top_s, ((0, 0), (0, topk - k)), constant_values=-jnp.inf)
    if return_scores:
        return out, top_s
    return out


def merge_topk(ids, sims, q, growing, growing_gids, topk, alive=None,
               return_scores: bool = False):
    """Merge per-segment candidates (n_seg, B, k_seg) with the growing tail
    into (B, topk) global ids. ``alive=None`` is the static merge
    (``_pipeline_impl``); a mask selects the live merge (``_live_chunk``)."""
    ids2, sims2 = flatten_candidates(ids, sims, alive=alive)
    return merge_flat(
        ids2, sims2, q, growing, growing_gids, topk,
        live=alive is not None, return_scores=return_scores,
    )
