"""ANNS index implementations (Milvus Table I): FLAT, IVF_FLAT, IVF_SQ8,
IVF_PQ, HNSW, SCANN, AUTOINDEX — all with jittable search paths.

Every family here is declared to the :mod:`~repro.vdms.registry` as one
:class:`~repro.vdms.registry.IndexFamily` spec (tunable params, build/search
callables, frozen-calibration keys, analytic cost hooks) at the bottom of
this module; ``build_index`` / ``search_index`` and the bundle lifecycle ops
dispatch through that registry, so an externally-registered family (see
``repro.vdms.ivf_pqr``) flows through every path below without edits.

Conventions
-----------
* Angular metric: all vectors L2-normalized, similarity = inner product
  (higher is better); returned "sims" follow that convention.
* Sealed segments are stacked into (n_seg, S, d); each segment has its own
  index; searches run per segment via ``lax.map`` and the engine merges.
* Every search returns (global_ids (Q, n_seg * k_seg), sims) with -1/-inf on
  padded slots.
* Build runs on host (numpy + jitted JAX pieces) and is timed by the engine —
  index build cost is part of the tuning cost the paper measures.
* Arrays named in a family's ``shared_arrays`` hold calibration state shared
  across segments (quantizer scales, PQ codebooks), not per-segment stacks.
  Incremental builds freeze these after the first sealed segment — like real
  systems that train quantizers once and reuse them for every later segment —
  so per-segment bundles stay concatenable.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.space import Param
from ..kernels import ops
from .fused import (
    fused_search_ivf_pq,
    fused_search_ivf_sq8,
    shard_search_ivf_pq,
    shard_search_ivf_sq8,
)
from .kmeans import kmeans, kmeans_l2
from .registry import REGISTRY, IndexFamily, get_family


def __getattr__(name: str):
    if name == "INDEX_TYPES":
        # derived, never a second source of truth: always == registry keys
        return tuple(REGISTRY.names())
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class IndexBundle:
    kind: str
    arrays: Dict[str, jnp.ndarray]  # stacked over segments (leading dim n_seg)
    static: Dict[str, Any]  # static search params (k_seg etc. added by engine)

    def memory_bytes(self) -> int:
        return int(sum(np.prod(a.shape) * a.dtype.itemsize for a in self.arrays.values()))


# =========================================================================
# helpers
# =========================================================================
def _storage(x: np.ndarray, bf16: bool) -> jnp.ndarray:
    return jnp.asarray(x, dtype=jnp.bfloat16 if bf16 else jnp.float32)


def _member_lists(assign: np.ndarray, nlist: int, cap: int) -> np.ndarray:
    """(nlist, cap) local-id lists, -1 padded; overflow beyond cap is dropped
    (mirrors real systems' bounded per-cluster scan). Fully vectorized: one
    stable argsort + a rank-within-cluster scatter, no per-cluster loop."""
    out = -np.ones((nlist, cap), dtype=np.int32)
    order = np.argsort(assign, kind="stable")
    sa = assign[order]
    starts = np.searchsorted(sa, np.arange(nlist), "left")
    pos = np.arange(sa.shape[0]) - starts[sa]  # rank within own cluster
    keep = pos < cap
    out[sa[keep], pos[keep]] = order[keep]
    return out


def _ivf_cap(seg_size: int, nlist: int, nprobe: int) -> int:
    cap = int(2.5 * seg_size / nlist) + 8
    if nprobe * cap > seg_size + 8 * nprobe:
        cap = max(8, seg_size // max(nprobe, 1) + 8)
    return cap


def _mask_pad(sims: jnp.ndarray, gids: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(gids >= 0, sims, -jnp.inf)


def _segment_topk(sims, cand, gids, k_seg: int):
    """One segment's best ``k_seg`` of its scored candidates (B, P): global
    ids and sims, each (B, k_seg). Empty candidates (-1) and padded slots
    (gid -1) turn -1/-inf but keep their width; a list shorter than
    ``k_seg`` pads with -1/-inf."""
    with obs.scope("segment_topk"):
        sims = jnp.where(cand >= 0, sims, -jnp.inf)
        k = min(k_seg, sims.shape[1])
        top_s, top_i = jax.lax.top_k(sims, k)
    with obs.scope("gid_map"):
        lids = jnp.take_along_axis(cand, top_i, axis=1)
        ids = jnp.where(lids >= 0, gids[jnp.maximum(lids, 0)], -1)
        top_s = jnp.where(ids >= 0, top_s, -jnp.inf)
    if k < k_seg:
        pad = k_seg - k
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        top_s = jnp.pad(top_s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    return ids, top_s


# =========================================================================
# FLAT — exhaustive
# =========================================================================
def build_flat(key, segs: np.ndarray, gids: np.ndarray, params, sys, frozen=None) -> IndexBundle:
    with obs.span("build.upload"):
        arrays = {"data": _storage(segs, sys["storage_bf16"]), "gids": jnp.asarray(gids)}
    return IndexBundle(kind="FLAT", arrays=arrays, static={})


def _search_flat(q: jnp.ndarray, arrays, *, k_seg: int):
    def per_seg(seg):
        data, gids = seg
        sims = ops.batched_ip(q, data)  # (B, S)
        with obs.scope("segment_topk"):
            sims = _mask_pad(sims, gids[None, :])
            top_s, top_i = jax.lax.top_k(sims, k_seg)
        with obs.scope("gid_map"):
            return gids[top_i], top_s

    ids, sims = jax.lax.map(per_seg, (arrays["data"], arrays["gids"]))
    return ids, sims  # (n_seg, B, k_seg)


# =========================================================================
# IVF family
# =========================================================================
def _build_ivf_common(key, segs, params, kmeans_iters):
    """Every IVF family's clustering: per-segment spherical k-means and the
    member lists. Returns (nprobe, centroids (n_seg, nlist, d), members
    (n_seg, nlist, cap)) on the host."""
    n_seg, s, d = segs.shape
    nlist = int(min(max(params["nlist"], 4), max(s // 8, 4)))
    nprobe = int(min(params["nprobe"], nlist))
    keys = jax.random.split(key, n_seg)
    with obs.span("build.upload"):
        x = jax.block_until_ready(jnp.asarray(segs))
    with obs.span("build.kmeans"):
        cents, assigns = jax.vmap(lambda k, xs: kmeans(k, xs, nlist, kmeans_iters))(keys, x)
        cents, assigns = np.asarray(cents), np.asarray(assigns)
    with obs.span("build.member_lists"):
        cap = _ivf_cap(s, nlist, nprobe)
        members = np.stack([_member_lists(assigns[z], nlist, cap) for z in range(n_seg)])
    return nprobe, cents, members


def _sq8_encode(segs, frozen):
    """(scale (d,) float32, int8 codes): one scale per dimension shared by
    every segment, or the frozen one of an earlier build."""
    with obs.span("build.encode"):
        if frozen is None:
            scale = np.abs(segs).max(axis=(0, 1)) / 127.0 + 1e-12
        else:
            scale = np.asarray(frozen["scale"], np.float32)
        codes = np.clip(np.round(segs / scale), -127, 127).astype(np.int8)
    return scale.astype(np.float32), codes


def build_ivf_flat(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    nprobe, cents, members = _build_ivf_common(key, segs, params, sys["kmeans_iters"])
    with obs.span("build.upload"):
        arrays = {
            "data": _storage(segs, sys["storage_bf16"]),
            "gids": jnp.asarray(gids),
            "centroids": jnp.asarray(cents),
            "members": jnp.asarray(members),
        }
    return IndexBundle(kind="IVF_FLAT", arrays=arrays, static={"nprobe": nprobe})


def _gather_candidates(q, centroids, members, *, nprobe):
    """Probe top-nprobe clusters; return flattened candidate local ids (B, P)."""
    with obs.scope("probe"):
        csim = jnp.dot(q, centroids.T, preferred_element_type=jnp.float32)  # (B, nlist)
        _, probe = jax.lax.top_k(csim, nprobe)  # (B, nprobe)
    cand = members[probe]  # (B, nprobe, cap)
    return cand.reshape(q.shape[0], -1)  # (B, P)


def _search_ivf_flat(q, arrays, *, k_seg: int, nprobe: int):
    def per_seg(seg):
        data, gids, cents, members = seg
        cand = _gather_candidates(q, cents, members, nprobe=nprobe)  # (B, P)
        safe = jnp.maximum(cand, 0)
        vecs = data[safe]  # (B, P, d)
        sims = jnp.einsum("bpd,bd->bp", vecs.astype(jnp.float32), q)
        return _segment_topk(sims, cand, gids, k_seg)

    return jax.lax.map(
        per_seg,
        (arrays["data"], arrays["gids"], arrays["centroids"], arrays["members"]),
    )


def build_ivf_sq8(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    nprobe, cents, members = _build_ivf_common(key, segs, params, sys["kmeans_iters"])
    scale, codes = _sq8_encode(segs, frozen)
    with obs.span("build.upload"):
        arrays = {
            "codes": jnp.asarray(codes),
            "scale": jnp.asarray(scale),
            "gids": jnp.asarray(gids),
            "centroids": jnp.asarray(cents),
            "members": jnp.asarray(members),
        }
    return IndexBundle(kind="IVF_SQ8", arrays=arrays, static={"nprobe": nprobe})


def _search_ivf_sq8(q, arrays, *, k_seg: int, nprobe: int):
    scale = arrays["scale"]

    def per_seg(seg):
        codes, gids, cents, members = seg
        cand = _gather_candidates(q, cents, members, nprobe=nprobe)
        safe = jnp.maximum(cand, 0)
        vecs = codes[safe].astype(jnp.float32) * scale[None, None, :]
        sims = jnp.einsum("bpd,bd->bp", vecs, q)
        return _segment_topk(sims, cand, gids, k_seg)

    return jax.lax.map(
        per_seg,
        (arrays["codes"], arrays["gids"], arrays["centroids"], arrays["members"]),
    )


def build_ivf_pq(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    n_seg, s, d = segs.shape
    m = int(params["m"])
    while d % m != 0:  # snap to a divisor of d
        m -= 1
    nbits = int(params["nbits"])
    c = 2**nbits
    nprobe, cents, members = _build_ivf_common(key, segs, params, sys["kmeans_iters"])
    dsub = d // m
    if frozen is None:
        # shared codebooks across segments (trained on the pooled sample)
        pool = segs.reshape(-1, m, dsub)
        sample = pool[:: max(1, pool.shape[0] // 8192)]
        keys = jax.random.split(jax.random.fold_in(key, 7), m)
        with obs.span("build.kmeans"):
            cb, _ = jax.vmap(
                lambda kk, xs: kmeans_l2(kk, xs, c, sys["kmeans_iters"])
            )(keys, jnp.asarray(sample.transpose(1, 0, 2)))  # (m, c, dsub)
            cb = np.asarray(cb)
    else:
        cb = np.asarray(frozen["codebooks"], np.float32)
    # encode: nearest codeword per subspace
    with obs.span("build.encode"):
        codes = np.empty((n_seg, s, m), dtype=np.uint8)
        x = segs.reshape(n_seg * s, m, dsub)
        for j in range(m):
            d2 = (
                np.sum(x[:, j] ** 2, 1)[:, None]
                - 2.0 * x[:, j] @ cb[j].T
                + np.sum(cb[j] ** 2, 1)[None, :]
            )
            codes[..., j] = np.argmin(d2, axis=1).astype(np.uint8).reshape(n_seg, s)
    with obs.span("build.upload"):
        arrays = {
            "codes": jnp.asarray(codes),
            "codebooks": jnp.asarray(cb.astype(np.float32)),
            "gids": jnp.asarray(gids),
            "centroids": jnp.asarray(cents),
            "members": jnp.asarray(members),
        }
    return IndexBundle(kind="IVF_PQ", arrays=arrays, static={"nprobe": nprobe, "m": m, "c": c})


def _search_ivf_pq(q, arrays, *, k_seg: int, nprobe: int, m: int, c: int):
    b, d = q.shape
    dsub = d // m
    qs = q.reshape(b, m, dsub)
    # similarity LUT: higher is better (IP of query sub-vector with codeword)
    lut = jnp.einsum("bmd,mcd->bmc", qs, arrays["codebooks"])  # (B, m, c)

    def per_seg(seg):
        codes, gids, cents, members = seg
        cand = _gather_candidates(q, cents, members, nprobe=nprobe)  # (B, P)
        safe = jnp.maximum(cand, 0)
        ccodes = codes[safe].astype(jnp.int32)  # (B, P, m)
        g = jnp.take_along_axis(
            lut[:, None, :, :], ccodes[..., None], axis=3
        )  # (B, P, m, 1)
        sims = jnp.sum(g[..., 0], axis=-1)
        return _segment_topk(sims, cand, gids, k_seg)

    return jax.lax.map(
        per_seg, (arrays["codes"], arrays["gids"], arrays["centroids"], arrays["members"])
    )


# =========================================================================
# HNSW (NSW-style kNN graph + diversity pruning + shortcut links)
# =========================================================================
@partial(jax.jit, static_argnames=("m_links", "ef_construction", "row_chunk"))
def _build_graph(data: jnp.ndarray, m_links: int, ef_construction: int, row_chunk: int = 512):
    """Graph build: exact kNN candidates (chunked) + HNSW diversity heuristic."""
    s, d = data.shape
    efc = min(ef_construction, s - 1)

    def knn_rows(rows):
        sims = jnp.dot(data[rows], data.T, preferred_element_type=jnp.float32)
        sims = sims.at[jnp.arange(rows.shape[0]), rows].set(-jnp.inf)  # no self
        top_s, top_i = jax.lax.top_k(sims, efc)
        return top_i, top_s

    n_chunks = (s + row_chunk - 1) // row_chunk
    pad_s = n_chunks * row_chunk
    rows = jnp.arange(pad_s) % s
    cand_i, cand_s = jax.lax.map(
        knn_rows, rows.reshape(n_chunks, row_chunk)
    )
    cand_i = cand_i.reshape(pad_s, efc)[:s]
    cand_s = cand_s.reshape(pad_s, efc)[:s]

    # diversity pruning (per-node, vectorized over node chunks):
    # iteratively select the best remaining candidate; discard candidates that
    # are closer to the selected neighbor than to the node itself.
    def prune_chunk(args):
        ci, cs, rows = args  # (C, efc), (C, efc), (C,)
        alive = jnp.isfinite(cs)

        def step(carry, t):
            alive, sel = carry
            score = jnp.where(alive, cs, -jnp.inf)
            j = jnp.argmax(score, axis=1)  # (C,)
            ok = jnp.take_along_axis(alive, j[:, None], 1)[:, 0]
            pick = jnp.take_along_axis(ci, j[:, None], 1)[:, 0]  # (C,)
            pick = jnp.where(ok, pick, rows)  # degenerate: self-link
            sel = sel.at[:, t].set(pick)
            # drop candidates nearer to `pick` than to the node
            pv = data[pick]  # (C, d)
            cv = data[ci]  # (C, efc, d)
            sim_to_pick = jnp.einsum("ced,cd->ce", cv, pv)
            alive = alive & (sim_to_pick <= cs) & (
                jnp.arange(efc)[None, :] != j[:, None]
            )
            return (alive, sel), None

        sel0 = jnp.broadcast_to(rows[:, None], (rows.shape[0], m_links)).astype(jnp.int32)
        (alive, sel), _ = jax.lax.scan(step, (alive, sel0), jnp.arange(m_links))
        return sel

    sel = jax.lax.map(
        prune_chunk,
        (
            cand_i.reshape(n_chunks, row_chunk, efc)
            if s == pad_s
            else jnp.pad(cand_i, ((0, pad_s - s), (0, 0))).reshape(n_chunks, row_chunk, efc),
            jnp.pad(cand_s, ((0, pad_s - s), (0, 0)), constant_values=-jnp.inf).reshape(
                n_chunks, row_chunk, efc
            )
            if s != pad_s
            else cand_s.reshape(n_chunks, row_chunk, efc),
            rows.reshape(n_chunks, row_chunk),
        ),
    )
    graph = sel.reshape(pad_s, m_links)[:s]
    # small-world shortcut links in the last columns (keeps the graph connected)
    n_rand = max(1, m_links // 8)
    key = jax.random.PRNGKey(s * 7 + m_links)
    shortcuts = jax.random.randint(key, (s, n_rand), 0, s, dtype=jnp.int32)
    graph = graph.at[:, -n_rand:].set(shortcuts)
    return graph


def build_hnsw(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    n_seg, s, d = segs.shape
    m_links = int(max(4, min(params["M"], 64)))
    efc = int(min(max(params["efConstruction"], 16), s - 1))
    graphs = jnp.stack(
        [_build_graph(jnp.asarray(segs[z]), m_links, efc) for z in range(n_seg)]
    )
    ef = int(min(max(params["ef"], 8), s))
    return IndexBundle(
        kind="HNSW",
        arrays={
            "data": _storage(segs, sys["storage_bf16"]),
            "gids": jnp.asarray(gids),
            "graph": graphs,
        },
        static={"ef": ef, "m_links": m_links},
    )


def _search_hnsw(q, arrays, *, k_seg: int, ef: int, m_links: int):
    b, d = q.shape

    def per_seg(seg):
        data, gids, graph = seg
        s = data.shape[0]
        dataf = data.astype(jnp.float32)
        # entry points: strided samples across the segment
        n_entry = min(4, ef)
        entries = (jnp.arange(n_entry) * (s // max(n_entry, 1))).astype(jnp.int32)
        beam_ids = jnp.broadcast_to(entries, (b, n_entry))
        beam_sims = jnp.einsum("bed,bd->be", dataf[beam_ids], q)
        pad = ef - n_entry
        beam_ids = jnp.pad(beam_ids, ((0, 0), (0, pad)), constant_values=0)
        beam_sims = jnp.pad(beam_sims, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        expanded = jnp.zeros((b, ef), dtype=bool)
        visited = jnp.zeros((b, s), dtype=bool)
        visited = visited.at[jnp.arange(b)[:, None], beam_ids].set(True)

        def step(carry, _):
            beam_ids, beam_sims, expanded, visited = carry
            score = jnp.where(expanded | ~jnp.isfinite(beam_sims), -jnp.inf, beam_sims)
            j = jnp.argmax(score, axis=1)  # (b,)
            has = jnp.isfinite(jnp.take_along_axis(score, j[:, None], 1)[:, 0])
            expanded = expanded.at[jnp.arange(b), j].set(True)
            node = jnp.take_along_axis(beam_ids, j[:, None], 1)[:, 0]  # (b,)
            nbrs = graph[node]  # (b, M)
            seen = jnp.take_along_axis(visited, nbrs, axis=1)  # (b, M)
            visited = visited.at[jnp.arange(b)[:, None], nbrs].set(True)
            nsims = jnp.einsum("bmd,bd->bm", dataf[nbrs], q)
            nsims = jnp.where(seen | ~has[:, None], -jnp.inf, nsims)
            all_ids = jnp.concatenate([beam_ids, nbrs], axis=1)
            all_sims = jnp.concatenate([beam_sims, nsims], axis=1)
            all_exp = jnp.concatenate([expanded, jnp.zeros_like(seen)], axis=1)
            top_s, top_i = jax.lax.top_k(all_sims, ef)
            beam_ids = jnp.take_along_axis(all_ids, top_i, axis=1)
            expanded = jnp.take_along_axis(all_exp, top_i, axis=1)
            return (beam_ids, top_s, expanded, visited), None

        (beam_ids, beam_sims, _, _), _ = jax.lax.scan(
            step, (beam_ids, beam_sims, expanded, visited), None, length=ef
        )
        k = min(k_seg, ef)
        top_s, top_i = jax.lax.top_k(beam_sims, k)
        lids = jnp.take_along_axis(beam_ids, top_i, axis=1)
        ids = jnp.where(jnp.isfinite(top_s), gids[lids], -1)
        top_s = jnp.where(ids >= 0, top_s, -jnp.inf)
        if k < k_seg:
            padk = k_seg - k
            ids = jnp.pad(ids, ((0, 0), (0, padk)), constant_values=-1)
            top_s = jnp.pad(top_s, ((0, 0), (0, padk)), constant_values=-jnp.inf)
        return ids, top_s

    return jax.lax.map(per_seg, (arrays["data"], arrays["gids"], arrays["graph"]))


# =========================================================================
# SCANN — IVF + int8 score-aware quantized scan + exact re-ranking
# =========================================================================
def build_scann(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    nprobe, cents, members = _build_ivf_common(key, segs, params, sys["kmeans_iters"])
    scale, codes = _sq8_encode(segs, frozen)
    reorder_k = int(max(params["reorder_k"], 1))
    with obs.span("build.upload"):
        arrays = {
            "codes": jnp.asarray(codes),
            "scale": jnp.asarray(scale),
            "data": _storage(segs, sys["storage_bf16"]),
            "gids": jnp.asarray(gids),
            "centroids": jnp.asarray(cents),
            "members": jnp.asarray(members),
        }
    return IndexBundle(kind="SCANN", arrays=arrays, static={"nprobe": nprobe, "reorder_k": reorder_k})


def _search_scann(q, arrays, *, k_seg: int, nprobe: int, reorder_k: int):
    scale = arrays["scale"]

    def per_seg(seg):
        codes, data, gids, cents, members = seg
        cand = _gather_candidates(q, cents, members, nprobe=nprobe)
        safe = jnp.maximum(cand, 0)
        approx = jnp.einsum(
            "bpd,bd->bp", codes[safe].astype(jnp.float32) * scale[None, None, :], q
        )
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


# =========================================================================
# AUTOINDEX — delegated IVF_FLAT build with derived parameters
# =========================================================================
def build_autoindex(key, segs, gids, params, sys, frozen=None) -> IndexBundle:
    s = segs.shape[1]
    auto = {"nlist": max(4, int(np.sqrt(s) * 2)), "nprobe": 16}
    return build_ivf_flat(key, segs, gids, auto, sys)


# =========================================================================
# analytic cost hooks (the engine's deterministic search/build model asks
# each family for its FLOP count; the shared rate/overhead arithmetic stays
# in engine.py — identical numbers to the historical per-kind if-chains)
# =========================================================================
def _chunk_cost_flat(st, arrays, n_sealed, seg_size, dim):
    return n_sealed * seg_size * dim * 2, 0


def _chunk_cost_ivf(bytes_scale: float):
    def cost(st, arrays, n_sealed, seg_size, dim):
        nlist = arrays["centroids"].shape[1]
        cap = arrays["members"].shape[2]
        return n_sealed * (nlist * dim + st["nprobe"] * cap * dim * bytes_scale) * 2, 0

    return cost


def _chunk_cost_ivf_pq(st, arrays, n_sealed, seg_size, dim):
    nlist = arrays["centroids"].shape[1]
    cap = arrays["members"].shape[2]
    flops = n_sealed * (
        nlist * dim * 2 + st["m"] * st["c"] * (dim // st["m"]) * 2 + st["nprobe"] * cap * st["m"]
    )
    return flops, 0


def _chunk_cost_hnsw(st, arrays, n_sealed, seg_size, dim):
    return n_sealed * st["ef"] * st["m_links"] * dim * 2, st["ef"]


def _chunk_cost_scann(st, arrays, n_sealed, seg_size, dim):
    nlist = arrays["centroids"].shape[1]
    cap = arrays["members"].shape[2]
    flops = n_sealed * (nlist * dim * 2 + st["nprobe"] * cap * dim + st["reorder_k"] * dim * 2)
    return flops, 0


def _build_cost_ivf_common(config, seg_size, dim):
    it = int(config.get("kmeans_iters", 8))
    nlist = int(config.get("nlist", max(4, int(np.sqrt(seg_size) * 2))))
    nlist = int(min(max(nlist, 4), max(seg_size // 8, 4)))
    return it * nlist * seg_size * dim * 2


def _build_cost_ivf_flat(config, seg_size, dim, first_build):
    return _build_cost_ivf_common(config, seg_size, dim)


def _build_cost_sq(config, seg_size, dim, first_build):
    return _build_cost_ivf_common(config, seg_size, dim) + seg_size * dim * 2


def _build_cost_ivf_pq(config, seg_size, dim, first_build):
    flops = _build_cost_ivf_common(config, seg_size, dim)
    it = int(config.get("kmeans_iters", 8))
    m = int(config.get("m", 8))
    while dim % m != 0:
        m -= 1
    c = 2 ** int(config.get("nbits", 8))
    dsub = dim // m
    flops += seg_size * m * c * dsub * 2  # encode
    if first_build:
        flops += it * m * c * min(seg_size, 8192) * dsub * 2  # codebook training
    return flops


def _build_cost_hnsw(config, seg_size, dim, first_build):
    efc = int(min(max(int(config.get("efConstruction", 128)), 16), max(seg_size - 1, 1)))
    m_links = int(max(4, min(int(config.get("M", 16)), 64)))
    return seg_size * seg_size * dim * 2 + seg_size * m_links * efc * dim


# =========================================================================
# registry dispatch — the ONLY way index builds/searches are reached
# =========================================================================
def build_index(
    key, segs, gids, index_type: str, params: Dict, sys: Dict, frozen: Dict | None = None
) -> IndexBundle:
    """Build per-segment indexes for the stacked segments ``(n_seg, S, d)``.

    Dispatches to the registered :class:`~repro.vdms.registry.IndexFamily`
    (unknown types raise with the sorted list of registered families).
    ``frozen`` (from :func:`frozen_state`) reuses a previous build's shared
    calibration (SQ8/SCANN scales, PQ codebooks) instead of re-training —
    the incremental-build path for live instances sealing one segment at a
    time. ``frozen=None`` reproduces the original from-scratch build exactly.
    """
    return get_family(index_type).build(key, segs, gids, params, sys, frozen=frozen)


def _family_of(bundle: IndexBundle) -> IndexFamily:
    return get_family(bundle.kind)


def frozen_state(bundle: IndexBundle) -> Dict[str, np.ndarray]:
    """Extract the segment-shared calibration arrays (the family's declared
    ``shared_arrays``) to freeze for incremental builds — empty for index
    families without shared state."""
    family = _family_of(bundle)
    if not family.supports_frozen:
        return {}
    return {
        k: np.asarray(bundle.arrays[k]) for k in family.shared_arrays if k in bundle.arrays
    }


def concat_bundles(a: IndexBundle, b: IndexBundle) -> IndexBundle:
    """Concatenate two bundles of the same kind/statics along the segment
    axis. Shared calibration arrays must be frozen-compatible and are taken
    from ``a`` (the incremental-build contract)."""
    if a.kind != b.kind or a.static != b.static:
        raise ValueError(
            f"cannot concat bundles: kind/static mismatch "
            f"({a.kind}/{a.static} vs {b.kind}/{b.static})"
        )
    shared = _family_of(a).shared_arrays
    arrays = {}
    for k, av in a.arrays.items():
        arrays[k] = av if k in shared else jnp.concatenate([av, b.arrays[k]], axis=0)
    return IndexBundle(kind=a.kind, arrays=arrays, static=dict(a.static))


def replace_segment(bundle: IndexBundle, z: int, seg_bundle: IndexBundle) -> IndexBundle:
    """Splice a freshly rebuilt single-segment bundle into position ``z`` —
    the compaction path (tombstoned vectors dropped, shapes preserved)."""
    if bundle.kind != seg_bundle.kind or bundle.static != seg_bundle.static:
        raise ValueError("cannot splice: kind/static mismatch")
    shared = _family_of(bundle).shared_arrays
    arrays = {}
    for k, av in bundle.arrays.items():
        if k in shared:
            arrays[k] = av
        else:
            arrays[k] = av.at[z].set(seg_bundle.arrays[k][0])
    return IndexBundle(kind=bundle.kind, arrays=arrays, static=dict(bundle.static))


def search_index(bundle: IndexBundle, q: jnp.ndarray, k_seg: int):
    """Returns (ids, sims) of shape (n_seg, B, k_seg) — merged by the engine.

    Dispatches on ``bundle.kind`` through the registry; the bundle's static
    params are passed to the family's search callable as keyword arguments.
    """
    return _family_of(bundle).search(q, bundle.arrays, k_seg=k_seg, **bundle.static)


# =========================================================================
# built-in family registrations (declaration order == historical space
# order, so the registry-derived SearchSpace stays bit-identical)
# =========================================================================
_NLIST = (16, 32, 64, 128, 256, 512)
_NPROBE = (1, 2, 4, 8, 16, 32, 64, 128)

REGISTRY.register(
    IndexFamily(
        name="FLAT",
        params=(),
        build=build_flat,
        search=_search_flat,
        chunk_cost=_chunk_cost_flat,
        description="exhaustive inner-product scan",
    )
)
REGISTRY.register(
    IndexFamily(
        name="IVF_FLAT",
        params=(
            Param("nlist", "grid", choices=_NLIST, default=128),
            Param("nprobe", "grid", choices=_NPROBE, default=8),
        ),
        build=build_ivf_flat,
        search=_search_ivf_flat,
        chunk_cost=_chunk_cost_ivf(1.0),
        build_cost=_build_cost_ivf_flat,
        description="inverted file over kmeans cells, raw vectors",
    )
)
REGISTRY.register(
    IndexFamily(
        name="IVF_SQ8",
        params=(
            Param("nlist", "grid", choices=_NLIST, default=128),
            Param("nprobe", "grid", choices=_NPROBE, default=8),
        ),
        build=build_ivf_sq8,
        search=_search_ivf_sq8,
        shared_arrays=("scale",),
        fused_search=fused_search_ivf_sq8,
        shard_search=shard_search_ivf_sq8,
        supports_frozen=True,
        chunk_cost=_chunk_cost_ivf(0.5),
        build_cost=_build_cost_sq,
        description="IVF over int8 scalar-quantized codes",
    )
)
REGISTRY.register(
    IndexFamily(
        name="IVF_PQ",
        params=(
            Param("nlist", "grid", choices=_NLIST, default=128),
            Param("m", "grid", choices=(4, 8, 16, 32), default=8),
            Param("nbits", "grid", choices=(4, 6, 8), default=8),
            Param("nprobe", "grid", choices=_NPROBE, default=8),
        ),
        build=build_ivf_pq,
        search=_search_ivf_pq,
        shared_arrays=("codebooks",),
        fused_search=fused_search_ivf_pq,
        shard_search=shard_search_ivf_pq,
        supports_frozen=True,
        chunk_cost=_chunk_cost_ivf_pq,
        build_cost=_build_cost_ivf_pq,
        description="IVF + product quantization (ADC lookup scan)",
    )
)
REGISTRY.register(
    IndexFamily(
        name="HNSW",
        params=(
            Param("M", "grid", choices=(8, 16, 32, 48), default=16),
            Param("efConstruction", "grid", choices=(32, 64, 128, 256), default=128),
            Param("ef", "grid", choices=(16, 32, 64, 128, 256), default=64),
        ),
        build=build_hnsw,
        search=_search_hnsw,
        chunk_cost=_chunk_cost_hnsw,
        build_cost=_build_cost_hnsw,
        description="NSW-style kNN graph with beam search",
    )
)
REGISTRY.register(
    IndexFamily(
        name="SCANN",
        params=(
            Param("nlist", "grid", choices=_NLIST, default=128),
            Param("nprobe", "grid", choices=_NPROBE, default=8),
            Param("reorder_k", "grid", choices=(32, 64, 128, 256, 512), default=64),
        ),
        build=build_scann,
        search=_search_scann,
        shared_arrays=("scale",),
        supports_frozen=True,
        chunk_cost=_chunk_cost_scann,
        build_cost=_build_cost_sq,
        description="IVF + int8 quantized scan + exact re-ranking",
    )
)
REGISTRY.register(
    IndexFamily(
        name="AUTOINDEX",
        params=(),
        build=build_autoindex,
        # builds_kind delegation: build_autoindex emits IVF_FLAT-kind bundles,
        # so bundle-keyed dispatch (search_index, analytic_chunk_seconds) uses
        # the IVF_FLAT family's hooks at runtime. search/chunk_cost here only
        # serve hand-constructed kind="AUTOINDEX" bundles (legacy contract);
        # build_cost IS live — the seal/build model dispatches on index_type.
        search=_search_ivf_flat,
        builds_kind="IVF_FLAT",
        chunk_cost=_chunk_cost_ivf(1.0),
        build_cost=_build_cost_ivf_flat,
        description="auto-derived IVF_FLAT (nlist ~ 2*sqrt(S), nprobe=16)",
    )
)
