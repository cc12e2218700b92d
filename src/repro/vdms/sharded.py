"""Sharded multi-device segment serving: sealed segments across a mesh.

``ShardedVDMS`` takes the engine's segment-native layout to its logical
scaling conclusion: sealed segments are *embarrassingly parallel* — each is
searched independently and only the per-segment top-k lists meet at the
merge — so a corpus that outgrows one device is placed across a 1-D
``("shard",)`` mesh (``distributed.make_shard_mesh``) via the existing
:class:`~repro.distributed.sharding.ShardingRules` machinery and searched
under ``shard_map`` with a two-level on-device top-k merge tree:

* **leaf (per shard)**: the family's fused ``shard_search`` hook (or its
  composed ``search`` fallback) scores the shard's local segment stack, then
  ``merge.partial_topk`` folds the per-segment candidates — alive-mask
  gating included — into one ``(B, k_shard)`` partial list;
* **root (replicated)**: the partial lists concatenate in shard order and
  ``merge.merge_flat`` finishes the reduction together with the replicated
  growing tail — literally the same arithmetic the single-device engine
  runs (``repro.vdms.merge``), which is why ``n_shards=1`` results are
  bit-identical and any shard count returns the same (gid, score) sets.

Placement (``distributed.segment_placement``) is contiguous blocks with dead
tail padding, so concatenating shard-local stacks in shard order reproduces
the unsharded segment order and every tie-break (lowest flat ``(segment,
slot)`` index) lands exactly where the unsharded merge puts it.

Dispatch modes: ``shard_map`` (real devices, or host-emulated via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), ``vmap`` (shard
axis batched on one device — same math, no parallelism; only when asked for
by name, as the test suite does when the mesh is bigger than the machine),
and a direct single-device path for ``n_shards=1``. More shards than devices
under ``shard_map`` (the ``auto`` choice) raise instead of emulating. See
``docs/SHARDING.md`` for the full contract.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.sharding import ShardingRules, make_shard_mesh, segment_placement
from .datasets import VectorDataset, recall_at_k
from .engine import (
    VDMSInstance,
    _bucket,
    analytic_chunk_seconds,
    get_search_pipeline,
)
from .merge import merge_flat, merge_topk, partial_topk
from .registry import get_family

# sharded additions to the analytic cost model (same convention as the
# engine's calibration constants: documented, deterministic)
_SHARD_MERGE_OVERHEAD = 8.0e-5  # one partial list folded at the root, per chunk (s)
_SHARD_DISPATCH_OVERHEAD = 1.5e-4  # collective dispatch per chunk, n_shards > 1 (s)

#: CI gate: minimum analytic QPS scaling from 1 to 4 shards at bench scale.
MIN_QPS_SCALING_1_TO_4 = 1.5

#: The invariants the sharded engine guarantees (and the bench/CI gate).
#: ``docs/SHARDING.md`` embeds :func:`shard_invariants_table` between
#: ``shard-invariants`` markers; a doc-sync test keeps them in lockstep.
SHARD_INVARIANTS: Tuple[Tuple[str, str, str], ...] = (
    (
        "placement",
        "contiguous blocks",
        "segment `z` lives on shard `z // ceil(n_seg / n_shards)`; the stack "
        "pads with dead segments (gids all `-1`) so every shard holds the "
        "same count",
    ),
    (
        "result sets",
        "shard-count-invariant",
        "the per-query `(gid, score)` set is identical for every `n_shards` "
        "(gated by `bench_sharded --check-invariants`)",
    ),
    (
        "single shard",
        "bit-identical",
        "`n_shards=1` returns byte-identical ids to the unsharded engine — "
        "same kernels, same `merge_topk`",
    ),
    (
        "tie-break",
        "lowest flat index",
        "equal scores resolve to the lowest `(segment, slot)` flat position "
        "at every merge level (`lax.top_k` order)",
    ),
    (
        "growing tail",
        "replicated",
        "the tail is brute-forced once at the merge root, after all sealed "
        "candidates — never sharded, never stale across shards",
    ),
    (
        "recall",
        "oracle-exact accounting",
        "bench recall is scored against the brute-force oracle and must "
        "match the unsharded engine exactly",
    ),
    (
        "QPS scaling",
        f">= {MIN_QPS_SCALING_1_TO_4}x at 4 shards",
        "1→4 shard throughput scaling gated in CI at n_base >= 1M "
        "(analytic mode; wall mode reports alongside)",
    ),
)


def shard_invariants_table() -> str:
    """Markdown table of :data:`SHARD_INVARIANTS` (doc-synced)."""
    rows = ["| Invariant | Rule | Detail |", "|---|---|---|"]
    for name, rule, detail in SHARD_INVARIANTS:
        rows.append(f"| {name} | {rule} | {detail} |")
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# the jitted sharded pipeline
# ---------------------------------------------------------------------------
def _shard_stage(kind: str, statics: Tuple, k_seg: int, use_hook: bool) -> Callable:
    """Per-shard candidate stage: the family's fused ``shard_search`` hook
    when registered (and the pipeline mode is fused), else its composed
    ``search`` — both return per-segment (n_seg_local, B, k_seg) GLOBAL ids
    and sims with identical masking semantics."""
    family = get_family(kind)
    st = dict(statics)
    if use_hook and family.shard_search is not None:
        return lambda q, arrays: family.shard_search(q, arrays, k_seg=k_seg, **st)
    return lambda q, arrays: family.search(q, arrays, k_seg=k_seg, **st)


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "dispatch", "kind", "statics", "k_seg", "topk",
        "n_shards", "use_hook", "live", "return_scores",
    ),
)
def _sharded_chunk(
    q, arrays, alive, growing, growing_gids, *,
    mesh, dispatch, kind, statics, k_seg, topk,
    n_shards, use_hook, live, return_scores,
):
    """One query chunk through the two-level merge tree.

    ``arrays`` carry a flat leading segment axis of ``n_shards * per``;
    ``alive`` is the global mask (+ sentinel) for the live flavor or a dummy
    when ``live=False``; ``growing`` / ``growing_gids`` are the replicated
    tail, merged once at the root.
    """
    stage = _shard_stage(kind, statics, k_seg, use_hook)
    alive_arg = alive if live else None

    if n_shards == 1:
        # direct path: exactly the single-device engine pipeline
        ids, sims = stage(q, arrays)
        return merge_topk(
            q=q, ids=ids, sims=sims, growing=growing, growing_gids=growing_gids,
            topk=topk, alive=alive_arg, return_scores=return_scores,
        )

    n_seg_p = arrays["gids"].shape[0]
    per = n_seg_p // n_shards
    k_shard = min(topk, per * k_seg)
    family = get_family(kind)
    shared = set(family.shared_arrays)

    def leaf(q_l, arrays_l, alive_l):
        ids, sims = stage(q_l, arrays_l)  # (per, B, k_seg)
        pid, psc = partial_topk(ids, sims, k_shard, alive=alive_l if live else None)
        return pid, psc

    if dispatch == "shard_map":
        specs_in = (
            P(),  # queries replicated
            {k: (P() if k in shared else P("shard")) for k in arrays},
            P(),  # alive mask replicated
        )
        def leaf_sm(q_l, arrays_l, alive_l):
            pid, psc = leaf(q_l, arrays_l, alive_l)
            return pid[None], psc[None]  # local leading shard axis of 1
        parts_i, parts_s = jax.shard_map(
            leaf_sm, mesh=mesh, in_specs=specs_in,
            out_specs=(P("shard"), P("shard")), check_vma=False,
        )(q, arrays, alive)
    else:  # "vmap": shard axis batched on one device — same math
        arrays_v = {
            k: (v if k in shared else v.reshape((n_shards, per) + v.shape[1:]))
            for k, v in arrays.items()
        }
        def leaf_v(arrays_l):
            full = {k: (arrays_v[k] if k in shared else arrays_l[k]) for k in arrays}
            return leaf(q, full, alive)
        parts_i, parts_s = jax.vmap(leaf_v)(
            {k: v for k, v in arrays_v.items() if k not in shared}
        )

    # root merge: concatenate partial lists in shard order (shard-major flat
    # position keeps the global tie-break order) and finish with the shared
    # merge arithmetic + the replicated growing tail
    b = parts_i.shape[1]
    ids2 = jnp.moveaxis(parts_i, 0, 1).reshape(b, n_shards * k_shard)
    sims2 = jnp.moveaxis(parts_s, 0, 1).reshape(b, n_shards * k_shard)
    return merge_flat(
        ids2, sims2, q, growing, growing_gids, topk,
        live=live, return_scores=return_scores,
    )


# ---------------------------------------------------------------------------
# the sharded serving instance
# ---------------------------------------------------------------------------
class ShardedVDMS:
    """Sealed segments placed across a device mesh, serving batched
    multi-stream queries through the two-level top-k merge tree.

    Build it three ways:

    * ``ShardedVDMS(dataset, config, n_shards=4)`` — bulk build (via
      :class:`VDMSInstance`) then place;
    * ``ShardedVDMS.from_instance(inst, n_shards=4)`` — place an existing
      static instance (shares its arrays; nothing is rebuilt);
    * ``ShardedVDMS.from_live(live, n_shards=4)`` — snapshot a streaming
      :class:`LiveVDMS` (sealed bundle + tombstone mask + visible tail) for
      sharded serving with the live merge semantics.
    """

    def __init__(
        self,
        dataset: Optional[VectorDataset] = None,
        config: Optional[Dict[str, Any]] = None,
        *,
        n_shards: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        dispatch: str = "auto",
        seed: int = 0,
        pipeline: Optional[str] = None,
        _state: Optional[Dict[str, Any]] = None,
    ):
        if _state is None:
            if dataset is None or config is None:
                raise ValueError("ShardedVDMS needs (dataset, config) or a _state")
            inst = VDMSInstance(dataset, config, seed=seed)
            _state = _state_from_instance(inst)
        self.dataset = _state.get("dataset")
        self.config = _state.get("config")
        self.kind = _state["kind"]
        self.static = dict(_state["static"])
        self.k_seg = int(_state["k_seg"])
        self.batch = int(_state["batch"])
        self.dim = int(_state["dim"])
        self.seg_size = int(_state["seg_size"])
        self.n_sealed = int(_state["n_sealed"])
        self.build_time = float(_state.get("build_time", 0.0))
        self.live = _state["alive"] is not None
        self.pipeline = pipeline  # None -> follow the engine's global mode
        if self.n_sealed <= 0:
            raise ValueError("nothing sealed to shard: the corpus has no sealed segments")

        # --- mesh + dispatch resolution --------------------------------
        if mesh is not None:
            if tuple(mesh.axis_names) != ("shard",):
                raise ValueError(f"expected a ('shard',) mesh, got {mesh.axis_names}")
            self.n_shards = int(mesh.devices.size) if n_shards is None else int(n_shards)
            if self.n_shards != mesh.devices.size:
                raise ValueError("n_shards must match the mesh size when both are given")
        else:
            self.n_shards = 1 if n_shards is None else int(n_shards)
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if dispatch == "auto":
            dispatch = "direct" if self.n_shards == 1 else "shard_map"
        if dispatch not in ("direct", "shard_map", "vmap"):
            raise ValueError(f"unknown dispatch {dispatch!r}")
        if dispatch == "direct" and self.n_shards != 1:
            raise ValueError("dispatch='direct' requires n_shards=1")
        if dispatch == "shard_map" and mesh is None and self.n_shards > len(jax.devices()):
            raise ValueError(
                f"{self.n_shards} shards but {len(jax.devices())} devices: "
                "ask for dispatch='vmap' to emulate the mesh on one device"
            )
        self.dispatch = dispatch
        self.mesh = mesh
        if dispatch == "shard_map" and self.mesh is None:
            self.mesh = make_shard_mesh(self.n_shards)
        self.rules = ShardingRules(self.mesh) if self.mesh is not None else None

        # --- placement: contiguous blocks, dead tail padding ------------
        self.per_shard, self.n_pad, self.shard_of = segment_placement(
            self.n_sealed, self.n_shards
        )
        family = get_family(self.kind)
        self.shared_arrays = tuple(family.shared_arrays)
        arrays = dict(_state["arrays"])
        if self.n_pad:
            arrays = {
                k: (v if k in self.shared_arrays else _pad_segments(k, v, self.n_pad))
                for k, v in arrays.items()
            }
        if self.rules is not None:
            # place through the ShardingRules machinery: the segment dim is
            # the logical "segments" axis, everything else replicated
            arrays = {
                k: jax.device_put(v, self._named_sharding(k, v))
                for k, v in arrays.items()
            }
        self.arrays = arrays
        self.growing = _replicate(self.mesh, _state["growing"])
        self.growing_gids = _replicate(self.mesh, _state["growing_gids"])
        alive = _state["alive"]
        if alive is None:  # static merge: the jit still wants an operand
            alive = jnp.zeros((1,), bool)
        self.alive = _replicate(self.mesh, alive)
        self.coverage = float(_state.get("coverage", 1.0))

        # serving instrumentation (the metrics ledger attaches here, same
        # contract as LiveVDMS.search_hooks)
        self.queries_served = 0
        self.last_latencies: np.ndarray = np.empty(0, np.float64)
        self.search_hooks: List[Callable[[int, np.ndarray, float], None]] = []
        self._warmed: set = set()
        self.compile_s = 0.0

    # ------------------------------------------------------------------
    @classmethod
    def from_instance(cls, inst: VDMSInstance, **kw) -> "ShardedVDMS":
        return cls(_state=_state_from_instance(inst), **kw)

    @classmethod
    def from_live(cls, live, **kw) -> "ShardedVDMS":
        """Snapshot a :class:`LiveVDMS` for sharded serving: sealed bundle,
        tombstone/quarantine-masked alive mask, and the bucketed visible
        tail — the exact operands ``_live_chunk`` would see, so a 1-shard
        snapshot serves bit-identical results to ``live.search``."""
        return cls(_state=_state_from_live(live), **kw)

    # ------------------------------------------------------------------
    def _named_sharding(self, name: str, v) -> NamedSharding:
        axes: Tuple[Optional[str], ...]
        if name in self.shared_arrays:
            axes = (None,) * v.ndim
        else:
            axes = ("segments",) + (None,) * (v.ndim - 1)
        return self.rules.sharding(axes, tuple(v.shape))

    def _use_hook(self) -> bool:
        mode = self.pipeline or get_search_pipeline()
        return mode == "fused"

    def _dispatch_chunk(self, q, topk: int, return_scores: bool = False):
        return _sharded_chunk(
            q, self.arrays, self.alive, self.growing, self.growing_gids,
            mesh=self.mesh, dispatch=self.dispatch, kind=self.kind,
            statics=tuple(sorted(self.static.items())), k_seg=self.k_seg,
            topk=topk, n_shards=self.n_shards, use_hook=self._use_hook(),
            live=self.live, return_scores=return_scores,
        )

    # ------------------------------------------------------------------
    def search(
        self, queries: np.ndarray, topk: int, mode: str = "analytic",
        return_scores: bool = False,
    ):
        """Search the sharded state. Returns ``(ids (Q, topk), elapsed)`` —
        or ``(ids, scores, elapsed)`` with ``return_scores=True``. Analytic
        mode charges the deterministic sharded cost model (max-over-shards
        leaf work + root merge overhead); wall mode times the dispatch with
        compile kept apart, mirroring ``LiveVDMS.search``."""
        queries = np.asarray(queries, np.float32)
        nq = queries.shape[0]
        b = min(self.batch, max(nq, 1))
        n_chunks = (nq + b - 1) // b
        out = np.empty((n_chunks * b, topk), np.int32)
        scores = np.empty((n_chunks * b, topk), np.float32) if return_scores else None
        chunk_s = np.zeros(n_chunks, np.float64)
        shape_key = (b, topk, self._use_hook(), return_scores)
        for c in range(n_chunks):
            lo = c * b
            chunk = queries[lo : lo + b]
            if chunk.shape[0] < b:  # pad the final chunk by wrapping
                chunk = np.concatenate([chunk, queries[: b - chunk.shape[0]]], axis=0)
            qj = jnp.asarray(chunk)
            if mode != "analytic" and shape_key not in self._warmed:
                t0 = time.perf_counter()
                jax.block_until_ready(self._dispatch_chunk(qj, topk, return_scores))
                self.compile_s += time.perf_counter() - t0
                self._warmed.add(shape_key)
            t0 = time.perf_counter()
            r = jax.block_until_ready(self._dispatch_chunk(qj, topk, return_scores))
            chunk_s[c] = time.perf_counter() - t0
            if return_scores:
                out[lo : lo + b] = np.asarray(r[0])
                scores[lo : lo + b] = np.asarray(r[1])
            else:
                out[lo : lo + b] = np.asarray(r)
        if mode == "analytic":
            chunk_s[:] = self._analytic_seconds_per_chunk(b)
        counts = np.minimum(b, nq - b * np.arange(n_chunks))
        elapsed = float(chunk_s.sum())
        lat = np.repeat(chunk_s / np.maximum(counts, 1), counts)
        self.last_latencies = lat
        self.queries_served += nq
        for hook in self.search_hooks:
            hook(nq, lat, elapsed)
        if return_scores:
            return out[:nq], scores[:nq], elapsed
        return out[:nq], elapsed

    def search_streams(
        self, streams: Sequence[np.ndarray], topk: int, mode: str = "analytic"
    ) -> Tuple[List[np.ndarray], float]:
        """Batched multi-stream dispatch: concatenate the per-stream query
        batches, run ONE sharded search over the union (amortizing dispatch
        and the merge tree across streams), split results back per stream."""
        streams = [np.asarray(s, np.float32).reshape(-1, self.dim) for s in streams]
        if not streams:
            return [], 0.0
        allq = np.concatenate(streams, axis=0)
        ids, elapsed = self.search(allq, topk, mode=mode)
        outs, lo = [], 0
        for s in streams:
            outs.append(ids[lo : lo + s.shape[0]])
            lo += s.shape[0]
        return outs, elapsed

    # --- analytic cost model ------------------------------------------
    def _analytic_seconds_per_chunk(self, batch: Optional[int] = None) -> float:
        """Deterministic per-chunk cost: shards run their leaves in
        parallel, so the leaf term charges the (padded) per-shard segment
        count — the critical shard — plus the root-merge terms that grow
        with the shard count. ``n_shards=1`` reduces exactly to the
        unsharded engine model."""
        base = analytic_chunk_seconds(
            self.kind,
            self.static,
            self.arrays,
            self.per_shard if self.n_shards > 1 else self.n_sealed,
            self.seg_size,
            int(self.growing.shape[0]),
            self.dim,
            self.batch if batch is None else batch,
        )
        if self.n_shards == 1:
            return base
        return base + self.n_shards * _SHARD_MERGE_OVERHEAD + _SHARD_DISPATCH_OVERHEAD

    def memory_gib(self) -> float:
        b = sum(int(v.size) * v.dtype.itemsize for v in self.arrays.values())
        b += int(self.growing.size) * self.growing.dtype.itemsize
        return b / (1024.0**3)

    def measure(
        self, topk: Optional[int] = None, repeats: int = 3, mode: str = "analytic"
    ) -> Dict[str, float]:
        """Objectives at the current shard count (dataset-built instances):
        QPS / recall@K / memory, same contract as ``VDMSInstance.measure``."""
        if self.dataset is None:
            raise ValueError("measure() needs a dataset-built ShardedVDMS")
        ds = self.dataset
        topk = topk or ds.k
        t0 = time.perf_counter()
        ids, _ = self.search(ds.queries, topk, mode="analytic")
        compile_time = time.perf_counter() - t0
        recall = recall_at_k(ids[:, : ds.k], ds.ground_truth)
        nq = ds.queries.shape[0]
        if mode == "analytic":
            b = min(self.batch, nq)
            n_chunks = (nq + b - 1) // b
            elapsed = self._analytic_seconds_per_chunk(b) * n_chunks
        else:
            times = []
            for _ in range(repeats):
                _, e = self.search(ds.queries, topk, mode="wall")
                times.append(e)
            elapsed = min(times)
        return {
            "speed": float(nq / max(elapsed, 1e-9)),
            "recall": float(recall),
            "mem_gib": float(self.memory_gib()),
            "build_time": float(self.build_time),
            "compile_time": float(compile_time),
            "n_shards": float(self.n_shards),
        }

    # --- serving telemetry --------------------------------------------
    def shard_segments(self) -> np.ndarray:
        """Real (non-padding) sealed segments per shard."""
        counts = np.zeros(self.n_shards, np.int64)
        np.add.at(counts, self.shard_of, 1)
        return counts

    def shard_coverage(self) -> np.ndarray:
        """Alive fraction of each shard's sealed vectors (1.0 for shards of
        a static instance; padding-only shards serve an empty slice and
        report 0 coverage honestly)."""
        gids = np.asarray(self.arrays["gids"]).reshape(self.n_shards, -1)
        alive = np.asarray(self.alive)
        cov = np.zeros(self.n_shards, np.float64)
        for s in range(self.n_shards):
            g = gids[s]
            g = g[g >= 0]
            if g.size == 0:
                cov[s] = 0.0
            elif self.live:
                cov[s] = float(alive[g].mean())
            else:
                cov[s] = 1.0
        return cov

    def stats(self) -> Dict[str, float]:
        """JSON-safe serving snapshot (the sharded metrics ledger input)."""
        cov = self.shard_coverage()
        segs = self.shard_segments()
        populated = segs > 0
        return {
            "n_shards": int(self.n_shards),
            "n_sealed": int(self.n_sealed),
            "per_shard": int(self.per_shard),
            "n_pad_segments": int(self.n_pad),
            "shard_skew": float(segs.max() / max(segs[populated].mean(), 1e-9))
            if populated.any()
            else 0.0,
            "min_shard_coverage": float(cov[populated].min()) if populated.any() else 0.0,
            "mean_shard_coverage": float(cov[populated].mean()) if populated.any() else 0.0,
            "growing_size": int(self.growing.shape[0]),
            "coverage": float(self.coverage),
            "queries_served": int(self.queries_served),
            "mem_gib": float(self.memory_gib()),
            "dispatch": self.dispatch,
        }


# ---------------------------------------------------------------------------
# state snapshots
# ---------------------------------------------------------------------------
def _pad_segments(name: str, v, n_pad: int):
    """Append dead padding segments: id-like arrays pad with -1 (gids map
    them to the dead slot / -inf), everything else with zeros."""
    pad_shape = (n_pad,) + tuple(v.shape[1:])
    fill = -1 if name in ("gids", "members") else 0
    pad = jnp.full(pad_shape, fill, v.dtype)
    return jnp.concatenate([v, pad], axis=0)


def _replicate(mesh: Optional[Mesh], v):
    v = jnp.asarray(v)
    if mesh is None:
        return v
    return jax.device_put(v, NamedSharding(mesh, P(*([None] * v.ndim))))


def _state_from_instance(inst: VDMSInstance) -> Dict[str, Any]:
    return {
        "dataset": inst.dataset,
        "config": dict(inst.config),
        "kind": inst.bundle.kind,
        "static": dict(inst.bundle.static),
        "arrays": dict(inst.bundle.arrays),
        "growing": inst.growing,
        "growing_gids": inst.growing_gids,
        "alive": None,  # static merge semantics
        "k_seg": inst.k_seg,
        "batch": inst.batch,
        "dim": inst.dataset.dim,
        "seg_size": inst.plan.seg_size,
        "n_sealed": inst.plan.n_sealed,
        "build_time": inst.build_time,
    }


def _state_from_live(live) -> Dict[str, Any]:
    if live.bundle is None:
        raise ValueError("nothing sealed to shard: LiveVDMS has no sealed segments")
    vis = live._visible_tail()
    nb = _bucket(vis.size)
    growing = np.zeros((nb, live.dim), np.float32)
    growing[: vis.size] = live.store[vis]
    ggids = np.full(nb, -1, np.int32)
    ggids[: vis.size] = vis
    alive_arr = live.alive
    coverage = 1.0
    if live.quarantined:
        # same degraded-mode masking live.search applies: quarantined
        # segments drop out of the merge, coverage reports the visible share
        alive_arr = live.alive.copy()
        sealed_alive = int((live.alive[: live.capacity] & (live.gid_seg >= 0)).sum())
        lost = 0
        for z in live.quarantined:
            row = live.seg_gids[z]
            valid = row[row >= 0]
            lost += int(live.alive[valid].sum())
            alive_arr[valid] = False
        total = sealed_alive + int(vis.size)
        coverage = float((total - lost) / max(total, 1))
    return {
        "dataset": None,
        "config": dict(live.config),
        "kind": live.bundle.kind,
        "static": dict(live.bundle.static),
        "arrays": dict(live.bundle.arrays),
        "growing": jnp.asarray(growing),
        "growing_gids": jnp.asarray(ggids),
        "alive": jnp.asarray(alive_arr),
        "k_seg": live.k_seg,
        "batch": live.batch,
        "dim": live.dim,
        "seg_size": live.seg_size,
        "n_sealed": live.n_sealed,
        "build_time": live.build_time,
        "coverage": coverage,
    }
