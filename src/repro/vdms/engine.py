"""VDMS query engine: builds a configured instance and measures the paper's
objectives — search speed (QPS), recall@K, and memory footprint.

Two measurement modes:
* ``wall``     — real wall-clock over the jitted search pipeline (the paper's
                 workload replay). Compile/build time is tracked separately as
                 the index-building cost.
* ``analytic`` — deterministic cost model counting the distance evaluations the
                 pipeline performs (used by tests and fast benchmark configs;
                 recall is still measured by actually running the search).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .datasets import VectorDataset, recall_at_k
from .faults import HEALTH_CODE, BuildCrashFault, FaultInjector, TransientEngineFault
from .indexes import (
    IndexBundle,
    build_index,
    concat_bundles,
    frozen_state,
    replace_segment,
    search_index,
)
from .merge import merge_topk
from .registry import get_family
from .segments import live_seg_size, plan_segments, stack_sealed

# analytic-mode calibration constants (documented, deterministic)
_FLOPS_RATE = 5.0e9  # effective CPU distance-eval rate (FLOP/s)
_CHUNK_OVERHEAD = 2.0e-4  # dispatch overhead per query chunk (s)
_SEG_OVERHEAD = 5.0e-5  # per-segment merge overhead per chunk (s)
_STEP_OVERHEAD = 6.0e-6  # per sequential graph-walk step (s)


def analytic_chunk_seconds(
    kind: str,
    st: Dict[str, Any],
    arrays: Dict[str, Any],
    n_sealed: int,
    seg_size: int,
    growing_searched: int,
    dim: int,
    batch: int,
) -> float:
    """Deterministic cost (seconds) of one query chunk — the shared analytic
    model behind static ``VDMSInstance.measure`` and live replays. The
    per-family FLOP count comes from the registered family's ``chunk_cost``
    hook (families without one are charged an exhaustive-scan estimate); the
    rate/overhead arithmetic here is identical to the original model."""
    d, b = dim, batch
    family = get_family(kind)
    if family.chunk_cost is not None:
        flops, steps = family.chunk_cost(st, arrays, n_sealed, seg_size, d)
    else:  # conservative default: brute-force scan of every sealed vector
        flops, steps = n_sealed * seg_size * d * 2, 0
    flops += growing_searched * d * 2  # growing-tail brute force
    flops *= b  # per chunk of b queries
    return (
        flops / _FLOPS_RATE
        + _CHUNK_OVERHEAD
        + n_sealed * _SEG_OVERHEAD
        + steps * _STEP_OVERHEAD
    )


# analytic index-build cost model (deterministic, like the search model):
# counts the dominant FLOPs of one per-segment build so streaming objectives
# can charge ingest overhead without wall-clock noise
_BUILD_RATE = 2.0e10  # effective build FLOP/s (batched kmeans / graph matmuls)
_BUILD_OVERHEAD = 5.0e-3  # per-build dispatch + allocation overhead (s)


def analytic_build_seconds(
    index_type: str, config: Dict[str, Any], seg_size: int, dim: int, first_build: bool
) -> float:
    """Deterministic cost (seconds) of sealing + indexing one segment.

    ``first_build`` additionally charges the one-off shared-calibration
    training (PQ codebooks) that incremental builds freeze afterwards. The
    per-family term comes from the registered family's ``build_cost`` hook
    (families without one are charged only the storage pass).
    """
    s, d = int(seg_size), int(dim)
    family = get_family(index_type)
    flops = float(s * d)  # storage pass
    if family.build_cost is not None:
        flops += family.build_cost(config, s, d, bool(first_build))
    return flops / _BUILD_RATE + _BUILD_OVERHEAD


# ---------------------------------------------------------------------------
# search-pipeline mode (fused vs composed)
# ---------------------------------------------------------------------------
#: process-wide pipeline selector, read OUTSIDE jit and passed as a static
#: argument (a module global read inside a traced function would not retrace)
_SEARCH_PIPELINE = "fused"


def set_search_pipeline(mode: str) -> None:
    """Select the search hot path: ``"fused"`` (default) routes chunks through
    a family's registered ``fused_search`` hook when it has one, ``"composed"``
    always runs the per-family ``search`` + generic merge. Families without a
    hook run composed either way, so "fused" is always safe to leave on."""
    global _SEARCH_PIPELINE
    if mode not in ("fused", "composed"):
        raise ValueError(f"unknown search pipeline {mode!r}; use 'fused' or 'composed'")
    _SEARCH_PIPELINE = mode


def get_search_pipeline() -> str:
    return _SEARCH_PIPELINE


def _pipeline_impl(
    qc, arrays, growing, growing_gids, kind, statics, k_seg, topk, fused=False, clamp=False
):
    """qc: (n_chunks, B, d) queries; returns (n_chunks, B, topk) global ids.

    ``fused=True`` dispatches through the family's registered ``fused_search``
    hook (all chunks flattened into one batched call); families without a
    hook — and segment-less instances — fall back to the composed path below,
    whose results are unchanged by this routing. ``clamp=True`` (set only when
    the instance's sealed segments carry no -1 padding) lets the hook narrow
    per-segment width to ``min(k_seg, topk)``; see ``repro.vdms.fused``.
    """
    family = get_family(kind)
    if fused and family.fused_search is not None and arrays["gids"].shape[0] > 0:
        n_chunks, b, d = qc.shape
        out = family.fused_search(
            qc.reshape(n_chunks * b, d),
            arrays,
            growing,
            growing_gids,
            k_seg=k_seg,
            topk=topk,
            clamp=clamp,
            **dict(statics),
        )
        return out.reshape(n_chunks, b, topk)
    bundle = IndexBundle(kind=kind, arrays=arrays, static=dict(statics))

    def chunk_fn(q):
        ids, sims = search_index(bundle, q, k_seg)  # (n_seg, B, k_seg)
        return merge_topk(ids, sims, q, growing, growing_gids, topk)

    return jax.lax.map(chunk_fn, qc)


_pipeline = partial(
    jax.jit, static_argnames=("kind", "statics", "k_seg", "topk", "fused", "clamp")
)(_pipeline_impl)
#: the jitted program itself, for ``VDMSInstance.search_program``: a caller
#: may wrap the module's ``_pipeline`` in a host span of its own
_pipeline_jit = _pipeline


@partial(jax.jit, static_argnames=("kind", "statics", "k_seg", "topk"))
def _pipeline_batch(qc, arrays, growing, growing_gids, kind, statics, k_seg, topk):
    """Vectorized multi-config dispatch: every per-instance operand carries a
    leading batch axis (arrays values, growing, growing_gids); the query chunks
    are shared. Returns (B, n_chunks, b, topk) global ids in ONE compiled
    program, amortizing dispatch + compile across the batch. Always runs the
    composed pipeline: fused hooks are a single-instance fast path and the
    vmapped stack is already one fused program."""

    def one(arrays_i, growing_i, gids_i):
        return _pipeline_impl(qc, arrays_i, growing_i, gids_i, kind, statics, k_seg, topk)

    return jax.vmap(one)(arrays, growing, growing_gids)


class VDMSInstance:
    """A built VDMS under one configuration."""

    def __init__(self, dataset: VectorDataset, config: Dict[str, Any], seed: int = 0):
        self.dataset = dataset
        self.config = dict(config)
        t0 = time.perf_counter()
        with obs.collect() as stages:
            self.plan = plan_segments(
                dataset.n,
                int(config["segment_max_size"]),
                float(config["seal_proportion"]),
                float(config["graceful_time"]),
            )
            segs, gids = stack_sealed(dataset.data, self.plan)
            key = jax.random.PRNGKey(seed)
            sys = {
                "kmeans_iters": int(config["kmeans_iters"]),
                "storage_bf16": bool(config["storage_bf16"]),
            }
            self.bundle = build_index(key, segs, gids, config["index_type"], config, sys)
            g0 = self.plan.growing_start
            g_searched = self.plan.growing_searched
            with obs.span("build.upload"):
                self.growing = jnp.asarray(dataset.data[g0 : g0 + g_searched])
                self.growing_gids = jnp.asarray(np.arange(g0, g0 + g_searched, dtype=np.int32))
                jax.block_until_ready(list(self.bundle.arrays.values()))
        self.build_time = time.perf_counter() - t0
        #: seconds of the build by stage (``build.stack_sealed``, ``build.upload``,
        #: ``build.kmeans``, ...; those of the index family); they sum to at most
        #: ``build_time``
        self.build_seconds = stages
        self.k_seg = int(config["topk_merge_width"])
        self.batch = int(config["search_batch_size"])
        # the fused top-k clamp is exact only when every sealed slot is real:
        # a trailing partial seal pads with -1 gids, whose dead slots must
        # keep consuming merge width to match the composed path bit-for-bit
        self._clamp_ok = bool(
            np.all(np.asarray(self.plan.sealed_valid) == self.plan.seg_size)
        )

    # ------------------------------------------------------------------
    def _chunked_queries(self, queries: np.ndarray) -> jnp.ndarray:
        q, d = queries.shape
        b = min(self.batch, q)
        n_chunks = (q + b - 1) // b
        pad = n_chunks * b - q
        if pad:
            queries = np.concatenate([queries, queries[:pad]], axis=0)
        return jnp.asarray(queries.reshape(n_chunks, b, d))

    def _pipeline_args(self, queries: np.ndarray, topk: int) -> tuple:
        """The arguments of the ``_pipeline`` call that searches ``queries``."""
        return (
            self._chunked_queries(queries),
            self.bundle.arrays,
            self.growing,
            self.growing_gids,
            self.bundle.kind,
            tuple(sorted(self.bundle.static.items())),
            self.k_seg,
            topk,
            get_search_pipeline() == "fused",
            self._clamp_ok,
        )

    def search(self, queries: np.ndarray, topk: int) -> np.ndarray:
        with obs.span("search.prep"):
            args = self._pipeline_args(queries, topk)
        with obs.span("search.dispatch"):
            out = _pipeline(*args)
        with obs.span("search.fetch"):
            out = np.asarray(out)
        return out.reshape(-1, topk)[: queries.shape[0]]

    def search_program(self, n_queries: int, topk: int) -> jax.stages.Compiled:
        """The compiled program that ``search`` runs for ``n_queries`` queries
        and ``topk``, for its HLO (``as_text``, whose metadata carries the
        ``vdms.*`` scopes), ``cost_analysis`` and ``memory_analysis``. Where
        the persistent compilation cache holds it, it is loaded, not compiled."""
        queries = np.zeros((n_queries, self.dataset.dim), np.float32)
        return _pipeline_jit.lower(*self._pipeline_args(queries, topk)).compile()

    def memory_gib(self) -> float:
        b = self.bundle.memory_bytes() + self.growing.size * self.growing.dtype.itemsize
        return b / (1024.0**3)

    # --- analytic cost model ------------------------------------------
    def _analytic_seconds_per_chunk(self) -> float:
        return analytic_chunk_seconds(
            self.bundle.kind,
            self.bundle.static,
            self.bundle.arrays,
            self.plan.n_sealed,
            self.plan.seg_size,
            self.plan.growing_searched,
            self.dataset.dim,
            self.batch,
        )

    # ------------------------------------------------------------------
    def measure(
        self, topk: int | None = None, repeats: int = 3, mode: str = "wall"
    ) -> Dict[str, float]:
        ds = self.dataset
        topk = topk or ds.k
        queries = ds.queries
        # one measured-apart warmup run → compile time + recall
        t0 = time.perf_counter()
        ids = self.search(queries, topk)
        compile_time = time.perf_counter() - t0
        recall = recall_at_k(ids[:, : ds.k], ds.ground_truth)
        n_chunks = (queries.shape[0] + self.batch - 1) // self.batch
        if mode == "analytic":
            elapsed = self._analytic_seconds_per_chunk() * n_chunks
        else:
            times = []
            args = self._pipeline_args(queries, topk)
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(_pipeline(*args))
                times.append(time.perf_counter() - t0)
            elapsed = min(times)
        qps = queries.shape[0] / max(elapsed, 1e-9)
        return {
            "speed": float(qps),
            "recall": float(recall),
            "mem_gib": float(self.memory_gib()),
            "build_time": float(self.build_time),
            "compile_time": float(compile_time),
        }


# ---------------------------------------------------------------------------
# live (streaming) instance
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("kind", "statics", "k_seg", "topk", "fused"))
def _live_chunk(
    q, arrays, alive_g, growing, growing_gids, kind, statics, k_seg, topk, fused=False
):
    """One query chunk against the live state: sealed segments searched via
    their indexes, the visible growing tail brute-forced, tombstones and
    padded slots filtered through the global ``alive_g`` mask at merge time
    (index -1 maps to the always-dead sentinel slot ``alive_g[-1]``).

    ``fused=True`` routes through the family's ``fused_search`` hook with
    ``alive=alive_g`` (the hook replicates this merge); live searches never
    clamp — compacted segments carry -1 padding that must consume width."""
    family = get_family(kind)
    if fused and family.fused_search is not None and arrays["gids"].shape[0] > 0:
        return family.fused_search(
            q,
            arrays,
            growing,
            growing_gids,
            k_seg=k_seg,
            topk=topk,
            clamp=False,
            alive=alive_g,
            **dict(statics),
        )
    bundle = IndexBundle(kind=kind, arrays=arrays, static=dict(statics))
    ids, sims = search_index(bundle, q, k_seg)  # (n_seg, B, k_seg)
    return merge_topk(ids, sims, q, growing, growing_gids, topk, alive=alive_g)


@partial(jax.jit, static_argnames=("topk",))
def _live_chunk_unsealed(q, growing, growing_gids, topk):
    """Chunk search before the first seal: brute force over the visible tail."""
    gs = jnp.dot(q, growing.T.astype(q.dtype), preferred_element_type=jnp.float32)
    gs = jnp.where(growing_gids[None, :] >= 0, gs, -jnp.inf)
    k = min(topk, growing.shape[0])
    top_s, top_i = jax.lax.top_k(gs, k)
    out = jnp.where(jnp.isfinite(top_s), growing_gids[top_i], -1)
    if k < topk:
        out = jnp.pad(out, ((0, 0), (0, topk - k)), constant_values=-1)
    return out


def _bucket(n: int) -> int:
    """Pad count for the visible growing tail: next power of two >= n (min
    64), so tail churn recompiles the chunk program only O(log) times."""
    if n <= 0:
        return 0
    b = 64
    while b < n:
        b *= 2
    return b


class LiveVDMS:
    """A *live* VDMS instance: bulk-loaded once, then ingesting timestamped
    inserts/deletes while serving searches — the streaming regime the paper's
    system parameters exist for.

    Lifecycle (Milvus-like):

    * inserts append to the growing tail; when the tail reaches the seal
      size ``ceil(seal_proportion * segment_max_size)`` it is sealed into a
      fixed-shape segment and indexed *incrementally* (one per-segment build;
      SQ8/SCANN scales and PQ codebooks are frozen after the first build,
      like real systems that train quantizers once);
    * deletes tombstone ids anywhere; a sealed segment whose dead fraction
      crosses ``compact_threshold`` is compacted — rebuilt in place from its
      survivors with ``-1``-id padding;
    * ``graceful_time`` is the bounded-consistency window over the *current*
      tail: each search scans only the oldest ``(1 - graceful_time)``
      fraction of the growing tail, so the freshest inserts may be invisible
      (fast but stale). Recall under that staleness is scored by the
      replayer against time-aware ground truth.
    """

    def __init__(
        self,
        config: Dict[str, Any],
        dim: int,
        capacity: int,
        seed: int = 0,
        compact_threshold: float = 0.3,
    ):
        self.config = dict(config)
        # the seal path is registry-dispatched: resolve the family up front so
        # unknown types and non-incremental families fail loudly at creation
        self._family = get_family(config["index_type"])
        if not self._family.supports_incremental:
            raise ValueError(
                f"index family {self._family.name!r} does not support "
                "incremental (streaming) builds"
            )
        self.dim = int(dim)
        self.capacity = int(capacity)
        self.seed = int(seed)
        self.compact_threshold = float(compact_threshold)
        self.seg_size = live_seg_size(
            int(config["segment_max_size"]), float(config["seal_proportion"])
        )
        self.graceful = float(np.clip(float(config["graceful_time"]), 0.0, 1.0))
        self.k_seg = int(config["topk_merge_width"])
        self.batch = int(config["search_batch_size"])
        self._sys = {
            "kmeans_iters": int(config["kmeans_iters"]),
            "storage_bf16": bool(config["storage_bf16"]),
        }
        self._key = jax.random.PRNGKey(seed)
        self.store = np.zeros((self.capacity, self.dim), np.float32)
        # +1 sentinel slot (always dead): merge maps id -1 there
        self.alive = np.zeros(self.capacity + 1, dtype=bool)
        self.gid_seg = np.full(self.capacity, -1, np.int32)  # gid -> sealed segment
        self.n_total = 0
        self.tail: List[int] = []
        self.bundle: IndexBundle | None = None
        self.seg_gids: List[np.ndarray] = []
        self._frozen: Dict[str, np.ndarray] | None = None
        # lifecycle diagnostics
        self.build_time = 0.0  # bootstrap (bulk-load) build seconds
        self.bootstrap_build_model_s = 0.0  # bootstrap builds, analytic model
        self.seal_build_s = 0.0  # incremental seal + compaction builds (wall)
        self.seal_build_model_s = 0.0  # same, under the analytic build model
        self.n_seals = 0
        self.n_compactions = 0
        self.n_deletes = 0
        self.seal_history: List[int] = []  # n_sealed after every lifecycle event
        self._warmed: set = set()  # compiled (n_sealed, bucket, b, topk) shapes
        self.compile_s = 0.0  # wall-mode warmup (compile) seconds, kept apart
        # search instrumentation: per-query latencies of the last search call
        # (a query is charged its chunk's elapsed / chunk width) plus hooks
        # ``fn(n_queries, latencies, elapsed)`` the metrics ledger attaches to
        self.queries_served = 0
        self.last_latencies: np.ndarray = np.empty(0, np.float64)
        self.search_hooks: List[Callable[[int, np.ndarray, float], None]] = []
        # fault-injection + degraded mode. Everything below is inert until
        # ``arm_faults`` installs an injector: every fault branch is gated on
        # ``self._faults is not None`` so the unarmed engine is byte-identical
        # to one that never heard of faults.
        self._faults: FaultInjector | None = None
        # sealed segment -> repair state while quarantined
        self.quarantined: Dict[int, Dict[str, Any]] = {}
        # per-sealed-segment build provenance ({"salt", "first"}) so a
        # quarantined segment can be rebuilt bitwise-identically: the same
        # fold_in salt + frozen-calibration choice replays the same build
        self._seg_meta: List[Dict[str, Any]] = []
        self._pending_seal: Dict[str, int] | None = None  # crashed-seal backoff
        self.last_coverage = 1.0  # visible fraction served by the last search
        self.n_quarantines = 0
        self.n_rebuilds = 0
        self.n_rebuild_failures = 0  # rebuilds whose retry budget exhausted
        self.n_seal_retries = 0  # crashed incremental builds (seal/compact)

    # --- state views ---------------------------------------------------
    @property
    def n_sealed(self) -> int:
        return len(self.seg_gids)

    @property
    def n_alive(self) -> int:
        return int(self.alive[: self.capacity].sum())

    def visible_ids(self) -> np.ndarray:
        """Sorted global ids of every alive vector (sealed + whole tail)."""
        return np.flatnonzero(self.alive[: self.capacity]).astype(np.int32)

    def memory_gib(self) -> float:
        b = len(self.tail) * self.dim * 4
        if self.bundle is not None:
            b += self.bundle.memory_bytes()
        return b / (1024.0**3)

    def stats(self) -> Dict[str, float]:
        """One structured snapshot of the instance's lifecycle state — the
        dict the serving metrics ledger (and ``bench_streaming``) consumes
        instead of poking at scattered attributes. All values are plain
        Python ints/floats (JSON-safe)."""
        n_total = int(self.n_total)
        n_alive = self.n_alive
        return {
            "n_total": n_total,
            "n_alive": n_alive,
            "tombstone_fraction": float((n_total - n_alive) / max(n_total, 1)),
            "n_sealed": int(self.n_sealed),
            "tail_size": len(self.tail),
            "visible_tail": int(self._visible_tail().size),
            "n_seals": int(self.n_seals),
            "n_compactions": int(self.n_compactions),
            "n_deletes": int(self.n_deletes),
            "seal_build_s": float(self.seal_build_s),
            "seal_build_model_s": float(self.seal_build_model_s),
            "bootstrap_build_model_s": float(self.bootstrap_build_model_s),
            "build_time": float(self.build_time),
            "compile_s": float(self.compile_s),
            "mem_gib": float(self.memory_gib()),
            "queries_served": int(self.queries_served),
            # degraded-mode / fault-injection telemetry (all zero when no
            # FaultPlan has ever been armed)
            "coverage": float(self.last_coverage),
            "quarantined_segments": len(self.quarantined),
            "n_quarantines": int(self.n_quarantines),
            "n_rebuilds": int(self.n_rebuilds),
            "n_rebuild_failures": int(self.n_rebuild_failures),
            "n_seal_retries": int(self.n_seal_retries),
            "n_faults_injected": int(self._faults.n_injected if self._faults else 0),
            "health_code": HEALTH_CODE[self.health()],
        }

    # --- ingestion -----------------------------------------------------
    def bootstrap(self, base: np.ndarray) -> None:
        """Bulk-load the pre-replay corpus (sealing as segments fill); the
        time spent is the initial ``build_time`` (index-building cost), not
        replay-time ingest overhead — the seal counters reset afterwards."""
        if self._faults is not None:
            # shadow-scoped injectors fail the matching bootstrap ordinal
            # (injected OOM) before any vector lands
            self._faults.on_bootstrap(int(np.asarray(base).shape[0]))
        t0 = time.perf_counter()
        self.insert(base)
        self.build_time += time.perf_counter() - t0
        self.bootstrap_build_model_s += self.seal_build_model_s
        self.seal_build_s = 0.0
        self.seal_build_model_s = 0.0

    def insert(self, vecs: np.ndarray) -> np.ndarray:
        """Append vectors (d,) or (n, d); seals segments as the tail fills.
        Returns the assigned global ids."""
        if self._faults is not None:
            self._fault_tick()
        vecs = np.asarray(vecs, np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None]
        n = vecs.shape[0]
        if self.n_total + n > self.capacity:
            raise ValueError(
                f"capacity exceeded: {self.n_total}+{n} > {self.capacity}"
            )
        gids = np.arange(self.n_total, self.n_total + n, dtype=np.int32)
        self.store[gids] = vecs
        self.alive[gids] = True
        self.n_total += n
        self.tail.extend(int(g) for g in gids)
        while len(self.tail) >= self.seg_size:
            if self._pending_seal is not None:
                break  # a crashed seal is backing off; the fault clock retries it
            if not self._try_seal():
                break
        return gids

    def _build_one(
        self,
        ids_row: np.ndarray,
        salt: int | None = None,
        use_frozen: bool | None = None,
        context: str = "seal",
    ) -> IndexBundle:
        """Incremental index build for one packed segment (gid -1 = padding).

        ``salt``/``use_frozen`` default to the live counters (normal seal /
        compaction path); a quarantine rebuild passes the segment's recorded
        provenance instead, replaying the original deterministic build —
        same key, same calibration choice — bitwise-identically."""
        seg = np.zeros((1, self.seg_size, self.dim), np.float32)
        valid = ids_row >= 0
        seg[0, valid] = self.store[ids_row[valid]]
        if salt is None:
            salt = self.n_seals + self.n_compactions
        key = jax.random.fold_in(self._key, salt)
        first = (self._frozen is None) if use_frozen is None else (not use_frozen)
        self.seal_build_model_s += analytic_build_seconds(
            self.config["index_type"], self.config, self.seg_size, self.dim, first
        )
        if self._faults is not None:
            # after the analytic charge: crashed attempts still cost build time
            self._faults.on_build(context)
        b = build_index(
            key, seg, ids_row[None], self.config["index_type"], self.config,
            self._sys, frozen=None if first else self._frozen,
        )
        jax.block_until_ready(list(b.arrays.values()))
        if self._frozen is None:
            self._frozen = frozen_state(b)
        return b

    def _try_seal(self) -> bool:
        """Seal one full tail slice. Returns False if the build crashed (the
        tail stays intact and a backoff retry is scheduled on the fault
        clock); raises :class:`TransientEngineFault` once the retry budget
        is exhausted."""
        t0 = time.perf_counter()
        ids = np.asarray(self.tail[: self.seg_size], np.int32)
        salt = self.n_seals + self.n_compactions
        first = self._frozen is None
        try:
            b = self._build_one(ids, context="seal")
        except BuildCrashFault:
            self.seal_build_s += time.perf_counter() - t0
            self.n_seal_retries += 1
            attempts = 1 if self._pending_seal is None else self._pending_seal["attempts"] + 1
            plan = self._faults.plan
            if attempts > plan.max_seal_retries:
                self._pending_seal = None
                raise TransientEngineFault(
                    f"seal crashed {attempts} times (budget {plan.max_seal_retries})"
                ) from None
            self._pending_seal = {
                "attempts": attempts,
                "next_tick": self._faults.tick + plan.backoff_base_ticks * 2 ** (attempts - 1),
            }
            return False
        self.tail = self.tail[self.seg_size :]
        self.bundle = b if self.bundle is None else concat_bundles(self.bundle, b)
        self.gid_seg[ids] = len(self.seg_gids)
        self.seg_gids.append(ids)
        self._seg_meta.append({"salt": salt, "first": first})
        self.n_seals += 1
        self.seal_build_s += time.perf_counter() - t0
        self.seal_history.append(self.n_sealed)
        self._pending_seal = None
        return True

    def delete(self, gid: int) -> bool:
        """Tombstone one vector; compacts its sealed segment if the dead
        fraction crosses the threshold. Returns False for already-dead ids."""
        if self._faults is not None:
            self._fault_tick()
        gid = int(gid)
        if gid < 0 or gid >= self.n_total or not self.alive[gid]:
            return False
        self.alive[gid] = False
        self.n_deletes += 1
        z = int(self.gid_seg[gid])
        if z >= 0 and z not in self.quarantined:
            row = self.seg_gids[z]
            valid = row[row >= 0]
            dead_frac = 1.0 - float(self.alive[valid].mean()) if valid.size else 1.0
            if dead_frac > self.compact_threshold:
                self._compact(z)
        return True

    def _compact(self, z: int) -> None:
        t0 = time.perf_counter()
        row = self.seg_gids[z]
        valid = row[row >= 0]
        survivors = valid[self.alive[valid]]
        new_row = np.full(self.seg_size, -1, np.int32)
        new_row[: survivors.size] = survivors
        salt = self.n_seals + self.n_compactions
        try:
            b = self._build_one(new_row, context="compact")
        except BuildCrashFault:
            # the old index still serves (tombstones filter at merge); skip —
            # the next delete past the threshold re-triggers compaction
            self.seal_build_s += time.perf_counter() - t0
            self.n_seal_retries += 1
            return
        self.bundle = replace_segment(self.bundle, z, b)
        self.seg_gids[z] = new_row
        self._seg_meta[z] = {"salt": salt, "first": False}
        self.gid_seg[survivors] = z
        self.n_compactions += 1
        self.seal_build_s += time.perf_counter() - t0
        self.seal_history.append(self.n_sealed)

    # --- fault injection + degraded mode -------------------------------
    def arm_faults(self, injector: FaultInjector | None) -> None:
        """Install (or clear, with ``None``) the fault injector driving this
        engine's fault clock. Arm after ``bootstrap`` so plan ticks line up
        with replayed operations rather than bulk-load inserts."""
        self._faults = injector

    def _fault_tick(self) -> None:
        """One step of the fault clock: apply newly-due events, then service
        scheduled repairs (crashed-seal retries, quarantine rebuilds)."""
        inj = self._faults
        for e in inj.advance():
            if self.n_sealed > 0:
                self._quarantine(e.segment % self.n_sealed, e.kind)
        self._service_repairs()

    def _quarantine(self, z: int, reason: str) -> None:
        if z in self.quarantined:
            return
        self.quarantined[z] = {
            "retries": 0,
            "next_tick": self._faults.tick + self._faults.plan.backoff_base_ticks,
            "reason": reason,
            "permanent": False,
        }
        self.n_quarantines += 1

    def _service_repairs(self) -> None:
        inj = self._faults
        tick, plan = inj.tick, inj.plan
        if self._pending_seal is not None and tick >= self._pending_seal["next_tick"]:
            while len(self.tail) >= self.seg_size:
                if not self._try_seal():
                    break
        for z in sorted(self.quarantined):
            st = self.quarantined[z]
            if st["permanent"] or tick < st["next_tick"]:
                continue
            t0 = time.perf_counter()
            meta = self._seg_meta[z]
            try:
                b = self._build_one(
                    self.seg_gids[z],
                    salt=meta["salt"],
                    use_frozen=not meta["first"],
                    context="rebuild",
                )
            except BuildCrashFault:
                self.seal_build_s += time.perf_counter() - t0
                st["retries"] += 1
                if st["retries"] >= plan.max_rebuild_retries:
                    st["permanent"] = True  # -> health() == "degraded"
                    self.n_rebuild_failures += 1
                else:
                    st["next_tick"] = tick + plan.backoff_base_ticks * 2 ** st["retries"]
                continue
            self.bundle = replace_segment(self.bundle, z, b)
            del self.quarantined[z]
            self.n_rebuilds += 1
            self.seal_build_s += time.perf_counter() - t0

    def searchable_ids(self) -> np.ndarray:
        """Sorted gids a search can actually return *right now*: alive, not
        in a quarantined segment, and not hidden behind the graceful-time
        consistency window — the visible set that honest (partial-coverage)
        recall accounting is scored against."""
        mask = self.alive[: self.capacity].copy()
        m = int(np.ceil((1.0 - self.graceful) * len(self.tail)))
        hidden = np.asarray(self.tail[m:], np.int32)
        if hidden.size:
            mask[hidden] = False
        for z in self.quarantined:
            row = self.seg_gids[z]
            mask[row[row >= 0]] = False
        return np.flatnonzero(mask).astype(np.int32)

    def health(self) -> str:
        """``healthy`` | ``rebuilding`` (repairs scheduled and within budget)
        | ``degraded`` (some quarantined segment exhausted its rebuilds)."""
        if any(st["permanent"] for st in self.quarantined.values()):
            return "degraded"
        if self.quarantined or self._pending_seal is not None:
            return "rebuilding"
        return "healthy"

    # --- search --------------------------------------------------------
    def _visible_tail(self) -> np.ndarray:
        """Alive gids of the tail slice a query may scan: the oldest
        ``(1 - graceful_time)`` fraction (newest inserts are skipped —
        the bounded-consistency window)."""
        m = int(np.ceil((1.0 - self.graceful) * len(self.tail)))
        if m == 0:
            return np.empty(0, np.int32)
        vis = np.asarray(self.tail[:m], np.int32)
        return vis[self.alive[vis]]

    def search(
        self, queries: np.ndarray, topk: int, mode: str = "analytic"
    ) -> Tuple[np.ndarray, float]:
        """Search the current visible state. Returns ``(global ids (Q, topk),
        elapsed seconds)`` — analytic mode charges the deterministic cost
        model for the live segment state; wall mode times the dispatch."""
        if self._faults is not None:
            self._fault_tick()
        queries = np.asarray(queries, np.float32)
        nq = queries.shape[0]
        b = min(self.batch, max(nq, 1))
        n_chunks = (nq + b - 1) // b
        vis = self._visible_tail()
        nb = _bucket(vis.size)
        growing = np.zeros((nb, self.dim), np.float32)
        growing[: vis.size] = self.store[vis]
        ggids = np.full(nb, -1, np.int32)
        ggids[: vis.size] = vis
        growing_j, ggids_j = jnp.asarray(growing), jnp.asarray(ggids)
        alive_arr = self.alive
        coverage = 1.0
        if self._faults is not None and self.quarantined:
            # degraded mode: mask quarantined segments out of the merge (same
            # array shape -> no recompile) and report the visible fraction
            alive_arr = self.alive.copy()
            sealed_alive = int((self.alive[: self.capacity] & (self.gid_seg >= 0)).sum())
            lost = 0
            for z in self.quarantined:
                row = self.seg_gids[z]
                valid = row[row >= 0]
                lost += int(self.alive[valid].sum())
                alive_arr[valid] = False
            total = sealed_alive + int(vis.size)
            coverage = float((total - lost) / max(total, 1))
        self.last_coverage = coverage
        alive_j = jnp.asarray(alive_arr)
        use_fused = get_search_pipeline() == "fused"

        def dispatch(chunk: np.ndarray) -> np.ndarray:
            if self.bundle is None:
                if nb == 0:
                    return np.full((b, topk), -1, np.int32)
                return np.asarray(
                    jax.block_until_ready(
                        _live_chunk_unsealed(jnp.asarray(chunk), growing_j, ggids_j, topk)
                    )
                )
            return np.asarray(
                jax.block_until_ready(
                    _live_chunk(
                        jnp.asarray(chunk),
                        self.bundle.arrays,
                        alive_j,
                        growing_j,
                        ggids_j,
                        self.bundle.kind,
                        tuple(sorted(self.bundle.static.items())),
                        self.k_seg,
                        topk,
                        use_fused,
                    )
                )
            )

        shape_key = (
            self.n_sealed if self.bundle is not None else -1, nb, b, topk, use_fused
        )
        out = np.empty((n_chunks * b, topk), np.int32)
        chunk_s = np.zeros(n_chunks, np.float64)
        for c in range(n_chunks):
            lo = c * b
            chunk = queries[lo : lo + b]
            if chunk.shape[0] < b:  # pad the final chunk by wrapping
                chunk = np.concatenate([chunk, queries[: b - chunk.shape[0]]], axis=0)
            if mode != "analytic" and shape_key not in self._warmed:
                # wall mode keeps compilation apart from the measured region,
                # mirroring the static path's measured-apart warmup run
                t0 = time.perf_counter()
                dispatch(chunk)
                self.compile_s += time.perf_counter() - t0
                self._warmed.add(shape_key)
            t0 = time.perf_counter()
            ids = dispatch(chunk)
            chunk_s[c] = time.perf_counter() - t0
            out[lo : lo + b] = ids
        if mode == "analytic":
            chunk_s[:] = analytic_chunk_seconds(
                self.bundle.kind if self.bundle is not None else "FLAT",
                self.bundle.static if self.bundle is not None else {},
                self.bundle.arrays if self.bundle is not None else {},
                self.n_sealed,
                self.seg_size,
                int(vis.size),
                self.dim,
                b,
            )
        counts = np.minimum(b, nq - b * np.arange(n_chunks))
        if self._faults is not None:
            # a latency storm distorts measured time only — never results
            mult, add = self._faults.latency_shape()
            if mult != 1.0 or add != 0.0:
                chunk_s = chunk_s * mult + add * counts
        elapsed = float(chunk_s.sum())
        # per-query wall latency: each chunk's elapsed is split over the real
        # queries it served (the final chunk's padding burden falls on them),
        # so latencies always sum to the batch elapsed — this is what makes
        # serving percentiles and throughput accounting consistent
        lat = np.repeat(chunk_s / np.maximum(counts, 1), counts)
        self.last_latencies = lat
        self.queries_served += nq
        for hook in self.search_hooks:
            hook(nq, lat, elapsed)
        return out[:nq], elapsed


# ---------------------------------------------------------------------------
# vectorized multi-config evaluation
# ---------------------------------------------------------------------------
def batch_signature(inst: VDMSInstance, topk: int | None = None) -> Tuple:
    """Static-shape fingerprint of an instance's compiled search program.

    Instances with equal signatures run the same XLA program modulo array
    contents, so their pipelines can be stacked and dispatched together via
    ``_pipeline_batch``.
    """
    topk = topk or inst.dataset.k
    return (
        inst.bundle.kind,
        tuple(sorted(inst.bundle.static.items())),
        tuple((k, a.shape, str(a.dtype)) for k, a in sorted(inst.bundle.arrays.items())),
        (inst.growing.shape, str(inst.growing.dtype)),
        inst.k_seg,
        inst.batch,
        topk,
    )


def measure_batch(
    instances: List[VDMSInstance],
    topk: int | None = None,
    repeats: int = 3,
    mode: str = "analytic",
) -> List[Dict[str, float]]:
    """Measure shape-identical instances in one vectorized dispatch.

    All instances must share one dataset and one :func:`batch_signature`;
    their arrays are stacked on a leading axis and searched by a single
    vmapped program, so compile and dispatch cost is paid once per batch
    instead of once per config. Recall is exact per config. In ``analytic``
    mode speed comes from each instance's deterministic cost model (identical
    to sequential ``measure``); in ``wall`` mode the batch is timed as one
    program and each config is charged an equal share of the wall time
    (amortized throughput — prefer per-instance measurement when single-config
    latency fidelity matters).
    """
    if not instances:
        return []
    inst0 = instances[0]
    ds = inst0.dataset
    topk = topk or ds.k
    if any(i.dataset is not ds for i in instances):
        raise ValueError("measure_batch requires a single shared dataset")
    if len({batch_signature(i, topk) for i in instances}) != 1:
        raise ValueError("measure_batch requires shape-identical instances")
    queries = ds.queries
    qc = inst0._chunked_queries(queries)
    arrays = {
        k: jnp.stack([i.bundle.arrays[k] for i in instances]) for k in inst0.bundle.arrays
    }
    growing = jnp.stack([i.growing for i in instances])
    gids = jnp.stack([i.growing_gids for i in instances])
    args = (
        qc,
        arrays,
        growing,
        gids,
        inst0.bundle.kind,
        tuple(sorted(inst0.bundle.static.items())),
        inst0.k_seg,
        topk,
    )
    t0 = time.perf_counter()
    out = np.asarray(jax.block_until_ready(_pipeline_batch(*args)))
    compile_time = time.perf_counter() - t0
    n_chunks = (queries.shape[0] + inst0.batch - 1) // inst0.batch
    if mode == "analytic":
        elapsed = [inst._analytic_seconds_per_chunk() * n_chunks for inst in instances]
    else:
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(_pipeline_batch(*args))
            times.append(time.perf_counter() - t0)
        elapsed = [min(times) / len(instances)] * len(instances)
    results = []
    for i, inst in enumerate(instances):
        ids = out[i].reshape(-1, topk)[: queries.shape[0]]
        recall = recall_at_k(ids[:, : ds.k], ds.ground_truth)
        qps = queries.shape[0] / max(elapsed[i], 1e-9)
        results.append(
            {
                "speed": float(qps),
                "recall": float(recall),
                "mem_gib": float(inst.memory_gib()),
                "build_time": float(inst.build_time),
                "compile_time": float(compile_time),
            }
        )
    return results
