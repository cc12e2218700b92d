"""The plain reference, and the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made. It
sees the corpus and the queries (made by the benchmark from the seed), the
configuration's stated semantics, and the ids the program answered:

* exact top-k by inner product over the raw float32 corpus, computed on the
  device at ``Precision.HIGHEST`` in blocks of corpus rows; over a corpus
  sharded by rows (``corpus.make_corpus_sharded``), each device scans its own
  rows under ``shard_map`` and the per-shard lists are merged, ties to the
  lower id as on one device;
* the score of any (query, id) pair in float64 on the host, either on the
  raw vector (``scoring: "f32"``) or on its SQ8 code (``scoring: "sq8"``:
  one shared per-dimension scale ``max|x| / 127``, codes ``round(x / scale)``
  clipped to +-127, dequantised as ``code * scale``), which is what IVF_SQ8
  states it scores, the scale taken over the rows of its sealed segments
  and only those rows scored so; the rows of the growing tail, which it
  searches by brute force, score raw (``sealed_rows``). Over a sharded corpus
  the per-dimension ``max|x|`` is
  reduced across the devices and the rest done on the host, bit for bit
  ``sq8_scale``.

The numbers compared, each against a limit that the configuration file
holds (``limits``) and ``PERF.md`` derives:

* ``bad_ids``: answer rows holding an id outside the corpus or an id twice;
  exact, limit 0.
* ``recall_loss``: 1 - recall@k against the exact top-k.
* ``order_gap``: the widest amount by which a later answer of a row outscores
  an earlier one under the reference's score. The program returns its
  answers best first, so this reads rounding when it scored as stated, and
  the size of its error when it scored in a lower precision or mapped an id
  to the wrong vector.
* ``rank_gap`` (exact families only): the widest amount by which an answer
  scores below the reference's k-th best.

For a cell that times builds, two more, of the build the check searches:

* ``kmeans_gap``: the spherical k-means objective (each corpus row's cosine
  to the nearest of its segment's centroids, mean over the rows) that the
  reference's own Lloyd run reaches in the stated ``kmeans_iters`` from its
  own start, less the objective of the build's centroids. It reads about 0
  when the build ran its iterations, and the iterations' worth when it
  skipped them.
* ``repeated_builds``: builds of the run (the warm build among them) whose
  centroids are bit for bit those of another: a build that returns an
  earlier index in place of its own work. Exact, limit 0.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("k", "block"))
def _exact_topk(corpus, queries, k: int, block: int):
    n, d = corpus.shape
    n_blocks = -(-n // block)
    padded = jnp.pad(corpus, ((0, n_blocks * block - n), (0, 0))).reshape(n_blocks, block, d)
    b = queries.shape[0]

    def step(carry, z):
        best_s, best_i = carry
        s = jnp.dot(queries, padded[z].T, precision=HIGHEST, preferred_element_type=jnp.float32)
        first = z * block
        s = jnp.where(first + jnp.arange(block)[None, :] < n, s, -jnp.inf)
        top_s, top_pos = jax.lax.top_k(jnp.concatenate([best_s, s], axis=1), k)
        kept = jnp.take_along_axis(best_i, jnp.minimum(top_pos, k - 1), axis=1)
        ids = jnp.where(top_pos < k, kept, first + top_pos - k).astype(jnp.int32)
        return (top_s, ids), None

    init = (jnp.full((b, k), -jnp.inf, jnp.float32), jnp.full((b, k), -1, jnp.int32))
    (scores, ids), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    return ids, scores


@partial(jax.jit, static_argnames=("k", "block", "n", "mesh"))
def _sharded_topk(corpus, queries, k: int, block: int, n: int, mesh: Mesh):
    per = corpus.shape[0] // mesh.size
    n_blocks = -(-per // block)
    b = queries.shape[0]

    def local(rows, q):
        first = jax.lax.axis_index("shard") * per

        def step(carry, z):
            best_s, best_i = carry
            start = jnp.minimum(z * block, per - block)  # the last block overlaps the one before
            x = jax.lax.dynamic_slice_in_dim(rows, start, block)
            s = jnp.dot(q, x.T, precision=HIGHEST, preferred_element_type=jnp.float32)
            row = start + jnp.arange(block)
            s = jnp.where(((row >= z * block) & (first + row < n))[None, :], s, -jnp.inf)
            top_s, top_pos = jax.lax.top_k(jnp.concatenate([best_s, s], axis=1), k)
            kept = jnp.take_along_axis(best_i, jnp.minimum(top_pos, k - 1), axis=1)
            ids = jnp.where(top_pos < k, kept, first + start + top_pos - k).astype(jnp.int32)
            return (top_s, ids), None

        init = (jnp.full((b, k), -jnp.inf, jnp.float32), jnp.full((b, k), -1, jnp.int32))
        (scores, ids), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
        return scores[None], ids[None]

    parts_s, parts_i = jax.shard_map(local, mesh=mesh, in_specs=(P("shard"), P()),
                                     out_specs=(P("shard"), P("shard")), check_vma=False)(corpus, queries)
    # shard-major order: among equal scores the lower shard, whose ids are lower, comes first
    scores, pos = jax.lax.top_k(jnp.moveaxis(parts_s, 0, 1).reshape(b, -1), k)
    return jnp.take_along_axis(jnp.moveaxis(parts_i, 0, 1).reshape(b, -1), pos, axis=1), scores


def sharded(corpus) -> bool:
    """Whether a device corpus is spread over more than one device."""
    sharding = getattr(corpus, "sharding", None)
    return sharding is not None and len(sharding.device_set) > 1


def exact_topk(corpus, queries, k: int, block: int = 32768, n: int | None = None) -> np.ndarray:
    """Exact top-``k`` ids (rows of ``queries``) over the device corpus; of a
    sharded corpus, over its first ``n`` rows."""
    if sharded(corpus):
        mesh = corpus.sharding.mesh
        q = jax.device_put(np.asarray(queries, np.float32), NamedSharding(mesh, P()))
        ids, _ = _sharded_topk(corpus, q, k, min(block, corpus.shape[0] // mesh.size),
                               corpus.shape[0] if n is None else n, mesh)
        return np.asarray(ids)
    ids, _ = _exact_topk(corpus, jnp.asarray(queries), k, min(block, corpus.shape[0]))
    return np.asarray(ids)


def sq8_scale(corpus: np.ndarray) -> np.ndarray:
    """IVF_SQ8's shared per-dimension scale, in float32 as it is stated."""
    return (np.abs(corpus).max(axis=0) / np.float32(127.0) + np.float32(1e-12)).astype(np.float32)


@jax.jit
def _abs_max(corpus, rows):
    first = jnp.arange(corpus.shape[0])[:, None] < rows
    return jnp.where(first, jnp.abs(corpus), 0.0).max(axis=0)


def sharded_sq8_scale(corpus, rows: int) -> np.ndarray:
    """``sq8_scale`` of the first ``rows`` rows of a sharded device corpus, bit
    for bit: the max reduced across the devices, then the division and the
    sum in float32 on the host, as ``sq8_scale`` does them."""
    return (np.asarray(_abs_max(corpus, rows)) / np.float32(127.0) + np.float32(1e-12)).astype(np.float32)


def sq8_vectors(rows: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """The SQ8 reading of corpus rows: codes dequantised, in float64."""
    codes = np.clip(np.round(rows / scale), -127, 127)
    return codes.astype(np.float64) * scale.astype(np.float64)


def sealed_rows(n: int, segment_max_size: int, seal_proportion: float) -> int:
    """Corpus rows in sealed segments, as the system parameters state them:
    whole segments of ``segment_max_size`` rows (at least 64), and the last,
    part-filled one where it holds ``seal_proportion`` of that or more. The
    rows after them are the growing tail."""
    s = min(max(int(segment_max_size), 64), n)
    full, rem = divmod(n, s)
    if full == 0:
        return n  # all of it one sealed segment
    return full * s + (rem if rem > 0 and rem >= seal_proportion * s else 0)


def pair_scores(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray, scoring: str,
                scale: np.ndarray | None = None, raw_from: int | None = None) -> np.ndarray:
    """float64 score of each (query row, id) pair; ids (m, k) into ``corpus``.
    Under ``sq8`` an id from ``raw_from`` on (the growing tail) scores raw."""
    rows = corpus[np.clip(ids, 0, corpus.shape[0] - 1)]  # (m, k, d)
    if scoring == "sq8":
        vecs = sq8_vectors(rows, sq8_scale(corpus) if scale is None else scale)
        if raw_from is not None and raw_from < corpus.shape[0]:
            vecs = np.where((ids >= raw_from)[..., None], rows.astype(np.float64), vecs)
    elif scoring == "f32":
        vecs = rows.astype(np.float64)
    else:
        raise ValueError(f"unknown scoring {scoring!r}; use 'f32' or 'sq8'")
    return np.einsum("mkd,md->mk", vecs, queries.astype(np.float64))


def order_gap(scores: np.ndarray) -> float:
    """Widest amount by which a later entry of a row outscores an earlier one."""
    later_best = np.maximum.accumulate(scores[:, ::-1], axis=1)[:, ::-1]
    gaps = later_best[:, 1:] - scores[:, :-1]
    return float(max(gaps.max(initial=0.0), 0.0))


def bad_rows(answers: np.ndarray, n: int) -> np.ndarray:
    """Rows holding an id outside [0, n) or an id twice."""
    srt = np.sort(answers, axis=1)
    return ((answers < 0) | (answers >= n)).any(axis=1) | np.any(srt[:, 1:] == srt[:, :-1], axis=1)


def compare(answers: np.ndarray, pool: np.ndarray, rows: np.ndarray, corpus_host: np.ndarray,
            corpus_device, scoring: str, exact: bool, chunk: int = 2048,
            raw_from: int | None = None) -> dict:
    """The numbers compared for ``answers`` (m, k) to the pool rows ``rows`` (m,).
    ``corpus_device`` holds ``corpus_host`` on the device: as it is, or sharded
    by rows with zero rows past them (``corpus.make_corpus_sharded``).
    ``raw_from``: the first row of the growing tail (``sealed_rows``), where
    the SQ8 scale ends and raw scores begin."""
    answers, rows = np.asarray(answers), np.asarray(rows)
    n, k = corpus_host.shape[0], answers.shape[1]
    bad = bad_rows(answers, n)
    unique, where = np.unique(rows, return_inverse=True)
    truth = np.concatenate([  # exact top-k of each distinct query, in fixed-size chunks
        exact_topk(corpus_device, np.resize(pool[unique[i : i + chunk]], (chunk, pool.shape[1])), k, n=n)
        [: min(chunk, unique.size - i)]
        for i in range(0, unique.size, chunk)
    ])[where]
    hits = (answers[:, :, None] == truth[:, None, :]).any(axis=2).sum()
    queries = pool[rows]
    scale, sealed = None, n if raw_from is None else raw_from
    if scoring == "sq8":
        scale = (sharded_sq8_scale(corpus_device, sealed) if sharded(corpus_device)
                 else sq8_scale(corpus_host[:sealed]))
    got = pair_scores(corpus_host, queries, answers, scoring, scale, raw_from)
    numbers = {
        "bad_ids": int(bad.sum()),
        "recall_loss": 1.0 - hits / answers.size,
        "order_gap": order_gap(got[~bad]) if (~bad).any() else float("inf"),
    }
    if exact:
        kth = pair_scores(corpus_host, queries, truth[:, -1:], scoring, scale, raw_from)[:, 0]
        short = np.where((answers < 0) | (answers >= n), np.inf, kth[:, None] - got)
        numbers["rank_gap"] = float(max(short.max(), 0.0))
    return numbers


def _cosines(x, cents):
    """(rows, clusters) cosines, scored at ``Precision.HIGHEST``."""
    sims = jnp.dot(x, cents.T, precision=HIGHEST, preferred_element_type=jnp.float32)
    norms = jnp.linalg.norm(x, axis=1, keepdims=True) * jnp.linalg.norm(cents, axis=1)[None, :]
    return sims / (norms + 1e-12)


@jax.jit
def _objective(corpus, centroids, gids):
    def per_seg(carry, seg):
        cents, g = seg
        valid = g >= 0
        best = _cosines(corpus[jnp.maximum(g, 0)], cents).max(axis=1)
        return (carry[0] + jnp.where(valid, best, 0.0).sum(), carry[1] + valid.sum()), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (total, count), _ = jax.lax.scan(per_seg, init, (centroids, gids))
    return total, count


def objective(corpus_device, centroids, gids) -> float:
    """Mean over the indexed rows of each row's best cosine to its segment's
    centroids: ``centroids`` (n_seg, nlist, d); ``gids`` (n_seg, s), the
    corpus row in each slot of a segment, -1 where the slot is empty."""
    total, count = _objective(corpus_device, jnp.asarray(centroids), jnp.asarray(gids))
    return float(total) / max(int(count), 1)


@partial(jax.jit, static_argnames=("iters",))
def _lloyd(corpus, gids, starts, iters: int):
    k = starts.shape[1]

    def per_seg(_, seg):
        g, start = seg
        valid = (g >= 0)[:, None]
        x = corpus[jnp.maximum(g, 0)]
        x = x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-12)

        def step(cents, _):
            member = jax.nn.one_hot(jnp.argmax(_cosines(x, cents), axis=1), k) * valid
            sums = jnp.dot(member.T, x, precision=HIGHEST)
            counts = member.sum(axis=0)[:, None]
            new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), cents)
            return new / (jnp.linalg.norm(new, axis=1, keepdims=True) + 1e-12), None

        cents, _ = jax.lax.scan(step, x[start], None, length=iters)
        return None, cents

    _, cents = jax.lax.scan(per_seg, None, (gids, starts))
    return cents


def lloyd_objective(corpus_device, segment: int, nlist: int, iters: int, seed: int) -> float:
    """The objective that spherical k-means reaches in ``iters`` Lloyd steps
    in each segment of ``segment`` consecutive corpus rows, each started from
    ``nlist`` of its rows drawn from ``seed``: the stated build, done plainly."""
    n = corpus_device.shape[0]
    n_seg = -(-n // segment)
    slots = np.arange(n_seg * segment).reshape(n_seg, segment)
    gids = np.where(slots < n, slots, -1).astype(np.int32)
    rng = np.random.default_rng([seed, 2])
    starts = np.stack([rng.choice(int(v), size=nlist, replace=False) for v in (gids >= 0).sum(1)])
    cents = _lloyd(corpus_device, jnp.asarray(gids), jnp.asarray(starts, jnp.int32), iters)
    return objective(corpus_device, cents, gids)


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit; a number without a limit
    is a fault of the configuration file, not a pass."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    table = {name: {"value": numbers[name], "limit": limits[name]} for name in sorted(numbers)}
    return all(v["value"] <= v["limit"] for v in table.values()), table
