"""Systems that stand in for the program to show that the check can fail.

None of these runs in the benchmark's own runs. ``readings.py`` reads them
on the chip to set each limit, and ``tests/`` shows on the CPU that each one
turns ``correct`` false:

* :class:`Bf16Reference` -- the control: the plain reference put in the
  program's place, an exhaustive search scored in bfloat16 (one pass on the
  MXU), the precision below the float32 the configurations state. FLAT has
  a lower-precision path of its own, ``storage_bf16``; :func:`control` takes
  that for it.
* :class:`Faulty` -- the program with one planted fault: ``half_batch``
  leaves half of each call's rows out (each odd row gets the answers of the
  even row before it); ``altered_answer`` alters one answer where it
  is produced (the best id of each call's first row moves to the next id);
  ``half_corpus`` leaves half of the corpus out of the index; and, where a
  cell times builds (``BUILD_FAULTS``), ``no_kmeans`` builds with no k-means
  iteration, and ``stale_build`` does each build's work but returns the
  first index it built, which is a build returning its state unchanged;
  on a configuration served on several shards (``SHARD_FAULTS``),
  ``drop_shard`` answers from all shards but the last: its segments are
  made dead, as the program's own padding segments are, which leaves out
  the exchange with one chip of the mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


@partial(jax.jit, static_argnames=("k", "block"))
def _bf16_topk(table, queries, k: int, block: int):
    n, d = table.shape
    n_blocks = -(-n // block)
    padded = jnp.pad(table, ((0, n_blocks * block - n), (0, 0))).reshape(n_blocks, block, d)
    q = queries.astype(jnp.bfloat16)

    def step(carry, z):
        best_s, best_i = carry
        s = jnp.dot(q, padded[z].T, preferred_element_type=jnp.float32)
        first = z * block
        s = jnp.where(first + jnp.arange(block)[None, :] < n, s, -jnp.inf)
        top_s, top_pos = jax.lax.top_k(jnp.concatenate([best_s, s], axis=1), k)
        kept = jnp.take_along_axis(best_i, jnp.minimum(top_pos, k - 1), axis=1)
        return (top_s, jnp.where(top_pos < k, kept, first + top_pos - k).astype(jnp.int32)), None

    b = queries.shape[0]
    init = (jnp.full((b, k), -jnp.inf, jnp.float32), jnp.full((b, k), -1, jnp.int32))
    (_, ids), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    return ids


class Bf16Reference:
    """The reference's exhaustive search, every score in bfloat16."""

    def __init__(self, scoring: str):
        self.scoring = scoring

    def build(self, dataset, seed: int):
        rows = dataset.data
        if self.scoring == "sq8":
            rows = reference.sq8_vectors(rows, reference.sq8_scale(rows))
        self.table = jnp.asarray(rows, jnp.bfloat16)
        return self

    def search(self, queries, topk: int) -> np.ndarray:
        block = min(32768, self.table.shape[0])
        return np.asarray(_bf16_topk(self.table, jnp.asarray(queries), topk, block))

    @staticmethod
    def clusters(built):
        return None  # an exhaustive search has no clusters to hold to the stated build


def control(config: dict):
    """The control of a configuration: the program's own lower-precision
    path where it has one, else the reference in bfloat16."""
    from bench.run import Program, shards_of

    if config["index"]["index_type"] == "FLAT":
        return Program({**config["index"], "storage_bf16": True}, shards_of(config))
    return Bf16Reference(config["scoring"])


class Faulty:
    """The program (a ``run.Program``) with one planted fault (``FAULTS``)."""

    def __init__(self, system, fault: str):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; use one of {FAULTS}")
        if fault == "no_kmeans":
            system = type(system)({**system.index_config, "kmeans_iters": 0}, system.shards)
        if fault in SHARD_FAULTS and system.shards < 2:
            raise ValueError(f"{fault!r} needs a configuration on several shards")
        self.system, self.fault, self.first = system, fault, None

    def build(self, dataset, seed: int):
        n = dataset.n
        if self.fault == "half_corpus":
            dataset = dataclasses.replace(dataset, data=dataset.data[: n // 2])
        inner = self.system.build(dataset, seed=seed)
        if self.fault == "drop_shard":
            _kill_last_shard(inner.inner)
        built = _FaultySearcher(inner, self.fault, n)
        if self.fault == "stale_build":
            self.first = self.first or built
            return self.first
        return built

    def clusters(self, built):
        return self.system.clusters(built.inner)


class _FaultySearcher:
    def __init__(self, inner, fault: str, n: int):
        self.inner, self.fault, self.n = inner, fault, n

    def search(self, queries, topk: int) -> np.ndarray:
        ids = np.array(self.inner.search(queries, topk))
        if self.fault == "half_batch":
            ids[1::2] = ids[: ids.shape[0] // 2 * 2 : 2]
        elif self.fault == "altered_answer":
            ids[0, 0] = (ids[0, 0] + 1) % self.n
        return ids


def _kill_last_shard(sharded) -> None:
    """Make every segment of a ``ShardedVDMS``'s last shard dead in place:
    id-like arrays -1, the others 0, each on its own sharding."""
    per = sharded.per_shard
    for name, v in sharded.arrays.items():
        if name not in sharded.shared_arrays:
            fill = -1 if name in ("gids", "members") else 0
            sharded.arrays[name] = jax.device_put(v.at[v.shape[0] - per:].set(fill), v.sharding)


BUILD_FAULTS = ("no_kmeans", "stale_build")
SHARD_FAULTS = ("drop_shard",)
FAULTS = ("half_batch", "altered_answer", "half_corpus", *BUILD_FAULTS, *SHARD_FAULTS)
