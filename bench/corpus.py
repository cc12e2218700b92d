"""The corpus and the query pool of a cell, made on the device from the seed.

The mixture is the one of ``_glove_like`` in ``repro/vdms/datasets.py``
(copied here so that an edit there cannot move the yardstick): clustered
Gaussians, one cluster per 256 rows (at least 32), centres of scale 2, each
cluster with its own spread in [0.6, 1.4), every row L2-normalised. Queries
are drawn from the same mixture as the corpus, as in ``make_dataset``.

Two generators draw it. ``make_corpus`` makes corpus and pool in one program
on one device; a configuration on one chip uses it. ``make_corpus_sharded``
makes the corpus across a ``("shard",)`` mesh, each device its own rows, one
block at a time, so that no device holds more than its share, and
``host_corpus_sharded`` makes the same rows a chunk at a time and copies each
to the host, so that the devices hold none of them once it returns; a
configuration with ``"shards": N > 1`` uses these two. ``make_corpus`` and
``make_corpus_sharded`` draw the same law but not the same bits: a seed gives
another corpus under each.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

#: rows of one block of ``make_corpus_sharded``: row ``r`` is row ``r % BLOCK``
#: of block ``r // BLOCK``, whatever the mesh
BLOCK = 8192
#: bytes of the blocks one device makes at a time (one block where that is more)
CHUNK_BYTES = 64 << 20


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 64 bits: the low word seeds the key and
    the high word is folded in, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} is not a whole number in [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@partial(jax.jit, static_argnames=("n", "n_queries", "dim"))
def _glove_like(key, n: int, n_queries: int, dim: int):
    total = n + n_queries
    n_clusters = max(32, total // 256)
    k_cent, k_assign, k_scale, k_noise = jax.random.split(key, 4)
    centers = jax.random.normal(k_cent, (n_clusters, dim), jnp.float32) * 2.0
    assign = jax.random.randint(k_assign, (total,), 0, n_clusters)
    scale = 0.6 + 0.8 * jax.random.uniform(k_scale, (n_clusters,), jnp.float32)
    x = centers[assign] + jax.random.normal(k_noise, (total, dim), jnp.float32) * scale[assign, None]
    x = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
    return x[:n], x[n:]


def make_corpus(seed: int, n: int, n_queries: int, dim: int):
    """(corpus (n, dim), queries (n_queries, dim)), float32 on the device."""
    return _glove_like(seed_key(seed), n, n_queries, dim)


def shard_mesh(shards: int) -> Mesh:
    """The benchmark's own 1-D ``("shard",)`` mesh over the first ``shards`` devices."""
    devices = jax.devices()
    if len(devices) < shards:
        raise ValueError(f"{shards} shards asked, {len(devices)} devices found")
    return Mesh(np.asarray(devices[:shards]), ("shard",))


def rows_per_shard(n: int, shards: int) -> int:
    """Rows each shard holds: ``n`` spread over ``shards`` in whole blocks."""
    return -(-n // (shards * BLOCK)) * BLOCK


def _mixture_rows(key, rows: int, centers, scale):
    """``rows`` rows of the mixture, each cluster and noise drawn from ``key``."""
    k_assign, k_noise = jax.random.split(key)
    assign = jax.random.randint(k_assign, (rows,), 0, centers.shape[0])
    noise = jax.random.normal(k_noise, (rows, centers.shape[1]), jnp.float32)
    x = centers[assign] + noise * scale[assign, None]
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)


@partial(jax.jit, static_argnames=("n", "n_queries", "dim", "mesh"))
def _law(key, n: int, n_queries: int, dim: int, mesh: Mesh):
    """The centres, the spreads, the rows' key and the queries, replicated."""
    n_clusters = max(32, (n + n_queries) // 256)
    k_law, k_rows, k_pool = (jax.random.fold_in(key, i) for i in range(3))
    k_cent, k_scale = jax.random.split(k_law)
    replicated = NamedSharding(mesh, P())
    centers = jax.lax.with_sharding_constraint(
        jax.random.normal(k_cent, (n_clusters, dim), jnp.float32) * 2.0, replicated)
    scale = jax.lax.with_sharding_constraint(
        0.6 + 0.8 * jax.random.uniform(k_scale, (n_clusters,), jnp.float32), replicated)
    pool = jax.lax.with_sharding_constraint(_mixture_rows(k_pool, n_queries, centers, scale), replicated)
    return centers, scale, k_rows, pool


@partial(jax.jit, static_argnames=("n", "count", "mesh"))
def _chunk(centers, scale, rows_key, first, n: int, count: int, mesh: Mesh):
    """Blocks ``first`` .. ``first + count - 1`` of every shard's rows, one
    block at a time: ``(mesh.size * count * BLOCK, dim)``, sharded by rows."""
    per_blocks = rows_per_shard(n, mesh.size) // BLOCK

    def local(centers, scale, rows_key, first):
        g0 = jax.lax.axis_index("shard") * per_blocks + first

        def block(j):
            g = g0 + j  # the global block index
            x = _mixture_rows(jax.random.fold_in(rows_key, g), BLOCK, centers, scale)
            row = g * BLOCK + jnp.arange(BLOCK)
            return jnp.where((row < n)[:, None], x, 0.0)

        return jax.lax.map(block, jnp.arange(count)).reshape(count * BLOCK, centers.shape[1])

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=P("shard"))(
        centers, scale, rows_key, first)


@partial(jax.jit, static_argnames=("rows", "dim", "mesh"))
def _zeros(rows: int, dim: int, mesh: Mesh):
    return jax.lax.with_sharding_constraint(jnp.zeros((rows, dim), jnp.float32), NamedSharding(mesh, P("shard")))


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def _place(corpus, chunk, first, mesh: Mesh):
    """``corpus`` with ``_chunk``'s blocks written in each shard's rows."""
    def local(corpus, chunk, first):
        return jax.lax.dynamic_update_slice_in_dim(corpus, chunk, first * BLOCK, axis=0)

    return jax.shard_map(local, mesh=mesh, in_specs=(P("shard"), P("shard"), P()), out_specs=P("shard"))(
        corpus, chunk, first)


def _chunks(seed: int, n: int, n_queries: int, dim: int, mesh: Mesh):
    """(queries, blocks a chunk holds of each shard, iterator of (first block,
    chunk) over every block of every shard). The last chunk starts early
    where the chunks do not divide a shard, making some blocks twice, alike."""
    centers, scale, rows_key, pool = _law(seed_key(seed), n, n_queries, dim, mesh)
    per_blocks = rows_per_shard(n, mesh.size) // BLOCK
    count = max(1, min(per_blocks, CHUNK_BYTES // (BLOCK * dim * 4)))

    def chunks():
        for first in range(0, per_blocks, count):
            first = jnp.int32(min(first, per_blocks - count))
            yield first, _chunk(centers, scale, rows_key, first, n=n, count=count, mesh=mesh)

    return pool, count, chunks()


def make_corpus_sharded(seed: int, n: int, n_queries: int, dim: int, mesh: Mesh):
    """(corpus, queries), float32, made across ``mesh`` (axis ``"shard"``).

    The corpus is one ``(mesh.size * rows_per_shard(n, mesh.size), dim)``
    array sharded by rows: shard ``s`` holds the contiguous rows from
    ``s * rows_per_shard``, made there one ``BLOCK`` at a time, each block's
    clusters and noise drawn from ``fold_in(key, its global block index)``.
    Rows ``n`` and past are zero. A row's values depend on the seed, ``n``,
    ``n_queries`` and ``dim`` alone, not on the mesh. The centres and spreads
    are drawn once and replicated; the queries are drawn from a key of their
    own and replicated. Each device holds its rows and one chunk of
    ``CHUNK_BYTES`` more while they are made.
    """
    pool, _, chunks = _chunks(seed, n, n_queries, dim, mesh)
    corpus = _zeros(mesh.size * rows_per_shard(n, mesh.size), dim, mesh)
    for first, chunk in chunks:
        corpus = _place(corpus, chunk, first, mesh=mesh).block_until_ready()  # one chunk queued at most
    return corpus, pool


def host_corpus_sharded(seed: int, n: int, n_queries: int, dim: int, mesh: Mesh):
    """``make_corpus_sharded``'s first ``n`` rows and its queries, float32 on
    the host, the same bits: the rows are made by the same programs one chunk
    at a time and each chunk copied and freed before the next, so that no
    device holds more than a chunk of ``CHUNK_BYTES`` of them, and the host
    the corpus once."""
    pool, count, chunks = _chunks(seed, n, n_queries, dim, mesh)
    per = rows_per_shard(n, mesh.size)
    rows = np.empty((n, dim), np.float32)
    for first, chunk in chunks:
        for shard in chunk.addressable_shards:
            start = (shard.index[0].start or 0) // (count * BLOCK) * per + int(first) * BLOCK
            stop = min(start + count * BLOCK, n)
            if stop > start:
                rows[start:stop] = np.asarray(shard.data)[: stop - start]
        chunk.delete()
    pool_host = np.asarray(pool)
    pool.delete()
    return rows, pool_host
