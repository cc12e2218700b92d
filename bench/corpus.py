"""The corpus and the query pool of a cell, made on the device from the seed.

The mixture is the one of ``_glove_like`` in ``repro/vdms/datasets.py``
(copied here so that an edit there cannot move the yardstick): clustered
Gaussians, one cluster per 256 rows (at least 32), centres of scale 2, each
cluster with its own spread in [0.6, 1.4), every row L2-normalised. Queries
are drawn from the same mixture as the corpus, as in ``make_dataset``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


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
