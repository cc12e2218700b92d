"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the semantics contracts: each Pallas kernel's test sweeps shapes and
dtypes and asserts allclose against the function here. They are also the
production implementation on non-TPU backends (``ops.py`` dispatches).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: f32 contractions on every backend: XLA's TPU default is one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def batched_ip(queries: jnp.ndarray, database: jnp.ndarray) -> jnp.ndarray:
    """Inner-product similarity matrix. queries (q, d), database (n, d) -> (q, n)."""
    return jnp.dot(queries, database.T, precision=HIGHEST, preferred_element_type=jnp.float32)


def l2_distance(queries: jnp.ndarray, database: jnp.ndarray) -> jnp.ndarray:
    """Squared-L2 distance matrix via the ||q||^2 - 2qx + ||x||^2 expansion."""
    qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)
    xn = jnp.sum(database.astype(jnp.float32) ** 2, axis=-1)
    ip = jnp.dot(queries, database.T, precision=HIGHEST, preferred_element_type=jnp.float32)
    return qn[:, None] - 2.0 * ip + xn[None, :]


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference multi-head attention with GQA and optional sliding window.

    q: (b, sq, hq, dh); k/v: (b, sk, hkv, dh); hq must be a multiple of hkv.
    Returns (b, sq, hq, dh). ``window`` = sliding-window size (keys within
    [i - window + 1, i] attend, Mistral convention).
    """
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, groups, dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    # positions: queries occupy the last sq slots of the sk-long key axis
    qpos = jnp.arange(sq) + (sk - sq)
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(b, sq, hq, dh).astype(q.dtype)
