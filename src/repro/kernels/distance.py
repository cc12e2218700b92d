"""Tiled MXU distance kernel (inner-product / squared-L2).

The VDMS hot spot: similarity of a query block against a database shard.
TPU adaptation (vs. the GPU cuBLAS-GEMM + epilogue formulation):

* the (Q, D) x (D, N) contraction is tiled onto the MXU with 128-aligned
  BlockSpecs; the K (=D) dimension is the innermost grid axis with a VMEM
  f32 accumulator, so arbitrary embedding dims stream through VMEM;
* the L2 epilogue (||q||^2 - 2 q.x + ||x||^2) is fused into the flush step —
  the norms ride along as VMEM blocks and the distance matrix never
  round-trips HBM between GEMM and epilogue.

Grid: (Q/bq, N/bn, D/bk), accumulating over the last (arbitrary) axis.
VMEM working set per step: bq*bk + bn*bk + bq*bn floats — the default tile
(128, 512, 128) uses ~0.6 MB, comfortably inside a v5e core's ~16 MB VMEM
with double buffering.

Memory-layout contract (shared by every kernel in this package, see
``docs/KERNELS.md``): operands arrive row-major and are zero-padded up to
the block multiple on every tiled axis by the host-side wrapper — padded
query rows produce garbage rows that the wrapper slices off, padded D
columns contribute zero to the contraction, and padded N columns are cut by
the final slice. All accumulation is f32 in VMEM scratch regardless of the
storage dtype, and the contraction runs at ``Precision.HIGHEST`` like the
XLA reference in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dist_kernel(q_ref, x_ref, qn_ref, xn_ref, o_ref, acc_ref, *, kind: str, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q, x = q_ref[...], x_ref[...]
    if q.dtype != x.dtype:  # Mosaic contracts one dtype: f32 queries, bf16 storage
        q, x = q.astype(jnp.float32), x.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        if kind == "ip":
            o_ref[...] = acc_ref[...]
        else:  # fused L2 epilogue: ||q||^2 - 2 q.x + ||x||^2
            o_ref[...] = qn_ref[...] - 2.0 * acc_ref[...] + xn_ref[...]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("kind", "bq", "bn", "bk", "interpret"))
def distance_pallas(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    kind: str = "ip",
    bq: int = 128,
    bn: int = 512,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """queries (q, d), database (n, d) -> (q, n) similarity/distance, f32."""
    assert kind in ("ip", "l2")
    q, d = queries.shape
    n, _ = database.shape
    bq, bn, bk = min(bq, _round_up(q, 8)), min(bn, _round_up(n, 128)), min(bk, _round_up(d, 128))
    qp, np_, dp = _round_up(q, bq), _round_up(n, bn), _round_up(d, bk)
    qpad = jnp.pad(queries, ((0, qp - q), (0, dp - d)))
    xpad = jnp.pad(database, ((0, np_ - n), (0, dp - d)))
    qn = jnp.sum(qpad.astype(jnp.float32) ** 2, axis=1, keepdims=True)  # (qp, 1)
    xn = jnp.sum(xpad.astype(jnp.float32) ** 2, axis=1, keepdims=True).T  # (1, np)
    k_steps = dp // bk
    grid = (qp // bq, np_ // bn, k_steps)

    out = pl.pallas_call(
        functools.partial(_dist_kernel, kind=kind, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bq, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bn), jnp.float32)],
        interpret=interpret,
    )(qpad, xpad, qn, xn)
    return out[:q, :n]
