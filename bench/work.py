"""Required work of a search call, and the chip's peaks, for roofline shares.

Roofline rule. The least time a call's kernel could take is the larger of

* required FLOPs / the chip's bf16 peak (``bf16_flops_per_s``), and
* required bytes / the chip's HBM bandwidth (``hbm_bytes_per_s``),

and a kernel's roofline share is the summed least time of the calls in the
traced window over the kernel's summed device time there. Which of the two
terms bounds is reported beside the share.

"Required" is counted from the index arrays and the call's queries, never
from a kernel's grid, so it reads the same work whatever implements it:

* IVF_SQ8, FLOPs: each query's probe against every segment's centroids
  (``2 * n_seg * nlist * d``), plus ``2 * d`` for each row of the clusters
  that query probes (rows as the member lists hold them).
* IVF_SQ8, bytes: the int8 codes of every cluster that at least one query of
  the call probes, the float32 centroids, the float32 queries, and the
  per-segment top-k the kernel returns (an int32 id and a float32 score for
  each of ``n_seg * B * k``), each counted once.
* FLAT: ``2 * B * n * d`` FLOPs; the float32 corpus read once and the
  float32 queries.

On a cell of several chips the same rule holds with one chip's peaks: the
summed least time of the calls' required work at one chip's peak, over the
kernel's device time summed over the cell's devices (``Trace.timeline`` of
each device). The required work counts the index as placed, less the dead
padding segments that even out the shards: they hold no row. On one chip the
sum has one term, and this is the rule above.

Peaks are keyed by the ``device_kind`` that JAX reports (``peaks.json``); a
device that is not in the table is an error.
"""
from __future__ import annotations

import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least time, which term bounds it: ``flops`` or ``bytes``)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


@partial(jax.jit, static_argnames=("nprobe",))
def _probed(queries, centroids, rows, nprobe: int):
    """Rows probed by each query, summed over segments, and the union mask
    of (segment, cluster) pairs that some query probes."""
    csim = jnp.einsum("bd,zld->zbl", queries, centroids, precision=jax.lax.Precision.HIGHEST)
    _, probe = jax.lax.top_k(csim, nprobe)  # (n_seg, B, nprobe)
    per_query = jnp.take_along_axis(rows[:, None, :], probe, axis=2).sum(axis=(0, 2))
    n_seg = centroids.shape[0]
    seg = jnp.broadcast_to(jnp.arange(n_seg)[:, None, None], probe.shape)
    hit = jnp.zeros(rows.shape, bool).at[seg, probe].set(True)
    return per_query, hit


def sq8_call(queries: np.ndarray, centroids, members, nprobe: int, k: int) -> tuple[float, float]:
    """(FLOPs, bytes) an IVF_SQ8 search call over ``queries`` requires."""
    b, d = queries.shape
    n_seg, nlist, _ = centroids.shape
    rows = jnp.sum(jnp.asarray(members) >= 0, axis=-1).astype(jnp.int32)  # (n_seg, nlist)
    per_query, hit = _probed(jnp.asarray(queries, jnp.float32), centroids, rows, min(nprobe, nlist))
    probed_rows = float(np.asarray(per_query, np.float64).sum())
    codes = float(np.asarray(jnp.where(hit, rows, 0), np.float64).sum()) * d
    flops = 2.0 * b * n_seg * nlist * d + 2.0 * d * probed_rows
    nbytes = codes + 4.0 * n_seg * nlist * d + 4.0 * b * d + 8.0 * n_seg * b * k
    return flops, nbytes


def flat_call(b: int, n: int, d: int) -> tuple[float, float]:
    """(FLOPs, bytes) a FLAT search call of ``b`` queries over ``n`` rows requires."""
    return 2.0 * b * n * d, 4.0 * n * d + 4.0 * b * d


#: substrings of the device-op names of each family's Pallas scan kernel: the
#: custom call takes the name of the jitted wrapper (``fused_ivf_sq8_topk_pallas.1``)
KERNELS = {"IVF_SQ8": "fused_ivf_sq8_topk_pallas", "FLAT": "distance_pallas"}


def kernel_match(index_type: str):
    """A test of device-op names (``bench/trace.py`` ``op_name``) for the
    family's scan kernel."""
    prefix = KERNELS[index_type] + "."
    return lambda name: name.startswith(prefix)
