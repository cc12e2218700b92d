"""Public jit'd wrappers for the compute hot-spot kernels.

``impl`` selects the backend:
  * "xla"              — the pure-jnp reference (production path on CPU and the
                          GSPMD dry-run path; XLA fuses these well),
  * "pallas"           — the TPU Pallas kernel (TARGET hardware),
  * "pallas_interpret" — the Pallas kernel executed in interpret mode (CPU
                          correctness validation; used by the test suite).

The default is resolved on first use, not at import: "pallas" when JAX's
default backend is a TPU, else "xla". Override it per call or with
set_default_impl().
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref

IMPLS = ("xla", "pallas", "pallas_interpret")
_DEFAULT_IMPL: Optional[str] = None  # resolved on first use


def set_default_impl(impl: str) -> None:
    """Pin the impl that ``impl=None`` resolves to. Jitted programs traced
    under the previous impl are dropped from JAX's in-memory caches, so the
    next call of any pipeline retraces with the new one."""
    global _DEFAULT_IMPL
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; use one of {IMPLS}")
    if impl != _DEFAULT_IMPL:
        jax.clear_caches()
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    global _DEFAULT_IMPL
    if _DEFAULT_IMPL is None:
        _DEFAULT_IMPL = "pallas" if jax.default_backend() == "tpu" else "xla"
    return _DEFAULT_IMPL


def _resolve(impl: Optional[str]) -> str:
    impl = impl or get_default_impl()
    if impl == "pallas_interpret" and jax.default_backend() == "tpu":
        raise ValueError("impl='pallas_interpret' on a TPU: use 'pallas'")
    return impl


def _cluster_of(members, s: int):
    """Per-segment inverse of the member lists, (n_seg, s) — the mask-scan
    kernels' view of cluster membership. A ``lax.map`` and not a ``vmap``:
    the TPU compiler takes seconds per hundred segments on a batched
    scatter, and a fraction of a second on the loop."""
    from .fused_scan import members_to_cluster_of

    return jax.lax.map(lambda m: members_to_cluster_of(m, s), members)


# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("impl",))
def batched_ip(queries, database, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.batched_ip(queries, database)
    from .distance import distance_pallas

    return distance_pallas(queries, database, kind="ip", interpret=impl == "pallas_interpret")


@partial(jax.jit, static_argnames=("impl",))
def l2_distance(queries, database, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.l2_distance(queries, database)
    from .distance import distance_pallas

    return distance_pallas(queries, database, kind="l2", interpret=impl == "pallas_interpret")


@partial(jax.jit, static_argnames=("nprobe", "k", "mask_dead", "impl"))
def fused_ivf_sq8_topk(q, codes, scale, centroids, members, gids, *,
                       nprobe: int, k: int, mask_dead: bool = False,
                       impl: Optional[str] = None):
    """Fused IVF probe → int8 dequant scan → top-k over stacked segments.

    q (B, d); codes (n_seg, s, d) int8; scale (d,); centroids
    (n_seg, nlist, d); members (n_seg, nlist, cap); gids (n_seg, s)
    -> (lids, sims), each (n_seg, B, k) with -1/-inf empty slots.

    Candidate SETS and scores match across impls (and the composed
    per-family search); slot ORDER among tied scores is impl-defined.
    ``mask_dead`` drops gid<0 slots before the top-k (the clamped static
    merge); default keeps them, mirroring the composed post-top-k masking.
    """
    impl = _resolve(impl)
    from .fused_scan import fused_ivf_sq8_topk_pallas, fused_ivf_sq8_topk_xla

    if impl == "xla":
        return jax.vmap(
            lambda c, ce, me, g: fused_ivf_sq8_topk_xla(
                q, c, scale, ce, me, g, nprobe=nprobe, k=k, mask_dead=mask_dead
            )
        )(codes, centroids, members, gids)
    return fused_ivf_sq8_topk_pallas(
        q, codes, scale, centroids, _cluster_of(members, codes.shape[1]), gids,
        nprobe=nprobe, k=k, mask_dead=mask_dead, interpret=impl == "pallas_interpret",
    )


@partial(jax.jit, static_argnames=("nprobe", "k", "mask_dead", "impl"))
def fused_ivf_pq_topk(q, lut, codes, centroids, members, gids, *,
                      nprobe: int, k: int, mask_dead: bool = False,
                      impl: Optional[str] = None):
    """Fused IVF probe → PQ ADC scan → top-k over stacked segments.

    q (B, d); lut (B, m, c) f32 ADC similarity table; codes (n_seg, s, m)
    integer; centroids (n_seg, nlist, d); members (n_seg, nlist, cap);
    gids (n_seg, s) -> (lids, sims), each (n_seg, B, k). Same set/order
    contract as :func:`fused_ivf_sq8_topk`.
    """
    impl = _resolve(impl)
    from .fused_adc import fused_ivf_pq_topk_pallas, fused_ivf_pq_topk_xla

    if impl == "xla":
        return jax.vmap(
            lambda c, ce, me, g: fused_ivf_pq_topk_xla(
                q, lut, c, ce, me, g, nprobe=nprobe, k=k, mask_dead=mask_dead
            )
        )(codes, centroids, members, gids)
    return fused_ivf_pq_topk_pallas(
        q, lut, codes, centroids, _cluster_of(members, codes.shape[1]), gids,
        nprobe=nprobe, k=k, mask_dead=mask_dead, interpret=impl == "pallas_interpret",
    )


@partial(jax.jit, static_argnames=("k", "impl"))
def topk_by_score(ids, sims, k: int, impl: Optional[str] = None):
    """Top-k-by-score selection over flat candidate lists — the merge-tree
    primitive behind ``repro.vdms.merge`` (composed / fused / sharded paths).

    ids, sims (B, W) -> (ids_k, sims_k), each (B, k), score-descending with
    ``lax.top_k`` tie semantics: equal scores keep the lowest flat index, so
    blockwise prefiltering (per-shard partial top-k) composes with a root
    merge without reordering ties. ``k`` must be <= W.

    All impls share the XLA lowering today: ``lax.top_k`` already maps to the
    TPU sort unit, so a dedicated Pallas kernel buys nothing until the merge
    is fused into the scan epilogue (see docs/KERNELS.md).
    """
    del impl  # reserved for a fused Pallas merge epilogue
    top_s, top_i = jax.lax.top_k(sims, k)
    return jnp.take_along_axis(ids, top_i, axis=1), top_s


@partial(jax.jit, static_argnames=("causal", "window", "impl"))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "xla":
        # block-scanned flash with custom VJP: never materializes (sq, sk);
        # ref.flash_attention remains the semantics oracle for tests.
        from .flash_xla import flash_attention_xla

        return flash_attention_xla(q, k, v, causal, window)
    from .flash_attention import flash_attention_pallas

    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, interpret=impl == "pallas_interpret"
    )
