"""Impl-switch parity for the public kernel wrappers in kernels/ops.py.

The CI ``kernel-parity`` job runs exactly this module: every op dispatched
through ``impl="pallas_interpret"`` (the Pallas kernel executed in interpret
mode on CPU) must match ``impl="xla"`` (the reference path), so TPU kernel
changes cannot land unexercised.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("q,n,d", [(16, 256, 64), (5, 100, 96)])
def test_batched_ip_parity(q, n, d):
    Q = jnp.asarray(RNG.standard_normal((q, d)), jnp.float32)
    X = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    got = ops.batched_ip(Q, X, impl="pallas_interpret")
    want = ops.batched_ip(Q, X, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("q,n,d", [(16, 256, 64), (3, 80, 33)])
def test_l2_distance_parity(q, n, d):
    Q = jnp.asarray(RNG.standard_normal((q, d)), jnp.float32)
    X = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    got = ops.l2_distance(Q, X, impl="pallas_interpret")
    want = ops.l2_distance(Q, X, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize(
    "b,sq,sk,hq,hkv,dh,causal,win",
    [(1, 64, 64, 4, 2, 32, True, None), (1, 96, 96, 2, 1, 32, True, 48)],
)
def test_flash_attention_parity(b, sq, sk, hq, hkv, dh, causal, win):
    q = jnp.asarray(RNG.standard_normal((b, sq, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, sk, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, sk, hkv, dh)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=win, impl="pallas_interpret")
    want = ops.flash_attention(q, k, v, causal=causal, window=win, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_impl_switch_roundtrip():
    before = ops.get_default_impl()
    try:
        ops.set_default_impl("pallas_interpret")
        assert ops.get_default_impl() == "pallas_interpret"
        X = jnp.asarray(RNG.standard_normal((4, 32)), jnp.float32)
        out = ops.batched_ip(X, X)  # default impl resolves to interpret mode
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(ops.batched_ip(X, X, impl="xla")),
            atol=2e-4,
            rtol=2e-4,
        )
    finally:
        ops.set_default_impl(before)


# ---------------------------------------------------------------------------
# fused search pipelines (probe -> scan -> in-kernel top-k)
# ---------------------------------------------------------------------------
def _ivf_fixture(n_seg, s, d, nlist, nprobe, dead_tail=0, seed=3):
    """Segments + centroids + member lists + gids for the fused ops, built
    with the same member-list layout (capacity-bound, -1 padded) the real
    IVF builds use."""
    from repro.vdms.indexes import _ivf_cap, _member_lists

    rng = np.random.default_rng(seed)
    segs = rng.standard_normal((n_seg, s, d)).astype(np.float32)
    assign = rng.integers(0, nlist, (n_seg, s))
    cents = np.stack([
        np.stack([
            segs[z][assign[z] == l].mean(0) if (assign[z] == l).any() else np.zeros(d)
            for l in range(nlist)
        ])
        for z in range(n_seg)
    ]).astype(np.float32)
    cap = _ivf_cap(s, nlist, nprobe)
    members = np.stack([_member_lists(assign[z], nlist, cap) for z in range(n_seg)])
    gids = np.arange(n_seg * s, dtype=np.int32).reshape(n_seg, s)
    if dead_tail:
        gids[:, -dead_tail:] = -1
    return segs, cents, members, gids


def _assert_topk_sets_match(a, b, atol=2e-4):
    """Fused contract: candidate SETS and scores match; tie order may not."""
    (la, sa), (lb, sb) = a, b
    la, sa, lb, sb = map(np.asarray, (la, sa, lb, sb))
    assert la.shape == lb.shape and sa.shape == sb.shape
    for z in range(la.shape[0]):
        for i in range(la.shape[1]):
            fa = {int(v) for v, x in zip(la[z, i], sa[z, i]) if np.isfinite(x)}
            fb = {int(v) for v, x in zip(lb[z, i], sb[z, i]) if np.isfinite(x)}
            assert fa == fb, f"lid sets differ at seg {z} row {i}: {fa ^ fb}"
            np.testing.assert_allclose(
                np.sort(sa[z, i][np.isfinite(sa[z, i])]),
                np.sort(sb[z, i][np.isfinite(sb[z, i])]),
                atol=atol,
            )


@pytest.mark.parametrize(
    "s,nlist,nprobe,k,dead,mask_dead",
    [
        (100, 10, 3, 16, 0, False),   # n < block size
        (256, 8, 4, 10, 0, False),    # exactly block-aligned n
        (120, 6, 2, 400, 20, False),  # k > candidate pool, dead slots kept
        (120, 6, 2, 12, 20, True),    # dead slots dropped pre-top-k
    ],
)
def test_fused_sq8_topk_parity(s, nlist, nprobe, k, dead, mask_dead):
    d, b = 40, 5
    segs, cents, members, gids = _ivf_fixture(2, s, d, nlist, nprobe, dead_tail=dead)
    scale = (np.abs(segs).max(axis=(0, 1)) / 127.0 + 1e-12).astype(np.float32)
    codes = np.clip(np.round(segs / scale), -127, 127).astype(np.int8)
    q = np.random.default_rng(4).standard_normal((b, d)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(codes), jnp.asarray(scale),
            jnp.asarray(cents), jnp.asarray(members), jnp.asarray(gids))
    kw = dict(nprobe=nprobe, k=k, mask_dead=mask_dead)
    _assert_topk_sets_match(
        ops.fused_ivf_sq8_topk(*args, impl="pallas_interpret", **kw),
        ops.fused_ivf_sq8_topk(*args, impl="xla", **kw),
    )


@pytest.mark.parametrize(
    "s,nlist,nprobe,k,dead,mask_dead",
    [
        (100, 10, 3, 16, 0, False),
        (256, 8, 4, 10, 0, False),
        (120, 6, 2, 400, 20, True),
    ],
)
def test_fused_pq_topk_parity(s, nlist, nprobe, k, dead, mask_dead):
    d, b, m, c = 40, 5, 4, 16
    segs, cents, members, gids = _ivf_fixture(2, s, d, nlist, nprobe, dead_tail=dead)
    rng = np.random.default_rng(5)
    dsub = d // m
    cb = (rng.standard_normal((m, c, dsub)) * 0.1).astype(np.float32)
    x = segs.reshape(-1, m, dsub)
    codes = np.empty((segs.shape[0], s, m), np.uint8)
    for j in range(m):
        d2 = (np.sum(x[:, j] ** 2, 1)[:, None] - 2 * x[:, j] @ cb[j].T
              + np.sum(cb[j] ** 2, 1)[None, :])
        codes[..., j] = np.argmin(d2, 1).astype(np.uint8).reshape(segs.shape[0], s)
    q = rng.standard_normal((b, d)).astype(np.float32)
    lut = np.einsum("bmd,mcd->bmc", q.reshape(b, m, dsub), cb).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(lut), jnp.asarray(codes),
            jnp.asarray(cents), jnp.asarray(members), jnp.asarray(gids))
    kw = dict(nprobe=nprobe, k=k, mask_dead=mask_dead)
    _assert_topk_sets_match(
        ops.fused_ivf_pq_topk(*args, impl="pallas_interpret", **kw),
        ops.fused_ivf_pq_topk(*args, impl="xla", **kw),
    )


def _sq8_case(n_seg, b, s, nlist, nprobe):
    d = 40
    segs, cents, members, gids = _ivf_fixture(n_seg, s, d, nlist, nprobe)
    scale = (np.abs(segs).max(axis=(0, 1)) / 127.0 + 1e-12).astype(np.float32)
    codes = np.clip(np.round(segs / scale), -127, 127).astype(np.int8)
    q = np.random.default_rng(6).standard_normal((b, d)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, codes, scale, cents, members, gids)))


def _pq_case(n_seg, b, s, nlist, nprobe):
    d, m, c = 40, 4, 64  # 64**4 code words: exact score ties are rare
    segs, cents, members, gids = _ivf_fixture(n_seg, s, d, nlist, nprobe)
    rng = np.random.default_rng(7)
    codes = rng.integers(0, c, (n_seg, s, m)).astype(np.uint8)
    q = rng.standard_normal((b, d)).astype(np.float32)
    lut = rng.standard_normal((b, m, c)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, lut, codes, cents, members, gids)))


@pytest.mark.parametrize("family", ["sq8", "pq"])
@pytest.mark.parametrize("b,bq", [(20, 8), (33, 16), (130, 128)])
def test_fused_kernels_tile_queries_and_segments(family, b, bq):
    """The stacked kernels' grid (segment, query block, tile): several
    segments and query blocks, ragged last blocks, must equal the XLA
    reference per segment."""
    from repro.kernels.fused_adc import fused_ivf_pq_topk_pallas
    from repro.kernels.fused_scan import fused_ivf_sq8_topk_pallas

    kw = dict(nprobe=2, k=12, mask_dead=False)
    if family == "sq8":
        args = _sq8_case(3, b, 300, 6, 2)
        got = fused_ivf_sq8_topk_pallas(
            *args[:4], ops._cluster_of(args[4], 300), args[5], bq=bq, interpret=True, **kw
        )
        want = ops.fused_ivf_sq8_topk(*args, impl="xla", **kw)
    else:
        args = _pq_case(3, b, 300, 6, 2)
        got = fused_ivf_pq_topk_pallas(
            *args[:4], ops._cluster_of(args[4], 300), args[5], bq=bq, interpret=True, **kw
        )
        want = ops.fused_ivf_pq_topk(*args, impl="xla", **kw)
    assert np.asarray(got[0]).shape == (3, b, 12)
    _assert_topk_sets_match(got, want)
