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
def _ivf_fixture(n_seg, s, d, nlist, nprobe, dead_tail=0, seed=3, segs=None, assign=None):
    """Segments + centroids + member lists + gids for the fused ops, built
    with the same member-list layout (capacity-bound, -1 padded) the real
    IVF builds use. ``segs`` and ``assign`` replace the random rows and
    cluster assignment."""
    from repro.vdms.indexes import _ivf_cap, _member_lists

    rng = np.random.default_rng(seed)
    if segs is None:
        segs = rng.standard_normal((n_seg, s, d)).astype(np.float32)
    if assign is None:
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


# Integer-valued fixtures: every score is an exact small integer on every path,
# so ties are exact and plentiful, and a plain replay of the kernel's selection
# rule in numpy can be compared with it exactly.
TILE = 256  # the fused kernels' default segment tile (``bn``)


def _tie_layout(rng, n_seg, s, lo, hi, width, first_tile=None):
    """Integer rows in [lo, hi) with exact duplicates, and the cluster
    assignment that keeps each duplicate in its twin's cluster: odd rows copy
    the even row before them (a tie inside a tile) and the second half copies
    the first (a tie across tiles). ``first_tile=(lo2, hi2)`` instead draws the
    first tile's rows from [lo2, hi2) and keeps no duplicates."""
    rows = rng.integers(lo, hi, (n_seg, s, width))
    assign = rng.integers(0, 4, (n_seg, s))
    if first_tile is not None:
        rows[:, :TILE] = rng.integers(*first_tile, (n_seg, TILE, width))
        return rows, assign
    for a in (rows, assign):
        a[:, 1::2] = a[:, 0:-1:2][:, : a[:, 1::2].shape[1]]
        a[:, s // 2 : 2 * (s // 2)] = a[:, : s // 2]
    return rows, assign


def _int_case(family, n_seg, b, s, nlist, nprobe, dead, data, seed=11):
    """(args of the ops call, exact scores (n_seg, b, s)) on integer data:
    ``data`` is ``"ties"`` (duplicated rows) or ``"first_tile"`` (every
    candidate past the first tile scores below every one inside it, so those
    tiles run no selection pass once the list is full)."""
    rng = np.random.default_rng(seed)
    d = 40
    first = data == "first_tile"
    if family == "sq8":
        codes, assign = _tie_layout(rng, n_seg, s, -3, 1 if first else 4, d,
                                    (1, 4) if first else None)
        assign = assign % nlist
        segs, cents, members, gids = _ivf_fixture(
            n_seg, s, d, nlist, nprobe, dead_tail=dead, segs=codes.astype(np.float32),
            assign=assign,
        )
        q = rng.integers(1 if first else -3, 4, (b, d))
        exact = np.einsum("bd,zsd->zbs", q, codes)
        scale = np.ones(d, np.float32)
        args = (q.astype(np.float32), codes.astype(np.int8), scale, cents, members, gids)
    else:
        m, c = 4, 16
        codes, assign = _tie_layout(rng, n_seg, s, c // 2 if first else 0, c, m,
                                    (0, c // 2) if first else None)
        assign = assign % nlist
        segs, cents, members, gids = _ivf_fixture(n_seg, s, d, nlist, nprobe, dead_tail=dead,
                                                  assign=assign)
        lut = rng.integers(-4, 5, (b, m, c))
        if first:
            lut[:, :, : c // 2] = rng.integers(1, 5, (b, m, c // 2))
            lut[:, :, c // 2 :] = rng.integers(-4, 1, (b, m, c // 2))
        exact = np.stack([lut[:, np.arange(m), codes[z]].sum(-1) for z in range(n_seg)])
        q = rng.standard_normal((b, d))
        args = (q.astype(np.float32), lut.astype(np.float32), codes.astype(np.uint8), cents,
                members, gids)
    return tuple(map(jnp.asarray, args)), exact


def _candidates(family, args, nprobe, mask_dead):
    """Each (segment, row)'s candidate local ids, from the XLA reference at
    the full segment width."""
    fn = ops.fused_ivf_sq8_topk if family == "sq8" else ops.fused_ivf_pq_topk
    s = args[-1].shape[1]  # gids (n_seg, s)
    lids, sims = fn(*args, nprobe=nprobe, k=s, mask_dead=mask_dead, impl="xla")
    lids, sims = np.asarray(lids), np.asarray(sims)
    return [[lids[z, r][np.isfinite(sims[z, r])] for r in range(lids.shape[1])]
            for z in range(lids.shape[0])]


def _gated_replay(cands, exact, k, bq, bn=TILE):
    """The kernels' selection rule replayed in numpy: each row's list is the
    first ``k`` of its candidates by (score descending, local id), and a tile
    runs, per query block, the largest over its rows of min(k, number of the
    tile's candidates strictly above the row's k-th score, -inf while fewer
    than k are held). Returns (lids, sims, passes per (segment, block))."""
    n_seg, b, s = exact.shape
    lids = np.full((n_seg, b, k), -1, np.int64)
    sims = np.full((n_seg, b, k), -np.inf)
    passes = np.zeros((n_seg, -(-b // bq)), np.int64)
    for z in range(n_seg):
        for i0 in range(0, b, bq):
            rows = range(i0, min(b, i0 + bq))
            held = {r: [] for r in rows}  # sorted (-score, lid)
            for t0 in range(0, s, bn):
                need = 0
                for r in rows:
                    tile = [(-int(exact[z, r, l]), int(l)) for l in cands[z][r]
                            if t0 <= l < t0 + bn]
                    thr = -held[r][k - 1][0] if len(held[r]) >= k else -np.inf
                    need = max(need, min(k, sum(-neg > thr for neg, _ in tile)))
                    held[r] = sorted(held[r] + tile)[:k]
                assert need <= k
                passes[z, i0 // bq] += need
            for r in rows:
                lids[z, r, : len(held[r])] = [l for _, l in held[r]]
                sims[z, r, : len(held[r])] = [-neg for neg, _ in held[r]]
    return lids, sims, passes


def _assert_exact_topk(got, want, exact, in_order):
    """``got`` against the replayed lists ``want``: the same scores; ids whose
    exact scores are the returned ones, each once; the same ids above each
    row's k-th score; and, ``in_order``, the very same slots (the kernels'
    tie rule: score descending, then local id)."""
    (gl, gs), (wl, ws) = map(np.asarray, got), want
    assert gl.shape == wl.shape
    np.testing.assert_array_equal(np.sort(gs, -1), np.sort(ws, -1))
    if in_order:
        np.testing.assert_array_equal(gl, wl)
        np.testing.assert_array_equal(gs, ws)
    for z in range(gl.shape[0]):
        for r in range(gl.shape[1]):
            live = np.isfinite(gs[z, r])
            ids = gl[z, r][live]
            assert len(set(ids.tolist())) == len(ids), f"repeated id at seg {z} row {r}"
            np.testing.assert_array_equal(exact[z, r][ids], gs[z, r][live])
            kth = ws[z, r][-1]
            assert set(ids[gs[z, r][live] > kth].tolist()) == set(
                wl[z, r][ws[z, r] > kth].tolist())


def _assert_parity(family, case, nprobe, k, mask_dead):
    """Pallas (interpret) and XLA through ``ops`` on an integer fixture, each
    against the replayed selection rule: the kernel slot for slot, the
    reference (whose ties follow probe order) as a top-k of the same scores."""
    args, exact = case
    fn = ops.fused_ivf_sq8_topk if family == "sq8" else ops.fused_ivf_pq_topk
    kw = dict(nprobe=nprobe, k=k, mask_dead=mask_dead)
    want = _gated_replay(_candidates(family, args, nprobe, mask_dead), exact, k, bq=8)[:2]
    _assert_exact_topk(fn(*args, impl="pallas_interpret", **kw), want, exact, in_order=True)
    _assert_exact_topk(fn(*args, impl="xla", **kw), want, exact, in_order=False)


def _old(*values):
    """A case of the original grid, under the id it has always had."""
    return pytest.param(*values, "normal", id="-".join(map(str, values)))


@pytest.mark.parametrize(
    "s,nlist,nprobe,k,dead,mask_dead,data",
    [
        _old(100, 10, 3, 16, 0, False),   # n < block size
        _old(256, 8, 4, 10, 0, False),    # exactly block-aligned n
        _old(120, 6, 2, 400, 20, False),  # k > candidate pool, dead slots kept
        _old(120, 6, 2, 12, 20, True),    # dead slots dropped pre-top-k
        pytest.param(600, 4, 2, 16, 0, False, "ties", id="ties"),
        pytest.param(600, 4, 2, 16, 40, True, "ties", id="ties-mask_dead"),
        pytest.param(1000, 4, 2, 16, 0, False, "first_tile", id="zero-pass-tiles"),
        pytest.param(1000, 4, 2, 16, 30, True, "first_tile", id="zero-pass-tiles-mask_dead"),
        pytest.param(600, 4, 2, 1, 0, False, "ties", id="k1-ties"),
        pytest.param(300, 6, 2, 1, 0, False, "normal", id="k1"),
        pytest.param(600, 8, 1, 128, 0, False, "ties", id="k-over-pool-ties"),
        pytest.param(1024, 4, 2, 128, 0, False, "normal", id="k128-tiles"),
        pytest.param(1024, 4, 2, 128, 100, True, "ties", id="k128-ties-mask_dead"),
    ],
)
def test_fused_sq8_topk_parity(s, nlist, nprobe, k, dead, mask_dead, data):
    d, b = 40, 5
    if data != "normal":
        _assert_parity("sq8", _int_case("sq8", 2, b, s, nlist, nprobe, dead, data),
                       nprobe, k, mask_dead)
        return
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
    "s,nlist,nprobe,k,dead,mask_dead,data",
    [
        _old(100, 10, 3, 16, 0, False),
        _old(256, 8, 4, 10, 0, False),
        _old(120, 6, 2, 400, 20, True),
        pytest.param(600, 4, 2, 16, 0, False, "ties", id="ties"),
        pytest.param(600, 4, 2, 16, 40, True, "ties", id="ties-mask_dead"),
        pytest.param(1000, 4, 2, 16, 0, False, "first_tile", id="zero-pass-tiles"),
        pytest.param(600, 4, 2, 1, 0, False, "ties", id="k1-ties"),
        pytest.param(600, 8, 1, 128, 20, False, "ties", id="k-over-pool-ties"),
        pytest.param(1024, 4, 2, 128, 0, False, "ties", id="k128-ties"),
    ],
)
def test_fused_pq_topk_parity(s, nlist, nprobe, k, dead, mask_dead, data):
    d, b, m, c = 40, 5, 4, 16
    if data != "normal":
        _assert_parity("pq", _int_case("pq", 2, b, s, nlist, nprobe, dead, data),
                       nprobe, k, mask_dead)
        return
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


def _pallas(family):
    from repro.kernels.fused_adc import fused_ivf_pq_topk_pallas
    from repro.kernels.fused_scan import fused_ivf_sq8_topk_pallas

    return fused_ivf_sq8_topk_pallas if family == "sq8" else fused_ivf_pq_topk_pallas


@pytest.mark.parametrize("family", ["sq8", "pq"])
@pytest.mark.parametrize(
    "b,bq,k",
    [
        pytest.param(20, 8, 12, id="20-8"),
        pytest.param(33, 16, 12, id="33-16"),
        pytest.param(130, 128, 12, id="130-128"),
        pytest.param(20, 8, 1, id="20-8-k1"),
        pytest.param(33, 16, 128, id="33-16-k128"),  # k > every row's candidate pool
    ],
)
def test_fused_kernels_tile_queries_and_segments(family, b, bq, k):
    """The stacked kernels' grid (segment, query block, tile): several
    segments and query blocks, ragged last blocks, must equal the XLA
    reference per segment."""
    kw = dict(nprobe=2, k=k, mask_dead=False)
    if family == "sq8":
        args = _sq8_case(3, b, 300, 6, 2)
        want = ops.fused_ivf_sq8_topk(*args, impl="xla", **kw)
    else:
        args = _pq_case(3, b, 300, 6, 2)
        want = ops.fused_ivf_pq_topk(*args, impl="xla", **kw)
    lids, sims, passes = _pallas(family)(
        *args[:4], ops._cluster_of(args[4], 300), args[5], bq=bq, interpret=True, **kw
    )
    assert np.asarray(lids).shape == (3, b, k)
    assert np.asarray(passes).shape == (3, -(-b // bq))
    _assert_topk_sets_match((lids, sims), want)


@pytest.mark.parametrize("family", ["sq8", "pq"])
@pytest.mark.parametrize("data,k", [("ties", 16), ("ties", 5), ("first_tile", 16)])
@pytest.mark.parametrize("mask_dead", [False, True])
def test_fused_topk_pass_count(family, data, k, mask_dead):
    """The kernels' selection-pass count per (segment, query block) equals a
    numpy replay of the gating rule over four tiles and three query blocks,
    and no tile runs more than ``k`` passes; the lists match the replay slot
    for slot."""
    b, bq, s, nlist, nprobe = 24, 8, 1000, 4, 2
    args, exact = _int_case(family, 2, b, s, nlist, nprobe, 40 if mask_dead else 0, data)
    kw = dict(nprobe=nprobe, k=k, mask_dead=mask_dead)
    lids, sims, passes = _pallas(family)(
        *args[:4], ops._cluster_of(args[4], s), args[5], bq=bq, interpret=True, **kw
    )
    want_l, want_s, want_passes = _gated_replay(
        _candidates(family, args, nprobe, mask_dead), exact, k, bq
    )
    np.testing.assert_array_equal(np.asarray(passes), want_passes)
    assert (want_passes <= k * -(-s // TILE)).all()
    if data == "first_tile":  # the first tile fills every list; the rest run no pass
        np.testing.assert_array_equal(want_passes, k)
    _assert_exact_topk((lids, sims), (want_l, want_s), exact, in_order=True)
