"""``sq8_topk_pass_share`` on the CPU at a tiny size, the kernel in interpret
mode: the share the kernel's own count gives, and nothing from a kernel
that returns no count."""
import functools
import types

import numpy as np
import pytest

from bench import run
from bench.tests.test_check import tiny

CELL = "glove100-sq8.closed"


@pytest.fixture(scope="module")
def stage():
    _, config, mix = tiny(CELL)
    config["shape"].update(n=11692)  # three sealed segments of 4,096, the last part-filled
    return run.set_up(config, mix, seed=2**32 + 5), mix


def ctx_of(stage):
    st, mix = stage
    log = types.SimpleNamespace(rows=[np.arange(mix["request_queries"]) + 7])
    return types.SimpleNamespace(searcher=st.searcher, pool=st.pool, mix=mix, log=log,
                                 say=lambda *a: None)


def test_share_is_the_kernels_count(stage, monkeypatch):
    from repro.kernels import fused_scan

    counts = []
    kernel = functools.partial(fused_scan.fused_ivf_sq8_topk_pallas, interpret=True)

    def counted(*args, **kw):
        out = kernel(*args, **kw)
        counts.append((np.asarray(out[2]), kw["k"]))
        return out

    monkeypatch.setattr(fused_scan, "fused_ivf_sq8_topk_pallas",
                        functools.wraps(kernel)(counted))
    share = run.reader("sq8_topk_pass_share")(ctx_of(stage))
    [(passes, k)] = counts
    assert passes.shape == (3, 1)  # 3 segments, one block of 64 query rows
    assert k == 64 and (passes <= k * 16).all() and (passes > 0).all()
    assert share == pytest.approx(100.0 * passes.sum() / (k * 16 * 1 * 3))
    assert 0.0 < share < 100.0


def test_a_kernel_without_a_count_reads_nothing(stage, monkeypatch):
    from repro.kernels import fused_scan

    kernel = functools.partial(fused_scan.fused_ivf_sq8_topk_pallas, interpret=True)
    monkeypatch.setattr(fused_scan, "fused_ivf_sq8_topk_pallas",
                        functools.wraps(kernel)(lambda *a, **kw: kernel(*a, **kw)[:2]))
    assert run.reader("sq8_topk_pass_share")(ctx_of(stage)) is None
