"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the pure-jnp
oracle in ref.py, plus gradient checks for the custom-VJP flash attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.distance import distance_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_xla import flash_attention_xla

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,n,d", [(17, 300, 96), (128, 1024, 128), (5, 64, 33), (1, 7, 256)])
@pytest.mark.parametrize("kind", ["ip", "l2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_kernel_matches_ref(q, n, d, kind, dtype):
    Q = jnp.asarray(RNG.standard_normal((q, d)), dtype)
    X = jnp.asarray(RNG.standard_normal((n, d)), dtype)
    got = distance_pallas(Q, X, kind=kind, interpret=True)
    want = ref.batched_ip(Q, X) if kind == "ip" else ref.l2_distance(Q, X)
    tol = 2e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


FA_CASES = [
    (2, 64, 64, 4, 2, 32, True, None),
    (1, 128, 256, 8, 8, 64, True, None),
    (2, 100, 100, 4, 1, 32, True, 48),
    (1, 1, 96, 4, 2, 64, True, None),  # decode-shaped
    (2, 48, 48, 6, 3, 16, False, None),  # bidirectional (encoder)
]


@pytest.mark.parametrize("b,sq,sk,hq,hkv,dh,causal,win", FA_CASES)
def test_flash_pallas_matches_ref(b, sq, sk, hq, hkv, dh, causal, win):
    q = jnp.asarray(RNG.standard_normal((b, sq, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, sk, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, sk, hkv, dh)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, window=win, interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


@pytest.mark.parametrize("b,sq,sk,hq,hkv,dh,causal,win", FA_CASES)
def test_flash_xla_matches_ref_fwd_and_grad(b, sq, sk, hq, hkv, dh, causal, win):
    q = jnp.asarray(RNG.standard_normal((b, sq, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, sk, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, sk, hkv, dh)), jnp.float32)
    got = flash_attention_xla(q, k, v, causal, win, 32, 64)
    want = ref.flash_attention(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    f1 = lambda *a: jnp.sum(jnp.sin(flash_attention_xla(*a, causal, win, 32, 64)))
    f2 = lambda *a: jnp.sum(jnp.sin(ref.flash_attention(*a, causal=causal, window=win)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=3e-4)


def test_flash_pallas_skips_fully_masked_tiles_correctly():
    # window smaller than one tile: many tiles fully masked
    q = jnp.asarray(RNG.standard_normal((1, 256, 2, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 256, 2, 32)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=16, bq=64, bk=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)
