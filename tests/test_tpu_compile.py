"""Compiles of the search path for a described TPU v5e chip, without a chip.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described rather than attached. That catches what interpret mode cannot:
block shapes that break the (8, 128) tiling rule, primitives Mosaic cannot
lower, and kernels that want more VMEM than the chip has. Shapes are the
chip smoke's: glove-100's d=100, segments of 4096 rows with nlist=128, a
per-segment width of k=64, and 1,000 queries padded into chunks of 32.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.distance import distance_pallas
from repro.kernels.fused_adc import fused_ivf_pq_topk_pallas
from repro.kernels.fused_scan import fused_ivf_sq8_topk_pallas
from repro.vdms import engine

D, S, NLIST, K, CAP = 100, 4096, 128, 64, 88
N_SEG = 289  # ceil(1,183,514 / 4096): glove-100 at the default segment size
ROWS = 1024  # 1,000 smoke queries in 32 chunks of the default batch of 32
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used
    return compiled.as_text()


def test_fused_sq8_kernel_compiles(one_chip):
    hlo = _compile(
        one_chip,
        lambda q, c, sc, ce, cl, g: fused_ivf_sq8_topk_pallas(q, c, sc, ce, cl, g, nprobe=8, k=K),
        ((ROWS, D), jnp.float32),
        ((N_SEG, S, D), jnp.int8),
        ((D,), jnp.float32),
        ((N_SEG, NLIST, D), jnp.float32),
        ((N_SEG, S), jnp.int32),
        ((N_SEG, S), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [5, 25])  # IVF_PQ's m=8 and m=32 snapped to divisors of 100
def test_fused_pq_kernel_compiles(one_chip, m):
    hlo = _compile(
        one_chip,
        lambda q, lut, c, ce, cl, g: fused_ivf_pq_topk_pallas(
            q, lut, c, ce, cl, g, nprobe=8, k=K
        ),
        ((ROWS, D), jnp.float32),
        ((ROWS, m, 256), jnp.float32),
        ((N_SEG, S, m), jnp.uint8),
        ((N_SEG, NLIST, D), jnp.float32),
        ((N_SEG, S), jnp.int32),
        ((N_SEG, S), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("family", ["sq8", "pq"])
def test_fused_kernels_compile_at_widest_k(one_chip, family):
    """The selection loop, whose trip count the scalar core reads from the
    tile's scores, at k=128 (the widest ``topk_merge_width``: no padding
    lanes in the running list); the tests above cover the default k=64."""
    queries, lists = ((ROWS, D), jnp.float32), ((N_SEG, S), jnp.int32)
    centroids = ((N_SEG, NLIST, D), jnp.float32)
    if family == "sq8":
        hlo = _compile(
            one_chip,
            lambda q, c, sc, ce, cl, g: fused_ivf_sq8_topk_pallas(
                q, c, sc, ce, cl, g, nprobe=8, k=128
            ),
            queries, ((N_SEG, S, D), jnp.int8), ((D,), jnp.float32), centroids, lists, lists,
        )
    else:
        hlo = _compile(
            one_chip,
            lambda q, lut, c, ce, cl, g: fused_ivf_pq_topk_pallas(
                q, lut, c, ce, cl, g, nprobe=8, k=128
            ),
            queries, ((ROWS, 5, 256), jnp.float32), ((N_SEG, S, 5), jnp.uint8), centroids,
            lists, lists,
        )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("storage", [jnp.float32, jnp.bfloat16])
def test_distance_kernel_compiles(one_chip, storage):
    hlo = _compile(
        one_chip, lambda q, x: distance_pallas(q, x), ((32, D), jnp.float32), ((S, D), storage)
    )
    assert "tpu_custom_call" in hlo


def test_ivf_sq8_pipeline_compiles(one_chip):
    """One whole jitted search step of IVF_SQ8 at the smoke's size, through
    the engine's fused hook with the Pallas impl pinned (the described chip
    is not the default backend, so the impl is set by hand)."""
    before = ops.get_default_impl()
    ops.set_default_impl("pallas")
    try:
        arrays = {
            "codes": ((N_SEG, S, D), jnp.int8),
            "scale": ((D,), jnp.float32),
            "gids": ((N_SEG, S), jnp.int32),
            "centroids": ((N_SEG, NLIST, D), jnp.float32),
            "members": ((N_SEG, NLIST, CAP), jnp.int32),
        }
        names = sorted(arrays)

        def step(qc, growing, growing_gids, *leaves):
            return engine._pipeline(
                qc, dict(zip(names, leaves)), growing, growing_gids,
                "IVF_SQ8", (("nprobe", 8),), K, 10, True, False,
            )

        hlo = _compile(
            one_chip,
            step,
            ((ROWS // 32, 32, D), jnp.float32),
            ((0, D), jnp.float32),
            ((0,), jnp.int32),
            *(arrays[n] for n in names),
        )
    finally:
        ops.set_default_impl(before)
    assert "tpu_custom_call" in hlo
