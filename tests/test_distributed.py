"""Distribution layer: sharding rules, divisibility fallbacks, conflict
resolution, and a real (subprocess) mini-dry-run with 8 host devices."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import ShardingRules, param_axes_for
from repro.launch import hlo_analysis


# ---------------------------------------------------------------------------
# rules (mesh=None paths are pure logic — no devices needed)
# ---------------------------------------------------------------------------
def test_rules_no_mesh_is_noop():
    rules = ShardingRules(None)
    assert rules.sharding(("batch", None)) is None


def test_param_axes_inference():
    assert param_axes_for(("layers", "attn", "wq"), (4, 128, 256)) == ("layers", "fsdp", "heads")
    assert param_axes_for(("embed",), (1024, 64)) == ("vocab", "fsdp")
    assert param_axes_for(("norm", "scale"), (64,)) == (None,)
    # unknown names fall back to replicated
    assert param_axes_for(("mystery",), (3, 4)) == (None, None)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
HLO_SAMPLE = textwrap.dedent(
    """
    %x = bf16[16,128]{1,0} parameter(0)
    ROOT %all-reduce = f32[64,128]{1,0} all-reduce(%dot), channel_id=1
    %ag = bf16[32,256]{1,0} all-gather(%y), dimensions={0}
    %rs.1 = (f32[8,8]{1,0}, f32[8,8]{1,0}) reduce-scatter(%a, %b), dimensions={0}
    %not-a-collective = f32[9,9]{1,0} add(%c, %d)
    """
)


def test_collective_bytes_parsing():
    cb = hlo_analysis.collective_bytes(HLO_SAMPLE)
    assert cb["all-reduce"] == 64 * 128 * 4
    assert cb["all-gather"] == 32 * 256 * 2
    assert cb["reduce-scatter"] == 2 * 8 * 8 * 4
    assert "add" not in cb
    counts = hlo_analysis.count_collectives(HLO_SAMPLE)
    assert counts == {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 1}


def test_roofline_terms_and_bottleneck():
    r = hlo_analysis.Roofline(
        arch="a",
        shape="s",
        mesh="16x16",
        chips=256,
        hlo_flops=1e18,
        hlo_bytes=1e12,
        coll_bytes=1e12,
        coll_breakdown={},
        coll_counts={},
        model_flops=5e17,
        peak_mem_per_dev=1e9,
    )
    assert r.compute_s == pytest.approx(1e18 / (256 * hlo_analysis.PEAK_FLOPS))
    assert r.bottleneck == "compute"
    assert 0 < r.roofline_fraction <= 1.0
    assert r.useful_ratio == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# mini dry-run in a subprocess (needs its own XLA_FLAGS before jax import)
# ---------------------------------------------------------------------------
MINI_DRYRUN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax
    from repro.configs.base import get_arch, reduce, SHAPES
    from repro.distributed.sharding import ShardingRules
    from repro.launch.dryrun import _compile_step
    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    cfg = dataclasses.replace(
        reduce(get_arch("glm4-9b")), name="mini", d_model=256, n_heads=8,
        n_kv_heads=4, head_dim=32, d_ff=512, vocab=1024, n_layers=2,
    )
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
    rules = ShardingRules(mesh)
    lowered, compiled = _compile_step(cfg, shape, mesh, rules, "nothing")
    from repro.launch import hlo_analysis
    cb = hlo_analysis.collective_bytes(compiled.as_text())
    ma = compiled.memory_analysis()
    print(json.dumps({
        "ok": True,
        "has_collectives": bool(cb),
        "temp": int(ma.temp_size_in_bytes),
    }))
    """
)


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # the child never takes a chip from its parent
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN],
        capture_output=True,
        text=True,
        env=env,
        timeout=420,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"] and result["has_collectives"]
    assert result["temp"] > 0
