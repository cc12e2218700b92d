import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Beyond-paper: VDTuner tunes this framework's own serving/training stack.

Remat strategy plays the role of the index type; flash block sizes and
sequence-parallelism are the parameters; the conflicting objectives are
(estimated step throughput, HBM headroom), both extracted from real XLA
compiles of a reduced model on an 8-device host mesh.

    PYTHONPATH=src python examples/tune_serving.py
"""
import dataclasses  # noqa: E402

import jax  # noqa: E402

from repro.configs import SHAPES, get_arch, reduce  # noqa: E402
from repro.core import VDTuner, pareto_front  # noqa: E402
from repro.tuning.serve_tuner import ServeTuningEnv, make_serving_space  # noqa: E402


def main():
    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    cfg = dataclasses.replace(
        reduce(get_arch("glm4-9b")), name="tune-target", d_model=256, n_heads=8,
        n_kv_heads=4, head_dim=32, d_ff=512, vocab=1024, n_layers=4,
        param_dtype="bfloat16",
    )
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=512, global_batch=8)
    import repro.configs.base as base

    # register the shape so the env can reference it
    base.SHAPES["tune_shape"] = shape

    env = ServeTuningEnv(cfg, "tune_shape", mesh)
    space = make_serving_space()
    print("== tuning the serving stack (each eval = one XLA compile) ==")
    tuner = VDTuner(space, env, seed=0, abandon_window=4, n_candidates=64, mc_samples=32)
    tuner.run(10)
    print("   pareto (steps/s proxy, HBM headroom):")
    for s, h in pareto_front(tuner.Y):
        print(f"     {s:10.2f}   {h:.3f}")
    best = max((o for o in tuner.history if not o.failed), key=lambda o: o.y[0])
    print(f"   fastest: {best.config['index_type']} "
          f"bq={best.config['flash_bq']} bk={best.config['flash_bk']} "
          f"seq_parallel={best.config['seq_parallel']} "
          f"(mem {best.raw.get('mem_gib', float('nan')):.2f} GiB/dev)")


if __name__ == "__main__":
    main()
