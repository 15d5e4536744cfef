"""Multi-device STD with the paper's stratified Fig.-2 schedule.

Drives the distributed-strategy registry (``repro.distributed``): pick any
of local / sync / strata / strata_overlap with ``--strategy``; the default
``strata_overlap`` runs the Latin-hypercube epoch schedule with the factor
shard rotations double-buffered behind compute.

Simulates 8 devices on CPU (the flags below MUST precede any jax import).

    python examples/multipod_std.py [--strategy strata]
"""
import argparse
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"  # simulated devices are CPU devices
import sys                                                      # noqa: E402
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                      # noqa: E402

from repro.core import FastTuckerConfig, init_state, rmse_mae   # noqa: E402
from repro.core import fasttucker as ft                         # noqa: E402
from repro.data.synthetic import planted_tensor                 # noqa: E402
from repro.distributed import get_strategy                      # noqa: E402
from repro.launch.mesh import make_host_mesh                    # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="strata_overlap")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    dims = (512, 384, 256)
    tensor = planted_tensor(dims, 200_000, noise=0.05, seed=0)
    train_t, test_t = tensor.split(0.1)
    cfg = FastTuckerConfig(dims=dims, ranks=(8,) * 3, core_rank=8,
                           batch_size=2048)

    mesh = make_host_mesh()
    M = mesh.devices.size
    print(f"running the {args.strategy!r} strategy on {M} devices "
          f"({M}^{len(dims)} = {M**len(dims)} blocks, "
          f"{M**(len(dims)-1)} strata)")

    strategy = get_strategy(args.strategy)
    plan = strategy.prepare(train_t, cfg,
                            mesh if strategy.needs_mesh else None, seed=0)
    dstate = strategy.init(plan, init_state(jax.random.PRNGKey(0), cfg),
                           jax.random.PRNGKey(1))
    step = strategy.make_step(plan)

    with mesh:
        next_eval = 50
        while int(dstate.step) < args.steps:
            dstate = step(dstate)
            if int(dstate.step) >= next_eval:
                next_eval += 50
                params = strategy.eval_params(plan, dstate)
                r, m = rmse_mae(params, test_t, ft.predict)
                print(f"step {int(dstate.step):3d}  RMSE {float(r):.4f}")
    print("conflict-free multi-device decomposition complete")


if __name__ == "__main__":
    main()
