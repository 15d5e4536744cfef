"""Convergence-speed benchmark → the canonical ``BENCH_convergence.json``.

Steps-to-RMSE-target and wall-clock-to-target, sketched warm start
(``core.sketch``) vs the cold uniform init, per (backend, strategy)
config (schema ``bench_convergence/v1``, validated by
``benchmarks.common.validate_bench_convergence``; CI smoke-checks both
the emitted and the committed file).

Both arms share ONE config / strategy plan / compiled step — the warm
arm builds its parameters with ``core.sketch.sketched_init_params``
directly (what ``FastTuckerConfig(init="sketched")`` calls underneath),
so the comparison isolates the initialization: same data split, same
step function, same eval cadence.  Wall-clock is training-only
(cumulative step time between evals; eval cost excluded symmetrically),
the warm arm's sketch cost is measured compiled (a throwaway first call
absorbs jit) and counted in full against its wall-clock-to-target.

The planted-tensor configs are deliberately in the regime the sketch is
built for: the cold SGD schedule plateaus ABOVE the warm start's landing
RMSE (decaying LR), so besides crossing the shared ``target_rmse`` in
fewer steps and less wall-clock, the warm arm's ``final_rmse`` is the
noise floor the cold arm never attains.  See docs/convergence.md.

Runs in a subprocess with forced host devices so the strata config is a
real multi-worker rotation (same idiom as ``bench_serve``):

    PYTHONPATH=src python -m benchmarks.bench_convergence \
        [--smoke] [--devices 2] [--out BENCH_convergence.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from .common import BENCH_CONVERGENCE_SCHEMA, row, validate_bench_convergence

DEVICES = 2

FULL = [
    dict(name="planted_local", backend="xla", strategy="local",
         dims=(400, 300, 200), nnz=150_000, rank=8, core_rank=8,
         batch=2048, sketch_batch=16_384, seed=0,
         target_rmse=0.12, horizon_steps=800, eval_every=50),
    dict(name="planted_strata", backend="xla", strategy="strata",
         dims=(400, 300, 200), nnz=150_000, rank=8, core_rank=8,
         batch=2048, sketch_batch=16_384, seed=0,
         target_rmse=0.12, horizon_steps=800, eval_every=50),
]
SMOKE = [
    dict(name="planted_local", backend="xla", strategy="local",
         dims=(60, 50, 40), nnz=8_000, rank=4, core_rank=4,
         batch=1024, sketch_batch=4_096, seed=0,
         target_rmse=0.30, horizon_steps=160, eval_every=20),
    dict(name="planted_strata", backend="xla", strategy="strata",
         dims=(60, 50, 40), nnz=8_000, rank=4, core_rank=4,
         batch=1024, sketch_batch=4_096, seed=0,
         target_rmse=0.30, horizon_steps=160, eval_every=20),
]


# ---------------------------------------------------------------------------
# child: the actual measurement (runs under forced host devices)
# ---------------------------------------------------------------------------

def _run_arm(strategy, plan, mesh, state0, loop_key, test_t, c) -> dict:
    """Train one arm to the horizon; trajectory + time-to-target."""
    import contextlib

    import jax

    from repro.core import rmse_mae
    from repro.core import fasttucker as ft

    step_fn = strategy.make_step(plan)
    dstate = strategy.init(plan, state0, loop_key)
    start = int(dstate.step)

    def ev():
        params = strategy.eval_params(plan, dstate)
        r, _ = rmse_mae(params, test_t, ft.predict)
        return float(r)

    traj = [[0, ev()]]                      # step-0 eval: where init lands
    train_s = 0.0
    wall_at = {0: 0.0}
    with (mesh if mesh is not None else contextlib.nullcontext()):
        while int(dstate.step) - start < c["horizon_steps"]:
            t0 = time.perf_counter()
            for _ in range(c["eval_every"]):
                dstate = step_fn(dstate)
            jax.block_until_ready(dstate.params.factors)
            train_s += time.perf_counter() - t0
            done = int(dstate.step) - start
            traj.append([done, ev()])
            wall_at[done] = train_s
    reached = [s for s, r in traj if r <= c["target_rmse"]]
    hit = min(reached) if reached else c["horizon_steps"]
    return {
        "reached": bool(reached),
        "steps_to_target": int(hit),
        "train_s_to_target": wall_at[hit],
        "final_rmse": traj[-1][1],
        "trajectory": traj,
    }


def _measure_config(c: dict) -> dict:
    import jax

    from repro.core import FastTuckerConfig, TrainState, init_params
    from repro.core.sketch import sketched_init_params
    from repro.data.synthetic import planted_tensor
    from repro.distributed import get_strategy
    from repro.launch.mesh import make_host_mesh

    dims = tuple(c["dims"])
    tensor = planted_tensor(dims, c["nnz"], rank=c["rank"],
                            core_rank=c["core_rank"], noise=0.05,
                            seed=c["seed"])
    train_t, test_t = tensor.split(0.1)
    cfg = FastTuckerConfig(
        dims=dims, ranks=(c["rank"],) * len(dims),
        core_rank=c["core_rank"], batch_size=c["batch"],
        backend=c["backend"], sketch_batch=c["sketch_batch"])

    strategy = get_strategy(c["strategy"])
    mesh = make_host_mesh() if strategy.needs_mesh else None
    plan = strategy.prepare(train_t, cfg, mesh, seed=c["seed"])

    key = jax.random.PRNGKey(c["seed"])
    key, init_key, loop_key = jax.random.split(key, 3)

    # warm-up lap: compile the step + sketch once so both arms time
    # steady-state execution, not jit
    _ = _run_arm(strategy, plan,
                 mesh, TrainState(init_params(init_key, cfg),
                                  jax.numpy.asarray(0, jax.numpy.int32)),
                 loop_key, test_t,
                 {**c, "horizon_steps": c["eval_every"]})
    jax.block_until_ready(sketched_init_params(
        jax.random.fold_in(init_key, 99), cfg,
        train_t.indices, train_t.values).factors)

    cold0 = TrainState(init_params(init_key, cfg),
                       jax.numpy.asarray(0, jax.numpy.int32))
    cold = _run_arm(strategy, plan, mesh, cold0, loop_key, test_t, c)
    cold["init_s"] = 0.0

    t0 = time.perf_counter()
    warm_params = sketched_init_params(init_key, cfg,
                                       train_t.indices, train_t.values)
    jax.block_until_ready(warm_params.factors)
    init_s = time.perf_counter() - t0
    warm0 = TrainState(warm_params,
                       jax.numpy.asarray(0, jax.numpy.int32))
    warm = _run_arm(strategy, plan, mesh, warm0, loop_key, test_t, c)
    warm["init_s"] = init_s

    for arm in (cold, warm):
        arm["wallclock_s_to_target"] = (
            arm.pop("train_s_to_target") + arm["init_s"])
    out = dict(c)
    out["dims"] = list(dims)
    out["cold"], out["sketched"] = cold, warm
    out["speedup_vs_cold"] = (cold["steps_to_target"]
                              / max(warm["steps_to_target"], 1))
    out["wallclock_speedup_vs_cold"] = (
        cold["wallclock_s_to_target"]
        / max(warm["wallclock_s_to_target"], 1e-9))
    return out


def measure(smoke: bool) -> dict:
    import jax

    configs = SMOKE if smoke else FULL
    return {"devices": jax.device_count(),
            "platform": jax.default_backend(),
            "configs": [_measure_config(c) for c in configs]}


# ---------------------------------------------------------------------------
# parent: subprocess with forced host devices, CSV rows, document assembly
# ---------------------------------------------------------------------------

def _run_child(smoke: bool, devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    # forced host devices are CPU devices: pin the platform so the child
    # never contends for an accelerator the parent's machine may hold
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src"), repo,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    cmd = [sys.executable, "-m", "benchmarks.bench_convergence",
           "--measure"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                          text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"convergence child failed\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}")
    return json.loads(proc.stdout)


def run(smoke: bool = False, devices: int = DEVICES,
        out_path: str | None = None) -> dict:
    res = _run_child(smoke, devices)
    doc = {
        "schema": BENCH_CONVERGENCE_SCHEMA,
        "generated_by": "benchmarks/bench_convergence.py",
        "smoke": smoke,
        "platform": res["platform"],
        "devices": res["devices"],
        "configs": res["configs"],
    }
    validate_bench_convergence(doc)

    for c in doc["configs"]:
        cold, warm = c["cold"], c["sketched"]
        row(f"conv/{c['name']}_cold_steps", cold["steps_to_target"],
            f"reached={cold['reached']} final={cold['final_rmse']:.4f}")
        row(f"conv/{c['name']}_warm_steps", warm["steps_to_target"],
            f"reached={warm['reached']} final={warm['final_rmse']:.4f} "
            f"init={warm['init_s']:.2f}s")
        row(f"conv/{c['name']}_speedup_steps", c["speedup_vs_cold"],
            f"target_rmse={c['target_rmse']}")
        row(f"conv/{c['name']}_speedup_wall",
            c["wallclock_speedup_vs_cold"],
            f"cold={cold['wallclock_s_to_target']:.2f}s "
            f"warm={warm['wallclock_s_to_target']:.2f}s")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {out_path}", flush=True)
    return doc


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / short horizons (CI schema check)")
    ap.add_argument("--devices", type=int, default=DEVICES,
                    help="forced host devices for the child process")
    ap.add_argument("--out", default="",
                    help="write the validated BENCH_convergence.json here")
    ap.add_argument("--measure", action="store_true",
                    help="internal: measure in-process and print JSON")
    args = ap.parse_args()
    if args.measure:
        print(json.dumps(measure(args.smoke)))
        return
    run(smoke=args.smoke, devices=args.devices, out_path=args.out or None)


if __name__ == "__main__":
    main()
