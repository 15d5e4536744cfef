"""Ingestion-bound sweep: the out-of-core store + stratum prefetch pipeline.

Measures what the ``NonzeroStore`` + ``StratumPrefetcher`` pipeline buys
on the strata strategy, per nnz scale:

    ``us_per_step_resident``  resident device buckets (the pre-PR path;
                              skipped above the device-residency budget —
                              the memory-bounded regime the store exists
                              for, recorded as null)
    ``us_per_step_sync``      store-fed, prefetch depth 0: the stratum
                              chunk is read (memmap) + ``device_put`` ON
                              the hot path every step — compute+transfer
    ``us_per_step_stream``    store-fed, prefetch depth ≥ 1: the chunk is
                              issued from a background thread ahead of
                              use — max(compute, transfer)
    ``us_per_stratum_load``   pure load+place cost of one chunk
    ``transfer_hidden_fraction``  (sync − stream) / load, clipped to
                              [0, 1] — how much of the per-step transfer
                              the prefetch discipline removed from the
                              critical path

plus full-epoch streaming stats at the largest scale (every stored
nonzero moved host→device once).  Strata need M > 1 devices to have a
non-trivial schedule, so the measurement runs in a subprocess with
``--xla_force_host_platform_device_count`` (same idiom as the CI
multi-device tier); results land in the v3 ``ingest`` section of
``BENCH_step.json`` via ``bench_sota_time.attach_ingest``.

    PYTHONPATH=src python -m benchmarks.bench_ingest \
        [--smoke] [--devices 4] [--attach BENCH_step.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

from .common import row

DEVICES = 4

# full sweep: parity point (resident fits comfortably) + the 10^7-nnz
# scale the resident path is budget-excluded from
FULL_POINTS = (
    dict(dims=(6000, 4000, 2000), nnz=1_000_000, rank=8, batch=4096),
    dict(dims=(20000, 15000, 10000), nnz=10_000_000, rank=8, batch=4096),
)
SMOKE_POINTS = (
    dict(dims=(40, 30, 20), nnz=4_000, rank=3, batch=256),
)

# simulated per-run device residency budget for the RESIDENT buckets (the
# paper's premise: Ω does not fit next to the factors). ~17 B/nnz puts
# 10^7 nnz well past this; the store streams one ~budget/S stratum at a
# time instead.
RESIDENT_BUDGET_BYTES = 128 * 2**20


# ---------------------------------------------------------------------------
# child: the actual measurement (runs under forced host devices)
# ---------------------------------------------------------------------------

def _time_steps(step_fn, dstate, iters: int):
    """Median us/step over ``iters`` individually-timed steps."""
    import jax

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        dstate = step_fn(dstate)
        jax.block_until_ready(dstate)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6, dstate


def _measure_point(point: dict, spill_root: str, depth: int) -> dict:
    import jax

    from repro.core import FastTuckerConfig, init_state
    from repro.data.pipeline import NonzeroStore
    from repro.data.synthetic import planted_tensor
    from repro.distributed import get_strategy
    from repro.distributed.strata import _block_sharding
    from repro.launch.mesh import make_host_mesh

    dims, nnz, J, batch = (point["dims"], point["nnz"], point["rank"],
                           point["batch"])
    M = jax.device_count()
    mesh = make_host_mesh()
    st = get_strategy("strata")
    cfg = FastTuckerConfig(dims=tuple(dims), ranks=(J,) * len(dims),
                           core_rank=J, batch_size=batch)
    tensor = planted_tensor(tuple(dims), nnz, rank=J, core_rank=J, seed=0)

    t0 = time.perf_counter()
    store = NonzeroStore.build(
        tensor, M, spill_dir=os.path.join(spill_root, f"nnz{nnz}"))
    build_s = time.perf_counter() - t0
    S = store.num_strata

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    state0 = init_state(k1, cfg)

    out = {
        "nnz": int(nnz), "dims": list(dims), "rank": J, "batch": batch,
        "devices": M, "store": "spill", "prefetch_depth": depth,
        "num_strata": S, "store_build_s": round(build_s, 3),
        "store_mb": round(store.nbytes / 2**20, 2),
        "stratum_mb": round(store.stratum_nbytes / 2**20, 3),
    }

    # pure chunk load+place cost (what depth-0 pays on the hot path)
    sharding = _block_sharding(st.prepare(tensor, cfg, mesh, seed=0,
                                          store=store))
    loads = []
    for s in range(min(S, 8)):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(store.stratum(s), sharding))
        loads.append(time.perf_counter() - t0)
    loads.sort()
    out["us_per_stratum_load"] = loads[len(loads) // 2] * 1e6

    def run_config(store_arg, d):
        plan = st.prepare(tensor, cfg, mesh, seed=0, store=store_arg,
                          prefetch_depth=d)
        dstate = st.init(plan, state0, k2)
        step_fn = st.make_step(plan)
        # one full epoch of warmup compiles every digit variant
        for _ in range(S):
            dstate = step_fn(dstate)
        jax.block_until_ready(dstate)
        us, dstate = _time_steps(step_fn, dstate, iters=S)
        fetch = getattr(step_fn, "prefetcher", None)
        if fetch is not None:
            fetch.close()
        return us, dstate

    resident_bytes = store.nbytes  # resident buckets = all chunks at once
    if resident_bytes <= RESIDENT_BUDGET_BYTES:
        out["us_per_step_resident"], _ = run_config(None, 0)
    else:
        out["us_per_step_resident"] = None
        out["resident_skipped"] = (
            f"buckets need {resident_bytes / 2**20:.0f} MiB device "
            f"residency > {RESIDENT_BUDGET_BYTES / 2**20:.0f} MiB budget")

    out["us_per_step_sync"], _ = run_config(store, 0)
    out["us_per_step_stream"], dstate = run_config(store, depth)

    hidden = ((out["us_per_step_sync"] - out["us_per_step_stream"])
              / max(out["us_per_stratum_load"], 1e-9))
    out["transfer_hidden_fraction"] = round(min(max(hidden, 0.0), 1.0), 4)
    if out["us_per_step_resident"]:
        out["stream_vs_resident"] = round(
            out["us_per_step_stream"] / out["us_per_step_resident"], 4)

    # full streaming epoch at this scale: every stored nonzero crosses
    # host→device once (steady state: the second, compile-free epoch)
    plan = st.prepare(tensor, cfg, mesh, seed=0, store=store,
                      prefetch_depth=depth)
    dstate = st.init(plan, state0, k2)
    step_fn = st.make_step(plan)
    for _ in range(S):
        dstate = step_fn(dstate)
    jax.block_until_ready(dstate)
    t0 = time.perf_counter()
    for _ in range(S):
        dstate = step_fn(dstate)
    jax.block_until_ready(dstate)
    epoch_s = time.perf_counter() - t0
    fetch = getattr(step_fn, "prefetcher", None)
    if fetch is not None:
        fetch.close()
    out["epoch_steps"] = S
    out["epoch_s"] = round(epoch_s, 4)
    out["ingest_nnz_per_s"] = round(store.nnz / epoch_s, 1)
    return out


def measure(smoke: bool, depth: int = 2) -> dict:
    points = SMOKE_POINTS if smoke else FULL_POINTS
    with tempfile.TemporaryDirectory(prefix="bench_ingest_") as spill:
        rows = [_measure_point(p, spill, depth) for p in points]
    import jax

    return {
        "generated_by": "benchmarks.bench_ingest",
        "smoke": smoke,
        "platform": jax.default_backend(),
        "resident_budget_mb": RESIDENT_BUDGET_BYTES // 2**20,
        "rows": rows,
    }


# ---------------------------------------------------------------------------
# parent: subprocess with forced host devices, CSV rows, BENCH hook
# ---------------------------------------------------------------------------

def _run_child(smoke: bool, devices: int, depth: int) -> dict:
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
    cmd = [sys.executable, "-m", "benchmarks.bench_ingest", "--measure",
           "--prefetch-depth", str(depth)]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                          text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"ingest child failed\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}")
    return json.loads(proc.stdout)


def run(smoke: bool = False, devices: int = DEVICES, depth: int = 2,
        attach: str | None = None) -> dict:
    ingest = _run_child(smoke, devices, depth)
    for r in ingest["rows"]:
        tag = f"ingest/nnz{r['nnz']}"
        if r.get("us_per_step_resident"):
            row(f"{tag}/resident", r["us_per_step_resident"], "1.00x")
        else:
            print(f"{tag}/resident,skipped,"
                  f"{r.get('resident_skipped', '')}", flush=True)
        row(f"{tag}/sync_depth0", r["us_per_step_sync"])
        row(f"{tag}/stream_depth{r['prefetch_depth']}",
            r["us_per_step_stream"],
            f"hidden={r['transfer_hidden_fraction']:.2f}")
        row(f"{tag}/stratum_load", r["us_per_stratum_load"],
            f"epoch={r['epoch_s']}s,{r['ingest_nnz_per_s']:.3g}nnz/s")
    if attach:
        from .bench_sota_time import attach_ingest

        attach_ingest(ingest, attach)
    return ingest


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI schema check)")
    ap.add_argument("--devices", type=int, default=DEVICES,
                    help="forced host devices for the child process")
    ap.add_argument("--prefetch-depth", type=int, default=2)
    ap.add_argument("--attach", default="",
                    help="merge results into this BENCH_step.json "
                         "(upgrades it to schema v3)")
    ap.add_argument("--measure", action="store_true",
                    help="internal: measure in-process and print JSON")
    args = ap.parse_args()
    if args.measure:
        print(json.dumps(measure(args.smoke, args.prefetch_depth)))
        return
    run(smoke=args.smoke, devices=args.devices, depth=args.prefetch_depth,
        attach=args.attach or None)


if __name__ == "__main__":
    main()
