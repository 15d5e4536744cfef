#!/usr/bin/env python3
"""Smoke test: train and serve sparse Tucker on a TPU at Netflix-prize shape.

Drives the paper's main path once through the entry points a user calls,
in ONE process that holds the chip:

  1. finds a TPU or exits non-zero before any work;
  2. builds a ratings tensor of shape 480,189 x 17,770 x 2,182 (users x
     movies x days) with 10^8 nonzeros from ``--seed`` and splits 90/10;
  3. trains FastTucker SGD (``local`` strategy, J = R = 32, batch 4096 —
     what ``repro.launch.std_train`` runs) in every step layout the CLI
     exposes (joint, ``--phase-split``, ``--sorted-batches``, joint with
     ``--dtype bfloat16``) on the compiled ``pallas`` backend, and the
     same steps from the same seed on the ``xla`` backend as the plain
     reference;
  4. serves ``predict`` and ``top_k`` over all 17,770 movies from the
     trained ``C^(n)`` tables through ``ServeFrontend`` over a
     ``TuckerServer`` (``pallas``), checked against an ``xla`` server;
  5. prints the device's peak memory.

Tolerances come from the matmul precision each path uses on the chip,
measured here rather than assumed: both paths contract the same probe,
and each one's error against a float64 host product is its precision.

``--chips 4`` runs only the paths that exist across chips instead:
``strata`` and ``strata_overlap`` training on a 4-device mesh (one shared
schedule, compared with each other), a check that every device holds a
factor shard, and row-sharded ``top_k`` against replicated ``top_k``.

Any failed check raises; the last line of standard output is a JSON
object ``{"ok": true, "device": {...}}`` only when every phase passed.

    python3 chip_smoke.py [--seed 0] [--steps 30] [--chips 4]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

SHAPE = (480_189, 17_770, 2_182)     # Netflix prize: users, movies, days
NNZ = 100_000_000
NNZ_FOUR_CHIPS = 10_000_000
RANK = 32                            # J = R = 32
BATCH = 4096                         # std_train's default --batch
LAYOUTS = {
    "joint": {},
    "phase_split": {"phase_split": True},
    "sorted_batches": {"sorted_batches": True},
    "joint_bf16": {"dtype": "bfloat16"},
}
BF16_UNIT_ROUNDOFF = 2.0 ** -8
TOP_K = 10


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def find_tpu():
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"platform {d.platform}, device_kind {d.device_kind}, "
        f"device count {len(devices)}")
    if d.platform != "tpu":
        log("no TPU found: this smoke test runs only on a TPU")
        sys.exit(2)
    return devices


def import_repo():
    """Import the package from this checkout's ``src/`` — never another."""
    here = Path(__file__).resolve().parent
    sys.path.insert(0, str(here / "src"))
    import repro  # a namespace package: check where each part came from

    paths = [Path(p).resolve() for p in repro.__path__]
    check(all(p.is_relative_to(here / "src") for p in paths),
          f"repro imported from {paths}, not this checkout")


def build_data(seed: int, nnz: int):
    from repro.data.synthetic import ratings_tensor

    t0 = time.perf_counter()
    tensor = ratings_tensor(SHAPE, nnz, seed=seed)
    train, test = tensor.split(0.1, seed=seed)
    train.values.block_until_ready()
    nbytes = sum(int(x.nbytes) for t in (train, test)
                 for x in (t.indices, t.values))
    log(f"data: ratings tensor {'x'.join(map(str, SHAPE))}, nnz {nnz} "
        f"(train {train.nnz}, test {test.nnz}), {nbytes / 2**30:.3f} GiB "
        f"COO on device, built in {time.perf_counter() - t0:.1f} s")
    return train, test


def matmul_precision() -> dict:
    """Relative error of each backend's contraction against float64.

    One probe at training widths: ``(BATCH, J) @ (J, R)`` summed over R
    (``kruskal_contract`` with one mode), values on the init scale.  The
    result is the precision each path's MXU dots actually run at here.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.dispatch import get_backend

    rng = np.random.default_rng(1)
    a = rng.uniform(0.0, 0.5, (BATCH, RANK)).astype(np.float32)
    b = rng.uniform(0.0, 0.5, (RANK, RANK)).astype(np.float32)
    exact = (a.astype(np.float64) @ b.astype(np.float64)).sum(-1)
    eps = {}
    for name in ("xla", "pallas"):
        bk = get_backend(name)
        fn = jax.jit(lambda x, y, bk=bk: bk.kruskal_contract((x,), (y,))[0])
        got = np.asarray(fn(jnp.asarray(a), jnp.asarray(b)), np.float64)
        eps[name] = float(np.abs(got - exact).max() / np.abs(exact).max())
    # never below f32's own resolution: two f32 paths that round
    # differently still differ by that much
    eps = {k: max(v, 2.0 ** -24) for k, v in eps.items()}
    log(f"matmul precision on this chip (max rel. error vs float64): "
        f"xla {eps['xla']:.3e}, pallas {eps['pallas']:.3e}")
    return eps


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def rel_diff(a, b) -> float:
    """Largest |a − b| over the leaves, relative to each leaf's scale."""
    import jax

    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        worst = max(worst, float(np.abs(x - y).max()
                                 / max(np.abs(y).max(), 1e-30)))
    return worst


# ---------------------------------------------------------------------------
# one chip: train in every layout, then serve
# ---------------------------------------------------------------------------

def train_local(train, test, *, backend: str, layout: dict, steps: int,
                seed: int) -> dict:
    """What ``std_train --strategy local`` runs, timed phase by phase."""
    import jax

    from repro.core import FastTuckerConfig, init_state, rmse_mae
    from repro.core import fasttucker as ft
    from repro.distributed import get_strategy

    cfg = FastTuckerConfig(dims=SHAPE, ranks=(RANK,) * 3, core_rank=RANK,
                           batch_size=BATCH, backend=backend, **layout)
    st = get_strategy("local")
    plan = st.prepare(train, cfg, None, seed=seed)
    key = jax.random.PRNGKey(seed)
    key, init_key, loop_key = jax.random.split(key, 3)
    ds = st.init(plan, init_state(init_key, cfg), loop_key)
    rmse0 = float(rmse_mae(st.eval_params(plan, ds), test, ft.predict)[0])

    t0 = time.perf_counter()
    step = st.lower_step(plan, ds).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        ds = step(ds, plan.indices, plan.values)
    jax.block_until_ready(ds)
    run_s = time.perf_counter() - t0
    params = st.eval_params(plan, ds)
    rmse1 = float(rmse_mae(params, test, ft.predict)[0])
    host = jax.tree.map(np.asarray, params)
    return {"compile_s": compile_s, "steps_per_s": steps / run_s,
            "rmse0": rmse0, "rmse1": rmse1, "params": host}


def one_chip(args, devices) -> None:
    train, test = build_data(args.seed, args.nnz)
    eps = matmul_precision()
    trained = None
    for name, layout in LAYOUTS.items():
        ref = train_local(train, test, backend="xla", layout=layout,
                          steps=args.steps, seed=args.seed)
        got = train_local(train, test, backend="pallas", layout=layout,
                          steps=args.steps, seed=args.seed)
        # first order: each step adds at most each path's dot error, and
        # bf16 storage rounds every update once more
        per_step = eps["xla"] + eps["pallas"]
        if layout.get("dtype") == "bfloat16":
            per_step += BF16_UNIT_ROUNDOFF
        tol = args.steps * per_step
        diff = rel_diff(got["params"], ref["params"])
        log(f"train {name}: pallas compile {got['compile_s']:.2f} s, "
            f"{got['steps_per_s']:.2f} steps/s, rmse {got['rmse0']:.5f} -> "
            f"{got['rmse1']:.5f} | xla compile {ref['compile_s']:.2f} s, "
            f"{ref['steps_per_s']:.2f} steps/s, rmse {ref['rmse0']:.5f} -> "
            f"{ref['rmse1']:.5f} | max rel. param diff {diff:.3e} "
            f"(tol {tol:.3e} = {args.steps} steps x {per_step:.3e})")
        for run in (got, ref):
            check(np.isfinite(run["rmse0"]) and np.isfinite(run["rmse1"]),
                  f"{name}: non-finite rmse")
            check(run["rmse1"] < run["rmse0"],
                  f"{name}: rmse did not decrease "
                  f"({run['rmse0']} -> {run['rmse1']})")
        check(diff <= tol, f"{name}: pallas vs xla differ by {diff} > {tol}")
        if name == "joint":
            trained = got["params"]
    serve(trained, test, eps)
    log(f"peak device bytes in use: {peak_bytes(devices[0])} "
        f"({peak_bytes(devices[0]) / 2**30:.3f} GiB)")


def _near_tie_equal(ids_a, scores_a, ids_b, scores_b, tol: float) -> int:
    """Top-k ids must match except where neighbouring scores tie within
    ``tol``; returns how many positions differed (all near-ties)."""
    check(np.allclose(scores_a, scores_b, rtol=0, atol=tol),
          f"top-k scores differ by {np.abs(scores_a - scores_b).max()}")
    differ = ids_a != ids_b
    for r, c in zip(*np.nonzero(differ)):
        row = scores_b[r]
        gap = min(abs(row[c] - row[j]) for j in (c - 1, c + 1)
                  if 0 <= j < row.shape[0])
        check(gap <= tol, f"top-k id mismatch at row {r} rank {c} "
                          f"is not a near-tie (gap {gap} > {tol})")
    return int(differ.sum())


def serve(params, test, eps) -> None:
    """``serve_tucker``'s path: TuckerServer behind the ServeFrontend."""
    from repro.serve import AdmissionConfig, ServeFrontend, TuckerServer

    t0 = time.perf_counter()
    server = TuckerServer(params, backend="pallas")
    ref = TuckerServer(params, backend="xla")
    log(f"serve: tables built in {time.perf_counter() - t0:.2f} s "
        f"(C^(n) rows {SHAPE})")
    rng = np.random.default_rng(7)
    pool = np.asarray(test.indices[:200_000])
    sizes = (1, 3, 17, 64, 200, 512, 1000, 2048)
    predict_reqs = [pool[rng.integers(0, len(pool), n)] for n in sizes]
    user_reqs = [rng.integers(0, SHAPE[0], n).astype(np.int32)
                 for n in (1, 8, 64, 128)]
    # admission generous enough that the first flush's compile sheds
    # nothing: a shed request is a failed check here, not load shedding
    admission = AdmissionConfig(max_queue=1 << 16, deadline_ms=600_000.0,
                                microbatch=512, max_wait_ms=2.0)

    async def run():
        async with ServeFrontend(server, admission) as fp, \
                ServeFrontend(server, admission, query="top_k",
                              top_k_args=(0, TOP_K)) as fk:
            preds = await asyncio.gather(*(fp.submit(q)
                                           for q in predict_reqs))
            tops = await asyncio.gather(*(fk.submit(u) for u in user_reqs))
        return preds, tops, fp.stats, fk.stats

    t0 = time.perf_counter()
    preds, tops, pstats, tstats = asyncio.run(run())
    wall = time.perf_counter() - t0
    # prediction = Σ_r Π_n c: N rounded products per term on each path
    tol_rel = 2 * len(SHAPE) * (eps["xla"] + eps["pallas"])
    worst = 0.0
    for q, got in zip(predict_reqs, preds):
        want = np.asarray(ref.predict(q))
        got = np.asarray(got)
        check(got.shape == want.shape and np.isfinite(got).all(),
              "predict answers malformed")
        worst = max(worst, float(np.abs(got - want).max()
                                 / max(np.abs(want).max(), 1e-30)))
    check(worst <= tol_rel, f"predict differs from xla by {worst} > {tol_rel}")
    ties = 0
    for u, (scores, items) in zip(user_reqs, tops):
        want_s, want_i = (np.asarray(x) for x in ref.top_k(0, u, TOP_K))
        scores, items = np.asarray(scores), np.asarray(items)
        check(items.shape == (len(u), TOP_K) and np.isfinite(scores).all(),
              "top-k answers malformed")
        ties += _near_tie_equal(items, scores, want_i, want_s,
                                tol_rel * float(np.abs(want_s).max()))
    check(server.predict_cache_size <= len(server.ladder),
          "predict compiled more buckets than the ladder bound")
    log(f"serve: {pstats.served_queries} predict queries in "
        f"{pstats.served} requests / {pstats.flushes} flushes, "
        f"{tstats.served_queries} top-{TOP_K} queries over all "
        f"{SHAPE[1]} movies, {wall:.2f} s incl. compile; "
        f"{server.predict_cache_size} compiled predict buckets (ladder "
        f"bound {len(server.ladder)}); max rel. predict diff vs xla "
        f"{worst:.3e} (tol {tol_rel:.3e}); top-k ids equal to xla "
        f"({ties} near-tie swaps)")
    check(pstats.shed_queue_full == pstats.shed_deadline == 0
          and tstats.shed_queue_full == tstats.shed_deadline == 0,
          "the front end shed requests")


# ---------------------------------------------------------------------------
# four chips: the paths that exist only across chips
# ---------------------------------------------------------------------------

def four_chips(args, devices) -> None:
    import jax

    from repro.core import FastTuckerConfig, init_state, rmse_mae
    from repro.core import fasttucker as ft
    from repro.distributed import get_strategy
    from repro.launch.mesh import make_host_mesh
    from repro.serve import TuckerServer

    check(len(devices) == 4, f"--chips 4 needs 4 devices, "
                             f"found {len(devices)}")
    log(f"nnz cut to {args.nnz} for the four-chip run (dims and ranks "
        f"unchanged): resident strata bucketing walks every nonzero in "
        f"host Python once per strategy, which at 10^8 would hold four "
        f"chips idle for minutes")
    train, test = build_data(args.seed, args.nnz)
    eps = matmul_precision()
    mesh = make_host_mesh()
    cfg = FastTuckerConfig(dims=SHAPE, ranks=(RANK,) * 3, core_rank=RANK,
                           batch_size=BATCH, backend="pallas")
    runs = {}
    # strata_overlap advances a chunk of strata per call: run both to the
    # same step count, a whole number of chunks
    steps = -(-args.steps // 4) * 4
    for name in ("strata", "strata_overlap"):
        st = get_strategy(name)
        t0 = time.perf_counter()
        plan = st.prepare(train, cfg, mesh, seed=args.seed)
        prep_s = time.perf_counter() - t0
        check(steps % st.steps_per_call(plan) == 0,
              f"{name}: {steps} steps is not a whole number of calls")
        key = jax.random.PRNGKey(args.seed)
        key, init_key, loop_key = jax.random.split(key, 3)
        ds = st.init(plan, init_state(init_key, cfg), loop_key)
        step = st.make_step(plan)
        with mesh:
            rmse0 = float(rmse_mae(st.eval_params(plan, ds), test,
                                   ft.predict)[0])
            t0 = time.perf_counter()
            ds = step(ds)
            jax.block_until_ready(ds)
            first_s = time.perf_counter() - t0
            first = int(ds.step)
            t0 = time.perf_counter()
            while int(ds.step) < steps:
                ds = step(ds)
            jax.block_until_ready(ds)
            run_s = time.perf_counter() - t0
            params = st.eval_params(plan, ds)
            rmse1 = float(rmse_mae(params, test, ft.predict)[0])
        check(int(ds.step) == steps, f"{name} stopped at step {ds.step}")
        shards = [{s.device for s in f.addressable_shards}
                  for f in ds.params.factors]
        # each stratum (chunk) schedule position is its own compiled
        # program, so the first epoch still compiles: not a step rate
        log(f"train {name} on 4 chips: prepare {prep_s:.1f} s, first call "
            f"({first} steps, incl. compile) {first_s:.2f} s, "
            f"{steps - first} more steps in {run_s:.2f} s incl. compiling "
            f"the other stratum programs, rmse {rmse0:.5f} -> "
            f"{rmse1:.5f} after {steps} steps, factor shards on devices "
            f"{[sorted(d.id for d in s) for s in shards]}")
        check(np.isfinite(rmse1) and rmse1 < rmse0,
              f"{name}: rmse {rmse0} -> {rmse1}")
        check(all(len(s) == 4 for s in shards),
              f"{name}: factor shards not on four distinct devices")
        runs[name] = jax.tree.map(np.asarray, params)
    tol = steps * 2 * eps["pallas"]
    diff = rel_diff(runs["strata_overlap"], runs["strata"])
    log(f"strata vs strata_overlap: max rel. param diff {diff:.3e} "
        f"(tol {tol:.3e} = {steps} steps x 2 x pallas precision)")
    check(diff <= tol, f"strata and strata_overlap differ by {diff}")
    peaks = [peak_bytes(d) for d in devices]
    log(f"peak device bytes in use per device: {peaks}")
    check(all(p > 0 for p in peaks), "a device reports no memory in use")

    params = runs["strata"]
    row = TuckerServer(params, backend="pallas", mesh=mesh, shard_mode="row")
    rep = TuckerServer(params, backend="pallas", mesh=mesh,
                       shard_mode="batch")
    users = np.random.default_rng(7).integers(0, SHAPE[0], 128)
    s_row, i_row = (np.asarray(x) for x in row.top_k(0, users, TOP_K))
    s_rep, i_rep = (np.asarray(x) for x in rep.top_k(0, users, TOP_K))
    tol_s = 2 * len(SHAPE) * eps["pallas"] * float(np.abs(s_rep).max())
    ties = _near_tie_equal(i_row, s_row, i_rep, s_rep, tol_s)
    log(f"top-{TOP_K} over {SHAPE[1]} movies for 128 users: row-sharded "
        f"tables vs replicated tables agree ({ties} near-tie swaps, max "
        f"score diff {np.abs(s_row - s_rep).max():.3e})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=30,
                    help="training steps per run")
    ap.add_argument("--nnz", type=int, default=None,
                    help=f"nonzeros (default {NNZ}; {NNZ_FOUR_CHIPS} with "
                         f"--chips 4)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    if args.nnz is None:
        args.nnz = NNZ if args.chips == 1 else NNZ_FOUR_CHIPS

    devices = find_tpu()
    import_repo()
    from repro.runtime.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args, devices)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
