"""Training cells: what ``launch/std_train.py`` runs with no flags.

Set-up makes the data on the device, builds the strategy's plan, state
and step exactly as the launcher does (strategy, backend, layout, dtype,
update order and donation all resolve from their defaults), drives the
first ``check_steps`` steps through the window's own step call, and keeps
the norms the check needs.  The window then trains on from there, with
the held-out evaluation every ``eval_every`` steps, until ``--seconds``
have passed at an evaluation.  The time to the RMSE target runs from the
window's start (step ``check_steps``, not step 0) to the point where the
held-out RMSE, taken as linear between the two evaluations on either
side of the target, reaches it.  After the window the plain reference
repeats the first steps from the seed, and the two are compared.
"""
from __future__ import annotations

import gc
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import datagen, reference


def keys(cfg: dict, seed: int):
    """(init, sampling) keys.  The tensor and the initial factors belong
    to the configuration (``data.seed``, ``init_seed``): the RMSE a run
    reaches moves with its starting point and its test set far more than
    with the order of its samples (0.926 to 1.024 after 8,000 steps over
    six seeds with both drawn from the seed), so every run solves the
    same problem from the same start, and the seed draws the samples."""
    return datagen.seed_key(int(cfg["init_seed"])), datagen.seed_key(seed)


def hyper(cfg: dict) -> tuple:
    return tuple(sorted((k, float(v)) for k, v in cfg["hyper"].items()))


@jax.jit
def _leaf_norms(a, b):
    """Per-leaf Frobenius norms of a - b, computed in float32."""
    return [jnp.sqrt(jnp.sum((x.astype(jnp.float32)
                              - y.astype(jnp.float32)) ** 2))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def leaf_norms(a, b) -> np.ndarray:
    return np.asarray([float(v) for v in _leaf_norms(a, b)])


def _leaf_lrs(cfg: dict, t: int) -> np.ndarray:
    """The stated rate of each leaf (factors, then core factors) at step t."""
    h = cfg["hyper"]
    N = len(cfg["dims"])
    lr_a = h["alpha_a"] / (1.0 + h["beta_a"] * t ** 1.5)
    lr_b = h["alpha_b"] / (1.0 + h["beta_b"] * t ** 1.5)
    return np.asarray([lr_a] * N + [lr_b] * N)


def norm_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf's |‖prog‖ - ‖ref‖|, over the larger of that leaf's
    reference norm and the median leaf's.  Leaves whose reference norm is
    under a thousandth of the median leaf's are left out: they move by
    round-off alone."""
    med = float(np.median(ref))
    keep = ref >= 1e-3 * med
    denom = np.maximum(ref, med)
    return float(np.max(np.abs(prog - ref)[keep] / denom[keep]))


@contextmanager
def planted(fault: str | None):
    """Break the timed path underneath, for the harness's own tests and
    the fault readings: ``unchanged`` (the step returns its state),
    ``half_batch`` (half of Psi is dropped, the mean taken over the
    rest).  ``None`` changes nothing."""
    if fault is None:
        yield
        return
    import repro.core.fasttucker as ft
    from repro.distributed import local

    saved = (ft.sample_batch_arrays, local.LocalStrategy.make_step)
    if fault == "half_batch":
        def half(key, indices, values, batch_size):
            return saved[0](key, indices, values, batch_size // 2)
        ft.sample_batch_arrays = half
    elif fault == "unchanged":
        local.LocalStrategy.make_step = lambda self, plan: (lambda ds: ds)
    else:
        raise ValueError(f"unknown fault {fault!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        ft.sample_batch_arrays = saved[0]
        local.LocalStrategy.make_step = saved[1]
        jax.clear_caches()


def crossing(prev: tuple, cur: tuple, target: float) -> tuple:
    """(seconds, steps) at which the held-out RMSE reaches ``target``,
    taking it as linear in time and in steps between two evaluations
    ``(seconds, steps, rmse)`` that lie on either side of it."""
    (t0, s0, r0), (t1, s1, r1) = prev, cur
    f = (r0 - target) / (r0 - r1)
    return t0 + f * (t1 - t0), s0 + f * (s1 - s0)


def stalls(chunks: list) -> list:
    """(index, ms over the median) of the chunks that took longer than
    1.3 times the median chunk."""
    med = float(np.median(chunks))
    return [(i, round(1e3 * (c - med), 1)) for i, c in enumerate(chunks)
            if c > 1.3 * med]


def run(ctx) -> dict:
    """Set-up, window and check of one training cell; see ``harness``."""
    from repro.core import FastTuckerConfig, init_state, rmse_mae
    from repro.core import fasttucker as ft
    from repro.core.sptensor import SparseTensor
    from repro.distributed import get_strategy
    from repro.distributed.base import step_donation
    from repro.kernels import dispatch

    cfg, mix = ctx.cfg, ctx.mix
    dims = tuple(cfg["dims"])
    k_init, k_loop = keys(cfg, ctx.seed)
    tr_i, tr_v, te_i, te_v = datagen.ratings(cfg)
    train_t = SparseTensor(tr_i, tr_v, dims)
    test_t = SparseTensor(te_i, te_v, dims)

    fcfg = FastTuckerConfig(dims=dims, ranks=tuple(cfg["ranks"]),
                            core_rank=int(cfg["core_rank"]),
                            batch_size=int(cfg["batch"]),
                            **ctx.variant)
    strategy = get_strategy(None)
    ctx.log(f"resolved: strategy {strategy.name}, backend "
            f"{dispatch.resolve_backend_name(fcfg.backend)}, update_order "
            f"{fcfg.update_order}, phase_split {fcfg.phase_split}, "
            f"sorted_batches {fcfg.sorted_batches}, dtype {fcfg.dtype}, "
            f"accum_dtype {fcfg.accum_dtype}, donate_argnums "
            f"{step_donation()}, batch {fcfg.batch_size}")
    if strategy.needs_mesh:
        raise SystemExit(f"strategy {strategy.name} needs a mesh; the "
                         "training cells run the default single-chip path")
    with planted(ctx.fault):
        return _run(ctx, strategy, fcfg, train_t, test_t, k_init, k_loop,
                    init_state, lambda p: rmse_mae(p, test_t, ft.predict))


def _run(ctx, st, fcfg, train_t, test_t, k_init, k_loop, init_state,
         evaluate_params) -> dict:
    cfg, mix = ctx.cfg, ctx.mix
    plan = st.prepare(train_t, fcfg, None, seed=ctx.seed)
    ds = st.init(plan, init_state(k_init, fcfg), k_loop)
    step = st.make_step(plan)

    def evaluate(ds) -> float:
        return float(evaluate_params(st.eval_params(plan, ds))[0])

    # the first steps, through the window's own call, kept for the check
    n_check = int(mix["check_steps"])
    p0 = jax.tree.map(jnp.copy, st.eval_params(plan, ds))
    rmse0 = evaluate(ds)
    ds = step(ds)
    grad_norms = leaf_norms(p0, st.eval_params(plan, ds)) / _leaf_lrs(cfg, 0)
    for _ in range(n_check - 1):
        ds = step(ds)
    change_norms = leaf_norms(st.eval_params(plan, ds), p0)
    del p0
    rmse_check = evaluate(ds)

    # the window: train on, evaluating every eval_every steps
    every = int(mix["eval_every"])
    target = float(cfg["rmse_target"] or 0.0)
    seconds = float(ctx.seconds)
    trace_from = seconds - float(mix["trace_seconds"])
    steps = 0
    reached = None
    rmse_last = rmse_check
    finite = True
    chunks = []
    tracer = ctx.tracer()
    ctx.window_start()
    t0 = time.perf_counter()
    if rmse_check <= target:
        reached = (0.0, 0.0)
    prev = (0.0, 0, rmse_check)
    while True:
        with jax.profiler.TraceAnnotation("chipbench.train_steps"):
            for _ in range(every):
                ds = step(ds)
        with jax.profiler.TraceAnnotation("chipbench.evaluate"):
            rmse_last = evaluate(ds)
        steps += every
        now = time.perf_counter() - t0
        chunks.append(now - prev[0])
        finite = finite and bool(np.isfinite(rmse_last))
        if reached is None and rmse_last <= target:
            reached = crossing(prev, (now, steps, rmse_last), target)
        prev = (now, steps, rmse_last)
        if steps % (every * 4) == 0:
            ctx.log(f"  {now:.3f} s, step {steps}, rmse {rmse_last:.5f}")
        if ctx.trace and not tracer.active and now >= trace_from:
            tracer.start(steps)
        if now >= seconds:
            break
    window_s = time.perf_counter() - t0
    traced = tracer.stop(steps) if ctx.trace else None
    ctx.window_end()
    peak = ctx.memory_peak()
    batch = int(cfg["batch"])
    ctx.log(f"window: {steps} steps in {window_s:.3f} s, rmse "
            f"{rmse_check:.5f} -> {rmse_last:.5f} (target {target}), "
            f"reached {reached}")
    ctx.log(f"chunks of {every} steps: median {1e3 * np.median(chunks):.1f}"
            f" ms, slow (> 1.3x median) {stalls(chunks)}")
    del ds, step, plan
    gc.collect()

    # the plain reference repeats the first steps from the seed (the keys
    # are drawn again: the step donated the ones the state held)
    k_init, k_loop = keys(cfg, ctx.seed)
    ref = ref_readings(cfg, train_t, test_t, k_init, k_loop, n_check)
    checks = {
        "grad_gap": norm_gap(grad_norms, ref["grad_norms"]),
        "change_gap": norm_gap(change_norms, ref["change_norms"]),
        "rmse_gap": max(abs(rmse0 - ref["rmse0"]) / ref["rmse0"],
                        abs(rmse_check - ref["rmse_check"])
                        / ref["rmse_check"]),
    }
    metrics = {"train_nnz_per_s": steps * batch / window_s}
    if reached is not None:
        metrics["time_to_rmse_s"] = reached[0]
    failed = int(reached is None) + int(not finite)
    return {
        "metrics": metrics,
        "attempted": steps,
        "failed": failed,
        "checks": checks,
        "memory_peak_bytes": peak,
        "counters": {"steps": steps, "steps_to_rmse":
                     None if reached is None else reached[1],
                     "batch": batch, "window_s": window_s},
        "traced": traced,
    }


def ref_readings(cfg, train_t, test_t, k_init, k_loop, n_check) -> dict:
    """The reference's norms and RMSEs over the first ``n_check`` steps."""
    with jax.default_matmul_precision("highest"):
        p0 = reference.init_params(
            k_init, dims=tuple(cfg["dims"]), ranks=tuple(cfg["ranks"]),
            core_rank=int(cfg["core_rank"]))
        test = (test_t.indices, test_t.values)
        rmse0 = reference.rmse(p0, *test)
        p = p0
        grad_norms = None
        for t in range(n_check):
            p = reference.sgd_step(p, k_loop, t, train_t.indices,
                                   train_t.values, batch=int(cfg["batch"]),
                                   hyper=hyper(cfg))
            if t == 0:
                grad_norms = leaf_norms(p0, p) / _leaf_lrs(cfg, 0)
        change_norms = leaf_norms(p, p0)
        return {"grad_norms": grad_norms, "change_norms": change_norms,
                "rmse0": rmse0, "rmse_check": reference.rmse(p, *test)}
