"""Serving cells: ``ServeFrontend`` over ``TuckerServer``, open loop.

Set-up draws factors from the seed (no training), builds the server and
front end as ``launch/serve_tucker.py`` does (default backend, no mesh,
``AdmissionConfig()`` at its defaults), and compiles every bucket of the
ladder for the cell's query.  The traffic is generated in full from the
seed before the window (``traffic.schedule``); the window then submits
each request at its due time, whatever happened to the ones before, and
times it from the due time to its answer; a request the front end sheds
is timed to its refusal and counted as failed.  After the window every
request's answer is collected (up to a minute past the close), and a
sample drawn from the seed is compared with the plain reference.
"""
from __future__ import annotations

import asyncio
import gc
import math
import time

import jax
import numpy as np

from chipbench import datagen, reference, traffic

DRAIN_S = 60.0


class TimedServer:
    """The server as the front end sees it, with each engine call timed on
    the host clock (``block_until_ready`` included) and, for the
    harness's own tests, a fault planted in its answers."""

    def __init__(self, server, fault: str | None = None):
        self._server = server
        self._fault = fault
        self.calls: list[tuple[float, float, int]] = []

    def __getattr__(self, name):
        return getattr(self._server, name)

    def _timed(self, fn, n, *args, **kw):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.engine_call"):
            out = jax.block_until_ready(fn(*args, **kw))
        self.calls.append((t0, time.perf_counter(), n))
        return out

    def top_k(self, mode, ids, k, target_mode=None):
        scores, items = self._timed(self._server.top_k, len(ids), mode, ids,
                                    k, target_mode=target_mode)
        if self._fault is not None:
            scores, items = np.array(scores), np.array(items)
            rows = slice(len(ids) // 2, None) if self._fault == "half_batch" \
                else slice(0, 1)
            items[rows] = (items[rows] + 1) % self._server.dims[
                target_mode if target_mode is not None else mode + 1]
        return scores, items

    def predict(self, indices):
        pred = self._timed(self._server.predict, len(indices), indices)
        if self._fault is not None:
            pred = np.array(pred)
            if self._fault == "half_batch":
                pred[len(indices) // 2:] = 0.0
            else:
                pred[0] += 1.0
        return pred


def run(ctx, keep_latencies: bool = False) -> dict:
    """Set-up, window and check of one serving cell; see ``harness``.
    ``keep_latencies`` adds every request's latency (the rate sweep)."""
    from repro.core.fasttucker import FastTuckerParams
    from repro.kernels import dispatch
    from repro.serve import AdmissionConfig, ServeFrontend, TuckerServer

    cfg, mix = ctx.cfg, ctx.mix
    k_fac, _ = jax.random.split(datagen.seed_key(ctx.seed))
    factors, core = datagen.serving_factors(k_fac, cfg)
    server = TuckerServer(FastTuckerParams(factors, core))
    admission = AdmissionConfig()
    ctx.log(f"resolved: backend {server.backend}, shard_mode "
            f"{server.shard_mode}, table dtype {server.table_dtype}, "
            f"ladder {server.ladder}, admission {admission}, default "
            f"backend {dispatch.resolve_backend_name(None)}")
    timed = TimedServer(server, ctx.fault)
    query = mix["query"]
    sched = traffic.schedule(mix, cfg, ctx.seed, ctx.seconds)

    warm_up(timed, mix, cfg, server.ladder,
            np.random.default_rng([ctx.seed, 1]))
    if query == "top_k":
        top_k_args = (int(mix["mode"]), int(mix["k"]),
                      int(mix["target_mode"]))
    else:
        top_k_args = None
    n = len(sched)
    lat = np.full(n, math.inf)
    late = np.zeros(n)
    shed = np.zeros(n, bool)
    answered = np.zeros(n, bool)
    # only the answers the check compares are kept, and a task is dropped
    # once done: what the window keeps alive, the collector has to scan
    checked = set(sample(ctx, n).tolist())
    answers: dict = {}
    tracer = ctx.tracer()
    trace_from = ctx.seconds - float(mix["trace_seconds"])

    async def window():
        from repro.serve.frontend import RequestShed

        async with ServeFrontend(timed, admission, query=query,
                                 top_k_args=top_k_args) as fe:
            await fe.submit(sched[0][1])          # the loop and its thread
            base = (fe.stats.served_queries, fe.stats.flushes)
            timed.calls.clear()

            async def client(i, due, payload):
                try:
                    answer = await fe.submit(payload)
                    lat[i] = time.perf_counter() - due
                    answered[i] = True
                    if i in checked:
                        answers[i] = answer
                except RequestShed:
                    lat[i] = time.perf_counter() - due
                    shed[i] = True

            pending: set = set()
            ctx.window_start()
            t0 = time.perf_counter()
            for i, (off, payload) in enumerate(sched):
                due = t0 + off
                delay = due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                late[i] = time.perf_counter() - due
                if ctx.trace and not tracer.active and off >= trace_from:
                    tracer.start(len(timed.calls))
                task = asyncio.ensure_future(client(i, due, payload))
                pending.add(task)
                task.add_done_callback(pending.discard)
            close = t0 + ctx.seconds
            if pending:
                await asyncio.wait(set(pending), timeout=max(
                    0.0, close + DRAIN_S - time.perf_counter()))
            for task in set(pending):
                task.cancel()
            traced = tracer.stop(len(timed.calls)) if ctx.trace else None
            ctx.window_end()
            stats = fe.stats
            return (stats.served_queries - base[0], stats.flushes - base[1],
                    traced)

    served_q, flushes, traced = asyncio.run(window())
    peak = ctx.memory_peak()
    never = int(np.sum(~answered & ~shed))
    calls = list(timed.calls)
    ctx.log(f"window: {n} requests due in {ctx.seconds} s, "
            f"{int(shed.sum())} shed, {never} never answered, generator "
            f"late by p50 {np.median(late) * 1e3:.3f} ms / max "
            f"{late.max() * 1e3:.3f} ms, {flushes} flushes")
    del timed
    gc.collect()

    checks = {"never_answered": float(never)}
    checks.update(check_answers(ctx, factors, core, sched, answers,
                                ctx.check_dtype))
    p95 = float(np.sort(lat)[max(0, math.ceil(0.95 * n) - 1)])
    metrics = {}
    if math.isfinite(p95):
        metrics["serve_p95_ms"] = p95 * 1e3
    return {
        "metrics": metrics,
        "attempted": n,
        "failed": int(shed.sum()) + never,
        "checks": checks,
        "memory_peak_bytes": peak,
        "counters": {"served_queries": served_q, "flushes": flushes,
                     "engine_calls": calls, "late_max_s": float(late.max()),
                     "query": query},
        "traced": traced,
        **({"latencies_s": lat} if keep_latencies else {}),
    }


def warm_up(timed, mix, cfg, ladder, rng) -> None:
    """Compile what the window runs: every bucket of the ladder through
    the engine (a flush holds up to max_queue queries, chunked at the
    ladder's top), and every flush size up to the mix's
    ``warm_sizes_upto``.  The engine trims each padded answer with an
    eager slice, which compiles one program per (bucket, size); slicing
    arrays of the answers' shapes and types builds the same programs
    without running the engine.  A larger flush compiles inside the
    window, and the harness counts it."""
    import jax.numpy as jnp

    for b in ladder:
        timed_call(timed, mix, traffic.payload(mix, cfg, rng, b))
    if mix["query"] == "top_k":
        k = int(mix["k"])
        answers = {b: (jnp.zeros((b, k), jnp.float32),
                       jnp.zeros((b, k), jnp.int32)) for b in ladder}
    else:
        answers = {b: (jnp.zeros((b,), jnp.float32),) for b in ladder}
    for n in range(1, min(int(mix["warm_sizes_upto"]), ladder[-1]) + 1):
        b = min(x for x in ladder if x >= n)
        if n < b:
            jax.block_until_ready([a[:n] for a in answers[b]])


def timed_call(timed, mix, payload):
    if mix["query"] == "top_k":
        return timed.top_k(int(mix["mode"]), payload, int(mix["k"]),
                           target_mode=int(mix["target_mode"]))
    return timed.predict(payload)


def sample(ctx, n: int) -> np.ndarray:
    """Which requests the check compares: all of them, or
    ``check_requests`` drawn from the seed."""
    want = int(ctx.mix.get("check_requests", 0))
    if not want or want >= n:
        return np.arange(n)
    rng = np.random.default_rng([ctx.seed, 2])
    return np.sort(rng.choice(n, want, replace=False))


def check_answers(ctx, factors, core, sched, answers, dtype) -> dict:
    """The widest gaps between the served answers (``answers``: request
    index -> answer, for the sampled requests that were answered) and the
    reference.

    ``dtype`` other than float32 puts the reference, rounded to it, in
    the program's place: the control.
    """
    mix = ctx.mix
    with jax.default_matmul_precision("highest"):
        tabs, colsums = reference.tables(factors, core)
        pick = sorted(answers)
        if mix["query"] == "top_k":
            return _check_topk(mix, tabs, colsums, sched, answers, pick,
                               dtype)
        return _check_predict(tabs, sched, answers, pick, dtype)


def _check_topk(mix, tabs, colsums, sched, answers, pick, dtype) -> dict:
    mode, target, k = int(mix["mode"]), int(mix["target_mode"]), int(
        mix["k"])
    I_t = tabs[target].shape[0]
    rank_gap = score_gap = 0.0
    block = 256
    ids = np.concatenate([np.asarray(sched[i][1]) for i in pick])
    served_s = np.concatenate([np.asarray(answers[i][0]) for i in pick])
    served_i = np.concatenate([np.asarray(answers[i][1]) for i in pick])
    for s in range(0, len(ids), block):
        e = min(s + block, len(ids))
        ref = reference.topk_scores(tabs, colsums, ids[s:e], mode=mode,
                                    target=target)
        if dtype == "float32":
            got_s, got_i = served_s[s:e], served_i[s:e]
        else:
            ctl = reference.topk_scores(tabs, colsums, ids[s:e], mode=mode,
                                        target=target, dtype=dtype)
            got_s, got_i = (np.asarray(x) for x in jax.lax.top_k(ctl, k))
        if (got_i.shape != (e - s, k) or (got_i < 0).any()
                or (got_i >= I_t).any()
                or any(len(set(r)) != k for r in got_i.tolist())):
            return {"topk_rank_gap": math.inf, "topk_score_gap": math.inf}
        best = np.asarray(jax.lax.top_k(ref, k)[0])
        ref_np = np.asarray(ref)
        scale = ref_np.std(axis=1)
        ref_at = np.take_along_axis(ref_np, got_i, axis=1)
        rank_gap = max(rank_gap, float(
            np.max(np.maximum(best - ref_at, 0.0) / scale[:, None])))
        score_gap = max(score_gap, float(
            np.max(np.abs(got_s - ref_at) / scale[:, None])))
    return {"topk_rank_gap": rank_gap, "topk_score_gap": score_gap}


def _check_predict(tabs, sched, answers, pick, dtype) -> dict:
    idx = np.concatenate([np.asarray(sched[i][1]) for i in pick])
    got = np.concatenate([np.asarray(answers[i]).reshape(-1) for i in pick])
    if got.shape[0] != idx.shape[0]:
        return {"predict_gap": math.inf}
    ref = np.asarray(reference.predict(tabs, idx))
    if dtype != "float32":
        got = np.asarray(reference.predict(tabs, idx, dtype=dtype))
    scale = float(np.sqrt(np.mean(ref.astype(np.float64) ** 2)))
    return {"predict_gap": float(np.max(np.abs(got - ref)) / scale)}
