"""Device time per program scope and the program's host spans, read from
synthetic traces and one real CPU trace (no chip needed)."""
from __future__ import annotations

import time
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, scopes, trace


def meta(pid, name, tid=None):
    if tid is None:
        return {"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": name}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": name}}


def op(pid, start_ns, dur_ns, tf_op=None, tid=3, name="fusion"):
    args = {} if tf_op is None else {"tf_op": tf_op}
    return {"ph": "X", "pid": pid, "tid": tid, "ts": start_ns / 1e3,
            "dur": dur_ns / 1e3, "name": name, "args": args}


def tpu(pid, ops):
    """One TPU's events: its ``XLA Ops`` (tid 3) and a module line."""
    return [meta(pid, f"/device:TPU:{pid}"), meta(pid, "XLA Ops", 3),
            meta(pid, "XLA Modules", 2),
            op(pid, 0, 10_000, "repro.step.grad/module", tid=2)] + ops


SCATTER = "jit(core_step)/jit(sgd_step)/repro.step.scatter/scatter-add:"
UPDATE = "jit(core_step)/jit(sgd_step)/repro.step.update/sub:"
OPS = [op(0, 0, 100, SCATTER), op(0, 50, 100, SCATTER),     # 0..150
       op(0, 120, 10, SCATTER),                               # nested
       op(0, 200, 100, UPDATE),
       # nested scopes: the innermost names the op
       op(0, 300, 40, "jit(f)/repro.step.grad/repro.step.scatter/add"),
       op(0, 400, 50, "dstate.params.factors[0]:"),           # unscoped
       op(0, 450, 30)]                                        # no tf_op


def test_scoped_is_the_union_per_innermost_scope():
    got = scopes.scoped(tpu(0, OPS))
    assert got == {"repro.step.scatter": 190.0, "repro.step.update": 100.0,
                   scopes.UNSCOPED: 80.0}


def test_scoped_is_averaged_over_devices():
    events = tpu(0, OPS) + tpu(1, [op(1, 0, 50, UPDATE)])
    got = scopes.scoped(events)
    assert got["repro.step.update"] == pytest.approx((100 + 50) / 2)
    assert got["repro.step.scatter"] == pytest.approx(190 / 2)


def test_on_the_cpu_the_ops_are_the_client_threads():
    events = [meta(7, "/host:CPU"), meta(7, "tf_XLAPjRtCpuClient/1", 11),
              meta(7, "python3", 12),
              op(7, 0, 100, "repro.eval.chunk/gather", tid=11),
              op(7, 0, 100, tid=11, name="ThreadpoolListener::Record"),
              op(7, 0, 999, "repro.eval.chunk/gather", tid=12)]
    assert scopes.scoped(events) == {"repro.eval.chunk": 100.0}


@pytest.mark.parametrize("path,scope", [
    (SCATTER, "repro.step.scatter"),
    ("jit(_top_k_impl)/repro.topk.select/top_k", "repro.topk.select"),
    ("jit(_chunk_err)/repro.eval.chunk/jit(cumprod)/reduce_window",
     "repro.eval.chunk"),
    ("tables[0]:", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_scope_of_an_op(path, scope):
    assert scopes.scope_of(path) == scope


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def planes(*host_lines, ops=()):
    return [NS(name="/device:TPU:0",
               lines=[NS(name="XLA Ops", events=list(ops))]),
            NS(name="/host:CPU",
               lines=[NS(name="python3", events=list(line))
                      for line in host_lines])]


def test_spans_are_the_program_and_benchmark_host_events():
    loop = [ev("repro.serve.flush", 100, 50, flush_id=3, requests=2),
            ev("_frontend.py:105_record", 110, 5)]
    worker = [ev("repro.serve.engine", 120, 20, flush_id=3),
              ev("chipbench.engine_call", 121, 10)]
    got = scopes.spans(planes(worker, loop, ops=[ev("repro.x", 0, 9)]))
    assert [s.name for s in got] == ["repro.serve.flush",
                                     "repro.serve.engine",
                                     "chipbench.engine_call"]
    flush, engine, call = got
    assert (flush.start_ns, flush.end_ns) == (100, 150)
    assert flush.stats == {"flush_id": 3, "requests": 2}
    assert engine.thread == call.thread != flush.thread


def test_the_existing_reduction_ignores_event_stats():
    ops = [ev("a", 0, 100, tf_op=SCATTER), ev("b", 50, 100),
           ev("c", 300, 100, tf_op=UPDATE)]
    host = [ev("repro.serve.flush", 150, 150, flush_id=1)]
    plain = [NS(name=e.name, start_ns=e.start_ns, duration_ns=e.duration_ns)
             for e in ops + host]
    with_stats = trace.reduce(planes(host, ops=ops), "tpu", 1e-6)
    without = trace.reduce(planes(plain[3:], ops=plain[:3]), "tpu", 1e-6)
    assert with_stats == without
    assert list(with_stats) == ["busy_s", "window_s", "breakdown"]
    assert with_stats["breakdown"]["idle_gaps"][0][0] == "repro.serve.flush"


def span(name, start, end, thread="w", **stats):
    return scopes.Span(name, start, end, stats, thread)


def traced(scoped=None, spans=(), busy_s=1e-6, count=4):
    return {"traced": {"busy_s": busy_s, "window_s": 2e-6, "count": count,
                       "t0": 0.0, "scoped": dict(scoped or {}),
                       "spans": list(spans)}}


def read(metric, run):
    return harness.load_reader(metric)(run)


def test_step_sweep_ms_reads_scatter_and_update_per_step():
    run = traced({"repro.step.scatter": 3e6, "repro.step.update": 1e6,
                  "repro.step.grad": 9e6}, count=4)
    assert read("step_sweep_ms", run) == pytest.approx(1.0)


@pytest.mark.parametrize("metric,scope", [
    ("eval_device_share", "repro.eval.chunk"),
    ("topk_select_share", "repro.topk.select"),
])
def test_shares_of_busy_time(metric, scope):
    run = traced({scope: 250.0, "repro.step.grad": 750.0}, busy_s=1e-6)
    assert read(metric, run) == pytest.approx(25.0)


FLUSHES = [span("repro.serve.flush", 0, 10, "loop", flush_id=0, requests=2,
                wait_sum_us=3000.0, wait_max_us=2000.0),
           span("repro.serve.flush", 20, 30, "loop", flush_id=1, requests=1,
                wait_sum_us=600.0, wait_max_us=600.0),
           span("repro.serve.flush", 40, 41, "loop", flush_id=2, requests=0,
                wait_sum_us=0.0, wait_max_us=0.0)]


def test_serve_queue_wait_ms_is_the_mean_wait_of_a_request():
    assert read("serve_queue_wait_ms", traced(spans=FLUSHES)) == \
        pytest.approx(3600.0 / 3 / 1e3)


def engine(start, dispatch, wait, fetch, wrapped=0):
    """An engine span of a flush: dispatch, then (in the benchmark) the
    wrapper's block of ``wrapped`` ns, then the wait and the fetch."""
    t = start + 1
    out = []
    if wrapped:
        out.append(span("chipbench.engine_call", t, t + dispatch + wrapped))
    out.append(span("repro.serve.dispatch", t, t + dispatch, buckets=1))
    t += dispatch + wrapped
    out.append(span("repro.serve.wait", t, t + wait))
    out.append(span("repro.serve.fetch", t + wait, t + wait + fetch))
    end = t + wait + fetch + 1
    return [span("repro.serve.engine", start, end, flush_id=0)] + out


@pytest.mark.parametrize("wrapped", [0, 700_000])
def test_serve_dispatch_ms_leaves_out_the_waits(wrapped):
    spans = engine(0, 1_000_000, 300_000, 100_000, wrapped) \
        + engine(5_000_000, 3_000_000, 0, 100_000, wrapped) \
        + [span("repro.serve.wait", 5_000_000, 9_000_000, thread="loop")]
    # host time: the dispatch and the 2 ns around it, in each flush
    assert read("serve_dispatch_ms", traced(spans=spans)) == \
        pytest.approx((1_000_002 + 3_000_002) / 2 / 1e6)


@pytest.mark.parametrize("metric", ["step_sweep_ms", "eval_device_share",
                                    "topk_select_share",
                                    "serve_queue_wait_ms",
                                    "serve_dispatch_ms"])
def test_readers_find_nothing_in_a_program_without_names(metric):
    """A program without scopes and spans (the parent of this reading)
    gives no number, and no error; an untraced run gives none either."""
    assert read(metric, traced({scopes.UNSCOPED: 1000.0})) is None
    assert read(metric, {"traced": None}) is None


def test_a_run_reads_its_own_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RUNS", tmp_path)
    run = {"traced": {"t0": time.perf_counter(), "busy_s": 1.0}}
    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path / "cell" / "trace"))
    with jax.profiler.TraceAnnotation("repro.serve.flush", flush_id=5,
                                      requests=1, wait_sum_us=10.0):
        jax.block_until_ready(x @ x)
    jax.profiler.stop_trace()
    t = scopes.of(run)
    assert [s.name for s in t["spans"]] == ["repro.serve.flush"]
    assert t["spans"][0].stats["flush_id"] == 5
    # the CPU's ops carry no scope path: all of them are unscoped
    assert set(t["scoped"]) == {scopes.UNSCOPED}
    # read once, then kept
    assert scopes.of(run) is t
    # a trace written before the run's window is not this run's
    later = {"traced": {"t0": time.perf_counter() + 5.0}}
    assert scopes.of(later)["spans"] == []
