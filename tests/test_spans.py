"""The program's names in a profiler trace: every phase of the jitted
training step, the evaluation and top-k carry their ``repro.*`` scope in
the lowered program, and a served flush records its host spans with
their stats on the threads that did the work."""
import asyncio
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import FastTuckerConfig, init_state
from repro.core import fasttucker as ft
from repro.core.metrics import _held_out_err
from repro.core.sptensor import SparseTensor
from repro.distributed import get_strategy
from repro.serve import AdmissionConfig, ServeFrontend, TuckerServer
from repro.serve.engine import _top_k_impl

DIMS = (9, 7, 5)
STEP_SCOPES = {"repro.step.sample", "repro.step.grad", "repro.step.scatter",
               "repro.step.update"}


# an order-10 tensor, as the paper's synthesis set goes to
DIMS10 = (6, 5, 4, 7, 3, 5, 4, 6, 3, 5)


def _cfg(dims=DIMS, **kw):
    ranks = (3, 4, 2) if dims == DIMS else (3,) * len(dims)
    return FastTuckerConfig(dims=dims, ranks=ranks, core_rank=3,
                            batch_size=16, **kw)


def _tensor(nnz=60, seed=0, dims=DIMS):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, d, nnz) for d in dims], 1)
    return SparseTensor(jax.numpy.asarray(idx, np.int32),
                        jax.numpy.asarray(rng.random(nnz), np.float32), dims)


def _lowered_text(lowered) -> str:
    return lowered.as_text(debug_info=True)


def _has_scope(text: str, scope: str) -> bool:
    """An op location of the lowered program names ``scope`` as one
    component of its path."""
    return re.search(rf'loc\("(?:[^"]*/)?{re.escape(scope)}/', text) \
        is not None


@pytest.mark.parametrize("update_order", ["jacobi", "gauss_seidel"])
@pytest.mark.parametrize("phase_split", [False, True])
def test_local_step_carries_every_step_scope(update_order, phase_split):
    cfg = _cfg(update_order=update_order, phase_split=phase_split)
    st = get_strategy("local")
    plan = st.prepare(_tensor(), cfg, None)
    key = jax.random.PRNGKey(0)
    ds = st.init(plan, init_state(key, cfg), key)
    text = _lowered_text(st.lower_step(plan, ds))
    for scope in STEP_SCOPES:
        assert _has_scope(text, scope), scope


def test_evaluation_chunk_carries_its_scope():
    cfg = _cfg()
    params = ft.init_params(jax.random.PRNGKey(0), cfg)
    t = _tensor()
    text = _lowered_text(_held_out_err.lower(params, t.indices, t.values,
                                             predict_fn=ft.predict, chunk=16))
    assert _has_scope(text, "repro.eval.chunk")
    # the pack is named inside it, and no op of the evaluation sits under
    # an inner repro.* scope: the evaluation's share counts all of it
    assert _has_scope(text, "lane_pack")
    paths = re.findall(r'loc\("([^"]*repro\.[^"]*)"', text)
    assert paths
    assert all(re.findall(r"repro\.[A-Za-z0-9_.]+", p)[-1]
               == "repro.eval.chunk" for p in paths), paths


def _op_paths(compiled) -> list[str]:
    """The scope path of every op of a compiled program (its ``op_name``
    metadata, which a TPU trace shows as the op's ``tf_op``)."""
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _innermost_repro(path: str) -> str:
    return re.findall(r"repro\.[A-Za-z0-9_.]+", path)[-1]


@pytest.mark.parametrize("dims", [DIMS, DIMS10], ids=["order3", "order10"])
def test_exclusive_products_are_named_inside_step_and_evaluation(dims):
    """The N-way exclusive products carry their own unprefixed name in
    the step and in the evaluation, under the enclosing ``repro.*`` scope,
    which keeps their device time."""
    cfg = _cfg(dims)
    t = _tensor(dims=dims)
    st = get_strategy("local")
    plan = st.prepare(t, cfg, None)
    key = jax.random.PRNGKey(0)
    ds = st.init(plan, init_state(key, cfg), key)
    step = st.lower_step(plan, ds).compile()
    evaluation = _held_out_err.lower(
        ft.init_params(key, cfg), t.indices, t.values,
        predict_fn=ft.predict, chunk=16).compile()
    for compiled, outer in ((step, "repro.step.grad"),
                            (evaluation, "repro.eval.chunk")):
        paths = [p for p in _op_paths(compiled)
                 if "exclusive_products" in p.split("/")]
        assert paths, outer
        assert any(p.endswith("/rev") for p in paths), paths
        for p in paths:
            assert f"{outer}/" in p.split("exclusive_products")[0], p
            assert _innermost_repro(p) == outer, p


def test_top_k_carries_score_and_select_scopes():
    server = TuckerServer(ft.init_params(jax.random.PRNGKey(0), _cfg()))
    lowered = jax.jit(_top_k_impl, static_argnums=(3, 4, 5, 6)).lower(
        server._tables, server._colsums, np.arange(4, dtype=np.int32),
        0, 1, 3, DIMS[1])
    text = _lowered_text(lowered)
    for scope in ("repro.topk.score", "repro.topk.select"):
        assert _has_scope(text, scope), scope
    # the selection is the top-k itself, and nothing of the scoring
    select = [ln for ln in text.splitlines() if "repro.topk.select" in ln]
    assert any("top_k" in ln for ln in select)
    assert not any("dot_general" in ln for ln in select)


def _host_spans(directory):
    """{thread line index: [(name, start, end, stats)]} of repro spans."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.setdefault(i, []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


@pytest.mark.parametrize("query", ["top_k", "predict"])
def test_flush_spans_on_a_real_trace(query, tmp_path):
    server = TuckerServer(ft.init_params(jax.random.PRNGKey(0), _cfg()))
    if query == "top_k":
        reqs = [np.array([i, i + 1], np.int32) for i in range(3)]
        kw = {"query": "top_k", "top_k_args": (0, 2, 1)}
    else:
        reqs = [np.array([[i, 1, 2]], np.int32) for i in range(3)]
        kw = {}
    queries = sum(len(r) for r in reqs)

    async def main():
        # the flush starts once all of them are queued
        async with ServeFrontend(server, AdmissionConfig(microbatch=queries),
                                 **kw) as fe:
            await fe.submit(reqs[0])              # compile outside the trace
            jax.profiler.start_trace(str(tmp_path))
            try:
                await asyncio.gather(*(fe.submit(r) for r in reqs))
            finally:
                jax.profiler.stop_trace()

    asyncio.run(main())
    threads = _host_spans(str(tmp_path))
    flushes = [s for spans in threads.values() for s in spans
               if s[0] == "repro.serve.flush"]
    engines = [(i, s) for i, spans in threads.items() for s in spans
               if s[0] == "repro.serve.engine"]
    assert len(flushes) == 1 and len(engines) == 1
    _, _, _, fstats = flushes[0]
    assert fstats["requests"] == 3 and fstats["queries"] == queries
    assert 0 <= fstats["wait_max_us"] <= fstats["wait_sum_us"]
    line, (_, e0, e1, estats) = engines[0]
    assert estats["flush_id"] == fstats["flush_id"]
    assert estats["queries"] == fstats["queries"]
    inner = {name: (s, e, st) for name, s, e, st in threads[line]
             if e0 <= s and e <= e1 and name != "repro.serve.engine"}
    assert set(inner) == {"repro.serve.dispatch", "repro.serve.wait",
                          "repro.serve.fetch"}
    assert inner["repro.serve.dispatch"][2]["buckets"] == 1
    # the engine runs on the worker thread, not the event loop's
    assert all(s[0] != "repro.serve.engine" for s in threads[
        next(i for i, spans in threads.items()
             if any(s[0] == "repro.serve.flush" for s in spans))])


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_compilation_cache_include_metadata_in_key",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


def test_cached_programs_keep_their_own_scope_names(tmp_path, monkeypatch):
    """A program that differs from a cached one only in its scope names is
    compiled anew, not loaded with the old names."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.runtime.compile_cache import use_compile_cache

    def under(scope):
        def f(x):
            with jax.named_scope(scope):
                return jax.numpy.sin(x) * 2.0
        return f

    saved = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert use_compile_cache() == str(tmp_path)
        # what JAX reads from the variable when it starts
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = np.ones(8, np.float32)
        jax.jit(under("repro.old")).lower(x).compile()
        assert os.listdir(tmp_path)                 # the cache was written
        jax.clear_caches()
        text = jax.jit(under("repro.new")).lower(x).compile().as_text()
        assert "repro.new" in text and "repro.old" not in text
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
