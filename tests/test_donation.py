"""Donated step buffers, exercised where they are cheap to debug.

Accelerators donate the step's ``DistState`` into the compiled update by
default (``REPRO_DONATE_STEP=auto``), and the serving programs donate
their padded index buffers.  CPU XLA honours donation too, so forcing it
on here runs the same aliasing a chip does: any read of a donated buffer
(a stale ``dstate`` kept across a step, a checkpoint written from the old
state) fails with "Array has been deleted".
"""
import contextlib

import jax
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core import FastTuckerConfig, init_state, rmse_mae
from repro.core import fasttucker as ft
from repro.data.synthetic import planted_tensor
from repro.distributed import get_strategy
from repro.distributed.base import DONATE_ENV_VAR
from repro.launch.mesh import make_host_mesh
from repro.serve import TuckerServer

DIMS = (40, 32, 24)


@pytest.fixture(scope="module")
def split():
    return planted_tensor(DIMS, 4000, rank=4, core_rank=4,
                          seed=1).split(0.1)


def _cfg(**kw):
    return FastTuckerConfig(dims=DIMS, ranks=(4, 4, 4), core_rank=4,
                            batch_size=128, **kw)


@pytest.mark.parametrize("layout", ["joint", "phase_split", "sorted"])
@pytest.mark.parametrize("compress", [False, True])
@pytest.mark.parametrize(
    "name", ["local", "sync", "strata", "strata_overlap"])
def test_donated_step_trains_and_checkpoints(split, tmp_path, monkeypatch,
                                             name, compress, layout):
    monkeypatch.setenv(DONATE_ENV_VAR, "on")
    train, test = split
    kw = {"phase_split": {"phase_split": True},
          "sorted": {"sorted_batches": True}}.get(layout, {})
    cfg = _cfg(**kw)
    st = get_strategy(name)
    mesh = make_host_mesh() if st.needs_mesh else None
    plan = st.prepare(train, cfg, mesh, compress=compress, seed=0)
    ds = st.init(plan, init_state(jax.random.PRNGKey(0), cfg),
                 jax.random.PRNGKey(1))
    step = st.make_step(plan)
    with (mesh if mesh is not None else contextlib.nullcontext()):
        first = ds
        ds = step(ds)
        # the step aliased its input state: the old buffers are gone
        assert jax.tree.leaves(first.params)[0].is_deleted()
        for _ in range(4):
            ds = step(ds)
        ckpt = CheckpointManager(str(tmp_path))
        st.save(plan, ckpt, ds)
        ds = st.restore(plan, ckpt, ds)
        ds = step(ds)
        r, _ = rmse_mae(st.eval_params(plan, ds), test, ft.predict)
    assert int(ds.step) == 6
    assert np.isfinite(float(r))


def test_donated_serving_programs(split):
    train, test = split
    params = init_state(jax.random.PRNGKey(0), _cfg()).params
    plain = TuckerServer(params, backend="xla", donate=False)
    srv = TuckerServer(params, backend="xla", donate=True)
    idx = np.asarray(test.indices[:50])
    users = np.arange(7)
    np.testing.assert_array_equal(np.asarray(srv.predict(idx)),
                                  np.asarray(plain.predict(idx)))
    np.testing.assert_array_equal(np.asarray(srv.top_k(0, users, 5)[1]),
                                  np.asarray(plain.top_k(0, users, 5)[1]))
    rows = np.asarray(params.factors[0][:2]) * 2
    srv.update_rows(0, np.array([1, 2]), rows)
    plain.update_rows(0, np.array([1, 2]), rows)
    srv.refresh_tables()
    np.testing.assert_array_equal(np.asarray(srv.predict(idx)),
                                  np.asarray(plain.predict(idx)))
