"""AOT-compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at Netflix-prize widths (batch
4096 and a ragged 1000, J = R = 32, factor tables of 480,189 and 17,770
rows) and compiles it for one chip of a ``v5e:2x2`` topology, which the
TPU compiler accepts without a chip attached.  That catches what
interpret mode cannot: block shapes Mosaic refuses, layouts that do not
match XLA's, primitives with no TPU lowering, and tiles past VMEM.

The topology is described inside a module-scoped fixture, never at
import, so every test worker collects the same tests and only the one
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.dispatch import get_backend
from repro.kernels.kruskal_contract import kruskal_contract
from repro.kernels.kruskal_grad import NUM_SCALARS, kruskal_grad
from repro.kernels.scatter_accum import scatter_accum
from repro.kernels.segment_reduce import segment_reduce

N, J, R = 3, 32, 32
NETFLIX_ROWS = (480_189, 17_770)
BATCHES = (4096, 1000)
DTYPES = ("float32", "bfloat16")

# (row_modes, want_core, emit_c, consume_c) for every step layout:
# joint, phase-split factor phase, phase-split core phase, and the
# Gauss-Seidel single-mode pass that consumes the cache
GRAD_VARIANTS = {
    "joint": (None, True, False, False),
    "factor_phase": (None, False, True, False),
    "core_phase": ((), True, False, True),
    "gauss_seidel_mode": ((1,), False, True, True),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of any cache dir
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=one_chip)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("variant", sorted(GRAD_VARIANTS))
def test_kruskal_grad_compiles(spec, variant, batch, dtype):
    row_modes, want_core, emit_c, consume_c = GRAD_VARIANTS[variant]
    args = [spec((N, batch, J), dtype), spec((N, J, R), dtype),
            spec((batch,), "float32"), spec((batch,), "float32"),
            spec((NUM_SCALARS,), "float32")]
    if consume_c:
        args.append(spec((N, batch, R), "float32"))

    def fn(*ops):
        return kruskal_grad(*ops, row_modes=row_modes, want_core=want_core,
                            emit_c=emit_c, interpret=False)

    _compile(fn, *args)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("batch", BATCHES)
def test_kruskal_contract_compiles(spec, batch, dtype):
    _compile(lambda a, b: kruskal_contract(a, b, interpret=False),
             spec((N, batch, J), dtype), spec((N, J, R), dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("rows", NETFLIX_ROWS)
def test_scatter_accum_compiles(spec, rows, batch, dtype):
    _compile(lambda g, i: scatter_accum(g, i, rows, interpret=False),
             spec((batch, J), dtype), spec((batch,), "int32"))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("rows", NETFLIX_ROWS)
def test_segment_reduce_compiles(spec, rows, batch, dtype):
    _compile(lambda g, i: segment_reduce(g, i, rows, interpret=False),
             spec((batch, J), dtype), spec((batch,), "int32"))


@pytest.mark.parametrize("sorted_batches", [False, True])
def test_backend_step_ops_compile(spec, sorted_batches):
    """The registry's compiled backend as the training step calls it:
    per-mode rows padded and stacked, the fused kernel, and the
    row-gradient scatter into the largest factor table."""
    bk = get_backend("pallas")
    batch, rows = BATCHES[0], NETFLIX_ROWS[0]

    def step(a0, a1, a2, b0, b1, b2, val, ids):
        kg = bk.kruskal_grad((a0, a1, a2), (b0, b1, b2), val,
                             lambda_a=0.01, lambda_b=0.01)
        scatter = bk.segment_reduce if sorted_batches else bk.scatter_accum
        return scatter(kg.row_grads[0], ids, rows), kg.core_grads

    _compile(step, *[spec((batch, J), "float32")] * 3,
             *[spec((J, R), "float32")] * 3, spec((batch,), "float32"),
             spec((batch,), "int32"))
