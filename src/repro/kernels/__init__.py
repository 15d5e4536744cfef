"""Accelerator kernels for the paper's hot loops, behind a backend registry.

Layout:

    dispatch.py         named-backend registry + resolution
                        ("xla" | "pallas" | "pallas_interpret";
                        $REPRO_KERNEL_BACKEND overrides the default)
    kruskal_contract.py Theorem-1 forward contraction (Pallas)
    kruskal_grad.py     fused forward + Eq.13/17 gradient pass — the whole
                        per-nonzero pipeline in ONE pallas_call (Pallas)
    scatter_accum.py    MXU one-hot scatter for factor-row gradients
                        (Pallas) — the UNSORTED-batch fallback: O(rows×B)
                        dense sweep, batch order free
    segment_reduce.py   segmented-reduce scatter for MODE-SORTED batches
                        (``core.sampling.sorted_batch_layout`` /
                        ``FastTuckerConfig(sorted_batches=True)``): row
                        tiles of the output, each fed its contiguous run
                        of sorted entries — O(B) adds, zero MXU work,
                        bitwise equal to the jnp reference (Pallas)
    tucker_matmul.py    Tucker-2 factorized dense layer (Pallas)
    flash_attention.py  flash attention for the LM workload (Pallas)
    tiling.py           shared TPU tiling rules (lane-dense per-sample
                        rows, VMEM-budgeted batch tiles)
    ref.py              pure-jnp oracles for every kernel (test ground truth)

Call sites select a backend by name — ``FastTuckerConfig(backend=...)``,
``--backend`` on the launch CLIs — and everything downstream routes through
``dispatch.get_backend(name)``.
"""
from . import dispatch, ref
from .dispatch import get_backend, register_backend

__all__ = ["dispatch", "ref", "get_backend", "register_backend"]
