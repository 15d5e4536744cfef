"""Batched FastTucker inference engine over trained (factors, core_factors).

See the package docstring (``repro.serve``) for the Theorem-1 math. The
engine caches the per-mode Kruskal products

    C^(n) = A^(n) B^(n) ∈ R^{I_n × R}          (all mode dots, precomputed)

and serves every query class from them without ever materializing the dense
tensor:

    predict            x̂(i_1..i_N) = Σ_r Π_n C^(n)[i_n, r]
    reconstruct_rows   one factored einsum over the C^(n) → requested slices
    top_k              scores = (C^(m)[ids] ⊙ Π_other σ^(k)) C^(t)ᵀ, σ^(k)
                       the column sums marginalizing unpinned modes

The contraction itself is routed through the named kernel-backend registry
(``repro.kernels.dispatch``): the cached tables are served as synthetic
FastTucker parameters ``(factors=C^(n), core_factors=I_R)`` — mode dots of
rows of C against the identity ARE the cached coefficients — so ``"xla"``,
``"pallas"`` and ``"pallas_interpret"`` all run their real Theorem-1
kernels on the hot path, not a serving-only code fork.

Requests are padded onto a fixed bucket ladder (``repro.serve.bucketing``)
so the jit cache stays bounded; every entry point's padded index buffer is
donated on accelerators.

Sharded serving (``mesh=``) comes in two modes behind one API, chosen by
``shard_mode`` (``repro.serve.policy`` decides under ``"auto"``):

  * ``"row"`` — tables row-shard over ``data`` (the strata training
    layout).  Every query runs a hand-written ``shard_map`` program with
    explicitly small collectives instead of whatever gathers GSPMD would
    pick: ``predict`` reassembles coefficient rows with one fused psum;
    ``top_k`` scores ONLY the local row shard of C^(t), takes a local
    ``lax.top_k`` and merges the M·k ``(score, global id)`` candidates
    with one all-gather — the flash-decode shard-merge idiom — so the
    per-query collective payload is O(B·R + M·k·B), not O(rows);
    ``reconstruct_rows`` shards the output over the largest free mode and
    all-gathers only the smaller tables plus the result blocks.
  * ``"batch"`` — tables replicated, request batches split over ``data``
    (``sharding.serve_table_replication``): zero per-query collectives,
    throughput scales with M — the small-table / high-QPS deployment.

Before this split existed, ``top_k``/``reconstruct_rows`` on a ``mesh=``
server silently ran against whatever layout GSPMD chose for the sharded
tables; both now have real shard-local programs in both modes, and an
unknown ``shard_mode`` raises instead of degrading.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.core.fasttucker import FastTuckerParams
from repro.core.fasttucker import predict as ft_predict
from repro.core.kruskal import mode_products
from repro.distributed.sharding import (
    serve_row_sharding, serve_table_replication,
)
from repro.kernels import dispatch

from .bucketing import (
    DEFAULT_MAX_BUCKET, DEFAULT_MIN_BUCKET, bucket_ladder, split_batch,
)
from .policy import ShardDecision, ShardPolicy, choose_shard_mode

_LETTERS = "abcdefghijklmnop"


class _TableSet(NamedTuple):
    """One immutable generation of serving state, swapped atomically.

    Every query entry point snapshots ``server._live`` ONCE on entry and
    serves all of its bucketed chunks from that snapshot, so an
    ``update_rows``/``refresh_tables`` swap landing mid-request can never
    produce a torn read: in-flight work finishes entirely against the old
    generation (whose buffers stay alive exactly as long as someone holds
    the snapshot), and the next request sees the new one.
    """

    version: int       # monotone generation counter
    tables: tuple      # placed C^(n), table_dtype storage
    colsums: tuple     # f32 column sums of the TRUE rows, per mode


# ---------------------------------------------------------------------------
# checkpoint → params (shape-driven, no writer pytree needed)
# ---------------------------------------------------------------------------

def load_params_from_checkpoint(
    directory, step: int | None = None,
    dims: Sequence[int] | None = None,
) -> tuple[FastTuckerParams, int]:
    """Recover (factors, core_factors) from a ``checkpoint.manager`` dir.

    Works for every tree the trainers write — ``TrainState`` and every
    strategy's ``DistState`` — by position: both flatten to
    ``[A^(1)..A^(N), B^(1)..B^(N), step, key, *ef]``, so the leading run of
    2-D leaves is exactly the parameters and its length fixes N. Shapes are
    cross-checked (``B^(n)`` rows must equal ``A^(n)`` cols, one shared R).

    ``dims`` trims factor rows — strata checkpoints carry rows padded to a
    device multiple; pass the true mode sizes to serve the trained slice.
    """
    manifest, leaves = CheckpointManager(directory).load_leaves(step)
    n2 = 0
    while n2 < len(leaves) and leaves[n2].ndim == 2:
        n2 += 1
    if n2 < 4 or n2 % 2:
        raise ValueError(
            f"checkpoint in {directory} does not look like FastTucker "
            f"state: leading 2-D leaf run has length {n2} (want even ≥ 4)")
    N = n2 // 2
    factors = leaves[:N]
    core_factors = leaves[N:n2]
    R = core_factors[0].shape[1]
    for n in range(N):
        if (core_factors[n].shape[0] != factors[n].shape[1]
                or core_factors[n].shape[1] != R):
            raise ValueError(
                f"checkpoint leaf shapes inconsistent at mode {n}: "
                f"A{factors[n].shape} vs B{core_factors[n].shape} (R={R})")
    if dims is not None:
        if len(dims) != N:
            raise ValueError(f"dims has {len(dims)} modes, checkpoint {N}")
        for n, d in enumerate(dims):
            if d > factors[n].shape[0]:
                raise ValueError(
                    f"dims[{n}]={d} exceeds checkpointed rows "
                    f"{factors[n].shape[0]}")
        factors = [f[:d] for f, d in zip(factors, dims)]
    return (
        FastTuckerParams(
            tuple(jnp.asarray(f) for f in factors),
            tuple(jnp.asarray(b) for b in core_factors),
        ),
        int(manifest["step"]),
    )


# ---------------------------------------------------------------------------
# query kernel bodies (plain functions: per-server jits wrap them so the
# index buffer can be donated, and the batch-sharded mode reuses them
# verbatim inside shard_map — bitwise the unsharded computation per chunk)
# ---------------------------------------------------------------------------

def _reconstruct_impl(tables, ids, mode, true_dims):
    """Factored slice reconstruction: (B, *dims except mode), f32 accum."""
    N = len(tables)
    rows = tables[mode][ids]                       # (B, R)
    operands, subs = [rows], ["zr"]
    out = "z"
    for n in range(N):
        if n == mode:
            continue
        operands.append(tables[n][: true_dims[n]])
        subs.append(f"{_LETTERS[n]}r")
        out += _LETTERS[n]
    return jnp.einsum(",".join(subs) + "->" + out, *operands,
                      preferred_element_type=jnp.float32)


def _top_k_impl(tables, colsums, ids, mode, target, k, true_target_dim):
    """(scores, item ids): rank ``target``-mode entries for each ``ids`` row,
    remaining modes marginalized by their column sums (f32 scores even for
    bf16 tables — the colsums are kept f32 and the dot accumulates f32)."""
    with jax.named_scope("repro.topk.score"):
        w = tables[mode][ids]                      # (B, R)
        for n in range(len(tables)):
            if n not in (mode, target):
                w = w * colsums[n][None, :]
        scores = jnp.matmul(w, tables[target][:true_target_dim].T,
                            preferred_element_type=jnp.float32)
    with jax.named_scope("repro.topk.select"):
        values, items = jax.lax.top_k(scores, k)
    return values, items


def _psum_row_gather(table, ids, block_rows, axis="data"):
    """Gather global ``ids`` rows from a row-sharded table: each row lives
    on exactly one device, so zero-masking the out-of-shard rows and one
    fused psum IS the gather (exact in any float dtype — the other shards
    contribute literal zeros).  Payload: one (B, R) all-reduce."""
    me = jax.lax.axis_index(axis)
    local = ids - me * block_rows
    valid = (local >= 0) & (local < block_rows)
    safe = jnp.clip(local, 0, block_rows - 1)
    rows = table[safe] * valid[:, None].astype(table.dtype)
    return jax.lax.psum(rows, axis)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class TuckerServer:
    """Batched query engine over one trained FastTucker model.

    Parameters
    ----------
    params : FastTuckerParams
        Trained ``(A^(n), B^(n))`` in the global (trimmed) layout, e.g.
        ``strategy.eval_params(...)`` or ``load_params_from_checkpoint``.
    backend : str | None
        Kernel backend for the prediction contraction (named registry;
        default resolves ``$REPRO_KERNEL_BACKEND`` then ``"xla"``).
    mesh : jax.sharding.Mesh | None
        Serve the C^(n) tables sharded over the mesh's ``data`` axis, in
        the layout ``shard_mode`` selects.
    shard_mode : str
        ``"row"`` (tables row-sharded, shard-local query programs),
        ``"batch"`` (tables replicated, request batches split over
        ``data``) or ``"auto"`` (``repro.serve.policy`` decides from
        table bytes × ``expected_qps``; the decision is recorded on
        ``self.shard_decision``).  Ignored without ``mesh`` — except that
        explicitly asking for a sharded mode then raises.
    expected_qps : float | None
        Declared query rate, consumed by the ``"auto"`` policy only.
    policy : ShardPolicy | None
        Threshold overrides for the ``"auto"`` decision.
    max_bucket / min_bucket : int
        Request bucket ladder bounds (see ``repro.serve.bucketing``).
        Batch-sharded servers round every bucket up to a multiple of the
        ``data`` extent so each device gets an equal request chunk.
    donate : "auto" | bool
        Donate the padded index buffer into the hot loops — predict,
        top_k AND reconstruct_rows ("auto" enables it off-CPU only;
        CPU XLA cannot donate and would warn per call).
    table_dtype : str | None
        Storage dtype for the cached C^(n) tables (and the synthetic
        identity core factors). ``None`` keeps the params' dtype — so
        bf16-trained checkpoints serve bf16 tables automatically;
        ``"bfloat16"`` halves the table memory of f32-trained params.
        The tables are always COMPUTED with f32 accumulation and only
        stored rounded; every query contraction re-accumulates in f32,
        so predictions/scores come back f32 regardless.
    """

    def __init__(
        self,
        params: FastTuckerParams,
        *,
        backend: str | None = None,
        mesh=None,
        shard_mode: str = "auto",
        expected_qps: float | None = None,
        policy: ShardPolicy | None = None,
        max_bucket: int = DEFAULT_MAX_BUCKET,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        donate: str | bool = "auto",
        table_dtype: str | None = None,
    ):
        self.backend = dispatch.resolve_backend_name(backend)
        dispatch.get_backend(self.backend)        # fail fast on typos
        N = len(params.factors)
        if N < 2 or len(params.core_factors) != N:
            raise ValueError(f"need ≥2 modes with matching core factors, "
                             f"got {N}/{len(params.core_factors)}")
        R = params.core_factors[0].shape[1]
        for n in range(N):
            if (params.factors[n].shape[1] != params.core_factors[n].shape[0]
                    or params.core_factors[n].shape[1] != R):
                raise ValueError(f"mode {n}: A{params.factors[n].shape} "
                                 f"incompatible with "
                                 f"B{params.core_factors[n].shape}")
        self._params = params
        # writable host mirror of the factor matrices: ``update_rows``
        # syncs dirty rows in place (O(dirty) per call) and ``params``
        # re-materializes device arrays only when actually read
        self._host_factors = [np.array(f) for f in params.factors]
        self._params_stale = False
        self.dims = tuple(int(f.shape[0]) for f in params.factors)
        self.order = N
        self.core_rank = int(R)
        self.ladder = bucket_ladder(max_bucket, min_bucket)
        dtype = jnp.dtype(table_dtype) if table_dtype is not None \
            else params.factors[0].dtype
        self.table_dtype = dtype
        self._eyes = tuple(jnp.eye(R, dtype=dtype) for _ in range(N))

        # compute the tables with f32 accumulation, store in table dtype
        tables32 = mode_products(params.factors, params.core_factors,
                                 accum_dtype=jnp.float32)
        # column sums over TRUE rows only — marginalization weights for
        # top_k; kept f32 (from the unrounded tables) even for bf16 storage
        colsums = tuple(t.sum(axis=0) for t in tables32)
        tables = tuple(t.astype(dtype) for t in tables32)

        if donate == "auto":
            donate = jax.default_backend() != "cpu"

        # ---- sharded-mode resolution (explicit, never silent) -------------
        self.mesh = mesh
        self.shard_decision: ShardDecision | None = None
        if mesh is None:
            if shard_mode in ("row", "batch"):
                raise ValueError(
                    f"shard_mode={shard_mode!r} requires mesh=")
            self.shard_mode = "none"
        else:
            if "data" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'data' axis, got {mesh.axis_names}")
            if shard_mode == "auto":
                self.shard_decision = choose_shard_mode(
                    sum(int(t.nbytes) for t in tables),
                    int(mesh.shape["data"]), expected_qps, policy)
                self.shard_mode = self.shard_decision.mode
            elif shard_mode in ("row", "batch"):
                self.shard_mode = shard_mode
            else:
                raise ValueError(
                    f"unknown shard_mode {shard_mode!r} "
                    "(want 'auto' | 'row' | 'batch')")

        # ---- per-mode table placement + compiled query programs ------------
        # (per-instance jits: the compile cache — and its bucket-ladder
        # bound — belongs to one server, and every entry point's padded
        # index buffer is donated into its hot loop off-CPU.)
        if self.shard_mode == "none":
            self._block_rows = None
            backend_name = self.backend

            def _predict_impl(tables_, eyes_, idx):
                return ft_predict(FastTuckerParams(tables_, eyes_), idx,
                                  backend=backend_name)

            self._predict_fn = jax.jit(
                _predict_impl, donate_argnums=(2,) if donate else ())
            self._top_k_fn = jax.jit(
                _top_k_impl,
                static_argnames=("mode", "target", "k", "true_target_dim"),
                donate_argnums=(2,) if donate else ())
            self._reconstruct_fn = jax.jit(
                _reconstruct_impl, static_argnames=("mode", "true_dims"),
                donate_argnums=(1,) if donate else ())
        elif self.shard_mode == "row":
            # rows pad to the data-axis multiple before sharding (strata
            # layout); padding rows are zero ⟹ zero coefficients.
            M = int(mesh.shape["data"])
            self._block_rows = tuple(-(-d // M) for d in self.dims)
            self._predict_fn = self._build_row_predict(donate)
            self._top_k_fn = self._build_row_top_k(donate)
            self._reconstruct_fn = self._build_row_reconstruct(donate)
        else:  # batch
            M = int(mesh.shape["data"])
            # every bucket must split evenly over the data axis: round the
            # ladder up to multiples of M (stays sorted, stays bounded)
            self.ladder = tuple(sorted({-(-b // M) * M for b in self.ladder}))
            self._block_rows = None
            self._predict_fn = self._build_batch_predict(donate)
            self._top_k_fn = self._build_batch_top_k(donate)
            self._reconstruct_fn = self._build_batch_reconstruct(donate)

        # delta-patch program: both row recomputes, the masked colsum
        # delta, and ONE scatter fused into a single compile — so a patch
        # costs exactly one table copy, however many rows are dirty.
        # Inputs are padded to a power-of-two row count (compile cache
        # grows log, not linearly, in distinct dirty sizes); pads repeat
        # the last (id, row) pair, whose duplicate identical writes keep
        # the scatter deterministic, and ``valid`` masks them out of the
        # colsum delta.  NOT donated — the pre-patch buffer must stay
        # alive for query snapshots taken before the swap (the
        # double-buffering half of the design).
        def _patch_impl(table, colsum, ids_, new_rows, old_rows, valid,
                        core):
            old32 = jnp.matmul(old_rows, core,
                               preferred_element_type=jnp.float32)
            new32 = jnp.matmul(new_rows, core,
                               preferred_element_type=jnp.float32)
            w = valid[:, None].astype(jnp.float32)
            colsum = colsum + ((new32 - old32) * w).sum(axis=0)
            return table.at[ids_].set(new32.astype(table.dtype)), colsum

        self._patch_fn = jax.jit(_patch_impl)

        # generation 0: queries snapshot self._live, swaps replace it whole
        self._live = _TableSet(0, self._place_tables(tables), colsums)

    # -- construction helpers -------------------------------------------------

    @classmethod
    def from_checkpoint(cls, directory, step: int | None = None,
                        dims: Sequence[int] | None = None, **kw
                        ) -> "TuckerServer":
        """Load the latest (or ``step``) committed checkpoint and serve it."""
        params, _ = load_params_from_checkpoint(directory, step, dims)
        return cls(params, **kw)

    # -- row-sharded query programs (shard-local + one small collective) ------

    def _build_row_predict(self, donate: bool):
        from jax import shard_map

        mesh, N = self.mesh, self.order
        block_rows, eyes, backend = self._block_rows, self._eyes, self.backend

        def local_fn(tables, idx):
            # tables: per-mode local row block (rows/M, R); idx replicated.
            me = jax.lax.axis_index("data")
            parts = []
            for n in range(N):
                local = idx[:, n] - me * block_rows[n]
                valid = (local >= 0) & (local < block_rows[n])
                safe = jnp.clip(local, 0, block_rows[n] - 1)
                rows = tables[n][safe] * valid[:, None].astype(tables[n].dtype)
                parts.append(rows)
            # each row lives on exactly one device ⟹ one fused psum IS the
            # gather; afterwards every device holds all coefficient rows.
            stacked = jax.lax.psum(jnp.stack(parts), "data")
            rows = tuple(stacked[n] for n in range(N))
            pred, _ = dispatch.get_backend(backend).kruskal_contract(
                rows, eyes)
            return pred

        sharded = shard_map(
            local_fn, mesh=mesh,
            in_specs=(tuple(P("data", None) for _ in range(N)), P()),
            out_specs=P(),
            check_vma=False,
        )
        # signature-compatible with the unsharded/batch predict (eyes are
        # already closed over): predict() calls every mode identically
        fn = jax.jit(sharded, donate_argnums=(1,) if donate else ())

        def call(tables, _eyes, idx):
            return fn(tables, idx)

        call.__wrapped_jit__ = fn
        return call

    def _build_row_top_k(self, donate: bool):
        """Shard-local top-k merge: score ONLY the local row shard of
        C^(t), take a local ``lax.top_k``, all-gather the M·k_local
        ``(score, global id)`` candidates and reduce them with one final
        top-k — the flash-decode shard-merge idiom.  The only collectives
        are one (B, R) psum (coefficient-row gather) and one O(M·k·B)
        all-gather; GSPMD's layout-chosen alternative gathers O(rows)."""
        from jax import shard_map

        mesh, N = self.mesh, self.order
        block_rows = self._block_rows

        @partial(jax.jit,
                 static_argnames=("mode", "target", "k", "true_target_dim"),
                 donate_argnums=(2,) if donate else ())
        def fn(tables, colsums, ids, mode, target, k, true_target_dim):
            tb = block_rows[target]
            # a shard can contribute at most tb rows; min(k, tb) candidates
            # per shard always cover the global top-k (Σ_d min(k, valid_d)
            # ≥ k whenever Σ_d valid_d = I_t ≥ k)
            k_local = min(k, tb)

            def local_fn(tables, colsums, ids):
                me = jax.lax.axis_index("data")
                w = _psum_row_gather(tables[mode], ids, block_rows[mode])
                for n in range(N):
                    if n not in (mode, target):
                        w = w * colsums[n][None, :]
                # (B, tb): identical contraction per output element as the
                # full matmul — the shard is a column slice of the scores
                scores = jnp.matmul(w, tables[target].T,
                                    preferred_element_type=jnp.float32)
                gids = me * tb + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 1)
                # padding rows (beyond the true dim) must never win
                scores = jnp.where(gids < true_target_dim, scores, -jnp.inf)
                s_loc, i_loc = jax.lax.top_k(scores, k_local)
                g_loc = me * tb + i_loc.astype(jnp.int32)
                # ONE small collective: all-gather the candidate triples.
                # Shard-major candidate order preserves the ascending-id
                # tie-break lax.top_k applies on the unsharded scores.
                cs = jax.lax.all_gather(s_loc, "data")   # (M, B, k_local)
                cg = jax.lax.all_gather(g_loc, "data")
                B = ids.shape[0]
                cs = cs.transpose(1, 0, 2).reshape(B, -1)
                cg = cg.transpose(1, 0, 2).reshape(B, -1)
                s, j = jax.lax.top_k(cs, k)
                return s, jnp.take_along_axis(cg, j, axis=1)

            sharded = shard_map(
                local_fn, mesh=mesh,
                in_specs=(tuple(P("data", None) for _ in range(N)),
                          tuple(P() for _ in range(N)), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
            return sharded(tables, colsums, ids)

        return fn

    def _build_row_reconstruct(self, donate: bool):
        """Shard-local reconstruction: gather the pinned-mode coefficient
        rows with one (B, R) psum, compute the output block owned by the
        local rows of the LARGEST free mode, and let the out_spec carry the
        block concatenation.  Only the smaller free modes' tables are
        all-gathered — the collective payload is the (unavoidable) result
        plus the small tables, never the big one."""
        from jax import shard_map

        mesh, N = self.mesh, self.order
        block_rows = self._block_rows

        @partial(jax.jit, static_argnames=("mode", "true_dims"),
                 donate_argnums=(1,) if donate else ())
        def fn(tables, ids, mode, true_dims):
            others = [n for n in range(N) if n != mode]
            n1 = max(others, key=lambda n: true_dims[n])
            pos = 1 + others.index(n1)          # n1's output axis

            def local_fn(tables, ids):
                w = _psum_row_gather(tables[mode], ids, block_rows[mode])
                operands, subs = [w], ["zr"]
                out = "z"
                for n in others:
                    if n == n1:
                        operands.append(tables[n])      # local row block
                    else:
                        full = jax.lax.all_gather(tables[n], "data",
                                                  tiled=True)
                        operands.append(full[: true_dims[n]])
                    subs.append(f"{_LETTERS[n]}r")
                    out += _LETTERS[n]
                return jnp.einsum(",".join(subs) + "->" + out, *operands,
                                  preferred_element_type=jnp.float32)

            out_axes: list = [None] * N
            out_axes[pos] = "data"
            sharded = shard_map(
                local_fn, mesh=mesh,
                in_specs=(tuple(P("data", None) for _ in range(N)), P()),
                out_specs=P(*out_axes),
                check_vma=False,
            )
            out = sharded(tables, ids)
            # trim n1's row padding (pad rows are zeros, but the caller
            # gets exactly (B, *true other dims) like every other mode)
            return jax.lax.slice_in_dim(out, 0, true_dims[n1], axis=pos)

        return fn

    # -- batch-sharded query programs (replicated tables, split batches) ------

    def _build_batch_predict(self, donate: bool):
        from jax import shard_map

        mesh, N, backend = self.mesh, self.order, self.backend

        def local_fn(tables, eyes, idx):
            # full tables, a 1/M slice of the batch: bitwise the unsharded
            # computation per request row, zero collectives.
            return ft_predict(FastTuckerParams(tables, eyes), idx,
                              backend=backend)

        sharded = shard_map(
            local_fn, mesh=mesh,
            in_specs=(tuple(P(None, None) for _ in range(N)),
                      tuple(P(None, None) for _ in range(N)),
                      P("data", None)),
            out_specs=P("data"),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(2,) if donate else ())

    def _build_batch_top_k(self, donate: bool):
        from jax import shard_map

        mesh, N = self.mesh, self.order

        @partial(jax.jit,
                 static_argnames=("mode", "target", "k", "true_target_dim"),
                 donate_argnums=(2,) if donate else ())
        def fn(tables, colsums, ids, mode, target, k, true_target_dim):
            def local_fn(tables, colsums, ids):
                return _top_k_impl(tables, colsums, ids, mode, target, k,
                                   true_target_dim)

            sharded = shard_map(
                local_fn, mesh=mesh,
                in_specs=(tuple(P(None, None) for _ in range(N)),
                          tuple(P() for _ in range(N)), P("data")),
                out_specs=(P("data"), P("data")),
                check_vma=False,
            )
            return sharded(tables, colsums, ids)

        return fn

    def _build_batch_reconstruct(self, donate: bool):
        from jax import shard_map

        mesh, N = self.mesh, self.order

        @partial(jax.jit, static_argnames=("mode", "true_dims"),
                 donate_argnums=(1,) if donate else ())
        def fn(tables, ids, mode, true_dims):
            def local_fn(tables, ids):
                return _reconstruct_impl(tables, ids, mode, true_dims)

            sharded = shard_map(
                local_fn, mesh=mesh,
                in_specs=(tuple(P(None, None) for _ in range(N)), P("data")),
                out_specs=P("data", *([None] * (N - 1))),
                check_vma=False,
            )
            return sharded(tables, ids)

        return fn

    # -- queries --------------------------------------------------------------

    def predict(self, indices) -> jax.Array:
        """Batched x̂ for arbitrary (i_1..i_N) tuples: (B, N) int → (B,).

        Requests are bucketed/padded (answers are invariant to batch size)
        and chunked above the largest bucket — the jit cache never exceeds
        ``len(self.ladder)`` entries per backend.  The host work, from
        the checks to the last trimmed answer, is the profiler span
        ``repro.serve.dispatch`` (stat ``buckets``: chunks launched).
        """
        with jax.profiler.TraceAnnotation("repro.serve.dispatch") as span:
            # pad on the HOST (numpy memcpy) so each bucket costs exactly
            # one device transfer + one executable launch — the per-request
            # Python overhead is what the ≥10× batched-vs-per-query margin
            # lives on
            indices = np.asarray(indices, np.int32)
            if indices.ndim != 2 or indices.shape[1] != self.order:
                raise ValueError(
                    f"indices must be (B, {self.order}), "
                    f"got {indices.shape}")
            B = indices.shape[0]
            # host-side range check: the sharded and unsharded gathers
            # disagree on out-of-range rows (zero-mask vs clamp), so reject
            # them here rather than return mode-dependent wrong answers
            if B and ((indices < 0).any()
                      or (indices >= np.asarray(self.dims)).any()):
                raise ValueError(
                    f"indices out of range for dims {self.dims}")
            if B == 0:
                # match the nonempty path: predictions are f32 accum
                # results even when the tables are stored bf16
                return jnp.zeros((0,), jnp.float32)
            live = self._live     # one snapshot: all chunks, one generation
            outs = []
            for padded, n in self._bucketed_chunks(indices):
                pred = self._predict_fn(live.tables, self._eyes, padded)
                outs.append(pred if n == padded.shape[0] else pred[:n])
            span.set_metadata(buckets=len(outs))
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def reconstruct_rows(self, mode: int, ids) -> jax.Array:
        """Factored reconstruction of whole mode-``mode`` slices.

        Returns (len(ids), *dims without ``mode``) — intended for small
        slice counts (recommender "row preview"); the dense tensor itself
        is never formed, only the requested slices.
        """
        mode = self._check_mode(mode)
        ids = self._check_ids(ids, mode)
        if len(ids) == 0:
            other = tuple(d for n, d in enumerate(self.dims) if n != mode)
            return jnp.zeros((0,) + other, jnp.float32)
        live = self._live         # one snapshot: all chunks, one generation
        outs = [
            self._reconstruct_fn(live.tables, chunk, mode=mode,
                                 true_dims=self.dims)[:n]
            for chunk, n in self._bucketed_chunks(ids)
        ]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def top_k(self, mode: int, ids, k: int, target_mode: int | None = None
              ) -> tuple[jax.Array, jax.Array]:
        """Top-k recommendation: for each entity ``ids`` of ``mode``, the
        ``k`` highest-scoring entries of ``target_mode`` (default: the next
        mode), remaining modes marginalized (summed) via cached column sums.

        Returns (scores (B, k), item ids (B, k)).  The host work is the
        profiler span ``repro.serve.dispatch``, as in ``predict``.
        """
        with jax.profiler.TraceAnnotation("repro.serve.dispatch") as span:
            mode = self._check_mode(mode)
            target = ((mode + 1) % self.order if target_mode is None
                      else self._check_mode(target_mode))
            if target == mode:
                raise ValueError(
                    f"target_mode must differ from mode {mode}")
            if not 1 <= k <= self.dims[target]:
                raise ValueError(f"k={k} outside 1..{self.dims[target]}")
            ids = self._check_ids(ids, mode)
            if len(ids) == 0:
                return (jnp.zeros((0, k), jnp.float32),
                        jnp.zeros((0, k), jnp.int32))
            live = self._live     # one snapshot: all chunks, one generation
            scores, items = [], []
            for chunk, n in self._bucketed_chunks(ids):
                s, i = self._top_k_fn(live.tables, live.colsums, chunk,
                                      mode=mode, target=target, k=k,
                                      true_target_dim=self.dims[target])
                scores.append(s[:n])
                items.append(i[:n])
            span.set_metadata(buckets=len(scores))
            if len(scores) == 1:
                return scores[0], items[0]
            return jnp.concatenate(scores), jnp.concatenate(items)

    # -- online refresh (delta patch + versioned swap) ------------------------

    @property
    def params(self) -> FastTuckerParams:
        """The model currently served (factors kept current by
        ``update_rows``).  Factor arrays re-materialize from the host
        mirror only after updates — reading this between every delta
        would re-pay the host→device transfer the mirror exists to
        avoid, so the loop-facing paths never touch it."""
        if self._params_stale:
            self._params = FastTuckerParams(
                tuple(jnp.asarray(f) for f in self._host_factors),
                self._params.core_factors)
            self._params_stale = False
        return self._params

    @property
    def table_version(self) -> int:
        """Monotone table-generation counter, bumped by every swap."""
        return self._live.version

    @property
    def _tables(self) -> tuple:
        """Live C^(n) tables (current generation's placed storage)."""
        return self._live.tables

    @property
    def _colsums(self) -> tuple:
        """Live f32 per-mode column sums (current generation)."""
        return self._live.colsums

    def update_rows(self, mode: int, ids, factor_rows) -> int:
        """Patch the serving tables for changed factor rows of one mode.

        Recomputes ONLY the dirty rows of C^(mode) = A^(mode) B^(mode)
        through ``mode_products`` (f32 accumulation, rounded once to
        ``table_dtype`` — so the patched table is bitwise what a full
        server rebuild from the updated params would store), updates the
        f32 column sums incrementally (subtract the old rows' sums, add
        the new), and publishes the result as a new table generation with
        one atomic ``_live`` swap.  In-flight queries that snapshotted the
        previous generation finish against it untouched — the patch never
        writes into a live buffer (no donation into the scatter).

        Parameters: ``ids`` are unique row ids of ``mode`` (duplicates
        raise — last-writer-wins scatter order would be undefined), and
        ``factor_rows`` is the matching ``(len(ids), J_mode)`` block of
        the updated A^(mode).  ``self.params`` is kept in sync so repeated
        deltas and ``refresh_tables()`` agree on the current model.

        Returns the new ``table_version`` (unchanged if ``ids`` is empty).
        """
        mode = self._check_mode(mode)
        ids = self._check_ids(ids, mode, grow_hint=True)
        if len(np.unique(ids)) != len(ids):
            raise ValueError(f"update_rows ids must be unique, got "
                             f"{len(ids) - len(np.unique(ids))} duplicates")
        mirror = self._host_factors[mode]
        J = int(mirror.shape[1])
        rows = np.asarray(np.asarray(factor_rows), mirror.dtype)
        if rows.shape != (len(ids), J):
            raise ValueError(f"factor_rows must be {(len(ids), J)}, "
                             f"got {tuple(rows.shape)}")
        if len(ids) == 0:
            return self.table_version
        live = self._live
        # pad to the next power of two: the fused patch program compiles
        # once per (mode, size class) — log-many entries, like the query
        # ladder.  Pads repeat the last entry; ``valid`` masks them out
        # of the colsum delta.
        f = len(ids)
        P = 1 << (max(f, 8) - 1).bit_length()
        sel = np.minimum(np.arange(P), f - 1)
        valid = np.arange(P) < f
        # same contraction per row as the full rebuild — a row subset of
        # A·B is row-wise the identical dot reduction, so the patched
        # rows (f32 accum, rounded once to table_dtype inside the fused
        # program) reproduce the rebuilt rows bitwise
        table, colsum = self._patch_fn(
            live.tables[mode], live.colsums[mode], ids[sel], rows[sel],
            mirror[ids[sel]], valid, self._params.core_factors[mode])
        # re-pin only when the patch came back on a different placement
        # (sharded modes, where GSPMD may choose its own): an
        # unconditional device_put would hand the next patch a table
        # whose layout never reaches a fixed point, recompiling the
        # fused program every generation
        if not table.sharding.is_equivalent_to(live.tables[mode].sharding,
                                               table.ndim):
            table = jax.device_put(table, live.tables[mode].sharding)

        # keep the model current: O(dirty) in-place mirror write; the
        # device-side ``params`` view re-materializes lazily on read
        mirror[ids] = rows
        self._params_stale = True

        tables = list(live.tables)
        tables[mode] = table
        colsums = list(live.colsums)
        colsums[mode] = colsum
        self._live = _TableSet(live.version + 1, tuple(tables),
                               tuple(colsums))
        return self._live.version

    def sync_factor_rows(self, mode: int, ids, factor_rows) -> None:
        """Write changed factor rows into ``self.params`` WITHOUT
        publishing a table generation.

        The rebuild-escalation half of the refresh supervisor: when drift
        says the next publish should be a full ``refresh_tables()``, the
        dirty rows still have to reach the model first — but routing them
        through ``update_rows`` would pay for (and publish) a delta patch
        that the rebuild immediately supersedes.  This is the O(dirty)
        mirror write alone; the same validation as ``update_rows``, same
        "params stay current" contract, no swap.
        """
        mode = self._check_mode(mode)
        ids = self._check_ids(ids, mode, grow_hint=True)
        if len(np.unique(ids)) != len(ids):
            raise ValueError(f"sync_factor_rows ids must be unique, got "
                             f"{len(ids) - len(np.unique(ids))} duplicates")
        mirror = self._host_factors[mode]
        J = int(mirror.shape[1])
        rows = np.asarray(np.asarray(factor_rows), mirror.dtype)
        if rows.shape != (len(ids), J):
            raise ValueError(f"factor_rows must be {(len(ids), J)}, "
                             f"got {tuple(rows.shape)}")
        if len(ids) == 0:
            return
        mirror[ids] = rows
        self._params_stale = True

    def refresh_tables(self) -> int:
        """Full-table rebuild from the current ``self.params`` + swap.

        The non-incremental alternative to ``update_rows`` — recompute
        every C^(n) and its f32 column sums from scratch, place them in
        this server's layout, and publish one new generation.  This is
        the baseline ``bench_refresh.py`` measures the delta patch
        against, and the recovery path when colsum drift from many
        incremental updates should be flushed.  Returns the new version.
        """
        tables32 = mode_products(self.params.factors,
                                 self.params.core_factors,
                                 accum_dtype=jnp.float32)
        colsums = tuple(t.sum(axis=0) for t in tables32)
        tables = tuple(t.astype(self.table_dtype) for t in tables32)
        live = self._live
        self._live = _TableSet(live.version + 1,
                               self._place_tables(tables), colsums)
        return self._live.version

    # -- introspection --------------------------------------------------------

    @property
    def predict_cache_size(self) -> int:
        """Number of compiled predict executables (bucketing keeps this
        ≤ len(self.ladder) across any batch-size distribution)."""
        fn = self._predict_fn
        # the row-mode predict wraps its jit in a signature-adapter lambda
        fn = getattr(fn, "__wrapped_jit__", fn)
        return fn._cache_size()

    # -- internals ------------------------------------------------------------

    def _check_mode(self, mode: int) -> int:
        mode = int(mode)
        if not 0 <= mode < self.order:
            raise ValueError(f"mode {mode} outside 0..{self.order - 1}")
        return mode

    def _check_ids(self, ids, mode: int, *, grow_hint: bool = False
                   ) -> np.ndarray:
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        if ids.ndim != 1:
            raise ValueError(f"ids must be 1-D, got shape {ids.shape}")
        if ids.size and (ids.min() < 0 or ids.max() >= self.dims[mode]):
            bad = ids[(ids < 0) | (ids >= self.dims[mode])]
            msg = (f"ids out of range for mode {mode}: id {int(bad[0])} "
                   f"vs built dim I={self.dims[mode]}")
            if grow_hint:
                msg += (" — online dim growth is not supported: the serving"
                        " tables are built at fixed mode sizes, so new"
                        " entities need a server rebuild from params with"
                        " the grown factor (see ROADMAP 'dim growth')")
            raise ValueError(msg)
        return ids

    def _place_tables(self, tables) -> tuple:
        """Place freshly computed C^(n) tables in this server's layout —
        pad + row-shard, replicate, or leave resident.  Construction and
        ``refresh_tables`` share this one placement policy, so every
        generation of ``_live.tables`` has identical layout."""
        if self.shard_mode == "row":
            M = int(self.mesh.shape["data"])
            padded = tuple(
                jnp.pad(t, ((0, -t.shape[0] % M), (0, 0))) for t in tables)
            return tuple(
                jax.device_put(t, serve_row_sharding(self.mesh, t.shape))
                for t in padded)
        if self.shard_mode == "batch":
            return tuple(
                jax.device_put(t, serve_table_replication(self.mesh))
                for t in tables)
        return tuple(tables)

    def _bucketed_chunks(self, arr: np.ndarray):
        """Yield (zero-padded chunk, true length) over the bucket ladder —
        the one bounded-compile chunk/pad policy every query path uses.
        Pads along axis 0 (index-0 rows), any trailing shape."""
        for start, bucket in split_batch(len(arr), self.ladder):
            n = min(bucket, len(arr) - start)
            if n == bucket:
                yield arr[start:start + n], n
            else:
                padded = np.zeros((bucket,) + arr.shape[1:], arr.dtype)
                padded[:n] = arr[start:start + n]
                yield padded, n
