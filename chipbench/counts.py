"""Operations and bytes that the algorithm needs, from shapes alone.

These count the work that one training step or one top-k flush has to
do, whatever implements it: a different step layout, kernel backend or
fusion leaves them unchanged.  The rooflines and utilizations divide them
by measured device time, so a count that is too high would show as a
share above 100%.

A configuration is a dict with ``dims`` (I_n), ``ranks`` (J_n),
``core_rank`` (R) and ``param_bytes`` (bytes per stored parameter).
"""
from __future__ import annotations

INDEX_BYTES = 4     # int32 coordinates
VALUE_BYTES = 4     # float32 ratings and scores


def train_flops_per_nnz(cfg: dict) -> int:
    """Multiply-adds (x2) for one sampled nonzero: per mode, the mode
    product a·B^(n) (2·J_n·R), the factor-row gradient Pexc·B^(n)^T
    (2·R·J_n) and its share of the core gradient a^T·(err·Pexc)
    (2·J_n·R): 6·R·sum_n J_n in all."""
    R = int(cfg["core_rank"])
    return sum(6 * int(J) * R for J in cfg["ranks"])


def train_step_flops(cfg: dict, batch: int) -> int:
    return train_flops_per_nnz(cfg) * int(batch)


def train_step_bytes(cfg: dict, batch: int) -> int:
    """HBM bytes one step cannot avoid: read the batch's factor rows
    (gather), read and write them again for the update, read the sampled
    COO entries, and read and write the core factors."""
    B, R, p = int(batch), int(cfg["core_rank"]), int(cfg["param_bytes"])
    rows = sum(B * int(J) * p for J in cfg["ranks"])
    coo = B * (len(cfg["dims"]) * INDEX_BYTES + VALUE_BYTES)
    core = 2 * sum(int(J) * R * p for J in cfg["ranks"])
    return 3 * rows + coo + core


def topk_flush_flops(cfg: dict, queries: int, target_mode: int) -> int:
    """Scoring ``queries`` entities against every row of C^(target):
    2·b·R·I_target."""
    return 2 * int(queries) * int(cfg["core_rank"]) * int(
        cfg["dims"][target_mode])


def topk_flush_bytes(cfg: dict, queries: int, target_mode: int) -> int:
    """Read C^(target) once (stored in ``param_bytes``), plus each query's
    R-wide weight row."""
    R, p = int(cfg["core_rank"]), int(cfg["param_bytes"])
    return int(cfg["dims"][target_mode]) * R * p + int(queries) * R * p


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of FLOPs over the bf16 peak and bytes
    over HBM bandwidth, and which of the two it is."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
