"""The work each kernel is required to do, from the call's shapes: the
numerators of the roofline shares. They count what the operation needs,
whatever implements it: padding rows, the aligned row groups a kernel
DMAs and recomputed work do not count."""

from __future__ import annotations

ELT = {"float32": 4, "int8": 1, "bfloat16": 2}


def flat_topk_work(n_rows: int, dim: int, batch: int,
                   emb_dtype: str) -> tuple[float, float]:
    """(operations, bytes) of one exact same-category top-1 scan: read the
    N×d table, its N-word meta row (valid and category), the N scales
    where the table is int8, and the B×d fp32 queries; 2·B·N·d
    multiply-adds."""
    table = n_rows * dim * ELT[emb_dtype]
    side = n_rows * 4 * (2 if emb_dtype == "int8" else 1)
    return 2.0 * batch * n_rows * dim, float(table + side + batch * dim * 4)


def scatter_update_work(rows: int, dim: int,
                        emb_dtype: str) -> tuple[float, float]:
    """(operations, bytes) of an in-place delta flush of ``rows`` distinct
    rows: each row's payload read once and written once."""
    return 0.0, 2.0 * rows * dim * ELT[emb_dtype]


def min_seconds(work: tuple[float, float], peaks: dict,
                flops_key: str = "bf16_flops_per_s") -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak HBM bandwidth."""
    ops, nbytes = work
    return max(ops / peaks[flops_key], nbytes / peaks["hbm_bytes_per_s"])
