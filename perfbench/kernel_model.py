"""Operations and bytes a kernel NEEDS, from its shapes alone.

These count the algorithm's work, not a program's: they read the same
whatever implements the step, so a roofline share built on them compares
implementations and cannot pass 100%.
"""

from __future__ import annotations

F32 = 4
I32 = 4


def pa2_step_bytes(batch: int, max_nnz: int) -> int:
    """HBM bytes one PA-II mini-batch update has to move. Per row: its
    ``max_nnz`` indices and values and its target come in; the ``max_nnz + 1``
    weights it touches (the bias is one more) are read for the margin, and
    read and written once more for the update."""
    touched = max_nnz + 1
    per_row = (
        max_nnz * (I32 + F32)  # indices, values
        + F32  # target
        + touched * F32  # gather for the margin
        + touched * 2 * F32  # scatter-add: read and write
    )
    return batch * per_row


def pa2_step_flops(batch: int, max_nnz: int) -> int:
    """Floating-point operations of the same update: a multiply-add per
    touched weight for the margin, one for the squared norm, one for the
    update, and a handful per row for the step size."""
    touched = max_nnz + 1
    return batch * (3 * 2 * touched + 8)


def roofline_seconds(flops: float, bytes_: float, peak_flops: float, peak_bytes: float):
    """The least time the chip could take, and which side bounds it."""
    t_f, t_b = flops / peak_flops, bytes_ / peak_bytes
    return (t_f, "compute") if t_f > t_b else (t_b, "memory")
