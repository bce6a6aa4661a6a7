"""Operations and bytes of C = A @ B, counted from the multiply itself.

The work of a set of output rows depends on A, B and C only, never on how
a kernel pads, tiles or sizes its tables, so a rewritten kernel is held to
the same work:

* operations: 2 per intermediate product (one multiply, one add);
* bytes: 8 per entry (a 4-byte index and a 4-byte f32 value) for each A
  entry of the rows, each product (the B entry it reads), and each C entry
  the rows write.

The HyperLogLog merge that estimates C's row sizes has its own count
(``hll_merge_work``): it reads sketches of B's rows, not B's entries.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ENTRY_BYTES = 8


def row_products(a_indptr, a_indices, b_indptr) -> np.ndarray:
    """Intermediate products of every row of A @ B (int64)."""
    a_indptr = np.asarray(a_indptr, np.int64)
    b_len = np.diff(np.asarray(b_indptr, np.int64))
    per_entry = b_len[np.asarray(a_indices)[: a_indptr[-1]]]
    csum = np.concatenate([[0], np.cumsum(per_entry)])
    return csum[a_indptr[1:]] - csum[a_indptr[:-1]]


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float
    products: int

    def least_seconds(self, peaks) -> tuple:
        """(least time on the chip, the bound that sets it)."""
        t_ops = self.ops / peaks.flops_per_s
        t_bytes = self.bytes / peaks.bytes_per_s
        return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bytes")


def rows_work(rows, products: np.ndarray, a_indptr, c_indptr) -> Work:
    """Work of the output rows ``rows`` of one multiply."""
    rows = np.asarray(rows, np.int64)
    a_nnz = np.diff(np.asarray(a_indptr, np.int64))[rows].sum()
    c_nnz = np.diff(np.asarray(c_indptr, np.int64))[rows].sum()
    p = int(products[rows].sum())
    return Work(ops=2.0 * p,
                bytes=float(ENTRY_BYTES * (a_nnz + p + c_nnz)), products=p)


def hll_merge_work(a_indptr, m_regs: int) -> Work:
    """Work of the merge of B's row sketches over every row of A, at
    ``m_regs`` 4-byte registers a sketch: per A entry its index (4 bytes)
    and B's sketch row (4 m bytes) read and m max operations; per A row
    its merged registers (4 m bytes) and its estimate (4 bytes) written.
    How a kernel pads the registers or blocks the rows does not count."""
    a_indptr = np.asarray(a_indptr, np.int64)
    rows, nnz = len(a_indptr) - 1, int(a_indptr[-1])
    return Work(ops=float(m_regs * nnz),
                bytes=float(4 * nnz + 4 * m_regs * nnz
                            + (4 * m_regs + 4) * rows), products=0)


def kernel_share(ctx, kernel: str, work_of) -> "float | None":
    """A kernel's share of its roofline, in %, over a traced window: the
    least time of ``work_of()``, its work in one call, over its device
    time per call (``kernel`` matches op or module names in the trace).
    ``None`` where the trace has no such kernel."""
    from bench.trace_reduce import kernel_seconds
    if ctx.trace is None:
        return None
    kernel_s = kernel_seconds(ctx.trace, kernel)
    if kernel_s <= 0.0:
        return None
    least, _ = work_of().least_seconds(ctx.peaks)
    return 100.0 * least * ctx.calls / kernel_s


def roofline_share(ctx, kernel: str, row_parts) -> "float | None":
    """An accumulator kernel's share of its roofline (``kernel_share``)
    for the work of its rows; ``None`` where the rung has no rows."""
    if not sum(len(r) for r in row_parts):
        return None
    return kernel_share(ctx, kernel, lambda: rows_work(
        np.concatenate(row_parts), ctx.products, ctx.a_indptr, ctx.c_indptr))
