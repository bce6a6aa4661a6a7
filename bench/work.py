"""Operations and bytes of C = A @ B, counted from the multiply itself.

The work of a set of output rows depends on A, B and C only, never on how
a kernel pads, tiles or sizes its tables, so a rewritten kernel is held to
the same work:

* operations: 2 per intermediate product (one multiply, one add);
* bytes: 8 per entry (a 4-byte index and a 4-byte f32 value) for each A
  entry of the rows, each product (the B entry it reads), and each C entry
  the rows write.
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


def roofline_share(ctx, kernel: str, row_parts) -> "float | None":
    """A kernel's share of its roofline, in %, over a traced window: the
    least time of its rows' work per call, over its device time per call
    (``kernel`` matches op or module names in the trace). ``None`` where
    the rung has no rows or the trace no such kernel."""
    from bench.trace_reduce import kernel_seconds
    if ctx.trace is None or not sum(len(r) for r in row_parts):
        return None
    kernel_s = kernel_seconds(ctx.trace, kernel)
    if kernel_s <= 0.0:
        return None
    w = rows_work(np.concatenate(row_parts), ctx.products, ctx.a_indptr,
                  ctx.c_indptr)
    least, _ = w.least_seconds(ctx.peaks)
    return 100.0 * least * ctx.calls / kernel_s
