"""The plain reference for C = A @ B, and the comparison that decides
``correct``. It uses scipy and numpy only, nothing of the program.

Structure must match exactly. scipy drops exact zeros from a value
product, so the structure is the pattern product (every value 1), which
also counts the products each entry sums. Values are held to the forward
error bound of f32 summation, ``count * 2**-23 * (|A| @ |B|)`` entrywise,
against scipy's float64 product of the same f32 inputs.

Two numbers come out of a comparison:

* ``rows_wrong``: rows whose length or column indices differ (limit 0);
* ``value_err_over_f32_bound``: the largest ratio of an entry's error to
  its bound, over the rows whose structure matched.

The control puts the reference in the program's place at the next lower
precision: A and B rounded to bfloat16, their exact product rounded to
bfloat16.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np
import scipy.sparse as sp

F32_UNIT = 2.0 ** -23


@dataclasses.dataclass
class Comparison:
    rows_wrong: int
    value_err_over_f32_bound: float
    entries: int

    def merged(self, other: "Comparison") -> "Comparison":
        return Comparison(
            self.rows_wrong + other.rows_wrong,
            max(self.value_err_over_f32_bound,
                other.value_err_over_f32_bound),
            self.entries + other.entries)


def _csr(indptr, indices, data, shape) -> sp.csr_matrix:
    return sp.csr_matrix((data, indices, indptr), shape=shape)


class Reference:
    """C = A @ B in float64 on one pattern of A and of B, for any values."""

    def __init__(self, a_indptr, a_indices, a_shape, b_indptr, b_indices,
                 b_shape):
        self.a = self._pattern(a_indptr, a_indices, a_shape)
        self.b = self._pattern(b_indptr, b_indices, b_shape)
        self.shape = (self.a[2][0], self.b[2][1])
        p = (_csr(*self.a[:2], np.ones(len(self.a[1])), self.a[2])
             @ _csr(*self.b[:2], np.ones(len(self.b[1])), self.b[2]))
        p.sort_indices()
        self.indptr = p.indptr.astype(np.int64)
        self.indices = p.indices
        self.count = p.data
        self._keys = None

    @staticmethod
    def _pattern(indptr, indices, shape):
        indptr = np.asarray(indptr, np.int64)
        return indptr, np.asarray(indices)[: indptr[-1]], tuple(shape)

    def _product(self, a_values, b_values) -> sp.csr_matrix:
        a = _csr(*self.a[:2], a_values[: len(self.a[1])], self.a[2])
        b = _csr(*self.b[:2], b_values[: len(self.b[1])], self.b[2])
        return a @ b

    def _on_pattern(self, m: sp.csr_matrix) -> np.ndarray:
        """Values of ``m`` at the reference structure (zeros where scipy
        dropped an entry that summed to exactly 0)."""
        m.sort_indices()
        if (m.nnz == len(self.indices) and np.array_equal(m.indptr,
                                                          self.indptr)
                and np.array_equal(m.indices, self.indices)):
            return m.data
        if self._keys is None:
            self._keys = self._row_keys(self.indptr, self.indices)
        keys = self._row_keys(m.indptr, m.indices)
        out = np.zeros(len(self.indices))
        out[np.searchsorted(self._keys, keys)] = m.data
        return out

    def _row_keys(self, indptr, indices) -> np.ndarray:
        rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                         np.diff(indptr))
        return rows * np.int64(self.shape[1]) + indices

    def exact(self, a_values, b_values) -> tuple:
        """(exact values, |A| @ |B| values), both on the structure."""
        a = np.asarray(a_values, np.float64)
        b = np.asarray(b_values, np.float64)
        return (self._on_pattern(self._product(a, b)),
                self._on_pattern(self._product(np.abs(a), np.abs(b))))

    def control(self, a_values, b_values) -> np.ndarray:
        """C's values computed at bfloat16: inputs and output rounded."""
        def bf16(v):
            return (np.asarray(v).astype(ml_dtypes.bfloat16)
                    .astype(np.float64))
        return (self._on_pattern(self._product(bf16(a_values),
                                               bf16(b_values)))
                .astype(ml_dtypes.bfloat16).astype(np.float64))

    def compare(self, c_indptr, c_indices, c_values, exact, mag
                ) -> Comparison:
        c_indptr = np.asarray(c_indptr, np.int64)
        c_indices = np.asarray(c_indices)
        m = self.shape[0]
        if (len(c_indptr) != m + 1 or c_indptr[0] != 0
                or np.any(np.diff(c_indptr) < 0)
                or len(c_indices) < c_indptr[-1]):
            return Comparison(m, float("inf"), 0)
        c_len = np.diff(c_indptr)
        r_len = np.diff(self.indptr)
        same = np.nonzero(c_len == r_len)[0]
        lens = r_len[same]
        total = int(lens.sum())
        starts = np.repeat(np.cumsum(lens) - lens, lens)
        within = np.arange(total, dtype=np.int64) - starts
        pos_c = np.repeat(c_indptr[same], lens) + within
        pos_r = np.repeat(self.indptr[same], lens) + within
        col_ok = c_indices[pos_c] == self.indices[pos_r]
        row_of = np.repeat(same, lens)
        bad_rows = np.unique(row_of[~col_ok])
        rows_wrong = int(m - len(same) + len(bad_rows))
        ok_row = np.ones(m, bool)
        ok_row[bad_rows] = False
        keep = ok_row[row_of]
        pos_c, pos_r = pos_c[keep], pos_r[keep]
        err = np.abs(np.asarray(c_values)[pos_c].astype(np.float64)
                     - exact[pos_r])
        bound = self.count[pos_r] * F32_UNIT * mag[pos_r]
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = np.where(err == 0, 0.0, err / bound)
        ratio = np.where(np.isnan(ratio), np.inf, ratio)
        worst = float(ratio.max()) if len(ratio) else 0.0
        return Comparison(rows_wrong, worst, int(len(pos_c)))
