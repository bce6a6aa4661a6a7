"""Input generators, one module per family, found by the ``generator`` key
of a configuration file. Each module has ``build(params)`` returning an
object with ``matrix(rng) -> Matrix`` (pattern and values) and
``values(rng) -> np.ndarray`` (new values on the same pattern)."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Tuple

import numpy as np


@dataclasses.dataclass
class Matrix:
    """A host CSR matrix: sorted, duplicate-free columns in every row."""
    indptr: np.ndarray
    indices: np.ndarray
    values: np.ndarray
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def transposed(self) -> Tuple["Matrix", np.ndarray]:
        """(the transpose, the index that takes this matrix's values to the
        transpose's), so that new values transpose by one gather."""
        m, n = self.shape
        rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(self.indptr))
        take = np.lexsort((rows, self.indices[: self.nnz]))
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(self.indices[: self.nnz], minlength=n),
                  out=indptr[1:])
        return Matrix(indptr, rows[take].astype(np.int32),
                      self.values[take], (n, m)), take


def load(name: str):
    return importlib.import_module(f"bench.generators.{name}")
