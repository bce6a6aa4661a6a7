"""The HPCG benchmark's problem: the 27-point stencil of a 3D Poisson-like
operator on an ``nx x ny x nz`` grid (``GenerateProblem_ref.cpp``).

Row ``iz*nx*ny + iy*nx + ix`` couples to every grid point within one step
in each of x, y and z that lies inside the grid (no ghost points: the
single-rank global grid is the local one), columns in ascending order.
The diagonal is 26 and every other entry -1, as HPCG sets them, so
``matrix`` does not depend on the seed.

``values`` gives new coefficients on the same pattern for traffic that
re-assembles the operator: off-diagonal entries ``-u`` with ``u`` uniform
in [0.5, 1.5) from the seed, and a diagonal of 26 times the row's mean
``u``, so the diagonal matches the off-diagonal sum in the interior and
exceeds it on the boundary, as HPCG's does.
"""
from __future__ import annotations

import numpy as np

from bench.generators import Matrix

DIAGONAL = 26.0
OFF_DIAGONAL = -1.0


class Hpcg:
    def __init__(self, nx: int, ny: int, nz: int):
        grid = np.indices((nz, ny, nx)).reshape(3, -1).T     # (iz, iy, ix)
        n = nx * ny * nz
        rows, cols = [], []
        row = np.arange(n, dtype=np.int64)
        # HPCG's loop order (sz, sy, sx) visits the columns in ascending order
        for sz in (-1, 0, 1):
            for sy in (-1, 0, 1):
                for sx in (-1, 0, 1):
                    q = grid + (sz, sy, sx)
                    ok = np.all((q >= 0) & (q < (nz, ny, nx)), axis=1)
                    rows.append(row[ok])
                    cols.append(row[ok] + sz * nx * ny + sy * nx + sx)
        rows, cols = np.concatenate(rows), np.concatenate(cols)
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=n), out=self.indptr[1:])
        self.indices = cols.astype(np.int32)
        self._rows = rows
        self._diag = rows == cols
        self.shape = (n, n)

    def matrix(self, rng: np.random.Generator) -> Matrix:
        values = np.where(self._diag, DIAGONAL, OFF_DIAGONAL)
        return Matrix(self.indptr, self.indices, values.astype(np.float32),
                      self.shape)

    def values(self, rng: np.random.Generator) -> np.ndarray:
        u = rng.uniform(0.5, 1.5, len(self.indices))
        lens = np.diff(self.indptr)
        mean_u = np.bincount(self._rows, weights=np.where(self._diag, 0.0, u),
                             minlength=self.shape[0]) / np.maximum(lens - 1, 1)
        return np.where(self._diag, DIAGONAL * mean_u[self._rows],
                        -u).astype(np.float32)


def build(params: dict) -> Hpcg:
    return Hpcg(params["nx"], params["ny"], params["nz"])
