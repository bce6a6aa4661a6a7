"""hypre's 7-point Laplacian, as the ``ij`` test program's ``-laplacian``
problem builds it with ``GenerateLaplacian`` (``par_laplace.c``) on one
rank: an ``nx x ny x nz`` grid, no ghost points.

Row ``iz*nx*ny + iy*nx + ix`` couples to itself and to each of its six
axis neighbours that lies inside the grid, columns in ascending order.
The diagonal is ``2 (cx + cy + cz)`` on every row, boundary rows included,
and the entry of an x, y or z neighbour is ``-cx``, ``-cy`` or ``-cz``, as
``GenerateLaplacian`` sets them, so ``matrix`` does not depend on the
seed.

``values`` gives new coefficients on the same pattern for traffic that
re-assembles the operator: each neighbour entry ``-c u`` with ``u``
uniform in [0.5, 1.5) from the seed, and a diagonal of ``2 (cx + cy +
cz)`` times the row's mean ``u``.
"""
from __future__ import annotations

import numpy as np

from bench.generators import Matrix


class Laplace7:
    def __init__(self, nx: int, ny: int, nz: int, cx: float, cy: float,
                 cz: float):
        n = nx * ny * nz
        grid = np.indices((nz, ny, nx)).reshape(3, -1).T     # (iz, iy, ix)
        row = np.arange(n, dtype=np.int64)
        # (z, y, x step, coefficient), in ascending column order
        steps = [(-1, 0, 0, -cz), (0, -1, 0, -cy), (0, 0, -1, -cx),
                 (0, 0, 0, 2.0 * (cx + cy + cz)),
                 (0, 0, 1, -cx), (0, 1, 0, -cy), (1, 0, 0, -cz)]
        rows, cols, coef = [], [], []
        for sz, sy, sx, c in steps:
            q = grid + (sz, sy, sx)
            ok = np.all((q >= 0) & (q < (nz, ny, nx)), axis=1)
            rows.append(row[ok])
            cols.append(row[ok] + sz * nx * ny + sy * nx + sx)
            coef.append(np.full(int(ok.sum()), c))
        rows, cols = np.concatenate(rows), np.concatenate(cols)
        order = np.lexsort((cols, rows))
        self._rows = rows[order]
        self._coef = np.concatenate(coef)[order]
        self._diag = self._rows == cols[order]
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(self._rows, minlength=n), out=self.indptr[1:])
        self.indices = cols[order].astype(np.int32)
        self.shape = (n, n)
        self.diagonal = 2.0 * (cx + cy + cz)

    def matrix(self, rng: np.random.Generator) -> Matrix:
        return Matrix(self.indptr, self.indices,
                      self._coef.astype(np.float32), self.shape)

    def values(self, rng: np.random.Generator) -> np.ndarray:
        u = rng.uniform(0.5, 1.5, len(self.indices))
        lens = np.diff(self.indptr)
        mean_u = np.bincount(self._rows, weights=np.where(self._diag, 0.0, u),
                             minlength=self.shape[0]) / np.maximum(lens - 1, 1)
        return np.where(self._diag, self.diagonal * mean_u[self._rows],
                        self._coef * u).astype(np.float32)


def build(params: dict) -> Laplace7:
    return Laplace7(params["nx"], params["ny"], params["nz"],
                    params["cx"], params["cy"], params["cz"])
