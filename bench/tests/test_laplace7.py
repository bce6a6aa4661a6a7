"""The 7-point Laplacian generator (hypre's ``GenerateLaplacian`` on one
rank) against a stencil built point by point, and the coefficients
``GenerateLaplacian`` gives each entry."""
import json

import numpy as np
import pytest
import scipy.sparse as sp

from bench import cells
from bench.generators import laplace7

with open(cells.BENCH_DIR / "configs" / "hypre_lap7_n104.json") as f:
    SMALL = json.load(f)["small"]       # the configuration at its CPU size
# a grid that is not a cube, with three different coefficients
ODD = {"nx": 4, "ny": 3, "nz": 5, "cx": 1.0, "cy": 2.5, "cz": 0.25}


def _stencil(p):
    """The operator point by point: (row, column) -> coefficient."""
    nx, ny, nz = p["nx"], p["ny"], p["nz"]
    out = {}
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                r = iz * nx * ny + iy * nx + ix
                out[r, r] = 2.0 * (p["cx"] + p["cy"] + p["cz"])
                for d, c, (jx, jy, jz) in (
                        (-1, p["cx"], (ix - 1, iy, iz)),
                        (1, p["cx"], (ix + 1, iy, iz)),
                        (-nx, p["cy"], (ix, iy - 1, iz)),
                        (nx, p["cy"], (ix, iy + 1, iz)),
                        (-nx * ny, p["cz"], (ix, iy, iz - 1)),
                        (nx * ny, p["cz"], (ix, iy, iz + 1))):
                    if 0 <= jx < nx and 0 <= jy < ny and 0 <= jz < nz:
                        out[r, r + d] = -c
    return out


def _a(m, values=None):
    v = m.values if values is None else values
    return sp.csr_matrix((v, m.indices, m.indptr), shape=m.shape)


@pytest.mark.parametrize("params", [ODD, SMALL], ids=["odd", "small"])
def test_pattern_and_values_match_the_stencil(params):
    m = laplace7.build(params).matrix(np.random.default_rng(0))
    want = _stencil(params)
    n = params["nx"] * params["ny"] * params["nz"]
    assert m.shape == (n, n) and m.nnz == len(want)
    rows = np.repeat(np.arange(n), np.diff(m.indptr))
    got = {(int(r), int(c)): float(v)
           for r, c, v in zip(rows, m.indices, m.values)}
    assert set(got) == set(want)
    for key, v in want.items():
        assert got[key] == np.float32(v)
    for r in range(n):
        assert np.all(np.diff(m.indices[m.indptr[r]:m.indptr[r + 1]]) > 0)


def test_values_are_generate_laplacians():
    m = laplace7.build(SMALL).matrix(np.random.default_rng(0))
    a = _a(m)
    # diagonal 2 (cx + cy + cz) on every row, boundary rows included
    assert np.all(a.diagonal() == np.float32(2.0 * (1.0 + 1.0 + 0.001)))
    assert sorted(set(m.values.tolist())) == sorted(
        {np.float32(-1.0), np.float32(-0.001), np.float32(4.002)})
    # an interior row's neighbours sum to its diagonal; a corner's do not
    off = np.asarray(a.sum(axis=1)).ravel()
    interior = 5 * 100 + 5 * 10 + 5
    assert off[interior] == pytest.approx(0.0, abs=1e-6)
    assert off[0] > 0.0
    # per dimension of n points 2 (n - 1) couplings besides the diagonal
    assert m.nnz == 1000 + 3 * 2 * 9 * 100


def test_matrix_is_symmetric():
    for params in (ODD, SMALL):
        a = _a(laplace7.build(params).matrix(np.random.default_rng(0)))
        assert (a != a.T).nnz == 0


def test_matrix_does_not_depend_on_the_seed():
    g = laplace7.build(SMALL)
    m0, m1 = (g.matrix(np.random.default_rng(s)) for s in (0, 2 ** 31 + 5))
    assert np.array_equal(m0.values, m1.values)
    assert np.array_equal(m0.indices, m1.indices)


def test_values_keep_the_pattern():
    g = laplace7.build(ODD)
    m = g.matrix(np.random.default_rng(0))
    v = g.values(np.random.default_rng(3))
    assert v.shape == m.values.shape and v.dtype == np.float32
    assert not np.array_equal(v, m.values)
    assert not np.array_equal(v, g.values(np.random.default_rng(4)))
    # each entry keeps its sign, and the diagonal its row's mean scaling
    assert np.all(np.sign(v) == np.sign(m.values))
    b = _a(m, v)
    assert np.all(b.diagonal() > 0)
    assert np.all(np.abs(v / m.values) >= 0.5 - 1e-6)
    assert np.all(np.abs(v / m.values) < 1.5)
