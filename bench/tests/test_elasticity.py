"""The elasticity generator (MFEM example 2's Q1 beam stiffness) against
what linear elasticity requires of it, and against a per-element dense
assembly written from the bilinear form."""
import itertools
import json

import numpy as np
import pytest
import scipy.sparse as sp

from bench import cells
from bench.generators import elasticity, hpcg

with open(cells.BENCH_DIR / "configs" / "mfem_ex2_beam_q1.json") as f:
    BEAM = json.load(f)["small"]        # the configuration at its CPU size
# a box that is not a cube, cut unevenly, with both materials
ODD = {"nx": 3, "ny": 2, "nz": 4, "extent": [1.5, 0.7, 2.0],
       "material_split_x": 1.0, "lambda": [50.0, 1.0], "mu": [30.0, 2.0]}


def _k(g, values=None):
    v = g.assemble() if values is None else values
    return sp.csr_matrix((v, g.indices, g.indptr), shape=g.shape)


def _nodes(params):
    nx, ny, nz = params["nx"], params["ny"], params["nz"]
    h = np.asarray(params["extent"]) / (nx, ny, nz)
    iz, iy, ix = np.indices((nz + 1, ny + 1, nx + 1)).reshape(3, -1)
    return np.stack([ix * h[0], iy * h[1], iz * h[2]], axis=1)


def _rigid_modes(x):
    """3 translations and 3 infinitesimal rotations, ordered by nodes."""
    n = len(x)
    modes = []
    for c in range(3):
        u = np.zeros((3, n))
        u[c] = 1.0
        modes.append(u.ravel())
    for c, e in ((0, 1), (1, 2), (0, 2)):
        u = np.zeros((3, n))
        u[c], u[e] = -x[:, e], x[:, c]
        modes.append(u.ravel())
    return modes


@pytest.mark.parametrize("params", [BEAM, ODD], ids=["small", "odd"])
def test_stiffness_is_symmetric_with_rigid_modes_in_its_null_space(params):
    g = elasticity.build(params)
    scale = np.random.default_rng(3).uniform(0.5, 1.5, len(g.lam))
    for k in (_k(g, g.assemble(eliminate=False)),
              _k(g, g.assemble(scale, eliminate=False))):
        assert abs(k - k.T).max() == 0.0
        norm = abs(k).sum(axis=1).max()
        for r in _rigid_modes(_nodes(params)):
            assert np.abs(k @ r).max() <= 1e-12 * norm * np.abs(r).max()
    # served in float32, on one pattern whatever the seed
    m = g.matrix(np.random.default_rng(0))
    assert m.values.dtype == np.float32
    v = g.values(np.random.default_rng(4))
    assert v.dtype == np.float32 and len(v) == m.nnz
    assert not np.array_equal(v, m.values)
    for vals in (m.values, v):
        a = sp.csr_matrix((vals, m.indices, m.indptr), shape=m.shape)
        assert abs(a - a.T).max() == 0.0


def test_fixed_end_is_eliminated_in_place():
    # every component of the nodes on x = 0 has a unit row and column,
    # and the zeroed entries stay in the pattern
    g = elasticity.build(BEAM)
    free = elasticity.build(dict(BEAM, fixed_x0=False))
    assert np.array_equal(g.indptr, free.indptr)
    assert np.array_equal(g.indices, free.indices)
    n_nodes = g.shape[0] // 3
    fixed = np.tile(np.arange(n_nodes) % (BEAM["nx"] + 1) == 0, 3)
    k, k0 = _k(g).toarray(), _k(free).toarray()
    assert np.array_equal(k[fixed], np.eye(len(k))[fixed])
    assert np.array_equal(k[:, fixed], np.eye(len(k))[:, fixed])
    keep = np.ix_(~fixed, ~fixed)
    assert np.array_equal(k[keep], k0[keep])
    assert fixed.sum() == 3 * (BEAM["ny"] + 1) * (BEAM["nz"] + 1)


@pytest.mark.parametrize("params", [BEAM, ODD], ids=["small", "odd"])
def test_rows_couple_the_components_of_the_node_neighbours(params):
    # the nodes that share a hex with a node are HPCG's 27-point stencil
    # on the grid of nodes; each row takes its own component of each, and
    # the other components where an element couples them: with
    # lambda == mu (the beam) half of those cancel, with lambda != mu none
    g = elasticity.build(params)
    nodes = hpcg.build({"nx": params["nx"] + 1, "ny": params["ny"] + 1,
                        "nz": params["nz"] + 1})
    n = len(nodes.indptr) - 1
    full = params["lambda"] != params["mu"]
    for c in range(3):
        for v in range(n):
            cols = nodes.indices[nodes.indptr[v]:nodes.indptr[v + 1]]
            got = g.indices[g.indptr[c * n + v]:g.indptr[c * n + v + 1]]
            own = got[(got >= c * n) & (got < (c + 1) * n)] - c * n
            assert np.array_equal(own, cols)
            assert np.isin(got % n, cols).all()
            assert (len(got) == 3 * len(cols)) == full
    assert (g.indptr[-1] == 9 * nodes.indptr[-1]) == full


def _brute_force(params):
    """Dense K from the bilinear form lambda div u div v + mu (grad u +
    grad u^T) : grad v, element by element, on the reference cube
    [-1, 1]^3 with 2-point Gauss, node by node and component by
    component, the fixed end eliminated; and the set of (row, col) where
    some element's entry is not 0."""
    nx, ny, nz = params["nx"], params["ny"], params["nz"]
    h = np.asarray(params["extent"], float) / (nx, ny, nz)
    n_nodes = (nx + 1) * (ny + 1) * (nz + 1)
    k = np.zeros((3 * n_nodes, 3 * n_nodes))
    touched = np.zeros_like(k, bool)
    corners = list(itertools.product((-1, 1), repeat=3))     # (sx, sy, sz)
    g1 = 1.0 / np.sqrt(3.0)
    for ez, ey, ex in itertools.product(range(nz), range(ny), range(nx)):
        ke = np.zeros((8, 8, 3, 3))
        stiff = (ex + 0.5) * h[0] < params["material_split_x"]
        lam = params["lambda"][0 if stiff else 1]
        mu = params["mu"][0 if stiff else 1]
        node = [(ex + (s[0] + 1) // 2) + (nx + 1) * (
            (ey + (s[1] + 1) // 2) + (ny + 1) * (ez + (s[2] + 1) // 2))
            for s in corners]
        for q in itertools.product((-g1, g1), repeat=3):
            grads = []
            for s in corners:
                f = [(1 + s[d] * q[d]) / 2 for d in range(3)]
                grads.append([s[d] / 2 * f[(d + 1) % 3] * f[(d + 2) % 3]
                              * 2 / h[d] for d in range(3)])
            det = np.prod(h) / 8                   # |J|, Gauss weights 1
            for a, b in itertools.product(range(8), repeat=2):
                ga, gb = grads[a], grads[b]
                for c, e in itertools.product(range(3), repeat=2):
                    val = lam * ga[c] * gb[e] + mu * (
                        (c == e) * np.dot(ga, gb) + ga[e] * gb[c])
                    ke[a, b, c, e] += det * val
        for a, b, c, e in itertools.product(range(8), range(8), range(3),
                                            range(3)):
            i, j = c * n_nodes + node[a], e * n_nodes + node[b]
            k[i, j] += ke[a, b, c, e]
            touched[i, j] |= abs(ke[a, b, c, e]) > 1e-12 * np.abs(ke).max()
    fixed = np.tile(np.arange(n_nodes) % (nx + 1) == 0, 3)
    fixed &= bool(params.get("fixed_x0"))
    k[fixed] = 0.0
    k[:, fixed] = 0.0
    k[fixed, fixed] = 1.0
    return k, touched


def test_generator_equals_a_per_element_dense_assembly():
    params = dict(BEAM, nx=2, ny=1, nz=1, extent=[2.0, 1.0, 1.0],
                  material_split_x=1.0)
    g = elasticity.build(params)
    want, touched = _brute_force(params)
    got = _k(g).toarray()
    pattern = _k(g, np.ones(len(g.indices))).toarray() > 0
    assert np.array_equal(pattern, touched)
    assert np.abs(got - want).max() <= 1e-12 * np.abs(want).max()
    # an element's zeros leave the pattern; entries that cancel between
    # elements, or that the elimination zeroes, stay in it
    assert touched.sum() < touched.size
    assert np.abs(want[touched]).min() < 1e-12 * np.abs(want).max()


def test_small_size_takes_the_estimation_workflow():
    from repro.core.analysis import analyze
    from repro.core.formats import csr_from_arrays
    m = elasticity.build(BEAM).matrix(np.random.default_rng(0))
    a = csr_from_arrays(m.indptr, m.indices, m.values, m.shape)
    r = analyze(a, a)
    assert r.workflow == "estimation"
    assert r.sampled_cr >= 8.0 and r.er >= 8.0
