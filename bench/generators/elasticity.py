"""Linear elasticity stiffness of a hex-meshed beam, order-1 (Q1) elements,
as MFEM's example 2 (``ex2.cpp``) assembles it and hands it to its solver.

The box ``[0, extent[0]] x [0, extent[1]] x [0, extent[2]]`` is cut into
``nx x ny x nz`` equal hexes. Nodes are numbered lexicographically, x
fastest: node ``ix + (nx+1) * (iy + (ny+1) * iz)``. The displacement has
three components, ordered by nodes (MFEM's default ``Ordering::byNODES``):
the DOF of component ``c`` at node ``v`` is ``c * n_nodes + v``.

Each element matrix is ``sum_q w_q B^T D B |J|`` over the 2 x 2 x 2 Gauss
points, with the isotropic ``D(lambda, mu)`` in Voigt order (xx, yy, zz,
xy, yz, xz, engineering shears). Elements whose centre lies below
``material_split_x`` take the first entry of ``lambda`` and ``mu``, the
others the second (ex2's two material attributes). Element matrices are
summed in float64 and cast to float32 once.

The pattern is what ``BilinearForm::Assemble()`` keeps: an element entry
that is 0 (to rounding) is skipped with its transpose, so only entries
that some element makes nonzero are stored. With ``lambda == mu``, 192 of
the 576 entries of an element matrix vanish. ``fixed_x0`` eliminates the
face x = 0 as ``FormLinearSystem`` does: every component of its nodes has
its row and column zeroed and 1 on the diagonal, and the pattern keeps the
zeroed entries.

``matrix`` does not depend on the seed. ``values`` re-assembles on the
same pattern with each element's (lambda, mu) scaled by one factor uniform
in [0.5, 1.5) from the seed, as a solver with a changing material does.
"""
from __future__ import annotations

import numpy as np

from bench.generators import Matrix

DIM = 3
GAUSS_1D = (0.5 - 0.5 / np.sqrt(3.0), 0.5 + 0.5 / np.sqrt(3.0))  # on [0, 1]
ZERO = 1e-12           # an element entry under this share of the largest


def shape_gradients(h) -> np.ndarray:
    """(8 Gauss points, 8 nodes, 3) gradients of the Q1 shape functions of
    an ``h[0] x h[1] x h[2]`` box, local node ``i + 2j + 4k``."""
    h = np.asarray(h, np.float64)
    bits = np.array([[(a >> d) & 1 for d in range(DIM)] for a in range(8)])
    pts = np.array([[GAUSS_1D[(q >> d) & 1] for d in range(DIM)]
                    for q in range(8)])
    # 1-D factors: xi for bit 1, 1 - xi for bit 0; their derivatives +-1
    f = np.where(bits[None], pts[:, None, :], 1.0 - pts[:, None, :])
    sign = np.where(bits, 1.0, -1.0)
    grad = np.empty((8, 8, DIM))
    for d in range(DIM):
        others = [e for e in range(DIM) if e != d]
        grad[:, :, d] = sign[:, d] * np.prod(f[:, :, others], axis=2) / h[d]
    return grad


def strain_matrices(grad: np.ndarray) -> np.ndarray:
    """(points, 6, 24) B per Gauss point; local DOF ``3 * node + c``."""
    q, n = grad.shape[:2]
    b = np.zeros((q, 6, n, DIM))
    for c in range(DIM):
        b[:, c, :, c] = grad[:, :, c]
    for row, (c, e) in zip((3, 4, 5), ((0, 1), (1, 2), (0, 2))):
        b[:, row, :, c] = grad[:, :, e]
        b[:, row, :, e] = grad[:, :, c]
    return b.reshape(q, 6, n * DIM)


def element_matrices(h) -> tuple:
    """(K_lambda, K_mu): the 24 x 24 element matrix is
    ``lambda * K_lambda + mu * K_mu``, D being linear in both."""
    grad = shape_gradients(h)
    b = strain_matrices(grad)
    w_det = np.prod(h) / 8.0                  # Gauss weights 1/8 on [0,1]^3
    d_lam = np.zeros((6, 6))
    d_lam[:3, :3] = 1.0
    d_mu = np.diag([2.0, 2.0, 2.0, 1.0, 1.0, 1.0])
    return tuple(w_det * np.einsum("qji,jk,qkl->il", b, d, b)
                 for d in (d_lam, d_mu))


class Elasticity:
    def __init__(self, nx: int, ny: int, nz: int, extent, material_split_x,
                 lam, mu, fixed_x0: bool = False):
        h = np.asarray(extent, np.float64) / (nx, ny, nz)
        self.k_lam, self.k_mu = element_matrices(h)
        # element e = ex + nx * (ey + ny * ez); its nodes i + 2j + 4k
        ez, ey, ex = np.indices((nz, ny, nx)).reshape(3, -1)
        corner = ex + (nx + 1) * (ey + (ny + 1) * ez)
        off = np.array([i + (nx + 1) * (j + (ny + 1) * k)
                        for k in (0, 1) for j in (0, 1) for i in (0, 1)])
        nodes = corner[:, None] + off[None, :]            # (E, 8)
        n_nodes = (nx + 1) * (ny + 1) * (nz + 1)
        dofs = (nodes[:, :, None] + n_nodes * np.arange(DIM)).reshape(
            len(nodes), 8 * DIM)                          # local 3*node + c
        n = DIM * n_nodes
        material = ((ex + 0.5) * h[0] >= material_split_x).astype(int)
        self.lam = np.asarray(lam, np.float64)[material]
        self.mu = np.asarray(mu, np.float64)[material]
        # Assemble() skips an element's zero entries: which ones are zero
        # follows from lambda / mu alone, so one mask per material
        masks = []
        for la, m in zip(lam, mu):
            ke = np.abs(la * self.k_lam + m * self.k_mu)
            masks.append(ke > ZERO * ke.max())
        self._keep = np.stack(masks)[material].reshape(len(nodes), -1)
        rows = np.repeat(dofs, 8 * DIM, axis=1)[self._keep]
        cols = np.tile(dofs, (1, 8 * DIM))[self._keep]
        keys, self._slot = np.unique(rows * np.int64(n) + cols,
                                     return_inverse=True)
        row, col = keys // n, keys % n
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(row, minlength=n), out=self.indptr[1:])
        self.indices = col.astype(np.int32)
        self.shape = (n, n)
        fixed = np.zeros(n, bool)
        if fixed_x0:
            x0 = np.arange(n_nodes) % (nx + 1) == 0
            fixed = np.tile(x0, DIM)
        self._zeroed = fixed[row] | fixed[col]
        self._one = fixed[row] & (row == col)

    def assemble(self, scale=None, eliminate: bool = True) -> np.ndarray:
        """Values on the pattern in float64, each element's (lambda, mu)
        times ``scale`` (one factor an element); the fixed DOFs eliminated
        unless ``eliminate`` is false."""
        s = np.ones(len(self.lam)) if scale is None else scale
        ke = (np.multiply.outer(self.lam * s, self.k_lam)
              + np.multiply.outer(self.mu * s, self.k_mu))
        v = np.bincount(self._slot, weights=ke.reshape(len(s), -1)[
            self._keep], minlength=len(self.indices))
        if eliminate:
            v[self._zeroed] = 0.0
            v[self._one] = 1.0
        return v

    def matrix(self, rng: np.random.Generator) -> Matrix:
        return Matrix(self.indptr, self.indices,
                      self.assemble().astype(np.float32), self.shape)

    def values(self, rng: np.random.Generator) -> np.ndarray:
        return self.assemble(rng.uniform(0.5, 1.5, len(self.lam))
                             ).astype(np.float32)


def build(params: dict) -> Elasticity:
    return Elasticity(params["nx"], params["ny"], params["nz"],
                      params["extent"], params["material_split_x"],
                      params["lambda"], params["mu"],
                      params.get("fixed_x0", False))
