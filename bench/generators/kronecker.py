"""Graph500 Kronecker (R-MAT) graph adjacency.

``2**scale`` vertices and ``edge_factor * 2**scale`` edges, each placed by
one quadrant draw per bit level with probabilities ``(a, b, c, 1-a-b-c)``;
then the vertex ids are permuted at random, as the Graph500 generator and
GAP's ``kron`` (``PermuteIDs``) do, so hubs do not cluster at low ids; then
symmetrized, self-loops dropped and duplicates merged. The edge sampling
is the same as the program's ``repro.graph.generators.rmat_csr``, copied
here so that the benchmark's inputs do not move with the program.

The graph is fixed by ``graph_seed`` in the configuration, so every run
multiplies the same graph; the run's seed draws the edge weights (uniform
in [0, 1), as the Graph500 SSSP kernel's are).
"""
from __future__ import annotations

import numpy as np

from bench.generators import Matrix


class Kronecker:
    def __init__(self, scale: int, edge_factor: int, a: float, b: float,
                 c: float, graph_seed: int):
        d = 1.0 - a - b - c
        if d < 0:
            raise ValueError("R-MAT probabilities must sum to <= 1")
        n = 1 << scale
        rng = np.random.default_rng(graph_seed)
        q = rng.choice(4, size=(edge_factor * n, scale), p=[a, b, c, d])
        bits = np.int64(1) << np.arange(scale - 1, -1, -1, dtype=np.int64)
        rows = ((q >> 1) & 1).astype(np.int64) @ bits
        cols = (q & 1).astype(np.int64) @ bits
        perm = rng.permutation(n)
        rows, cols = perm[rows], perm[cols]
        rows, cols = np.concatenate([rows, cols]), np.concatenate([cols, rows])
        keep = rows != cols
        keys = np.unique(rows[keep] * np.int64(n) + cols[keep])
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(keys // n, minlength=n), out=self.indptr[1:])
        self.indices = (keys % n).astype(np.int32)
        self.shape = (n, n)

    def values(self, rng: np.random.Generator) -> np.ndarray:
        return rng.random(len(self.indices), dtype=np.float32)

    def matrix(self, rng: np.random.Generator) -> Matrix:
        return Matrix(self.indptr, self.indices, self.values(rng), self.shape)


def build(params: dict) -> Kronecker:
    return Kronecker(params["scale"], params["edge_factor"], params["a"],
                     params["b"], params["c"], params["graph_seed"])
