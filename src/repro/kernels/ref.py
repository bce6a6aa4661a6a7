"""Pure-jnp oracles for every Pallas kernel in this package.

Each function mirrors the exact input layout of its kernel (ELL blocks,
flat B arrays, window bases) so tests can `assert_allclose` kernel output
against the oracle across shape/dtype sweeps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hll import hash32, _rho, _alpha


# ---------------------------------------------------------------------------
# HLL sketch construction oracle — from ELL column-index layout.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("m_regs",))
def hll_sketch_ref(ell_cols: jax.Array, *, m_regs: int) -> jax.Array:
    """(R, E) int32 col indices (pad = -1) -> (R, m_regs) int32 registers."""
    p = m_regs.bit_length() - 1
    valid = ell_cols >= 0
    h = hash32(jnp.maximum(ell_cols, 0))
    reg = (h & jnp.uint32(m_regs - 1)).astype(jnp.int32)
    rho = jnp.where(valid, _rho(h, p), 0)
    onehot = reg[:, :, None] == jnp.arange(m_regs, dtype=jnp.int32)
    contrib = jnp.where(onehot, rho[:, :, None], 0)
    return jnp.max(contrib, axis=1)


# ---------------------------------------------------------------------------
# HLL merge + estimate oracle.
# ---------------------------------------------------------------------------

def hll_estimate_from_regs(regs: jax.Array, clip_max: float | None = None):
    m = regs.shape[-1]
    r = regs.astype(jnp.float32)
    inv_sum = jnp.sum(jnp.exp2(-r), axis=-1)
    e_raw = _alpha(m) * m * m / inv_sum
    v = jnp.sum(regs == 0, axis=-1).astype(jnp.float32)
    e_small = m * jnp.log(jnp.where(v > 0, m / jnp.maximum(v, 1e-9), 1.0))
    # small-range gate on the linear-counting estimate, as in
    # core.hll.estimate_cardinality and the merge kernel
    e = jnp.where((e_small <= 2.5 * m) & (v > 0), e_small, e_raw)
    if clip_max is not None:
        e = jnp.clip(e, 0.0, clip_max)
    return e


@jax.jit
def hll_merge_ref(a_ell: jax.Array, sketches: jax.Array):
    """a_ell (RA, K) int32 B-row ids (pad rows point at an all-zero sketch
    row, i.e. index sketches.shape[0]-1). Returns (merged (RA, m), est (RA,))."""
    gathered = sketches[a_ell]                     # (RA, K, m)
    merged = jnp.max(gathered, axis=1)
    return merged, hll_estimate_from_regs(merged)


# ---------------------------------------------------------------------------
# Dense-accumulator numeric kernel oracle (windowed Gustavson).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("window",))
def spgemm_dense_ref(a_cols, a_vals, row_lo, b_indptr, b_cols, b_vals,
                     *, window: int):
    """Oracle for the binned dense-accumulator kernel.

    a_cols: (R, E) int32 B-row ids per output row (pad = -1)
    a_vals: (R, E) float
    row_lo: (R,) int32 window base per row
    b_*:    flat CSR arrays of B (b_cols pad = -1 beyond nnz)
    Returns (acc (R, window) float, counts (R, window) int32) where counts
    is the number of products landing on each slot (presence = counts > 0).
    """
    R, E = a_cols.shape
    nnz_b = b_cols.shape[0]

    def per_row(acols, avals, lo):
        acc = jnp.zeros((window,), b_vals.dtype)
        cnt = jnp.zeros((window,), jnp.int32)

        def body(e, carry):
            acc, cnt = carry
            k = acols[e]
            av = avals[e]
            active = k >= 0
            kc = jnp.maximum(k, 0)
            start = b_indptr[kc]
            length = jnp.where(active, b_indptr[kc + 1] - start, 0)
            # gather the full B row (bounded by nnz_b) in one masked sweep
            idx = jnp.arange(nnz_b, dtype=jnp.int32)
            in_row = (idx >= start) & (idx < start + length)
            cols_local = jnp.where(in_row, b_cols[idx] - lo, -1)
            ok = in_row & (cols_local >= 0) & (cols_local < window)
            contrib = jnp.where(ok, av * b_vals[idx], 0)
            tgt = jnp.where(ok, cols_local, 0)
            acc = acc.at[tgt].add(jnp.where(ok, contrib, 0))
            cnt = cnt.at[tgt].add(jnp.where(ok, 1, 0))
            return acc, cnt

        return jax.lax.fori_loop(0, E, body, (acc, cnt))

    return jax.vmap(per_row)(a_cols, a_vals, row_lo)


@partial(jax.jit, static_argnames=("tile", "n_cols"))
def spgemm_longrow_ref(a_cols, a_vals, b_indptr, b_cols, b_vals,
                       *, tile: int, n_cols: int):
    """Oracle for the column-tiled long-row kernel: full-width accumulation
    (R, n_cols_padded) assembled from `tile`-wide windows."""
    n_tiles = (n_cols + tile - 1) // tile
    width = n_tiles * tile
    lo = jnp.zeros((a_cols.shape[0],), jnp.int32)
    acc, cnt = spgemm_dense_ref(a_cols, a_vals, lo, b_indptr, b_cols, b_vals,
                                window=width)
    return acc, cnt
