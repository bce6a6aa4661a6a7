"""Per-kernel interpret-mode sweeps: shapes x dtypes vs pure-jnp oracles."""
import numpy as np
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats
from repro.kernels import hll as khll, ref as kref, spgemm_dense as kdense
from repro.kernels import ops as kops


@pytest.mark.parametrize("m_regs", [32, 64, 128])
@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (32, 384)])
def test_hll_sketch_kernel_sweep(m_regs, shape):
    r, e = shape
    rng = np.random.default_rng(r * e + m_regs)
    cols = rng.integers(0, 10_000, (r, e)).astype(np.int32)
    for i in range(r):
        cols[i, rng.integers(0, e):] = -1
    out = khll.hll_sketch(jnp.asarray(cols), m_regs=m_regs, interpret=True)
    ref = kref.hll_sketch_ref(jnp.asarray(cols), m_regs=m_regs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("m_regs", [32, 64])
@pytest.mark.parametrize("ra,k,nb", [(4, 8, 16), (16, 32, 64), (8, 5, 100)])
def test_hll_merge_kernel_sweep(m_regs, ra, k, nb):
    rng = np.random.default_rng(ra * k + nb)
    bcols = rng.integers(0, 5000, (nb, 128)).astype(np.int32)
    sk = np.asarray(kref.hll_sketch_ref(jnp.asarray(bcols), m_regs=m_regs))
    sk = np.vstack([sk, np.zeros((1, m_regs), np.int32)])
    a_ell = rng.integers(0, nb, (ra, k)).astype(np.int32)
    for i in range(ra):
        a_ell[i, rng.integers(1, k + 1):] = nb  # sentinel padding
    indptr = np.arange(ra + 1, dtype=np.int32) * k
    merged, est = khll.hll_merge(jnp.asarray(indptr),
                                 jnp.asarray(a_ell.reshape(-1)),
                                 jnp.asarray(sk), interpret=True)
    mref, eref = kref.hll_merge_ref(jnp.asarray(a_ell), jnp.asarray(sk))
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(mref))
    np.testing.assert_allclose(np.asarray(est), np.asarray(eref), rtol=1e-5)


@pytest.mark.parametrize("m_regs", [32, 64])
@pytest.mark.parametrize("ra,lo,hi,nb", [(12, 129, 400, 300),
                                         (1100, 0, 3, 50)])
def test_hll_merge_kernel_ragged_rows(m_regs, ra, lo, hi, nb):
    """Ragged CSR rows at unaligned starts: rows longer than one 128-entry
    chunk, empty rows, sentinel entries and more rows than one merge
    block. The TPU interpreter raises on any out-of-bounds DMA read."""
    rng = np.random.default_rng(ra * hi + nb + m_regs)
    bcols = rng.integers(0, 5000, (nb, 128)).astype(np.int32)
    sk = np.asarray(kref.hll_sketch_ref(jnp.asarray(bcols), m_regs=m_regs))
    sk = np.vstack([sk, np.zeros((1, m_regs), np.int32)])
    lens = rng.integers(lo, hi + 1, ra)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    idx = rng.integers(0, nb + 1, int(indptr[-1])).astype(np.int32)
    a_ell = np.full((ra, hi), nb, np.int32)  # sentinel padding
    for i in range(ra):
        a_ell[i, :lens[i]] = idx[indptr[i]:indptr[i + 1]]
    merged, est = khll.hll_merge(jnp.asarray(indptr), jnp.asarray(idx),
                                 jnp.asarray(sk),
                                 interpret=pltpu.InterpretParams())
    mref, eref = kref.hll_merge_ref(jnp.asarray(a_ell), jnp.asarray(sk))
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(mref))
    np.testing.assert_allclose(np.asarray(est), np.asarray(eref), rtol=1e-5)


def _random_bin(seed, nB, n, R, E, dtype):
    rng = np.random.default_rng(seed)
    b = formats.random_uniform_csr(seed, nB, n, 10.0, dtype=dtype)
    b_indptr = np.asarray(b.indptr)
    a_rows = rng.integers(0, nB, (R, E)).astype(np.int32)
    a_vals = rng.standard_normal((R, E)).astype(dtype)
    for i in range(R):
        ln = rng.integers(1, E + 1)
        a_rows[i, ln:] = -1
        a_vals[i, ln:] = 0
    k = np.maximum(a_rows, 0)
    a_starts = np.where(a_rows >= 0, b_indptr[k], 0).astype(np.int32)
    a_lens = np.where(a_rows >= 0, b_indptr[k + 1] - b_indptr[k], 0).astype(np.int32)
    b_cols_p, b_vals_p = kops.pad_b_flat(b)
    return b, a_rows, a_vals, a_starts, a_lens, b_cols_p, b_vals_p


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("R,E,W", [(4, 8, 256), (8, 16, 512), (16, 4, 1024),
                                   (8, kdense.ELL_TILE + 88, 256)])
def test_dense_kernel_sweep(dtype, R, E, W):
    nB, n = 48, W - 16
    (b, a_rows, a_vals, a_starts, a_lens,
     b_cols_p, b_vals_p) = _random_bin(R * E + W, nB, n, R, E, dtype)
    row_lo = np.zeros((R, 1), np.int32)
    acc, cnt = kdense.spgemm_dense_bin(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(a_starts),
        jnp.asarray(a_lens), jnp.asarray(row_lo), b_cols_p, b_vals_p,
        window=W, interpret=True)
    racc, rcnt = kref.spgemm_dense_ref(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(row_lo[:, 0]),
        jnp.asarray(b.indptr), b_cols_p, b_vals_p, window=W)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(racc),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(cnt).astype(np.int32), np.asarray(rcnt))
    # the count-only variant tiles the ELL axis the same way
    cnt_only = kdense.spgemm_count_bin(
        jnp.asarray(a_rows), jnp.asarray(a_starts), jnp.asarray(a_lens),
        jnp.asarray(row_lo), b_cols_p, window=W, interpret=True)
    np.testing.assert_array_equal(np.asarray(cnt_only), np.asarray(cnt))


def test_dense_kernel_windowed_offset():
    """Non-zero window bases (row_lo) must translate columns correctly."""
    R, E, W, nB, n = 8, 8, 256, 32, 700
    (b, a_rows, a_vals, a_starts, a_lens,
     b_cols_p, b_vals_p) = _random_bin(99, nB, n, R, E, np.float32)
    rng = np.random.default_rng(1)
    row_lo = rng.integers(0, n - W, (R, 1)).astype(np.int32)
    acc, cnt = kdense.spgemm_dense_bin(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(a_starts),
        jnp.asarray(a_lens), jnp.asarray(row_lo), b_cols_p, b_vals_p,
        window=W, interpret=True)
    racc, rcnt = kref.spgemm_dense_ref(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(row_lo[:, 0]),
        jnp.asarray(b.indptr), b_cols_p, b_vals_p, window=W)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(racc), atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(cnt).astype(np.int32), np.asarray(rcnt))


@pytest.mark.parametrize("tiles", [2, 3])
def test_longrow_kernel_tiled(tiles):
    R, E, W = 4, 8, 128
    n = W * tiles - 32
    (b, a_rows, a_vals, a_starts, a_lens,
     b_cols_p, b_vals_p) = _random_bin(7 * tiles, 40, n, R, E, np.float32)
    row_lo = np.zeros((R, 1), np.int32)
    acc, cnt = kdense.spgemm_dense_bin(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(a_starts),
        jnp.asarray(a_lens), jnp.asarray(row_lo), b_cols_p, b_vals_p,
        window=W, col_tiles=tiles, interpret=True)
    racc, rcnt = kref.spgemm_longrow_ref(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(b.indptr),
        b_cols_p, b_vals_p, tile=W, n_cols=n)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(racc), atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(cnt).astype(np.int32), np.asarray(rcnt))


def test_count_kernel_matches_dense_counts():
    R, E, W = 8, 8, 512
    (b, a_rows, a_vals, a_starts, a_lens,
     b_cols_p, b_vals_p) = _random_bin(5, 64, W - 10, R, E, np.float32)
    row_lo = np.zeros((R, 1), np.int32)
    cnt_only = kdense.spgemm_count_bin(
        jnp.asarray(a_rows), jnp.asarray(a_starts), jnp.asarray(a_lens),
        jnp.asarray(row_lo), b_cols_p, window=W, interpret=True)
    _, cnt = kdense.spgemm_dense_bin(
        jnp.asarray(a_rows), jnp.asarray(a_vals), jnp.asarray(a_starts),
        jnp.asarray(a_lens), jnp.asarray(row_lo), b_cols_p, b_vals_p,
        window=W, interpret=True)
    np.testing.assert_array_equal(np.asarray(cnt_only), np.asarray(cnt))


def test_extract_window_rows():
    acc = jnp.asarray(np.array([[0.0, 2.0, 0.0, -1.0], [5.0, 0.0, 0.0, 0.0]]))
    cnt = jnp.asarray(np.array([[0, 1, 2, 1], [3, 0, 0, 0]], np.float32))
    row_lo = jnp.asarray(np.array([[10], [20]], np.int32))
    cols, vals, nnz = kops.extract_window_rows(acc, cnt, row_lo, cap=3)
    cols, vals, nnz = map(np.asarray, (cols, vals, nnz))
    assert nnz.tolist() == [3, 1]
    assert cols[0].tolist() == [11, 12, 13]
    # structural zero at local col 2 must be kept with value 0
    assert vals[0].tolist() == [2.0, 0.0, -1.0]
    assert cols[1, 0] == 20 and vals[1, 0] == 5.0
