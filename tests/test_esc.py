"""ESC product enumeration (``core.esc.expand``) and the passes built on it.

``expand`` numbers products by prefix scans; the reference below numbers
them the straightforward way, a binary search of every product over the
per-slot product offsets, and every ``Expanded`` field must match it bit
for bit, padding lanes included. The cases cover the edges of the scan:
empty rows of A, zero-length rows of B at the start, middle and end of an
A row, padding slots, ``total == p_cap`` (heads at ``p_cap`` are dropped),
no products at all, and a rectangular B.
"""
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import esc
from repro.core.formats import PAD_COL


def _reference_expand(a_indptr, a_indices, a_values, b_indptr, b_indices,
                      b_values, *, p_cap, num_rows_a, with_values=True):
    """Per-product binary search over the slots' product offsets."""
    cap_a = a_indices.shape[0]
    nnz_a = a_indptr[-1]
    slot_valid = jnp.arange(cap_a, dtype=jnp.int32) < nnz_a
    b_len = b_indptr[1:] - b_indptr[:-1]
    k_of_slot = jnp.clip(a_indices, 0, b_len.shape[0] - 1)
    len_of_slot = jnp.where(slot_valid, b_len[k_of_slot], 0)
    offsets = jnp.concatenate([jnp.zeros((1,), len_of_slot.dtype),
                               jnp.cumsum(len_of_slot)])
    total = offsets[-1].astype(jnp.int32)

    p = jnp.arange(p_cap, dtype=jnp.int32)
    j = jnp.searchsorted(offsets, p, side="right").astype(jnp.int32) - 1
    j = jnp.clip(j, 0, cap_a - 1)
    t = p - offsets[j].astype(jnp.int32)
    valid = p < total

    slot = jnp.arange(cap_a, dtype=jnp.int32)
    a_row = jnp.searchsorted(a_indptr, slot, side="right").astype(
        jnp.int32) - 1
    a_row = jnp.clip(a_row, 0, num_rows_a - 1)
    rows = jnp.where(valid, a_row[j], num_rows_a)
    k = k_of_slot[j]
    b_pos = jnp.clip(b_indptr[k].astype(jnp.int32) + t, 0,
                     b_indices.shape[0] - 1)
    cols = jnp.where(valid, b_indices[b_pos], PAD_COL)
    if with_values:
        vals = jnp.where(valid, a_values[j] * b_values[b_pos], 0)
    else:
        vals = jnp.zeros((p_cap,), jnp.float32)
    return esc.Expanded(rows, cols, vals, valid, total)


def _csr_arrays(row_lists, n_cols, seed, cap=None):
    """(indptr, indices, values) of a CSR with sorted rows; ``cap`` pads
    the flat arrays with ``PAD_COL`` / 0 past nnz."""
    rng = np.random.default_rng(seed)
    indptr = np.zeros(len(row_lists) + 1, np.int32)
    indptr[1:] = np.cumsum([len(r) for r in row_lists])
    nnz = int(indptr[-1])
    cap = nnz if cap is None else cap
    indices = np.full(cap, PAD_COL, np.int32)
    values = np.zeros(cap, np.float32)
    if nnz:
        indices[:nnz] = np.concatenate([sorted(r) for r in row_lists if r])
        # positive values: no sum cancels, so scipy keeps every entry
        values[:nnz] = rng.uniform(0.5, 1.5, nnz).astype(np.float32)
    return indptr, indices, values, (len(row_lists), n_cols)


def _random_rows(seed, m, n, max_len, empty_share):
    rng = np.random.default_rng(seed)
    return [[] if rng.random() < empty_share else
            list(rng.choice(n, rng.integers(1, max_len + 1), replace=False))
            for _ in range(m)]


# name -> (A rows, A cols, A cap, B rows, B cols, p_cap or None for the
# next power of two of the products)
CASES = {
    "empty_a_rows": ([[], [0, 2], [], [], [1, 3], []], 4, None,
                     [[1], [0, 2, 3], [3], [0, 1]], 4, None),
    "zero_length_b_rows": ([[0, 1, 2, 3, 4], [1, 3], [0, 4], [2]], 5, None,
                           [[], [0, 2], [], [1, 4], []], 5, None),
    "padding_slots": ([[0, 2], [1], [], [0, 1, 2]], 3, 11,
                      [[1, 2], [0], [0, 1, 2]], 3, None),
    # 8 products in 8 lanes; trailing empty B rows and padding put slot
    # heads exactly at p_cap, where the scatter drops them
    "total_equals_p_cap": ([[0, 1], [2], [1, 2]], 3, 7,
                           [[0, 1, 2], [1], []], 3, 8),
    # B holds entries, but only in a row that no entry of A names
    "no_products": ([[0], [], [1, 0]], 3, 4, [[], [], [0, 2]], 3, 16),
    "rectangular_b": ([[0, 5], [1, 2, 3], [], [4]], 6, None,
                      [[0, 8], [3], [], [1, 2, 7], [6], [0, 4, 5, 8]], 9,
                      None),
    "random_skewed": (_random_rows(1, 40, 30, 12, 0.3), 30, 400,
                      _random_rows(2, 30, 50, 9, 0.25), 50, None),
}


def _operands(name):
    a_rows, a_n, a_cap, b_rows, b_n, p_cap = CASES[name]
    a = _csr_arrays(a_rows, a_n, seed=10, cap=a_cap)
    b = _csr_arrays(b_rows, b_n, seed=11)
    products = sum(len(b_rows[k]) for r in a_rows for k in r)
    if p_cap is None:
        p_cap = max(8, 1 << max(products - 1, 0).bit_length())
    assert products <= p_cap
    return a, b, products, p_cap


@pytest.mark.parametrize("with_values", [True, False])
@pytest.mark.parametrize("name", sorted(CASES))
def test_expand_matches_binary_search_reference(name, with_values):
    (ap, ai, av, (m, _)), (bp, bi, bv, _), products, p_cap = _operands(name)
    args = [jnp.asarray(x) for x in (ap, ai, av, bp, bi, bv)]
    if not with_values:
        args[2] = args[5] = None
    kw = dict(p_cap=p_cap, num_rows_a=m, with_values=with_values)
    got = esc.expand(*args, **kw)
    want = _reference_expand(*args, **kw)
    assert int(got.total) == products
    for field in esc.Expanded._fields:
        g, w = np.asarray(getattr(got, field)), np.asarray(getattr(want,
                                                                   field))
        assert g.dtype == w.dtype, field
        if g.dtype.kind == "f":  # bit patterns: -0.0 and NaN count too
            g, w = g.view(np.int32), w.view(np.int32)
        np.testing.assert_array_equal(g, w, err_msg=field)


@pytest.mark.parametrize("name", sorted(CASES))
def test_esc_passes_match_scipy(name):
    (ap, ai, av, (m, k)), (bp, bi, bv, (kb, n)), _, p_cap = _operands(name)
    assert k == kb
    nnz_a, nnz_b = int(ap[-1]), int(bp[-1])
    ref = (sp.csr_matrix((av[:nnz_a].astype(np.float64), ai[:nnz_a], ap),
                         shape=(m, k))
           @ sp.csr_matrix((bv[:nnz_b].astype(np.float64), bi[:nnz_b], bp),
                           shape=(k, n))).tocsr()
    ref.sort_indices()

    counts = esc.symbolic_exact(*(jnp.asarray(x) for x in (ap, ai, bp, bi)),
                                p_cap=p_cap, num_rows_a=m)
    np.testing.assert_array_equal(np.asarray(counts), np.diff(ref.indptr))

    res = esc.esc_spgemm(*(jnp.asarray(x) for x in (ap, ai, av, bp, bi, bv)),
                         p_cap=p_cap, out_cap=p_cap, num_rows_a=m)
    nnz = int(res.nnz)
    assert nnz == ref.nnz
    np.testing.assert_array_equal(np.asarray(res.indptr), ref.indptr)
    np.testing.assert_array_equal(np.asarray(res.indices)[:nnz], ref.indices)
    assert (np.asarray(res.indices)[nnz:] == PAD_COL).all()
    np.testing.assert_allclose(np.asarray(res.values)[:nnz], ref.data,
                               rtol=1e-5)  # f32 sums of < 100 terms
