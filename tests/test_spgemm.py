"""End-to-end Ocean SpGEMM behaviour tests + hypothesis property tests."""
import numpy as np
import pytest

try:  # hypothesis is optional: the suite must collect and pass without it
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fixed-seed fallback, same properties
    from _hypothesis_fallback import given, settings, st

from repro.core import formats, workflow
from repro.core.analysis import OceanConfig, analyze


def dense_of(c):
    return np.asarray(c.to_dense())


def struct_of(c):
    ip = np.asarray(c.indptr)
    ii = np.asarray(c.indices)
    out = set()
    for r in range(c.m):
        for j in range(int(ip[r]), int(ip[r + 1])):
            out.add((r, int(ii[j])))
    return out


def assert_csr_equal(c, ref, tol=1e-4):
    np.testing.assert_allclose(dense_of(c), dense_of(ref), atol=tol)
    assert struct_of(c) == struct_of(ref)


def assert_sorted_rows(c):
    ip = np.asarray(c.indptr)
    ii = np.asarray(c.indices)
    for r in range(c.m):
        row = ii[int(ip[r]) : int(ip[r + 1])]
        assert np.all(np.diff(row) > 0), f"row {r} not strictly sorted"


@pytest.mark.parametrize("name,gen", [
    ("uniform", lambda: formats.random_uniform_csr(1, 300, 300, 10.0)),
    ("powerlaw", lambda: formats.powerlaw_csr(2, 256, 256, 8.0)),
    ("banded", lambda: formats.banded_csr(3, 200, 200, 16)),
    ("block", lambda: formats.block_sparse_csr(4, 256, 256, 32)),
    ("skewed", lambda: formats.skewed_rows_csr(5, 400, 400, 5.0)),
    ("hypersparse", lambda: formats.hypersparse_csr(6, 800, 800)),
])
def test_ocean_matches_reference_AA(name, gen):
    a = gen()
    ref = workflow.spgemm_reference(a, a)
    c, rep = workflow.ocean_spgemm(a, a)
    assert_csr_equal(c, ref)
    assert_sorted_rows(c)
    assert rep.nnz_out == ref.nnz


def test_rectangular_AAt():
    a = formats.random_uniform_csr(7, 128, 512, 12.0)
    at = formats.csr_from_dense(np.asarray(a.to_dense()).T)
    ref = workflow.spgemm_reference(a, at)
    c, rep = workflow.ocean_spgemm(a, at)
    assert_csr_equal(c, ref)


@pytest.mark.parametrize("wf", ["symbolic", "estimation", "upper_bound"])
def test_forced_workflows_all_correct(wf):
    a = formats.random_uniform_csr(8, 200, 200, 14.0)
    ref = workflow.spgemm_reference(a, a)
    c, rep = workflow.ocean_spgemm(a, a, force_workflow=wf)
    assert rep.workflow == wf
    assert_csr_equal(c, ref)


@pytest.mark.parametrize("assisted,hybrid", [(False, False), (True, False),
                                             (True, True)])
def test_ablation_versions_correct(assisted, hybrid):
    a = formats.skewed_rows_csr(9, 300, 300, 6.0)
    ref = workflow.spgemm_reference(a, a)
    c, _ = workflow.ocean_spgemm(a, a, assisted=assisted, hybrid=hybrid)
    assert_csr_equal(c, ref)


def test_overflow_fallback_underestimation():
    """Force overflow by shrinking the expansion factor to ~0 so binned
    capacities undershoot; the fallback must still give exact results."""
    a = formats.random_uniform_csr(10, 200, 200, 16.0)
    cfg = OceanConfig(expansion=0.05, expansion_small_regs=0.05,
                      cr_threshold=0.0, er_threshold=0.0,
                      upper_bound_avg_products=0.0)
    ref = workflow.spgemm_reference(a, a)
    c, rep = workflow.ocean_spgemm(a, a, cfg, force_workflow="estimation")
    assert_csr_equal(c, ref)
    assert rep.overflow_rows > 0, "test should actually exercise overflow"


def test_longrow_path_exercised():
    """A matrix whose output range exceeds the widest window must route
    through the column-tiled long-row kernel and stay correct."""
    n = 6000  # > WINDOW_LADDER max (4096)
    rng = np.random.default_rng(0)
    m = 40
    rows, cols = [], []
    for i in range(m):
        c = rng.choice(n, 80, replace=False)  # scattered across full range
        rows.extend([i] * len(c))
        cols.extend(c)
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    indptr = np.zeros(m + 1, np.int64)
    np.add.at(indptr, np.asarray(rows) + 1, 1)
    a = formats.csr_from_arrays(np.cumsum(indptr), cols, vals, (m, n))
    # B maps columns across the whole range
    b = formats.random_uniform_csr(1, n, n, 3.0)
    ref = workflow.spgemm_reference(a, b)
    # hash_rung=False: the hash accumulator would otherwise absorb these
    # sparse scattered rows (its intended behavior — tests/test_hash.py
    # covers that routing); this test pins the column-tiled kernel itself.
    c, rep = workflow.ocean_spgemm(a, b, OceanConfig(hash_rung=False),
                                   force_workflow="symbolic")
    longrow_bins = [k for k in rep.bins if "x" in k and not k.endswith("x1")]
    assert longrow_bins, rep.bins
    assert_csr_equal(c, ref)
    # with the rung enabled the same rows route to hash bins and stay exact
    c2, rep2 = workflow.ocean_spgemm(a, b, force_workflow="symbolic")
    assert any(k.startswith("hash_t") for k in rep2.bins if rep2.bins[k]), \
        rep2.bins
    assert_csr_equal(c2, ref)


@pytest.mark.parametrize("force", [None, "symbolic"])
def test_wide_output_keys_do_not_wrap(force):
    """(rows + 1) * n_cols >= 2**31 with x64 disabled: a packed int
    (row, col) key would wrap. The ESC bins (upper-bound workflow), the
    hash twin (symbolic workflow) and the oracle sort on (row, col)
    instead, so all of them agree with scipy."""
    m, k, n = 2500, 5000, 1_000_000
    assert (m + 1) * n >= 2**31
    a = formats.random_uniform_csr(3, m, k, 4.0)
    b = formats.random_uniform_csr(4, k, n, 3.0)
    c, rep = workflow.ocean_spgemm(a, b, force_workflow=force, cache=False)
    assert rep.workflow == (force or "upper_bound")
    busy = {key for key, rows in rep.bins.items() if rows}
    assert ("esc" in busy) if force is None else any(
        key.startswith("hash_t") for key in busy), rep.bins
    assert workflow.scipy_mismatch(c, a, b) is None
    assert workflow.scipy_mismatch(workflow.spgemm_reference(a, b),
                                   a, b) is None


def test_longrow_past_tile_cap_routes_to_esc():
    """Long rows narrower than LONGROW_MAX_TILES column tiles keep the
    column-tiled kernel; wider ones take the exact ESC bin instead."""
    from repro.core import binning
    m = 4
    products = np.full(m, 5000)
    pred = products.astype(float)
    a_nnz = np.full(m, 50)
    lo = np.zeros(m, np.int64)
    for tiles, to_esc in ((binning.LONGROW_MAX_TILES, False),
                          (binning.LONGROW_MAX_TILES + 1, True)):
        n = tiles * binning.LONGROW_TILE
        bp = binning.plan_bins(pred, products, lo, lo + n - 1, a_nnz, n,
                               expansion=1.0, workflow="symbolic",
                               hash_enabled=False)
        long_bins = [bn for bn in bp.dense_bins if bn.is_longrow]
        assert len(bp.esc_rows) == (m if to_esc else 0)
        assert bool(long_bins) != to_esc
        if long_bins:
            assert long_bins[0].col_tiles == tiles


def test_analysis_table1_selection():
    cfg = OceanConfig()
    # hypersparse -> upper_bound (avg products < 64)
    hs = formats.hypersparse_csr(11, 1000, 1000)
    assert analyze(hs, hs, cfg).workflow == "upper_bound"
    # dense-ish banded with high ER & CR -> estimation
    bw = formats.banded_csr(12, 512, 512, 48)
    r = analyze(bw, bw, cfg)
    assert r.workflow == "estimation" and r.er >= 8 and r.sampled_cr >= 8
    # moderate uniform -> symbolic (CR too small)
    u = formats.random_uniform_csr(13, 1024, 1024, 16.0)
    r = analyze(u, u, cfg)
    assert r.workflow == "symbolic"


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@st.composite
def sparse_matrix(draw, max_dim=60):
    m = draw(st.integers(2, max_dim))
    n = draw(st.integers(2, max_dim))
    density = draw(st.floats(0.01, 0.4))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    mat = (rng.random((m, n)) < density) * rng.integers(-3, 4, (m, n))
    return mat.astype(np.float32)


@settings(max_examples=25, deadline=None)
@given(sparse_matrix(), sparse_matrix())
def test_property_ocean_equals_dense_matmul(am, bm):
    """For arbitrary matrices (integer values -> exact arithmetic, possible
    cancellation), Ocean's values match the dense product and its structure
    matches the boolean product."""
    k = min(am.shape[1], bm.shape[0])
    am, bm = am[:, :k], bm[:k, :]
    a = formats.csr_from_dense(am)
    b = formats.csr_from_dense(bm)
    if a.nnz == 0 or b.nnz == 0:
        return
    c, _ = workflow.ocean_spgemm(a, b)
    np.testing.assert_allclose(dense_of(c), am @ bm, atol=1e-5)
    want_struct = ((np.abs(am) @ np.abs(bm)) > 0)
    got = np.zeros_like(want_struct)
    ip, ii = np.asarray(c.indptr), np.asarray(c.indices)
    for r in range(c.m):
        got[r, ii[int(ip[r]):int(ip[r + 1])]] = True
    assert np.array_equal(got, want_struct)


@settings(max_examples=15, deadline=None)
@given(sparse_matrix(max_dim=40))
def test_property_csr_roundtrip(am):
    a = formats.csr_from_dense(am)
    np.testing.assert_array_equal(dense_of(a), am)
