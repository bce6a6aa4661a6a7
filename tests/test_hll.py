"""HLL estimator unit + property tests."""
import numpy as np
import jax.numpy as jnp
import pytest

try:  # hypothesis is optional: the suite must collect and pass without it
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fixed-seed fallback, same properties
    from _hypothesis_fallback import given, settings, st

from repro.core import formats, hll


def true_union_cardinality(a, b):
    A = np.abs(np.asarray(a.to_dense())) > 0
    B = np.abs(np.asarray(b.to_dense())) > 0
    return (A.astype(np.int64) @ B.astype(np.int64) > 0).sum(axis=1)


@pytest.mark.parametrize("m_regs", [32, 64, 128])
def test_estimate_accuracy(m_regs):
    a = formats.random_uniform_csr(10, 300, 400, 12.0)
    b = formats.random_uniform_csr(11, 400, 3000, 20.0)
    sk = hll.sketch_rows(b, m_regs)
    est = np.asarray(hll.estimate_row_nnz(a, sk, b.n))
    true = true_union_cardinality(a, b)
    mask = true > 0
    rel = np.abs(est[mask] - true[mask]) / true[mask]
    # paper Fig. 8: mean rel err ~0.13/0.10/0.07; allow slack for small set
    bound = {32: 0.22, 64: 0.17, 128: 0.13}[m_regs]
    assert rel.mean() < bound, rel.mean()


def test_merge_property_max():
    """merge(sketch(X), sketch(Y)) == sketch(X u Y) — elementwise max."""
    rng = np.random.default_rng(0)
    x = rng.choice(10_000, 500, replace=False).astype(np.int32)
    y = rng.choice(10_000, 700, replace=False).astype(np.int32)
    m = 64

    def sketch_of(ids):
        csr = formats.csr_from_arrays(
            np.array([0, len(ids)]), ids, np.ones(len(ids), np.float32),
            (1, 10_000))
        return np.asarray(hll.sketch_rows(csr, m))[0]

    sx, sy = sketch_of(x), sketch_of(np.setdiff1d(y, x))
    sxy = sketch_of(np.union1d(x, y))
    assert np.array_equal(np.maximum(sx, sy), sxy)


def test_estimate_monotone_clip():
    regs = jnp.zeros((4, 64), jnp.int32)
    est = hll.estimate_cardinality(regs)
    assert np.allclose(np.asarray(est), 0.0, atol=1e-3)  # empty set -> ~0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2**20), min_size=1, max_size=2000),
       st.sampled_from([32, 64, 128]))
def test_estimate_error_bound_property(ids, m_regs):
    """Estimate should be within ~6 sigma of truth for arbitrary id sets."""
    ids = np.unique(np.asarray(ids, np.int32))
    csr = formats.csr_from_arrays(
        np.array([0, len(ids)]), ids, np.ones(len(ids), np.float32),
        (1, 2**20 + 1))
    est = float(np.asarray(hll.estimate_cardinality(
        hll.sketch_rows(csr, m_regs)))[0])
    true = len(ids)
    sigma = 1.04 / np.sqrt(m_regs)
    assert est >= 0
    assert abs(est - true) <= max(6 * sigma * true, 8.0)


@pytest.mark.parametrize("m_regs", [32, 64])
def test_error_envelope_100_trials(m_regs):
    """Relative error over 100 seeded trials stays within the HLL
    standard-error envelope sigma = 1.04/sqrt(m) (paper §3.1), with slack.

    All trials share one CSR capacity so a single jit specialization serves
    every draw (values-only updates)."""
    cap = 20_000
    rng = np.random.default_rng(1234)
    rels = []
    for _ in range(100):
        true = int(10 ** rng.uniform(2.2, np.log10(cap)))  # log-uniform
        ids = rng.choice(2**20, true, replace=False).astype(np.int32)
        csr = formats.csr_from_arrays(np.array([0, true]), ids,
                                      np.ones(true, np.float32),
                                      (1, 2**20), capacity=cap)
        est = float(np.asarray(hll.estimate_cardinality(
            hll.sketch_rows(csr, m_regs)))[0])
        rels.append((est - true) / true)
    rels = np.asarray(rels)
    sigma = 1.04 / np.sqrt(m_regs)
    assert abs(rels.mean()) < 0.35 * sigma, rels.mean()   # unbiased-ish
    assert rels.std() < 1.35 * sigma, rels.std()          # envelope + slack
    assert np.abs(rels).max() < 6.0 * sigma, np.abs(rels).max()


def test_small_range_correction_branch():
    """Cardinalities << m must take estimate_cardinality's linear-counting
    branch (v > 0 zero registers and e_small <= 2.5m) and be near-exact."""
    m = 64
    rng = np.random.default_rng(7)
    for true in (1, 2, 5, 10, 20, 40):
        ids = rng.choice(2**20, true, replace=False).astype(np.int32)
        csr = formats.csr_from_arrays(np.array([0, true]), ids,
                                      np.ones(true, np.float32),
                                      (1, 2**20), capacity=64)
        regs = np.asarray(hll.sketch_rows(csr, m))[0]
        # confirm the branch condition actually holds for this input
        v = int((regs == 0).sum())
        e_small = m * np.log(m / max(v, 1e-9))
        assert v > 0 and e_small <= 2.5 * m, (true, v, e_small)
        est = float(np.asarray(hll.estimate_cardinality(
            hll.sketch_rows(csr, m)))[0])
        # linear counting: std ~= sqrt(m(e^t - t - 1)) with t = true/m;
        # allow ~3 sigma around that envelope
        t = true / m
        lc_sigma = np.sqrt(m * (np.exp(t) - t - 1))
        assert abs(est - true) <= max(2.0, 3.0 * lc_sigma), (true, est)


def test_cohen_estimator_sane():
    b = formats.random_uniform_csr(3, 200, 1000, 15.0)
    a = formats.random_uniform_csr(4, 100, 200, 10.0)
    mins = hll.cohen_build(b.indptr, b.indices, k=16, num_rows=b.m, n_cols=b.n)
    merged = hll.cohen_merge(a.indptr, a.indices, mins, num_rows_a=a.m)
    est = np.asarray(hll.cohen_estimate(merged, clip_max=b.n))
    true = true_union_cardinality(a, b)
    mask = true > 0
    rel = np.abs(est[mask] - true[mask]) / true[mask]
    assert rel.mean() < 0.5


@pytest.mark.parametrize("v", [0, 5, 6, 63])
def test_small_range_gate_boundary_lockstep(v):
    """Gate boundary cases: the linear-counting branch engages iff v > 0
    and e_small <= 2.5m (for m = 64 that flips between v = 5 and v = 6),
    and the core estimator and the Pallas merge kernel agree exactly on
    which branch each side of the boundary takes."""
    from repro.kernels import hll as khll
    from repro.kernels import ops as kops
    m = 64
    regs = np.full(m, 3, np.int32)
    regs[:v] = 0
    e_small = m * np.log(m / v) if v > 0 else np.inf
    e_raw = hll._alpha(m) * m * m / np.sum(np.exp2(-regs.astype(np.float64)))
    takes_lc = v > 0 and e_small <= 2.5 * m
    # the branch flips exactly at v >= m * e^-2.5 (v >= 6 for m = 64)
    assert takes_lc == (v >= int(np.ceil(m * np.exp(-2.5))))
    want = e_small if takes_lc else e_raw
    est = float(np.asarray(hll.estimate_cardinality(
        jnp.asarray(regs)[None, :]))[0])
    assert est == pytest.approx(want, rel=1e-4), (v, est, want)
    # Pallas merge kernel finalizes through the identical gate (lockstep)
    sk = np.stack([regs, np.zeros(m, np.int32)]).astype(np.int32)
    # one A row over B rows 0 and 1 (row 1 = all-zero sentinel)
    merged, est_k = khll.hll_merge(jnp.asarray([0, 2], jnp.int32),
                                   jnp.asarray([0, 1], jnp.int32),
                                   jnp.asarray(sk),
                                   interpret=kops.use_interpret())
    np.testing.assert_array_equal(np.asarray(merged)[0], regs)
    assert float(np.asarray(est_k)[0]) == pytest.approx(want, rel=1e-4)
