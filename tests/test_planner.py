"""Planner/executor split: plan caching, reuse, and the batched API."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_bit_identical
from repro.core import formats, planner, workflow


def with_values(a, values):
    """Same sparsity pattern, new values (padding slots kept at 0)."""
    values = np.array(values)
    values[a.nnz:] = 0
    return formats.CSR(a.indptr, a.indices, jnp.asarray(values), a.shape,
                       a.nnz)


@pytest.fixture()
def cache():
    return planner.PlanCache(maxsize=8)


@pytest.mark.parametrize("gen", [
    lambda: formats.random_uniform_csr(41, 220, 220, 10.0),   # symbolic
    lambda: formats.banded_csr(42, 180, 180, 40),             # estimation
    lambda: formats.hypersparse_csr(43, 700, 700),            # upper_bound
])
def test_cached_plan_output_identical(gen, cache):
    a = gen()
    c_fresh, rep_fresh = workflow.ocean_spgemm(a, a, cache=cache)
    c_cached, rep_cached = workflow.ocean_spgemm(a, a, cache=cache)
    assert not rep_fresh.plan_cache_hit
    assert rep_cached.plan_cache_hit
    assert_bit_identical(c_fresh, c_cached)
    assert rep_cached.bins == rep_fresh.bins
    assert rep_cached.workflow == rep_fresh.workflow


def test_cache_hit_skips_analysis_and_binning(cache):
    a = formats.random_uniform_csr(44, 250, 250, 12.0)
    _, rep1 = workflow.ocean_spgemm(a, a, cache=cache)
    assert cache.stats() == {"hits": 0, "misses": 1, "size": 1}
    assert rep1.setup_seconds > 0.0  # fresh plan did real planning work

    _, rep2 = workflow.ocean_spgemm(a, a, cache=cache)
    assert cache.stats() == {"hits": 1, "misses": 1, "size": 1}
    # zero analysis/prediction/binning work on the cached path
    for k in ("analysis", "prediction", "binning"):
        assert rep2.stage_seconds[k] == 0.0, (k, rep2.stage_seconds)
    assert rep2.plan_cache_hit


def test_values_only_update_hits_cache(cache):
    a = formats.random_uniform_csr(45, 200, 200, 9.0)
    _, _ = workflow.ocean_spgemm(a, a, cache=cache)
    rng = np.random.default_rng(0)
    a2 = with_values(a, rng.standard_normal(a.capacity).astype(np.float32))
    c2, rep2 = workflow.ocean_spgemm(a2, a2, cache=cache)
    assert rep2.plan_cache_hit
    ref = workflow.spgemm_reference(a2, a2)
    np.testing.assert_allclose(np.asarray(c2.to_dense()),
                               np.asarray(ref.to_dense()), atol=1e-4)


def test_structure_or_knob_change_misses(cache):
    a = formats.random_uniform_csr(46, 150, 150, 8.0)
    workflow.ocean_spgemm(a, a, cache=cache)
    # different knobs -> different key -> miss
    workflow.ocean_spgemm(a, a, cache=cache, force_workflow="symbolic")
    # different structure -> miss
    b = formats.random_uniform_csr(47, 150, 150, 8.0)
    workflow.ocean_spgemm(b, b, cache=cache)
    assert cache.stats()["hits"] == 0
    assert cache.stats()["misses"] == 3


def test_lru_eviction_bounds_size():
    cache = planner.PlanCache(maxsize=2)
    mats = [formats.random_uniform_csr(50 + i, 100, 100, 6.0)
            for i in range(3)]
    for m in mats:
        workflow.ocean_spgemm(m, m, cache=cache)
    assert len(cache) == 2
    # the oldest plan was evicted -> miss on re-use
    workflow.ocean_spgemm(mats[0], mats[0], cache=cache)
    assert cache.stats()["hits"] == 0


def test_explicit_plan_execution_matches():
    a = formats.banded_csr(48, 160, 160, 30)
    plan = planner.build_plan(a, a)
    c1, rep1 = workflow.ocean_spgemm(a, a, plan=plan)
    c2, _ = workflow.ocean_spgemm(a, a, cache=False)
    assert_bit_identical(c1, c2)
    assert rep1.workflow == plan.workflow


def test_reuse_b_sketches_is_bit_exact():
    b = formats.banded_csr(49, 200, 200, 40)
    a = formats.banded_csr(51, 180, 200, 40)
    plan = planner.build_plan(a, b, force_workflow="estimation")
    assert plan.b_sketches is not None
    sk_cache = plan.reuse_b_sketches()
    assert len(sk_cache) == 1
    plan2 = planner.build_plan(a, b, force_workflow="estimation",
                               sketch_cache=sk_cache)
    c1, _ = planner.execute_plan(plan, a, b)
    c2, _ = planner.execute_plan(plan2, a, b)
    assert_bit_identical(c1, c2)


def test_many_matches_per_call_loop_bit_exact():
    b = formats.random_uniform_csr(52, 180, 180, 12.0)
    a_list = [formats.random_uniform_csr(53 + i, 140, 180, 8.0)
              for i in range(4)]
    cache1 = planner.PlanCache()
    many = workflow.ocean_spgemm_many(a_list, b, cache=cache1)
    cache2 = planner.PlanCache()
    loop = [workflow.ocean_spgemm(a, b, cache=cache2) for a in a_list]
    for (cm, _), (cl, _) in zip(many, loop):
        assert_bit_identical(cm, cl)


def test_many_amortizes_sketches_on_estimation_workflow():
    """On the estimation workflow the batched API must build B sketches
    once; a shared sketch cache observed from outside must end up with
    exactly one entry per (m_regs, seed)."""
    b = formats.banded_csr(54, 220, 220, 50)
    a_list = [formats.banded_csr(55 + i, 200, 220, 50) for i in range(3)]
    sk_cache = {}
    cache = planner.PlanCache()
    for a in a_list:
        _, rep = workflow.ocean_spgemm(a, b, cache=cache,
                                       force_workflow="estimation",
                                       sketch_cache=sk_cache)
        assert rep.workflow == "estimation"
    assert len(sk_cache) == 1


def test_plan_shape_mismatch_rejected():
    a = formats.random_uniform_csr(60, 100, 100, 5.0)
    b = formats.random_uniform_csr(61, 120, 120, 5.0)
    plan = planner.build_plan(a, a)
    with pytest.raises(ValueError):
        planner.execute_plan(plan, b, b)


def test_default_cache_counter_increments():
    """The acceptance-criteria counter: repeated ocean_spgemm on an
    unchanged pattern hits the process-wide plan cache."""
    planner.DEFAULT_PLAN_CACHE.clear()
    a = formats.random_uniform_csr(62, 130, 130, 7.0)
    workflow.ocean_spgemm(a, a)
    workflow.ocean_spgemm(a, a)
    assert planner.DEFAULT_PLAN_CACHE.hits == 1
    assert planner.DEFAULT_PLAN_CACHE.misses == 1


def test_symbolic_exact_host_matches_jit_path():
    """The host numpy twin the planner speculates with on certain-symbolic
    workflows must agree bit for bit with the jitted symbolic_exact —
    including duplicate-column collisions, empty rows, and rectangular
    shapes (the equality promised by esc.symbolic_exact_host's docstring)."""
    from repro.core import esc
    from repro.core.formats import pow2_at_least
    cases = [
        (formats.random_uniform_csr(80, 90, 90, 6.0),
         formats.random_uniform_csr(81, 90, 110, 7.0)),
        (formats.powerlaw_csr(82, 120, 120, 8.0),
         formats.banded_csr(83, 120, 120, 20)),
        (formats.hypersparse_csr(84, 200, 160),
         formats.random_uniform_csr(85, 160, 60, 3.0)),
    ]
    for a, b in cases:
        host = esc.symbolic_exact_host(
            np.asarray(a.indptr), np.asarray(a.indices),
            np.asarray(b.indptr), np.asarray(b.indices),
            num_rows_a=a.m, n_cols_b=b.n)
        prods = (np.asarray(b.indptr)[1:] - np.asarray(b.indptr)[:-1])[
            np.asarray(a.indices)].sum()
        p_cap = pow2_at_least(max(int(prods), 1), floor=64)
        dev = esc.symbolic_exact(
            jnp.asarray(a.indptr), jnp.asarray(a.indices),
            jnp.asarray(b.indptr), jnp.asarray(b.indices),
            num_rows_a=a.m, p_cap=p_cap)
        np.testing.assert_array_equal(host, np.asarray(dev))
        assert host.dtype == np.int32


def test_certain_symbolic_prediction_uses_host_twin_bit_identically():
    """A forced-symbolic plan built through the speculative host path and
    one built from the device path execute to identical outputs."""
    a = formats.random_uniform_csr(86, 140, 140, 8.0)
    plan = planner.build_plan(a, a, force_workflow="symbolic")
    c1, _ = planner.execute_plan(plan, a, a)
    c2, _ = workflow.ocean_spgemm(a, a, cache=False)
    assert_bit_identical(c1, c2)
