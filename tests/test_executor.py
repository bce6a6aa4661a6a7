"""Unified executor pipeline: pipelined/threaded == serial bit-identity,
shape bucketing of shards (shared jit specializations), overlap metrics,
and the EscOverflowError / PlanCache-locking satellites.

conftest forces a 4-device host platform, so multi-device dispatch and the
completion-order collect run for real (virtual CPU devices — the same code
path as a multi-chip host).
"""
import os
import threading
import time
import types

import jax
import numpy as np
import pytest

try:  # hypothesis is optional: the suite must collect and pass without it
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fixed-seed fallback, same properties
    from _hypothesis_fallback import given, settings, st

from conftest import assert_bit_identical
from repro.core import (dispatch, esc, executor, formats, partition, planner,
                        workflow)
from repro.core.analysis import OceanConfig
from repro.kernels import ops as kops
from repro.kernels import spgemm_dense as kdense
from repro.serving import SpGEMMService

N_DEV = len(jax.devices())

GENS = [
    ("uniform", lambda: formats.random_uniform_csr(41, 220, 220, 10.0)),
    ("banded", lambda: formats.banded_csr(42, 180, 180, 40)),
    ("hypersparse", lambda: formats.hypersparse_csr(43, 700, 700)),
    ("skewed", lambda: formats.skewed_rows_csr(44, 400, 400, 5.0)),
    ("powerlaw", lambda: formats.powerlaw_csr(45, 256, 256, 8.0)),
]


def both_executors(plan, a, b, n_dev):
    """(serial, pipelined, threaded) results for a plan at a device count."""
    if n_dev == 1:
        def run(ex):
            return planner.execute_plan(plan, a, b, executor=ex)
    else:
        splan = partition.partition_plan(plan, n_dev)

        def run(ex):
            return planner.execute_sharded_plan(splan, a, b, executor=ex)
    return run("serial"), run("pipelined"), run("threaded")


# ---------------------------------------------------------------------------
# Acceptance: pipelined output is bit-identical to serial
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,gen", GENS)
@pytest.mark.parametrize("n_dev", [1, 4])
def test_pipelined_equals_serial(name, gen, n_dev):
    a = gen()
    plan = planner.build_plan(a, a)
    (c1, r1), (c2, r2), (c3, r3) = both_executors(plan, a, a, n_dev)
    assert_bit_identical(c1, c2)
    assert_bit_identical(c1, c3)
    assert r1.nnz_out == r2.nnz_out == r3.nnz_out
    assert r1.executor == "serial" and r2.executor == "pipelined"
    assert r3.executor == "threaded"
    assert r1.overlap_seconds == 0.0 and r1.merge_overlap_frac == 0.0
    assert 0.0 <= r3.merge_overlap_frac <= 1.0


@pytest.mark.parametrize("wf", ["estimation", "symbolic", "upper_bound"])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_pipelined_equals_serial_across_workflows(wf, n_dev):
    a = formats.random_uniform_csr(70, 180, 180, 9.0)
    plan = planner.build_plan(a, a, force_workflow=wf)
    assert plan.workflow == wf
    (c1, _), (c2, _), (c3, _) = both_executors(plan, a, a, n_dev)
    assert_bit_identical(c1, c2)
    assert_bit_identical(c1, c3)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_pipelined_equals_serial_under_overflow(n_dev):
    """Deliberately undersized capacities: the overflow fallback must run
    identically through the overlapped merge."""
    a = formats.random_uniform_csr(10, 200, 200, 16.0)
    cfg = OceanConfig(expansion=0.05, expansion_small_regs=0.05,
                      cr_threshold=0.0, er_threshold=0.0,
                      upper_bound_avg_products=0.0)
    plan = planner.build_plan(a, a, cfg, force_workflow="estimation")
    (c1, r1), (c2, r2), (c3, r3) = both_executors(plan, a, a, n_dev)
    assert r1.overflow_rows > 0
    assert r2.overflow_rows == r1.overflow_rows
    assert r3.overflow_rows == r1.overflow_rows
    assert_bit_identical(c1, c2)
    assert_bit_identical(c1, c3)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_pipelined_equals_serial_empty_and_single_bin_plans(n_dev):
    # fully empty plan: no dense bins, no ESC, every row empty
    z = formats.csr_from_dense(np.zeros((6, 6), np.float32))
    plan = planner.build_plan(z, z)
    assert not plan.dense and plan.esc is None
    (c1, r1), (c2, r2), (c3, r3) = both_executors(plan, z, z, n_dev)
    assert r1.nnz_out == r2.nnz_out == r3.nnz_out == 0
    assert_bit_identical(c1, c2)
    assert_bit_identical(c1, c3)
    # ESC-only plan (hypersparse -> upper_bound short rows), no dense bins
    h = formats.hypersparse_csr(46, 300, 300)
    plan_h = planner.build_plan(h, h)
    if not plan_h.dense and plan_h.esc is not None:
        (c1, _), (c2, _), (c3, _) = both_executors(plan_h, h, h, n_dev)
        assert_bit_identical(c1, c2)
        assert_bit_identical(c1, c3)
    # dense-only plan (banded estimation), empty ESC
    d = formats.banded_csr(47, 120, 120, 25)
    plan_d = planner.build_plan(d, d)
    assert plan_d.esc is None and plan_d.dense
    (c1, _), (c2, _), (c3, _) = both_executors(plan_d, d, d, n_dev)
    assert_bit_identical(c1, c2)
    assert_bit_identical(c1, c3)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
def test_property_pipelined_exact_on_random_pairs(seed, n_dev):
    rng = np.random.default_rng(seed)
    m, k, n = (int(rng.integers(2, 60)) for _ in range(3))
    am = ((rng.random((m, k)) < 0.15) *
          rng.integers(-3, 4, (m, k))).astype(np.float32)
    bm = ((rng.random((k, n)) < 0.15) *
          rng.integers(-3, 4, (k, n))).astype(np.float32)
    a, b = formats.csr_from_dense(am), formats.csr_from_dense(bm)
    if a.nnz == 0 or b.nnz == 0:
        return
    plan = planner.build_plan(a, b)
    (c1, _), (c2, _), (c3, _) = both_executors(plan, a, b, n_dev)
    assert_bit_identical(c1, c2)
    assert_bit_identical(c1, c3)
    np.testing.assert_allclose(np.asarray(c2.to_dense()), am @ bm, atol=1e-5)


def test_unknown_executor_rejected():
    a = formats.banded_csr(48, 60, 60, 10)
    plan = planner.build_plan(a, a)
    with pytest.raises(ValueError):
        planner.execute_plan(plan, a, a, executor="warp")


# ---------------------------------------------------------------------------
# Overlap metrics
# ---------------------------------------------------------------------------

def test_overlap_metrics_populated_on_multi_bin_plans():
    a = formats.skewed_rows_csr(44, 400, 400, 5.0)
    plan = planner.build_plan(a, a)
    n_launches = len(plan.dense) + (plan.esc is not None)
    assert n_launches >= 2, "structure must produce a multi-launch plan"
    _, rep = planner.execute_plan(plan, a, a, executor="pipelined")
    assert rep.overlap_seconds > 0.0
    assert 0.0 < rep.merge_overlap_frac <= 1.0
    for k in ("dispatch", "collect", "merge"):
        assert k in rep.stage_seconds
    # sharded pipelined execution reports overlap too
    splan = partition.partition_plan(plan, N_DEV)
    _, rep_s = planner.execute_sharded_plan(splan, a, a,
                                            executor="pipelined")
    assert rep_s.overlap_seconds > 0.0


def test_threaded_equals_serial_under_slow_collect(monkeypatch):
    """Inject a slow collect (each slab materialization sleeps, releasing
    the GIL): the merge worker must overlap real merge work with the
    collect loop — overlap metrics strictly positive — while staying
    bit-identical to the serial reference computed before the patch."""
    a = formats.skewed_rows_csr(44, 400, 400, 5.0)
    plan = planner.build_plan(a, a)
    n_launches = len(plan.dense) + (plan.esc is not None) + len(plan.hash)
    assert n_launches >= 2, "structure must produce a multi-launch plan"
    c_ref, _ = planner.execute_plan(plan, a, a, executor="serial")

    real = executor._materialize

    def slow_materialize(it, copies):
        time.sleep(0.005)  # sleep releases the GIL: worker merges meanwhile
        return real(it, copies)

    monkeypatch.setattr(executor, "_materialize", slow_materialize)
    c_thr, rep = planner.execute_plan(plan, a, a, executor="threaded")
    assert_bit_identical(c_ref, c_thr)
    assert rep.executor == "threaded"
    assert rep.overlap_seconds > 0.0
    assert 0.0 < rep.merge_overlap_frac <= 1.0
    for k in ("dispatch", "collect", "merge"):
        assert k in rep.stage_seconds
    # sharded threaded execution overlaps and stays exact too
    splan = partition.partition_plan(plan, N_DEV)
    c_s, rep_s = planner.execute_sharded_plan(splan, a, a,
                                              executor="threaded")
    assert_bit_identical(c_ref, c_s)
    assert rep_s.overlap_seconds > 0.0


def test_workflow_and_service_thread_executor_choice():
    a = formats.random_uniform_csr(81, 200, 200, 8.0)
    c_ser, r_ser = workflow.ocean_spgemm(a, a, cache=False,
                                         executor="serial")
    c_pip, r_pip = workflow.ocean_spgemm(a, a, cache=False,
                                         executor="pipelined")
    c_thr, r_thr = workflow.ocean_spgemm(a, a, cache=False,
                                         executor="threaded")
    assert r_ser.executor == "serial" and r_pip.executor == "pipelined"
    assert r_thr.executor == "threaded"
    assert_bit_identical(c_ser, c_pip)
    assert_bit_identical(c_ser, c_thr)

    svc = SpGEMMService(executor="serial")
    _, rep1 = svc.multiply(a, a)
    assert rep1.executor == "serial"
    # per-request override of the service default
    c2, rep2 = svc.multiply(a, a, executor="pipelined")
    assert rep2.executor == "pipelined" and rep2.plan_cache_hit
    assert_bit_identical(c_ser, c2)
    assert svc.stats.merge_seconds > 0.0  # pipelined request was accounted
    assert 0.0 <= svc.stats.merge_overlap_frac <= 1.0


def test_many_threads_executor_and_stays_exact():
    b = formats.random_uniform_csr(52, 160, 160, 10.0)
    a_list = [formats.random_uniform_csr(53 + i, 120, 160, 7.0)
              for i in range(2)]
    many = workflow.ocean_spgemm_many(a_list, b, cache=planner.PlanCache(),
                                      executor="serial")
    loop = [workflow.ocean_spgemm(a, b, cache=False, executor="pipelined")
            for a in a_list]
    for (cm, rm), (cl, _) in zip(many, loop):
        assert rm.executor == "serial"
        assert_bit_identical(cm, cl)


# ---------------------------------------------------------------------------
# Acceptance: shape bucketing shares jit specializations across shards
# and across topologies
# ---------------------------------------------------------------------------

def _active_dense_jit():
    use_pallas = (not kops.use_interpret()
                  or os.environ.get("REPRO_CPU_NUMERIC") == "pallas")
    return kdense.spgemm_dense_bin if use_pallas else kops._dense_bin_xla


def test_bucket_shard_rows_ladder():
    assert partition.bucket_shard_rows(1, 1000) == partition.SHARD_ROW_FLOOR
    assert partition.bucket_shard_rows(33, 1000) == 64
    # clamp: a shard never pads past its whole bin, which is what lets
    # 2- and 4-device splits of a small bin land on one shape
    assert partition.bucket_shard_rows(20, 40) == 32
    assert partition.bucket_shard_rows(33, 40) == 40


def test_shard_shapes_bucketed_and_inert():
    a = formats.banded_csr(9, 60, 60, 18)
    plan = planner.build_plan(a, a)
    assert plan.dense, "structure must produce dense bins"
    for n_dev in (2, 4):
        splan = partition.partition_plan(plan, n_dev)
        for sh in splan.shards:
            for be in sh.dense:
                parent = plan.dense[be.bin_id]
                want = partition.bucket_shard_rows(be.n_valid,
                                                   len(parent.rows))
                assert be.a_rows.shape[0] == want
                assert len(be.rows) == be.n_valid  # host metadata unpadded
                # per-rung capacity: a pure function of (bin, rung),
                # never of the particular shard or topology
                assert be.p_cap == partition.rung_capacity_cap(
                    parent.cost, want, parent.p_cap)
                assert be.p_cap <= parent.p_cap
                # pad rows are inert: no A entries, zero-length B rows
                lens = np.asarray(be.a_lens)[be.n_valid:]
                assert (lens == 0).all()


def test_dense_rung_p_cap_shrinks_large_bin_shards():
    """Satellite: XLA-path shards of a large bin size their static product
    slots by the per-rung ladder instead of inheriting the whole bin's
    p_cap — and stay bit-identical."""
    a = formats.banded_csr(7, 1200, 1200, 60)
    plan = planner.build_plan(a, a)
    big = max(plan.dense, key=lambda be: len(be.rows))
    assert len(big.rows) > 4 * partition.SHARD_ROW_FLOOR
    splan = partition.partition_plan(plan, 4)
    shard_pcaps = [be.p_cap for sh in splan.shards for be in sh.dense
                   if be.bin_id == big.bin_id]
    assert shard_pcaps and all(p <= big.p_cap for p in shard_pcaps)
    assert any(p < big.p_cap for p in shard_pcaps)
    c1, _ = planner.execute_plan(plan, a, a)
    c2, _ = planner.execute_sharded_plan(splan, a, a)
    assert_bit_identical(c1, c2)


def test_esc_shard_shapes_bucketed_and_inert():
    """Satellite: ESC shard sub-CSRs are shape-bucketed like dense bins —
    rows up the bucket_shard_rows ladder (inert empty tail rows), nnz and
    product capacities up per-rung pow2 ladders clamped to the bin's."""
    h = formats.hypersparse_csr(43, 700, 700)
    plan = planner.build_plan(h, h)
    assert plan.esc is not None, "structure must produce an ESC bin"
    assert plan.esc.n_valid == len(plan.esc.rows)
    for n_dev in (2, 4):
        splan = partition.partition_plan(plan, n_dev)
        for sh in splan.shards:
            ex = sh.esc
            if ex is None:
                continue
            r_pad = partition.bucket_shard_rows(ex.n_valid,
                                                len(plan.esc.rows))
            assert ex.sub_indptr.shape[0] == r_pad + 1
            assert len(ex.rows) == ex.n_valid  # host metadata unpadded
            # pad rows are inert: the padded indptr tail repeats, so they
            # hold zero nnz and enumerate zero products
            tail = np.asarray(ex.sub_indptr)[ex.n_valid:]
            assert (tail == ex.sub_indptr[ex.n_valid]).all()
            assert ex.p_cap == ex.out_cap <= plan.esc.p_cap
            assert ex.sub_indices.shape == ex.src.shape
            assert ex.sub_indices.shape[0] >= int(ex.sub_indptr[-1])


def test_esc_shards_share_jit_specializations_across_topologies():
    """ESC shards of one bin hit the same esc_spgemm specialization across
    devices and topologies (small bins clamp to one shape, like dense)."""
    fn = esc.esc_spgemm
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jit cache-size probe unavailable on this jax")
    # small ESC bin (<= SHARD_ROW_FLOOR rows): the ladder clamp lands every
    # topology's shards on one shape, mirroring the dense 60-row probe
    h = formats.hypersparse_csr(61, 50, 50)
    plan = planner.build_plan(h, h)
    assert plan.esc is not None
    assert len(plan.esc.rows) <= partition.SHARD_ROW_FLOOR
    splan2 = partition.partition_plan(plan, 2)
    splan4 = partition.partition_plan(plan, 4)
    # one bucketed shape per bin, whatever the topology
    shapes = {(ex.sub_indptr.shape, ex.sub_indices.shape, ex.p_cap)
              for sp in (splan2, splan4)
              for sh in sp.shards if (ex := sh.esc) is not None}
    assert len(shapes) == 1, shapes
    size0 = fn._cache_size()
    planner.execute_sharded_plan(splan2, h, h)
    size2 = fn._cache_size()
    planner.execute_sharded_plan(splan4, h, h)
    size4 = fn._cache_size()
    # compilations bounded per (bin, rung, device), never per shard
    assert size2 - size0 <= 2
    assert size4 - size2 <= 2
    planner.execute_sharded_plan(partition.partition_plan(plan, 4), h, h)
    assert fn._cache_size() == size4
    c1, _ = planner.execute_plan(plan, h, h)
    c2, _ = planner.execute_sharded_plan(splan4, h, h)
    assert_bit_identical(c1, c2)


def test_shards_share_jit_specializations_across_topologies():
    """Acceptance criterion: two shards of one bin on different devices,
    and the same structure partitioned for 2- vs 4-device topologies, hit
    the same jit specialization (counted via the jit cache-size probe).

    The 60-row bin sits below bucketing's clamp, so every topology pads
    its shards to one shape; larger bins share per ladder rung instead
    (see partition.bucket_shard_rows).
    """
    fn = _active_dense_jit()
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jit cache-size probe unavailable on this jax")
    a = formats.banded_csr(9, 60, 60, 18)  # one dense bin of 60 rows
    plan = planner.build_plan(a, a)
    assert plan.dense
    splan2 = partition.partition_plan(plan, 2)
    splan4 = partition.partition_plan(plan, 4)
    # every shard of a bin carries one bucketed shape, whatever the topology
    shapes = {(be.bin_id, tuple(be.a_rows.shape), be.p_cap)
              for sp in (splan2, splan4)
              for sh in sp.shards for be in sh.dense}
    assert len(shapes) == len(plan.dense)

    size0 = fn._cache_size()
    planner.execute_sharded_plan(splan2, a, a)
    size2 = fn._cache_size()
    planner.execute_sharded_plan(splan4, a, a)
    size4 = fn._cache_size()
    # 2-device run: at most one specialization per (bin, device) — never
    # per shard shape; 4-device run adds entries only for the two *new*
    # devices (the cpu:0/cpu:1 shards replay the existing specializations)
    assert size2 - size0 <= 2 * len(plan.dense)
    assert size4 - size2 <= 2 * len(plan.dense)
    # same topology re-partitioned: zero new compilations
    planner.execute_sharded_plan(partition.partition_plan(plan, 4), a, a)
    assert fn._cache_size() == size4
    # and the merged outputs stay bit-identical to the unsharded plan
    c1, _ = planner.execute_plan(plan, a, a)
    c2, _ = planner.execute_sharded_plan(splan4, a, a)
    assert_bit_identical(c1, c2)


# ---------------------------------------------------------------------------
# Satellites: EscOverflowError + locked PlanCache reads
# ---------------------------------------------------------------------------

def test_esc_overflow_error_unified():
    assert issubclass(esc.EscOverflowError, ValueError)
    a = formats.random_uniform_csr(90, 64, 64, 8.0)
    res = workflow.spgemm_reference(a, a)
    true_nnz = res.nnz
    assert true_nnz > 4
    # esc_to_csr path
    from repro.core.formats import pow2_at_least
    p_cap = pow2_at_least(int(np.asarray(a.row_nnz()).sum()) ** 2 + 1,
                          floor=64)
    r = esc.esc_spgemm(a.indptr, a.indices, a.values, a.indptr, a.indices,
                       a.values, p_cap=p_cap, out_cap=4, num_rows_a=a.m)
    with pytest.raises(esc.EscOverflowError):
        esc.esc_to_csr(r, (a.m, a.n), 4)
    # executor slab path raises the same type
    fake = types.SimpleNamespace(nnz=np.int32(10), indptr=None,
                                 indices=None, values=None)
    with pytest.raises(esc.EscOverflowError):
        executor._esc_to_slab(fake, np.arange(3), 3, out_cap=4,
                              copies=dispatch.new_copy_bytes())


def test_plan_cache_thread_safety_smoke():
    """Hammer lookup/insert/stats/len concurrently: all reads go through
    the lock now, so no torn stats or runtime errors."""
    cache = planner.PlanCache(maxsize=8)
    errors = []

    def worker(tid):
        try:
            for i in range(300):
                key = f"k{tid}-{i % 12}"
                cache.insert(key, i)
                cache.lookup(key)
                cache.lookup(f"missing-{i}")
                s = cache.stats()
                assert set(s) == {"hits", "misses", "size"}
                assert 0 <= s["size"] <= 8
                assert 0 <= len(cache) <= 8
        except Exception as e:  # pragma: no cover - failure diagnostics
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(cache) <= 8
    s = cache.stats()
    assert s["hits"] + s["misses"] == cache.hits + cache.misses


def test_ensure_esc_capacity_helper():
    """Both overflow raise sites funnel through one helper with one
    message format."""
    assert esc.ensure_esc_capacity(4, 4) == 4
    assert esc.ensure_esc_capacity(0, 4) == 0
    with pytest.raises(esc.EscOverflowError,
                       match=r"widget overflow: nnz 5 > capacity 4"):
        esc.ensure_esc_capacity(5, 4, where="widget")


# ---------------------------------------------------------------------------
# Satellite: stale feed-forward sizes (workflow 'known')
# ---------------------------------------------------------------------------

def _assert_matches_reference(c, ref):
    for x, y in zip(c.to_scipy_like(), ref.to_scipy_like()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_stale_zero_feed_clamped_not_dropped():
    """A stale/elided feed reporting 0 for provably non-empty rows must
    not bin those rows as empty: the planner clamps live rows to >= 1 and
    the overflow fallback corrects, bit-identically in every mode."""
    a = formats.random_uniform_csr(60, 200, 200, 8.0)
    ref = workflow.spgemm_reference(a, a)
    feed = np.zeros(a.m, np.int64)  # maximally stale: all zeros
    plan = planner.build_plan(a, a, known_sizes=feed)
    assert plan.workflow == "known" and plan.feed_forward
    # only truly product-free rows were binned empty
    live = np.asarray(plan.products) > 0
    assert len(plan.empty_rows) == int((~live).sum())
    for n_dev in (1, 4):
        (c1, _), (c2, _), (c3, _) = both_executors(plan, a, a, n_dev)
        assert_bit_identical(c1, c2)
        assert_bit_identical(c1, c3)
        _assert_matches_reference(c1, ref)


def test_size_feed_stale_after_rhs_mutation_stays_exact():
    """Sizes measured against one RHS, then the RHS mutates: a SizeFeed
    entry injected for the new pattern pair (simulating out-of-band
    staleness) still yields the exact product — understatement is absorbed
    by the overflow fallback, zeros by the planner's clamp."""
    from repro.graph import chain
    a = formats.random_uniform_csr(61, 160, 160, 6.0)
    b1 = formats.random_uniform_csr(62, 160, 160, 6.0)
    b2 = formats.random_uniform_csr(63, 160, 160, 14.0)  # mutated RHS
    c1, _ = workflow.ocean_spgemm(a, b1, cache=False)
    stale = np.diff(np.asarray(c1.indptr)).astype(np.int64)
    # the direct known_sizes= path
    ref2 = workflow.spgemm_reference(a, b2)
    c2, rep = workflow.ocean_spgemm(a, b2, cache=False, known_sizes=stale)
    assert rep.workflow == "known"
    _assert_matches_reference(c2, ref2)
    # the SizeFeed machinery path (chain runner consults the feed)
    from repro.core.analysis import OceanConfig
    feed = chain.SizeFeed()
    key2 = planner.structure_key(a, b2, OceanConfig(), None, True, True)
    feed.record(key2, stale)
    runner = chain.ChainRunner(b2, size_feed=feed)
    c3, rep3 = runner.step(a)
    assert rep3.feed_forward, "runner must have consulted the stale feed"
    _assert_matches_reference(c3, ref2)
