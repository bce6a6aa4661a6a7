"""Hash-accumulator rung: binning selection, kernel/XLA parity (incl. the
multi-row tile's boundary cases), executor bit-identity across serial /
pipelined / threaded / sharded execution (incl. the overflow -> spill ->
exact-ESC fallback), fused merge post-ops, jit-cache sharing across
topologies, and the measured autotuner's cache discipline.

conftest forces a 4-device host platform, so sharded hash dispatch runs
for real (virtual CPU devices).
"""
import numpy as np
import pytest

from conftest import assert_bit_identical
from repro.core import binning, executor, formats, partition, planner, \
    tuning, workflow
from repro.kernels import ops as kops
from repro.kernels import spgemm_hash as khash


def assert_matches_reference(c, ref):
    """Exact equality against the oracle, trimmed to nnz (capacities of a
    plan's output and the reference differ; the valid prefix must not)."""
    for x, y in zip(c.to_scipy_like(), ref.to_scipy_like()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def powerlaw_pair():
    """Heavy column reuse: products >> distinct output nnz, so mid-density
    rows land on the hash rung (width >= HASH_ADVANTAGE * table)."""
    a = formats.powerlaw_csr(3, 512, 512, 12.0)
    return a, a


def run_all_modes(plan, a, b):
    """(serial, pipelined, threaded, sharded-2, sharded-4) results for one
    plan — the full executor-mode property matrix."""
    outs = [planner.execute_plan(plan, a, b, executor="serial"),
            planner.execute_plan(plan, a, b, executor="pipelined"),
            planner.execute_plan(plan, a, b, executor="threaded")]
    for n_dev, mode in ((2, "pipelined"), (4, "threaded")):
        splan = partition.partition_plan(plan, n_dev)
        outs.append(planner.execute_sharded_plan(splan, a, b,
                                                 executor=mode))
    return outs


# ---------------------------------------------------------------------------
# Binning selection
# ---------------------------------------------------------------------------

def test_hash_rung_selected_for_scattered_rows():
    a, b = powerlaw_pair()
    plan = planner.build_plan(a, b)
    assert plan.hash, "powerlaw structure must engage the hash rung"
    hash_rows = {k: v for k, v in plan.bins_describe.items()
                 if k.startswith("hash_t")}
    assert sum(hash_rows.values()) > 0
    for hb in plan.hash:
        assert hb.table & (hb.table - 1) == 0  # pow2 primary table
        assert binning.HASH_MIN_TABLE <= hb.table <= binning.HASH_MAX_TABLE
        # spill is a pure function of the table size (shard invariance)
        assert hb.spill == binning.hash_spill_of(hb.table)


def test_hash_rung_disabled_paths():
    a, b = powerlaw_pair()
    # V1/V2 ablation: hybrid=False disables the hash rung alongside ESC
    plan = planner.build_plan(a, b, hybrid=False)
    assert not plan.hash
    # config knob: hash_rung=False keeps hybrid dense/ESC but no hash bins
    from repro.core.analysis import OceanConfig
    plan2 = planner.build_plan(a, b, OceanConfig(hash_rung=False))
    assert not plan2.hash and plan2.dense
    c_ref = workflow.spgemm_reference(a, b)
    for p in (plan, plan2):
        c, _ = planner.execute_plan(p, a, b)
        assert_matches_reference(c, c_ref)


def test_plan_bins_hash_mask_properties():
    """plan_bins routes a row to hash iff its table fits VMEM and its
    window is >= HASH_ADVANTAGE x the table; hash rows leave dense bins."""
    m = 6
    pred = np.array([4, 4, 4, 4, 4000, 0], np.float64)
    products = np.array([100, 100, 100, 100, 8000, 0], np.int64)
    lo = np.zeros(m, np.int64)
    hi = np.array([255, 15, 255, 7, 4095, 0], np.int64)
    a_nnz = np.full(m, 4, np.int64)
    bp = binning.plan_bins(pred, products, lo, hi, a_nnz, 4096,
                           expansion=1.0, workflow="symbolic")
    hash_rows = np.concatenate([hb.rows for hb in bp.hash_bins]) \
        if bp.hash_bins else np.zeros(0, np.int64)
    dense_rows = np.concatenate([db.rows for db in bp.dense_bins]) \
        if bp.dense_bins else np.zeros(0, np.int64)
    # rows 0, 2: width 256 >= 4 * table(8->32) -> hash
    assert {0, 2} <= set(hash_rows.tolist())
    # rows 1, 3: narrow windows, dense wins
    assert {1, 3} <= set(dense_rows.tolist())
    # row 4: table would exceed HASH_MAX_TABLE -> dense/longrow ladder
    assert 4 in set(dense_rows.tolist())
    assert not (set(hash_rows.tolist()) & set(dense_rows.tolist()))
    # disabled: every hash row falls back to the dense ladder
    bp_off = binning.plan_bins(pred, products, lo, hi, a_nnz, 4096,
                               expansion=1.0, workflow="symbolic",
                               hash_enabled=False)
    assert not bp_off.hash_bins
    all_dense = np.concatenate([db.rows for db in bp_off.dense_bins])
    assert set(hash_rows.tolist()) <= set(all_dense.tolist())


# ---------------------------------------------------------------------------
# Kernel vs XLA fallback parity
# ---------------------------------------------------------------------------

def test_hash_kernel_matches_xla_bit_identical():
    """The Pallas probe-insert kernel (interpret mode) and the XLA sorted
    segment-sum fallback accumulate in the same product-enumeration order,
    so integer-valued floats match bit for bit."""
    rng = np.random.default_rng(11)
    r, nb, n_cols = 8, 6, 512
    blen = 24
    # distinct columns bounded by 80 < table + spill = 96: no overflow
    b_cols = rng.integers(0, 80, nb * blen).astype(np.int32)
    b_vals = rng.integers(1, 5, nb * blen).astype(np.float32)
    pad = formats.pow2_at_least(nb * blen, floor=128)
    b_cols = np.concatenate([b_cols,
                             np.full(pad - nb * blen, -1, np.int32)])
    b_vals = np.concatenate([b_vals,
                             np.zeros(pad - nb * blen, np.float32)])
    a_rows = np.tile(np.arange(nb, dtype=np.int32), (r, 1))
    a_vals = rng.integers(1, 4, (r, nb)).astype(np.float32)
    a_starts = np.tile(np.arange(nb, dtype=np.int32) * blen, (r, 1))
    a_lens = np.full((r, nb), blen, np.int32)
    table, spill = 64, binning.hash_spill_of(64)
    p_cap = formats.pow2_at_least(r * nb * blen, floor=64)

    keys, vals, skeys, svals, fail = khash.spgemm_hash_bin(
        a_rows, a_vals, a_starts, a_lens, b_cols, b_vals,
        table=table, spill=spill, f_chunk=128, interpret=True)
    k_cols, k_vals, k_nnz = (np.asarray(x) for x in
                             kops.extract_hash_rows(keys, vals, skeys,
                                                    svals, fail))
    x_cols, x_vals, x_nnz = (np.asarray(x) for x in kops._hash_bin_xla(
        a_rows, a_vals, a_starts, a_lens, b_cols, b_vals,
        table=table, spill=spill, p_cap=p_cap))
    assert (k_nnz == x_nnz).all()
    for i in range(r):
        n = int(k_nnz[i])
        assert n <= table + spill  # no overflow in this workload
        assert (k_cols[i, :n] == x_cols[i, :n]).all()
        assert (k_vals[i, :n] == x_vals[i, :n]).all()
    # ground truth: dense accumulation
    dense = np.zeros((r, n_cols), np.float64)
    for i in range(r):
        for jj in range(nb):
            s = a_starts[i, jj]
            for e in range(a_lens[i, jj]):
                dense[i, b_cols[s + e]] += float(a_vals[i, jj]) * \
                    float(b_vals[s + e])
    for i in range(r):
        n = int(x_nnz[i])
        got = dict(zip(x_cols[i, :n].tolist(), x_vals[i, :n].tolist()))
        want = {c: v for c, v in enumerate(dense[i]) if v != 0}
        assert got == want


def test_hash_kernel_overflow_flag_exact():
    """fail > 0 exactly when a row's distinct count exceeds table+spill —
    the invariant the merge's overflow scan relies on, on both backends."""
    n_cols = 4096
    table, spill = 32, binning.hash_spill_of(32)
    width = table + spill
    rng = np.random.default_rng(5)
    # row 0: width distinct columns (fits exactly); row 1: width + 1
    cases = [width, width + 1]
    r, blen = len(cases), max(cases)
    b_cols = np.full(r * blen, -1, np.int32)
    for i, d in enumerate(cases):
        b_cols[i * blen: i * blen + d] = rng.choice(n_cols, d, replace=False)
    b_vals = np.ones(r * blen, np.float32)
    pad = formats.pow2_at_least(r * blen, floor=128)
    b_cols = np.concatenate([b_cols, np.full(pad - r * blen, -1, np.int32)])
    b_vals = np.concatenate([b_vals, np.zeros(pad - r * blen, np.float32)])
    a_rows = np.zeros((r, 1), np.int32)
    a_vals = np.ones((r, 1), np.float32)
    a_starts = (np.arange(r, dtype=np.int32) * blen).reshape(r, 1)
    a_lens = np.array(cases, np.int32).reshape(r, 1)

    keys, vals, skeys, svals, fail = khash.spgemm_hash_bin(
        a_rows, a_vals, a_starts, a_lens, b_cols, b_vals,
        table=table, spill=spill, f_chunk=128, interpret=True)
    fail = np.asarray(fail)[:, 0]
    assert fail[0] == 0 and fail[1] > 0
    _, _, k_nnz = (np.asarray(x) for x in
                   kops.extract_hash_rows(keys, vals, skeys, svals,
                                          np.asarray(fail).reshape(-1, 1)))
    p_cap = formats.pow2_at_least(sum(cases), floor=64)
    _, _, x_nnz = (np.asarray(x) for x in kops._hash_bin_xla(
        a_rows, a_vals, a_starts, a_lens, b_cols, b_vals,
        table=table, spill=spill, p_cap=p_cap))
    # non-overflow rows agree exactly; overflow rows cross the width
    # threshold on both backends (counts there are diagnostic only — the
    # merge discards the slab row and reroutes to the exact ESC fallback)
    assert k_nnz[0] == x_nnz[0] == width
    assert k_nnz[1] > width and x_nnz[1] > width


def _tile_workload(r, seed=11):
    """Non-overflow r-row hash workload (distinct cols < table + spill)."""
    rng = np.random.default_rng(seed)
    nb, blen = 6, 24
    b_cols = rng.integers(0, 80, nb * blen).astype(np.int32)
    b_vals = rng.integers(1, 5, nb * blen).astype(np.float32)
    pad = formats.pow2_at_least(nb * blen, floor=128)
    b_cols = np.concatenate([b_cols,
                             np.full(pad - nb * blen, -1, np.int32)])
    b_vals = np.concatenate([b_vals,
                             np.zeros(pad - nb * blen, np.float32)])
    a_rows = np.tile(np.arange(nb, dtype=np.int32), (r, 1))
    a_vals = rng.integers(1, 4, (r, nb)).astype(np.float32)
    a_starts = np.tile(np.arange(nb, dtype=np.int32) * blen, (r, 1))
    a_lens = np.full((r, nb), blen, np.int32)
    return a_rows, a_vals, a_starts, a_lens, b_cols, b_vals


@pytest.mark.parametrize("r", [1, 5, 8, 11])
def test_hash_kernel_tile_boundaries(r):
    """The multi-row tiled kernel is bit-identical across tile sizes,
    including row counts that are not a multiple of the tile (the kernel's
    internal pad path) and the T=1 row-sequential degeneracy."""
    work = _tile_workload(r)
    table, spill = 64, binning.hash_spill_of(64)
    outs = {}
    for tile in (1, 4, 8):
        keys, vals, skeys, svals, fail = khash.spgemm_hash_bin(
            *work, table=table, spill=spill, f_chunk=128, tile=tile,
            interpret=True)
        outs[tile] = tuple(np.asarray(x) for x in kops.extract_hash_rows(
            keys, vals, skeys, svals, fail))
        assert outs[tile][0].shape[0] == r  # pad rows sliced off
    for tile in (4, 8):
        for x, y in zip(outs[1], outs[tile]):
            np.testing.assert_array_equal(x, y)
    # the T=1 degeneracy matches the XLA twin exactly (per-row tables
    # depend only on the row's own products, so this covers every tile)
    a_lens = work[3]
    p_cap = formats.pow2_at_least(int(a_lens.sum()), floor=64)
    x_out = tuple(np.asarray(x) for x in kops._hash_bin_xla(
        *work, table=table, spill=spill, p_cap=p_cap))
    nnz = outs[1][2]
    assert (nnz == x_out[2]).all()
    for i in range(r):
        n = int(nnz[i])
        np.testing.assert_array_equal(outs[1][0][i, :n], x_out[0][i, :n])
        np.testing.assert_array_equal(outs[1][1][i, :n], x_out[1][i, :n])


@pytest.mark.parametrize("f_chunk", [128, 64])
def test_hash_kernel_b_window_stays_in_bounds(f_chunk):
    """One tile holds the last B row and a B row 300 entries longer: the
    tile streams chunks for the long row while the short one has ended, so
    the short row's window would start past B's end. Run in the TPU
    interpreter, which raises on any out-of-bounds DMA read."""
    from jax.experimental.pallas import tpu as pltpu
    long_len, short_len, tile = 300, 10, 8
    b_cols = (np.arange(long_len + short_len) % 97).astype(np.int32)
    b_vals = np.arange(1, long_len + short_len + 1).astype(np.float32)
    a_rows = np.full((tile, 1), -1, np.int32)
    a_rows[:2, 0] = (0, 1)
    a_starts = np.zeros((tile, 1), np.int32)
    a_starts[1, 0] = long_len
    a_lens = np.zeros((tile, 1), np.int32)
    a_lens[:2, 0] = (long_len, short_len)
    a_vals = np.ones((tile, 1), np.float32)
    work = (a_rows, a_vals, a_starts, a_lens, b_cols, b_vals)
    table, spill = 128, binning.hash_spill_of(128)
    out = khash.spgemm_hash_bin(*work, table=table, spill=spill,
                                f_chunk=f_chunk, tile=tile,
                                interpret=pltpu.InterpretParams())
    k_cols, k_vals, k_nnz = (np.asarray(x) for x in
                             kops.extract_hash_rows(*out))
    x_cols, x_vals, x_nnz = (np.asarray(x) for x in kops._hash_bin_xla(
        *work, table=table, spill=spill, p_cap=512))
    assert k_nnz.tolist() == x_nnz.tolist() == [97, 10] + [0] * 6
    for i in range(2):
        n = int(k_nnz[i])
        np.testing.assert_array_equal(k_cols[i, :n], x_cols[i, :n])
        np.testing.assert_array_equal(k_vals[i, :n], x_vals[i, :n])


def test_hash_kernel_ell_tiled_bit_identical():
    """A bin wider than ELL_TILE slots runs over several ELL grid steps;
    each row still takes its slots in order, so the output matches the XLA
    twin bit for bit."""
    from repro.kernels.spgemm_dense import ELL_TILE
    rng = np.random.default_rng(3)
    r, e, nb, blen = 8, ELL_TILE + 88, 40, 2
    b_cols = rng.integers(0, 80, nb * blen).astype(np.int32)
    b_vals = rng.integers(1, 5, nb * blen).astype(np.float32)
    a_rows = rng.integers(0, nb, (r, e)).astype(np.int32)
    a_rows[3, 100:] = -1  # a short row among long ones
    a_vals = rng.integers(1, 4, (r, e)).astype(np.float32)
    a_starts = a_rows * blen
    a_lens = np.where(a_rows >= 0, blen, 0).astype(np.int32)
    work = (a_rows, a_vals, a_starts, a_lens, b_cols, b_vals)
    table, spill = 64, binning.hash_spill_of(64)
    out = khash.spgemm_hash_bin(*work, table=table, spill=spill,
                                f_chunk=128, interpret=True)
    k_out = tuple(np.asarray(x) for x in kops.extract_hash_rows(*out))
    x_out = tuple(np.asarray(x) for x in kops._hash_bin_xla(
        *work, table=table, spill=spill,
        p_cap=formats.pow2_at_least(r * e * blen, floor=64)))
    np.testing.assert_array_equal(k_out[2], x_out[2])
    for i in range(r):
        n = int(k_out[2][i])
        assert 0 < n <= table + spill
        np.testing.assert_array_equal(k_out[0][i, :n], x_out[0][i, :n])
        np.testing.assert_array_equal(k_out[1][i, :n], x_out[1][i, :n])


@pytest.mark.parametrize("tile", [1, 4, 12])
def test_hash_kernel_compiled_tile_must_fill_sublanes(tile):
    """A compiled kernel takes only multiples of the 8-row sublane tile;
    other tiles fail up front instead of deep in the TPU lowering."""
    work = _tile_workload(8)
    with pytest.raises(ValueError, match="multiple of 8"):
        khash.spgemm_hash_bin(*work, table=64, spill=32, tile=tile)


def test_hash_bin_op_tile_invariant_through_backend():
    """kops.hash_bin_op output is invariant to the tile knob on whichever
    backend path is active (Pallas tiles the grid, XLA ignores it)."""
    work = _tile_workload(5, seed=12)
    table, spill = 64, binning.hash_spill_of(64)
    p_cap = formats.pow2_at_least(int(work[3].sum()), floor=64)
    outs = [tuple(np.asarray(x) for x in kops.hash_bin_op(
        *work, table=table, spill=spill, p_cap=p_cap,
        tile=tile)) for tile in (1, 8)]
    for x, y in zip(*outs):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Executor bit-identity matrix
# ---------------------------------------------------------------------------

def test_hash_bit_identity_matrix():
    a, b = powerlaw_pair()
    plan = planner.build_plan(a, b)
    assert plan.hash
    ref = workflow.spgemm_reference(a, b)
    outs = run_all_modes(plan, a, b)
    for c, rep in outs:
        assert_matches_reference(c, ref)
        assert sum(v for k, v in rep.bins.items()
                   if k.startswith("hash_t")) > 0
    # cross-mode outputs of one plan share capacities: bit-identical
    for c, _ in outs[1:]:
        assert_bit_identical(outs[0][0], c)


def test_hash_overflow_spill_fallback_bit_identical():
    """An understated feed (known_sizes=1 for every row) forces every row
    into the smallest hash tables; rows whose true nnz exceeds table+spill
    take the exact-ESC fallback — identically in every execution mode."""
    a, b = powerlaw_pair()
    feed = np.ones(a.m, np.int64)
    plan = planner.build_plan(a, b, known_sizes=feed)
    assert plan.workflow == "known" and plan.feed_forward
    assert plan.hash
    ref = workflow.spgemm_reference(a, b)
    reps = []
    for c, rep in run_all_modes(plan, a, b):
        assert_matches_reference(c, ref)
        reps.append(rep)
    assert reps[0].overflow_rows > 0, "understated tables must overflow"
    assert len({r.overflow_rows for r in reps}) == 1
    # feed-forward sizes (tracked when post-ops run) are exact despite the
    # overflow: hash rows' approximate overflow counts are overwritten by
    # the fallback slab's exact values before finalize
    post = executor.MergePostOps(n_cols=b.n)
    _, rep_post = planner.execute_plan(plan, a, b, post=post)
    raw = rep_post.raw_row_nnz
    true_sizes = np.diff(np.asarray(ref.indptr))
    assert raw is not None and (np.asarray(raw) == true_sizes).all()


def test_hash_empty_bins_and_post_ops():
    """Hash rows interoperate with fused MergePostOps (mask + transform +
    threshold) and with plans whose other families are empty."""
    a, b = powerlaw_pair()
    plan = planner.build_plan(a, b)
    assert plan.hash
    ref = workflow.spgemm_reference(a, b)
    # mask = the reference pattern of every other row; boolean transform
    ptr = np.asarray(ref.indptr).copy()
    keep = np.arange(a.m) % 2 == 0
    mask_ptr = np.zeros(a.m + 1, np.int64)
    mask_ptr[1:] = np.cumsum(np.where(keep, np.diff(ptr), 0))
    idx = np.asarray(ref.indices)
    mask_idx = np.concatenate([idx[ptr[i]:ptr[i + 1]]
                               for i in range(a.m) if keep[i]]
                              or [np.zeros(0, np.int32)])
    post = executor.MergePostOps(n_cols=b.n, mask_indptr=mask_ptr,
                                 mask_indices=mask_idx,
                                 transform=np.sign, threshold=0.5)
    c1, _ = planner.execute_plan(plan, a, b, executor="serial", post=post)
    c2, _ = planner.execute_plan(plan, a, b, executor="pipelined", post=post)
    assert_bit_identical(c1, c2)
    splan = partition.partition_plan(plan, 4)
    c3, _ = planner.execute_sharded_plan(splan, a, b, post=post)
    assert_bit_identical(c1, c3)
    # masked rows: only even rows survive, values are signs
    out_rows = np.diff(np.asarray(c1.indptr))
    assert (out_rows[~keep] == 0).all()
    vals = np.asarray(c1.values)[: c1.nnz]
    assert set(np.unique(vals)).issubset({-1.0, 1.0})


def test_hash_shard_shapes_and_jit_cache_across_topologies():
    """Hash shard slices keep bin-pure kernel shapes (table/spill/f_chunk
    from the bin, rows up the bucket ladder) and different topologies
    replay the same jit specializations."""
    a, b = powerlaw_pair()
    plan = planner.build_plan(a, b)
    assert plan.hash
    splan2 = partition.partition_plan(plan, 2)
    splan4 = partition.partition_plan(plan, 4)
    for sp in (splan2, splan4):
        for sh in sp.shards:
            for hb in sh.hash:
                parent = plan.hash[hb.bin_id - len(plan.dense)]
                assert (hb.table, hb.spill, hb.f_chunk, hb.tile) == \
                    (parent.table, parent.spill, parent.f_chunk,
                     parent.tile)
                want = partition.bucket_shard_rows(hb.n_valid,
                                                   len(parent.rows))
                assert hb.a_rows.shape[0] == want
                assert hb.p_cap == partition.rung_capacity_cap(
                    parent.cost, want, parent.p_cap)
                lens = np.asarray(hb.a_lens)[hb.n_valid:]
                assert (lens == 0).all()  # pad rows inert
    fn = (khash.spgemm_hash_bin if kops._use_pallas_path()
          else kops._hash_bin_xla)
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jit cache-size probe unavailable on this jax")
    n_bins = len(plan.hash)
    size0 = fn._cache_size()
    planner.execute_sharded_plan(splan2, a, b)
    size2 = fn._cache_size()
    planner.execute_sharded_plan(splan4, a, b)
    size4 = fn._cache_size()
    # bounded per (bin, rung, device), never per shard
    assert size2 - size0 <= 2 * n_bins
    assert size4 - size2 <= 2 * n_bins
    planner.execute_sharded_plan(partition.partition_plan(plan, 4), a, b)
    assert fn._cache_size() == size4


# ---------------------------------------------------------------------------
# Measured autotuner
# ---------------------------------------------------------------------------

def test_tuning_cache_measures_once_and_lru():
    cache = tuning.TuningCache(maxsize=2)
    t1 = tuning.hash_tuning_for(64, cache=cache)
    assert t1.load_factor in tuning.LOAD_FACTOR_CANDIDATES
    pallas = kops._use_pallas_path()
    f_cands = (tuning.F_CHUNK_CANDIDATES_PALLAS if pallas
               else tuning.F_CHUNK_CANDIDATES)
    assert t1.f_chunk in f_cands
    t_cands = (tuning.TILE_CANDIDATES_PALLAS if pallas
               else tuning.TILE_CANDIDATES)
    assert t1.tile_rows in t_cands
    misses0 = cache.stats()["misses"]
    t2 = tuning.hash_tuning_for(64, cache=cache)
    assert t2 == t1  # cached, not re-measured
    assert cache.stats()["misses"] == misses0
    assert cache.stats()["hits"] >= 1
    # LRU bound holds
    tuning.hash_tuning_for(128, cache=cache)
    tuning.hash_tuning_for(256, cache=cache)
    assert len(cache) <= 2


def test_tuning_failure_falls_back_to_default(monkeypatch):
    """A failing measurement no longer falls back to DEFAULT_TUNING: it
    raises, caches nothing, and the next plan measures again."""
    cache = tuning.TuningCache()
    calls = []

    def boom(rung):
        calls.append(rung)
        raise RuntimeError("no backend")

    monkeypatch.setattr(tuning, "_measure", boom)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="no backend"):
            tuning.hash_tuning_for(512, cache=cache)
    assert calls == [512, 512]
    assert len(cache) == 0 and cache.stats()["hits"] == 0


def test_tuning_measures_through_real_backend_path(monkeypatch):
    """_measure must time kops.hash_bin_op — the executor's dispatching
    entry point — and sweep the tile dimension: every candidate call
    carries explicit f_chunk/tile kwargs from the candidate grids."""
    calls = []
    real = kops.hash_bin_op

    def spy(*args, **kw):
        calls.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr(kops, "hash_bin_op", spy)
    t = tuning.hash_tuning_for(64, cache=tuning.TuningCache())
    assert calls, "measurement never reached the backend path"
    pallas = kops._use_pallas_path()
    f_cands = (tuning.F_CHUNK_CANDIDATES_PALLAS if pallas
               else tuning.F_CHUNK_CANDIDATES)
    t_cands = (tuning.TILE_CANDIDATES_PALLAS if pallas
               else tuning.TILE_CANDIDATES)
    assert {kw["f_chunk"] for kw in calls} == set(f_cands)
    # the tile ladder descends and may be pruned on monotone regression,
    # so the sweep visits a non-empty prefix of the candidates — always
    # including the widest tile — and never anything off the grid
    tiles_seen = {kw["tile"] for kw in calls}
    assert tiles_seen and tiles_seen <= set(t_cands)
    assert t_cands[0] in tiles_seen
    assert t.f_chunk in f_cands and t.tile_rows in t_cands


def test_tuning_key_separates_rungs():
    assert tuning.tuning_key(64) != tuning.tuning_key(128)
    assert tuning.tuning_key(64) == tuning.tuning_key(64)


def test_tuning_measurement_log_records_candidates_and_pruning():
    """Every timed candidate (winners AND losers) lands in the
    measurement log, pruned tile-ladder tails are recorded as strict
    tails of the descending ladder, and exactly one winner is stamped
    per sweep."""
    tuning.clear_measurement_log()
    tuning.hash_tuning_for(64, cache=tuning.TuningCache())
    log = tuning.measurement_log()
    assert 64 in log and not set(log) - {64}
    entries = log[64]
    cands = [e for e in entries if "tile_rows" in e and "seconds" in e]
    assert len(cands) >= 2  # losing candidates survive, not just the winner
    assert all(e["seconds"] > 0.0 for e in cands)
    winners = [e for e in entries if "winner" in e]
    assert len(winners) == 1
    pallas = kops._use_pallas_path()
    t_cands = (tuning.TILE_CANDIDATES_PALLAS if pallas
               else tuning.TILE_CANDIDATES)
    assert winners[0]["winner"]["tile_rows"] in t_cands
    for e in entries:
        if "pruned_tiles" in e:
            k = len(e["pruned_tiles"])
            assert k >= 1 and tuple(e["pruned_tiles"]) == t_cands[-k:]
    # snapshot semantics: the log survives reads, clears on request
    assert tuning.measurement_log()
    tuning.clear_measurement_log()
    assert tuning.measurement_log() == {}


def test_planner_exec_uses_tuned_f_chunk_and_tile():
    a, b = powerlaw_pair()
    plan = planner.build_plan(a, b)
    assert plan.hash
    for hb in plan.hash:
        tuned = tuning.hash_tuning_for(hb.table)
        assert hb.f_chunk == tuned.f_chunk
        assert hb.tile == tuned.tile_rows
