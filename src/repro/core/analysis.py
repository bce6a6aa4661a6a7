"""Ocean's analysis step (paper §3.2, §4.3): cheap statistics + sampling that
select the workflow and configure the accumulators.

Everything here is O(nnz_A) + O(nnz_B) + O(sample * m_regs), mirroring the
paper's lightweight analysis. Results surface as host scalars because
workflow/kernel selection happens on the host (exactly as CUDA SpGEMM picks
kernels on the host after its analysis step).

The step is organized as a staged :class:`AnalysisPipeline` whose device
stages can be partitioned across a device set (``analyze(..., devices=N)``)
through the same dispatch/collect substrate the numeric executor uses
(``core.dispatch``): A's rows and B's rows are split into contiguous
cost-balanced blocks (``partition.contiguous_split`` on per-row nnz), each
device computes its block's ``products_per_row`` / column ranges / HLL
registers, and the host folds the partials with *exact* merge operators
(disjoint segment-sum concatenation for products, elementwise min/max for
ranges, register-wise max for sketches), so the sharded result is
bit-identical to the monolithic one — property-tested in
``tests/test_analysis_pipeline.py``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace
from . import hll
from .dispatch import (DeviceSpec, Launch, collect_in_completion_order,
                       device_context, new_copy_bytes, overlap_host_work,
                       resolve_devices, start_async_host_copies, to_device,
                       to_host)
from .formats import CSR, flat_gather_index, pow2_at_least
from .hll import row_ids_from_indptr


@dataclasses.dataclass(frozen=True)
class OceanConfig:
    """Paper §4.3 constants (faithful defaults)."""
    # HLL register count: 32 when ER < er_register_switch else 64.
    m_regs_small: int = 32
    m_regs_large: int = 64
    er_register_switch: float = 48.0
    # Workflow selection thresholds (Table 1).
    upper_bound_avg_products: float = 64.0
    er_threshold: float = 8.0
    cr_threshold: float = 8.0
    # Sampling (paper: ratio 0.03, clamped to [600, 10000]).
    sample_ratio: float = 0.03
    sample_min: int = 600
    sample_max: int = 10_000
    # Hash-table/bin expansion: 1.5x (2.0x at m=32 per §5.3).
    expansion: float = 1.5
    expansion_small_regs: float = 2.0
    # Assisted sizing (§4.1): conservative CR = mean - cr_sigma * std, >= 1.
    cr_sigma: float = 1.0
    # Dense-accumulator bitmap-query threshold (§4.1) — GPU-latency-specific,
    # kept for the cost model/ablation bookkeeping.
    bitmap_query_cr: float = 2.0
    # Hash-accumulator rung (§3.3/§4.1): select per-row open-addressing
    # tables for mid-density scattered rows. Rides the hybrid switch —
    # ``hybrid=False`` ablations disable it regardless of this knob.
    hash_rung: bool = True
    seed: int = 0

    def m_regs(self, er: float) -> int:
        return self.m_regs_small if er < self.er_register_switch else self.m_regs_large

    def expansion_for(self, m_regs: int) -> float:
        return self.expansion_small_regs if m_regs <= 32 else self.expansion


# ---------------------------------------------------------------------------
# Per-shard device statistics. Invalid (padding) slots route to an overflow
# segment that is dropped: masked slots must never touch a real row's
# statistics, because the sharded pipeline's row blocks carry pow2 shape
# padding (and callers may pass capacity-padded CSRs).
#
# Each stage has a traceable ``_impl`` body shared by the standalone jitted
# wrapper and the fused wave jits below — every stage is an integer segment
# reduction, so fusing them into one launch cannot change any value.
# ---------------------------------------------------------------------------

def _products_impl(a_indptr, a_indices, b_indptr, num_rows_a: int):
    cap = a_indices.shape[0]
    nnz_a = a_indptr[-1]
    valid = jnp.arange(cap, dtype=jnp.int32) < nnz_a
    b_len = (b_indptr[1:] - b_indptr[:-1]).astype(jnp.int32)
    k = jnp.clip(a_indices, 0, b_len.shape[0] - 1)
    contrib = jnp.where(valid, b_len[k], 0)
    row = jnp.where(valid, jnp.clip(row_ids_from_indptr(a_indptr, cap), 0,
                                    num_rows_a - 1), num_rows_a)
    return jax.ops.segment_sum(contrib, row,
                               num_segments=num_rows_a + 1)[:num_rows_a]


def _ranges_impl(indptr, indices, num_rows: int):
    cap = indices.shape[0]
    nnz = indptr[-1]
    valid = jnp.arange(cap, dtype=jnp.int32) < nnz
    row = jnp.where(valid, jnp.clip(row_ids_from_indptr(indptr, cap), 0,
                                    num_rows - 1), num_rows)
    big = jnp.int32(2**31 - 1)
    mins = jax.ops.segment_min(jnp.where(valid, indices, big), row,
                               num_segments=num_rows + 1)[:num_rows]
    maxs = jax.ops.segment_max(jnp.where(valid, indices, -1), row,
                               num_segments=num_rows + 1)[:num_rows]
    return mins, maxs


def _out_ranges_impl(a_indptr, a_indices, b_min, b_max, num_rows_a: int):
    cap = a_indices.shape[0]
    nnz_a = a_indptr[-1]
    valid = jnp.arange(cap, dtype=jnp.int32) < nnz_a
    row = jnp.where(valid, jnp.clip(row_ids_from_indptr(a_indptr, cap), 0,
                                    num_rows_a - 1), num_rows_a)
    k = jnp.clip(a_indices, 0, b_min.shape[0] - 1)
    big = jnp.int32(2**31 - 1)
    lo = jax.ops.segment_min(jnp.where(valid, b_min[k], big), row,
                             num_segments=num_rows_a + 1)[:num_rows_a]
    hi = jax.ops.segment_max(jnp.where(valid, b_max[k], -1), row,
                             num_segments=num_rows_a + 1)[:num_rows_a]
    return lo, hi


@partial(jax.jit, static_argnames=("num_rows_a",))
def products_per_row(a_indptr, a_indices, b_indptr, *, num_rows_a: int):
    """Number of intermediate products per output row — O(nnz_A)."""
    return _products_impl(a_indptr, a_indices, b_indptr, num_rows_a)


@partial(jax.jit, static_argnames=("num_rows",))
def row_col_ranges(indptr, indices, *, num_rows: int):
    """Per-row (min_col, max_col) — used to bound dense-accumulator windows."""
    return _ranges_impl(indptr, indices, num_rows)


@partial(jax.jit, static_argnames=("num_rows_a",))
def output_col_ranges(a_indptr, a_indices, b_min, b_max, *, num_rows_a: int):
    """Upper bound on each C row's column range from B-row ranges."""
    return _out_ranges_impl(a_indptr, a_indices, b_min, b_max, num_rows_a)


# Fused wave launches: one device dispatch (and one async D2H) per wave
# instead of one per stage. The monolithic path runs all three statistics
# stages in a single launch; the sharded path pairs each device's A-block
# with its same-slot B-block so wave 1 (products + B ranges) and wave 2
# (output ranges + sketches) are each one launch per device.

@partial(jax.jit, static_argnames=("num_rows_a", "num_rows_b"))
def _fused_stats(a_indptr, a_indices, b_indptr, b_indices,
                 *, num_rows_a: int, num_rows_b: int):
    prod = _products_impl(a_indptr, a_indices, b_indptr, num_rows_a)
    b_min, b_max = _ranges_impl(b_indptr, b_indices, num_rows_b)
    lo, hi = _out_ranges_impl(a_indptr, a_indices, b_min, b_max, num_rows_a)
    return prod, lo, hi


@partial(jax.jit, static_argnames=("num_rows_a", "num_rows_b"))
def _fused_wave1(a_indptr, a_indices, b_indptr_full, sb_indptr, sb_indices,
                 *, num_rows_a: int, num_rows_b: int):
    prod = _products_impl(a_indptr, a_indices, b_indptr_full, num_rows_a)
    mins, maxs = _ranges_impl(sb_indptr, sb_indices, num_rows_b)
    return prod, mins, maxs


@partial(jax.jit, static_argnames=("num_rows_a", "num_rows_b",
                                   "m_regs", "seed"))
def _fused_wave2(a_indptr, a_indices, b_min, b_max, sb_indptr, sb_indices,
                 *, num_rows_a: int, num_rows_b: int, m_regs: int, seed: int):
    lo, hi = _out_ranges_impl(a_indptr, a_indices, b_min, b_max, num_rows_a)
    regs = hll.sketch_registers_impl(sb_indptr, sb_indices, m_regs,
                                     num_rows_b, seed)
    return lo, hi, regs


@dataclasses.dataclass
class AnalysisResult:
    """Everything the workflow selector and binning need."""
    nnz_a: int
    nnz_b: int
    total_products: int
    products_row: jax.Array          # (m,) int32
    er: float                        # Input Expansion Ratio
    nproducts_avg: float
    m_regs: int
    b_sketches: Optional[jax.Array]  # (nB, m_regs) int32 (None if skipped)
    sampled_cr: Optional[float]      # Sampled Output Compression Ratio
    cr_mean: Optional[float]         # per-row CR sample mean
    cr_std: Optional[float]          # per-row CR sample std
    out_lo: jax.Array                # (m,) per-row output col-range bounds
    out_hi: jax.Array
    workflow: str                    # 'upper_bound'|'estimation'|'symbolic'|'known'
    sample_rows: Optional[np.ndarray] = None
    # exact per-row output nnz fed forward by the caller (graph chains: the
    # previous numeric pass measured them). When set, workflow == 'known',
    # sketching/sampling were skipped, and the planner enters binning with
    # these as symbolic-grade row statistics.
    known_sizes: Optional[np.ndarray] = None
    cr_sigma: float = 1.0            # OceanConfig.cr_sigma at analysis time
    n_shards: int = 1                # device shards the analysis ran across
    # per-shard host-side seconds: dispatch enqueue + block commit + the
    # blocking collect/merge of that shard's partials. On async backends
    # device compute overlaps these, so this reads as "host time spent on
    # shard i", not device execution time.
    shard_seconds: Optional[List[float]] = None
    # Host work the caller slotted behind analysis wave 2 (the planner's
    # binning prework — see ``analyze(..., overlap_work=...)``): seconds it
    # took, and whether at least one wave-2 launch was still in flight when
    # it started. Pure timing telemetry — excluded from sharded/monolithic
    # parity comparisons like n_shards/shard_seconds.
    wave2_overlap_seconds: float = 0.0
    wave2_overlapped: bool = False
    # bytes the analysis moved between host and device ("d2h", "h2d"):
    # the waves' uploads and read-backs, and the CR sample's. Telemetry,
    # excluded from parity comparisons like shard_seconds.
    copy_bytes: Dict[str, int] = dataclasses.field(
        default_factory=new_copy_bytes)

    @property
    def conservative_cr(self) -> float:
        """§4.1 assisted sizing: mean - cr_sigma * std, clipped to >= 1."""
        if self.cr_mean is None:
            return 1.0
        return max(1.0, self.cr_mean - self.cr_sigma * self.cr_std)


def _pick_sample_rows(num_rows: int, cfg: OceanConfig) -> np.ndarray:
    n = int(round(num_rows * cfg.sample_ratio))
    n = int(np.clip(n, min(cfg.sample_min, num_rows), cfg.sample_max))
    rng = np.random.default_rng(cfg.seed)
    return rng.choice(num_rows, size=n, replace=False).astype(np.int32)


def sketches_for(b: CSR, m_regs: int, seed: int,
                 sketch_cache: Optional[Dict] = None) -> jax.Array:
    """B-row sketches, reused from ``sketch_cache`` when present.

    The cache is a plain dict keyed by ``(m_regs, seed)``; sharing one dict
    across calls against the same B amortizes sketch construction over a
    stream of left-hand sides (``ocean_spgemm_many`` / plan reuse).
    Construction is deterministic — and the sharded pipeline's merged
    sketches are bit-identical to monolithic ones — so the key is
    deliberately device-independent: sketches built at any shard count
    interchange with sketches built at any other.
    """
    key = (m_regs, seed)
    if sketch_cache is not None and key in sketch_cache:
        return sketch_cache[key]
    sp, si, r_pad = _block_arrays(np.asarray(b.indptr),
                                  np.asarray(b.indices), 0, b.m)
    sk = hll.build_sketches(sp, si, m_regs=m_regs, num_rows=r_pad,
                            seed=seed)[: b.m]
    if sketch_cache is not None:
        sketch_cache[key] = sk
    return sk


# ---------------------------------------------------------------------------
# Sharded device stages
# ---------------------------------------------------------------------------

# Shard-block shapes are rounded up pow2 ladders so analysis blocks share
# jit specializations across matrices, splits, and topologies, exactly like
# partition.bucket_shard_rows does for execution shards. The ladders are
# deliberately *unclamped* (no cap at the matrix's own size): clamping would
# make each block's shape depend on (m, nnz) of the full matrix, forking a
# fresh specialization per input — the dominant cold-plan cost. Padding is
# inert: indptr repeats its last value (empty rows) and index slots past nnz
# are masked by every stage above.
SHARD_ROW_FLOOR = 64
SHARD_NNZ_FLOOR = 256


def _block_arrays(indptr: np.ndarray, indices: np.ndarray, r0: int, r1: int
                  ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Padded (sub_indptr, sub_indices, padded_rows) of rows [r0, r1)."""
    rows = r1 - r0
    lo, hi = int(indptr[r0]), int(indptr[r1])
    r_pad = pow2_at_least(max(rows, 1), floor=SHARD_ROW_FLOOR)
    n_pad = pow2_at_least(max(hi - lo, 1), floor=SHARD_NNZ_FLOOR)
    sub_ptr = np.full(r_pad + 1, hi - lo, np.int32)
    sub_ptr[: rows + 1] = indptr[r0:r1 + 1] - lo
    sub_idx = np.zeros(n_pad, np.int32)
    sub_idx[: hi - lo] = indices[lo:hi]
    return sub_ptr, sub_idx, r_pad


def _bucket_ptr(indptr: np.ndarray, rows: int) -> np.ndarray:
    """Full indptr padded to the pow2 row bucket (trailing empty rows)."""
    r_pad = pow2_at_least(max(rows, 1), floor=SHARD_ROW_FLOOR)
    out = np.full(r_pad + 1, int(indptr[rows]), np.int32)
    out[: rows + 1] = indptr[: rows + 1]
    return out


def _pad_sketch_rows(sk, rows: int) -> jax.Array:
    """Pad a (n, m) sketch array with all-zero rows up to ``rows``.

    Zero registers are the HLL identity (empty-row sketch), and merge
    consumers mask invalid gathers anyway, so padding is value-inert; it
    exists purely to keep merge-stage jit specializations bucketed."""
    sk = jnp.asarray(sk)
    if sk.shape[0] >= rows:
        return sk
    return jnp.concatenate(
        [sk, jnp.zeros((rows - sk.shape[0], sk.shape[1]), jnp.int32)],
        axis=0)


@dataclasses.dataclass
class _ShardBlock:
    """One device's contiguous row block of a CSR, committed to the device."""
    index: int                 # shard slot (device position)
    device: object
    r0: int
    r1: int
    indptr: jax.Array          # (r_pad+1,) device-resident, padded
    indices: jax.Array         # (n_pad,) device-resident, padded
    r_pad: int

    @property
    def rows(self) -> int:
        return self.r1 - self.r0


class AnalysisPipeline:
    """Ocean's analysis as a staged pipeline with shardable device stages.

    Stage graph (device stages marked *):

        wave 1:  *A-products (per A-row block)   *B-ranges (per B-row block)
                       |                               |
                 segment-sum concat              min/max merge
                       |                               |
        host:    ER / nproducts_avg / m_regs / workflow gate
                       |
        wave 2:  *A-out-ranges (needs merged B ranges)
                 *B-sketches   (needs m_regs; skipped for upper_bound /
                                build_sketches=False / sketch-cache hit)
                       |                  |
                 min/max concat     register-wise max merge
                       |
        host:    sampled CR + workflow selection (monolithic: tiny sample)

    Every merge operator is exact (integer sums over disjoint row blocks,
    min/max, register max), so ``run(devices=N)`` is bit-identical to
    ``run()`` for every field of :class:`AnalysisResult`. Device launches
    go through ``core.dispatch`` — the same dispatch/collect substrate as
    the numeric executor — so D2H copies overlap with outstanding compute
    and partials merge in completion order.
    """

    def __init__(self, cfg: OceanConfig = OceanConfig()):
        self.cfg = cfg

    def _needs_sketches(self, er: float, nproducts_avg: float,
                        build_sketches: bool) -> bool:
        """The single gate for the sketch stage — shared by the sharded
        wave-2 dispatch and the host tail so the two can never diverge
        (a divergence would surface as all-zero merged sketches)."""
        return (build_sketches
                and nproducts_avg >= self.cfg.upper_bound_avg_products
                and er >= self.cfg.er_threshold)

    def run(self, a: CSR, b: CSR, *, build_sketches: bool = True,
            sketch_cache: Optional[Dict] = None,
            devices: DeviceSpec = None,
            known_sizes: Optional[np.ndarray] = None,
            overlap_work=None) -> AnalysisResult:
        """``overlap_work``, when given, is a host callable
        ``overlap_work(prod_row_host)`` run while the wave-2 launches
        (output ranges / sketches) are still in flight — the slot the
        planner uses to start binning prework on wave-1 products. It must
        not depend on any wave-2 output; its wall time and whether it
        genuinely overlapped in-flight work land on
        ``AnalysisResult.wave2_overlap_seconds`` / ``wave2_overlapped``.
        """
        if known_sizes is not None:
            known_sizes = np.asarray(known_sizes, np.int64)
            if known_sizes.shape != (a.m,):
                raise ValueError(
                    f"known_sizes shape {known_sizes.shape} != ({a.m},)")
            # exact sizes make every estimation artifact dead weight: skip
            # sketch construction (and, below, sampling + selection)
            build_sketches = False
        devs = resolve_devices(devices) if devices is not None else None
        if devs is not None and (len(devs) <= 1 or a.m == 0 or b.m == 0):
            devs = None
        if devs is None:
            return self._run_monolithic(a, b, build_sketches, sketch_cache,
                                        known_sizes, overlap_work)
        return self._run_sharded(a, b, devs, build_sketches, sketch_cache,
                                 known_sizes, overlap_work)

    # -- single-device path (the legacy monolithic analyze) ----------------

    def _run_monolithic(self, a: CSR, b: CSR, build_sketches: bool,
                        sketch_cache: Optional[Dict],
                        known_sizes: Optional[np.ndarray] = None,
                        overlap_work=None) -> AnalysisResult:
        cfg = self.cfg
        a_ptr, a_idx = np.asarray(a.indptr), np.asarray(a.indices)
        b_ptr, b_idx = np.asarray(b.indptr), np.asarray(b.indices)
        copies = new_copy_bytes()
        # Bucket both matrices onto the pow2 shape ladder so this single
        # fused launch (all three statistics stages, one dispatch, one
        # async D2H) reuses its jit specialization across matrices.
        with trace.span("analysis.wave1") as ws:
            t0_w1 = time.perf_counter()
            sa_ptr, sa_idx, ra_pad = _block_arrays(a_ptr, a_idx, 0, a.m)
            sb_ptr, sb_idx, rb_pad = _block_arrays(b_ptr, b_idx, 0, b.m)
            sa_ptr, sa_idx, sb_ptr, sb_idx = (
                to_device(x, copies) for x in (sa_ptr, sa_idx, sb_ptr,
                                               sb_idx))
            prod_p, lo_p, hi_p = _fused_stats(sa_ptr, sa_idx, sb_ptr,
                                              sb_idx, num_rows_a=ra_pad,
                                              num_rows_b=rb_pad)
            wave1 = [Launch("stats", 0, (prod_p, lo_p, hi_p))]
            start_async_host_copies(wave1)
            ws.measured(t0_w1, time.perf_counter() - t0_w1).set(fused=True)
        ov_s, ov_pending = 0.0, False
        prod_row = None
        if overlap_work is not None:
            # The fused launch is dispatched but not awaited: the prework
            # runs behind whatever the backend still has in flight (it
            # blocks only on the products slice, which the work needs).
            def prework():
                rows = to_host(prod_p, copies)[: a.m]
                overlap_work(rows)
                return rows

            prod_row, ov_s, ov_pending = overlap_host_work(wave1, prework)

        def sketch_builder(m: int):
            key = (m, cfg.seed)
            if sketch_cache is not None and key in sketch_cache:
                return sketch_cache[key], None
            full = hll.build_sketches(sb_ptr, sb_idx, m_regs=m,
                                      num_rows=rb_pad, seed=cfg.seed)
            sk = full[: b.m]
            if sketch_cache is not None:
                sketch_cache[key] = sk
            return sk, full

        with trace.span("analysis.wave2") as ws:
            t0_w2 = time.perf_counter()
            if prod_row is None:
                prod_row = to_host(prod_p, copies)[: a.m]
            out_lo = to_host(lo_p, copies)[: a.m]
            out_hi = to_host(hi_p, copies)[: a.m]
            ws.measured(t0_w2, time.perf_counter() - t0_w2)
        return self._finish(
            a, b, prod_row=prod_row,
            out_lo=out_lo, out_hi=out_hi,
            build_sketches=build_sketches, sketch_builder=sketch_builder,
            n_shards=1, shard_seconds=None, known_sizes=known_sizes,
            wave2_overlap_seconds=ov_s, wave2_overlapped=ov_pending,
            copies=copies)

    # -- device-partitioned path -------------------------------------------

    def _run_sharded(self, a: CSR, b: CSR, devs: Tuple,
                     build_sketches: bool,
                     sketch_cache: Optional[Dict],
                     known_sizes: Optional[np.ndarray] = None,
                     overlap_work=None) -> AnalysisResult:
        # partition is imported lazily: it depends on the plan containers
        # (planner), which import this module.
        from .partition import contiguous_split
        cfg = self.cfg
        n_dev = len(devs)
        shard_s = [0.0] * n_dev
        copies = new_copy_bytes()
        a_ptr, a_idx = np.asarray(a.indptr), np.asarray(a.indices)
        b_ptr, b_idx = np.asarray(b.indptr), np.asarray(b.indices)

        # Analysis work is O(nnz) in each matrix, so per-row nnz is the
        # balance weight (per-row products are this stage's *output*).
        a_blocks = contiguous_split(
            (a_ptr[1:] - a_ptr[:-1]).astype(np.int64), n_dev)
        b_blocks = contiguous_split(
            (b_ptr[1:] - b_ptr[:-1]).astype(np.int64), n_dev)

        def commit(blocks, ptr, idx) -> List[_ShardBlock]:
            parts = []
            for i, (r0, r1) in enumerate(blocks):
                if r1 <= r0:
                    continue
                t0 = time.perf_counter()
                sp, si, r_pad = _block_arrays(ptr, idx, r0, r1)
                dev = devs[i]
                parts.append(_ShardBlock(
                    index=i, device=dev, r0=r0, r1=r1,
                    indptr=to_device(sp, copies, dev),
                    indices=to_device(si, copies, dev), r_pad=r_pad))
                shard_s[i] += time.perf_counter() - t0
            return parts

        a_parts = commit(a_blocks, a_ptr, a_idx)
        b_parts = commit(b_blocks, b_ptr, b_idx)
        b_by = {p.index: p for p in b_parts}
        # The full-B indptr every products launch consumes rides the same
        # pow2 row bucket as the blocks, so its shape (hence the fused
        # wave's jit specialization) is matrix-independent too.
        b_ptr_pad = _bucket_ptr(b_ptr, b.m)
        rb_full = b_ptr_pad.shape[0] - 1

        # ---- wave 1: one fused launch per device slot holding both an
        # A-block (products) and its same-slot B-block (column ranges);
        # unpaired blocks fall back to the standalone stage jits ----
        with trace.span("analysis.wave1") as ws:
            t0_w1 = time.perf_counter()
            launches: List[Launch] = []
            order = 0
            fused1 = set()
            for part in a_parts:
                bpart = b_by.get(part.index)
                t0 = time.perf_counter()
                with device_context(part.device):
                    bp = to_device(b_ptr_pad, copies, part.device)
                    if bpart is not None:
                        prod, mins, maxs = _fused_wave1(
                            part.indptr, part.indices, bp,
                            bpart.indptr, bpart.indices,
                            num_rows_a=part.r_pad, num_rows_b=bpart.r_pad)
                        launches.append(Launch(("w1", part, bpart), order,
                                               (prod, mins, maxs)))
                        fused1.add(part.index)
                    else:
                        out = products_per_row(part.indptr, part.indices, bp,
                                               num_rows_a=part.r_pad)
                        launches.append(Launch(("prod", part, None), order,
                                               (out,)))
                order += 1
                shard_s[part.index] += time.perf_counter() - t0
            for part in b_parts:
                if part.index in fused1:
                    continue
                t0 = time.perf_counter()
                with device_context(part.device):
                    mins, maxs = row_col_ranges(part.indptr, part.indices,
                                                num_rows=part.r_pad)
                launches.append(Launch(("brange", part, None), order,
                                       (mins, maxs)))
                order += 1
                shard_s[part.index] += time.perf_counter() - t0
            start_async_host_copies(launches)

            prod_row = np.zeros(a.m, np.int32)
            b_min = np.full(b.m, np.iinfo(np.int32).max, np.int32)
            b_max = np.full(b.m, np.iinfo(np.int32).min, np.int32)

            def fold_prod(part, arr):
                # disjoint row blocks: per-block segment sums concatenate
                prod_row[part.r0:part.r1] = arr[: part.rows]

            def fold_brange(part, mn, mx):
                np.minimum(b_min[part.r0:part.r1], mn[: part.rows],
                           out=b_min[part.r0:part.r1])
                np.maximum(b_max[part.r0:part.r1], mx[: part.rows],
                           out=b_max[part.r0:part.r1])

            for it in collect_in_completion_order(launches):
                kind, part, bpart = it.tag
                t0 = time.perf_counter()
                host = [to_host(x, copies) for x in it.arrays]
                if kind == "w1":
                    fold_prod(part, host[0])
                    fold_brange(bpart, host[1], host[2])
                elif kind == "prod":
                    fold_prod(part, host[0])
                else:
                    fold_brange(part, host[0], host[1])
                shard_s[part.index] += time.perf_counter() - t0
            ws.measured(t0_w1, time.perf_counter() - t0_w1).set(
                shards=n_dev)

        total_products = int(prod_row.astype(np.int64).sum())
        er = total_products / max(a.nnz, 1)
        nproducts_avg = total_products / max(a.m, 1)
        m_regs = cfg.m_regs(er)
        need_sketches = self._needs_sketches(er, nproducts_avg,
                                             build_sketches)
        cached_sk = (sketch_cache.get((m_regs, cfg.seed))
                     if need_sketches and sketch_cache is not None else None)

        # ---- wave 2: output ranges (+ sketches on a cache miss), again
        # fused per device slot when the slot holds both blocks ----
        build_shard_sketches = need_sketches and cached_sk is None
        # The merged B ranges are broadcast padded with the min/max gather
        # identities (matching the segment-op defaults above) so their
        # shape stays on the row bucket; padded entries are masked.
        bmin_pad = np.full(rb_full, np.iinfo(np.int32).max, np.int32)
        bmin_pad[: b.m] = b_min
        bmax_pad = np.full(rb_full, -1, np.int32)
        bmax_pad[: b.m] = b_max
        with trace.span("analysis.wave2") as ws:
            t0_w2 = time.perf_counter()
            launches = []
            fused2 = set()
            for part in a_parts:
                bpart = b_by.get(part.index) if build_shard_sketches else None
                t0 = time.perf_counter()
                with device_context(part.device):
                    bmin_d = to_device(bmin_pad, copies, part.device)
                    bmax_d = to_device(bmax_pad, copies, part.device)
                    if bpart is not None:
                        lo, hi, regs = _fused_wave2(
                            part.indptr, part.indices, bmin_d, bmax_d,
                            bpart.indptr, bpart.indices,
                            num_rows_a=part.r_pad, num_rows_b=bpart.r_pad,
                            m_regs=m_regs, seed=cfg.seed)
                        launches.append(Launch(("w2", part, bpart), order,
                                               (lo, hi, regs)))
                        fused2.add(part.index)
                    else:
                        lo, hi = output_col_ranges(part.indptr, part.indices,
                                                   bmin_d, bmax_d,
                                                   num_rows_a=part.r_pad)
                        launches.append(Launch(("orange", part, None), order,
                                               (lo, hi)))
                order += 1
                shard_s[part.index] += time.perf_counter() - t0
            if build_shard_sketches:
                for part in b_parts:
                    if part.index in fused2:
                        continue
                    t0 = time.perf_counter()
                    with device_context(part.device):
                        regs = hll.build_sketches(
                            part.indptr, part.indices, m_regs=m_regs,
                            num_rows=part.r_pad, seed=cfg.seed)
                    launches.append(Launch(("sketch", part, None), order,
                                           (regs,)))
                    order += 1
                    shard_s[part.index] += time.perf_counter() - t0
            start_async_host_copies(launches)

            # Caller-provided host prework (planner binning) rides behind the
            # in-flight wave-2 launches; it consumes only the wave-1 merged
            # products, which are already host-resident here.
            ov_s, ov_pending = 0.0, False
            if overlap_work is not None:
                _, ov_s, ov_pending = overlap_host_work(
                    launches, lambda: overlap_work(prod_row))

            out_lo = np.full(a.m, np.iinfo(np.int32).max, np.int32)
            out_hi = np.full(a.m, np.iinfo(np.int32).min, np.int32)
            sketch_parts: List[Tuple[int, int, np.ndarray]] = []

            def fold_orange(part, lo, hi):
                np.minimum(out_lo[part.r0:part.r1], lo[: part.rows],
                           out=out_lo[part.r0:part.r1])
                np.maximum(out_hi[part.r0:part.r1], hi[: part.rows],
                           out=out_hi[part.r0:part.r1])

            for it in collect_in_completion_order(launches):
                kind, part, bpart = it.tag
                t0 = time.perf_counter()
                host = [to_host(x, copies) for x in it.arrays]
                if kind == "w2":
                    fold_orange(part, host[0], host[1])
                    sketch_parts.append((bpart.r0, bpart.r1, host[2]))
                elif kind == "orange":
                    fold_orange(part, host[0], host[1])
                else:
                    sketch_parts.append((part.r0, part.r1, host[0]))
                shard_s[part.index] += time.perf_counter() - t0
            ws.measured(t0_w2, time.perf_counter() - t0_w2).set(
                shards=n_dev)

        def sketch_builder(m: int):
            if cached_sk is not None:
                return cached_sk, None
            assert sketch_parts, \
                "sketch stage was gated off but the host tail wants " \
                "sketches — _needs_sketches gates must agree"
            merged = hll.merge_register_partials(sketch_parts, num_rows=b.m,
                                                 m_regs=m)
            sk = to_device(merged, copies)
            if sketch_cache is not None:
                sketch_cache[(m, cfg.seed)] = sk
            return sk, None

        return self._finish(
            a, b, prod_row=prod_row, out_lo=out_lo, out_hi=out_hi,
            build_sketches=build_sketches, sketch_builder=sketch_builder,
            n_shards=n_dev, shard_seconds=shard_s, known_sizes=known_sizes,
            wave2_overlap_seconds=ov_s, wave2_overlapped=ov_pending,
            copies=copies)

    # -- shared host tail: workflow gate + sampled CR ----------------------

    def _finish(self, a: CSR, b: CSR, *, prod_row, out_lo, out_hi,
                build_sketches: bool, sketch_builder,
                n_shards: int,
                shard_seconds: Optional[List[float]],
                known_sizes: Optional[np.ndarray] = None,
                wave2_overlap_seconds: float = 0.0,
                wave2_overlapped: bool = False,
                copies: Optional[Dict[str, int]] = None) -> AnalysisResult:
        cfg = self.cfg
        copies = copies if copies is not None else new_copy_bytes()
        total_products = int(np.asarray(prod_row, np.int64).sum())
        nnz_a, nnz_b = a.nnz, b.nnz
        er = total_products / max(nnz_a, 1)
        nproducts_avg = total_products / max(a.m, 1)
        m_regs = cfg.m_regs(er)

        if known_sizes is not None:
            # Feed-forward path (graph chains): the caller measured the
            # exact output row nnz of this very pattern pair in a prior
            # numeric pass. Exact sizes trump Table-1 selection — no
            # sketches, no sampling, no symbolic sort; the planner bins
            # these like symbolic results (no expansion slack).
            return AnalysisResult(
                nnz_a=nnz_a, nnz_b=nnz_b, total_products=total_products,
                products_row=prod_row, er=er, nproducts_avg=nproducts_avg,
                m_regs=m_regs, b_sketches=None, sampled_cr=None,
                cr_mean=None, cr_std=None, out_lo=out_lo, out_hi=out_hi,
                workflow="known", cr_sigma=cfg.cr_sigma,
                n_shards=n_shards, shard_seconds=shard_seconds,
                known_sizes=known_sizes,
                wave2_overlap_seconds=wave2_overlap_seconds,
                wave2_overlapped=wave2_overlapped, copy_bytes=copies)

        if nproducts_avg < cfg.upper_bound_avg_products:
            return AnalysisResult(
                nnz_a=nnz_a, nnz_b=nnz_b, total_products=total_products,
                products_row=prod_row, er=er, nproducts_avg=nproducts_avg,
                m_regs=m_regs, b_sketches=None, sampled_cr=None,
                cr_mean=None, cr_std=None, out_lo=out_lo, out_hi=out_hi,
                workflow="upper_bound", cr_sigma=cfg.cr_sigma,
                n_shards=n_shards, shard_seconds=shard_seconds,
                wave2_overlap_seconds=wave2_overlap_seconds,
                wave2_overlapped=wave2_overlapped, copy_bytes=copies)

        sketches = None
        sampled_cr = cr_mean = cr_std = None
        sample_rows = None
        if self._needs_sketches(er, nproducts_avg, build_sketches):
            # Sketch construction O(nnz_B) + sampled merge (~3% of runtime).
            sketches, sk_padded = sketch_builder(m_regs)
            rb_pad = pow2_at_least(max(b.m, 1), floor=SHARD_ROW_FLOOR)
            if sk_padded is None or sk_padded.shape[0] != rb_pad:
                sk_padded = _pad_sketch_rows(sketches, rb_pad)
            # The sampling prework (row pick + sub-CSR gather + padding) is
            # pure host work independent of the sketch values, so it rides
            # behind the in-flight sketch launch — the estimation-workflow
            # twin of the planner's wave-2 binning prework.
            in_flight = [Launch("sketches", 0, (sk_padded,))]
            start_async_host_copies(in_flight)

            def _sample_prework():
                rows = _pick_sample_rows(a.m, cfg)
                new_ptr, src = flat_gather_index(np.asarray(a.indptr), rows)
                sub_idx = np.asarray(a.indices)[src]
                return (rows,) + _block_arrays(new_ptr, sub_idx, 0,
                                               len(rows))

            (sample_rows, sp, si, r_pad), est_s, est_pend = \
                overlap_host_work(in_flight, _sample_prework)
            wave2_overlap_seconds += est_s
            wave2_overlapped = wave2_overlapped or est_pend
            merged = hll.merge_sketches(to_device(sp, copies),
                                        to_device(si, copies), sk_padded,
                                        num_rows_a=r_pad)
            est = hll.estimate_cardinality(merged, clip_max=b.n)
            est = np.maximum(to_host(est, copies)[: len(sample_rows)], 1.0)
            prods = np.asarray(prod_row)[sample_rows].astype(np.float64)
            mask = prods > 0
            if mask.any():
                per_row_cr = prods[mask] / est[mask]
                sampled_cr = float(prods[mask].sum() / est[mask].sum())
                cr_mean = float(per_row_cr.mean())
                cr_std = float(per_row_cr.std())
            else:
                sampled_cr, cr_mean, cr_std = 1.0, 1.0, 0.0

        if (er >= cfg.er_threshold and sampled_cr is not None
                and sampled_cr >= cfg.cr_threshold):
            workflow = "estimation"
        else:
            workflow = "symbolic"

        return AnalysisResult(
            nnz_a=nnz_a, nnz_b=nnz_b, total_products=total_products,
            products_row=prod_row, er=er, nproducts_avg=nproducts_avg,
            m_regs=m_regs, b_sketches=sketches, sampled_cr=sampled_cr,
            cr_mean=cr_mean, cr_std=cr_std, out_lo=out_lo, out_hi=out_hi,
            workflow=workflow, sample_rows=sample_rows,
            cr_sigma=cfg.cr_sigma, n_shards=n_shards,
            shard_seconds=shard_seconds,
            wave2_overlap_seconds=wave2_overlap_seconds,
            wave2_overlapped=wave2_overlapped, copy_bytes=copies)


def analyze(a: CSR, b: CSR, cfg: OceanConfig = OceanConfig(),
            build_sketches: bool = True,
            sketch_cache: Optional[Dict] = None,
            devices: DeviceSpec = None,
            known_sizes: Optional[np.ndarray] = None,
            overlap_work=None) -> AnalysisResult:
    """The Ocean analysis step. Selects the workflow per Table 1:

        upper_bound  if nproducts_avg < 64
        estimation   if nproducts_avg >= 64 and ER >= 8 and sampled CR >= 8
        symbolic     otherwise

    ``devices`` partitions the device stages across a device set (int,
    device sequence, or 1-D mesh — same specs as ``ocean_spgemm``); the
    result is bit-identical to the single-device run for every field.
    ``known_sizes`` (per-row exact output nnz, fed forward from a prior
    numeric pass over the same pattern pair — see ``repro.graph.chain``)
    short-circuits selection to the ``"known"`` workflow: sketching,
    sampling, and CR estimation are skipped entirely.
    ``overlap_work(prod_row_host)`` runs host-side while the wave-2
    launches are in flight (see :meth:`AnalysisPipeline.run`).
    """
    return AnalysisPipeline(cfg).run(a, b, build_sketches=build_sketches,
                                     sketch_cache=sketch_cache,
                                     devices=devices,
                                     known_sizes=known_sizes,
                                     overlap_work=overlap_work)


def sharded_merge_estimate(a: CSR, sketches_with_sentinel,
                           *, clip_max: Optional[int] = None,
                           devices: DeviceSpec = None,
                           copies: Optional[Dict[str, int]] = None
                           ) -> np.ndarray:
    """Device-partitioned ``kernels.ops.merge_estimate_op`` (prediction
    stage): per-row HLL output-size estimates for C = A @ B.

    A's rows split into contiguous nnz-balanced blocks
    (``partition.contiguous_split`` — the merge is O(nnz_A) and
    row-partitionable); each device merges the B sketches over its block's
    rows and the host concatenates the disjoint per-row estimates. Each
    row's merged registers depend only on that row's indices (padding maps
    to the all-zero sentinel sketch), so the sharded result is
    bit-identical to the monolithic one at any shard count. Block shapes
    ride the same pow2 ladders as the sharded analysis stages, bounding
    jit specializations across splits and topologies. ``copies`` counts
    the blocks' uploads and the estimates' read-backs.
    """
    from repro.kernels import ops as kops
    copies = copies if copies is not None else new_copy_bytes()
    devs = resolve_devices(devices) if devices is not None else None
    if devs is not None and (len(devs) <= 1 or a.m == 0):
        devs = None
    a_ptr, a_idx = np.asarray(a.indptr), np.asarray(a.indices)
    if devs is None:
        # Single-device merges ride the same pow2 block bucket as shards
        # so the merge/estimate specialization is matrix-independent.
        sp, si, r_pad = _block_arrays(a_ptr, a_idx, 0, a.m)
        sub = CSR(to_device(sp, copies), to_device(si, copies),
                  jnp.zeros((si.shape[0],), jnp.float32),
                  (r_pad, a.n), int(sp[-1]))
        _, est = kops.merge_estimate_op(sub, sketches_with_sentinel,
                                        clip_max=clip_max)
        return to_host(est, copies)[: a.m]
    blocks = contiguous_split_rows(a_ptr, len(devs))
    sk_host = to_host(sketches_with_sentinel, copies)
    launches: List[Launch] = []
    order = 0
    for i, (r0, r1) in enumerate(blocks):
        if r1 <= r0:
            continue
        sp, si, r_pad = _block_arrays(a_ptr, a_idx, r0, r1)
        dev = devs[i]
        with device_context(dev):
            sub = CSR(to_device(sp, copies, dev), to_device(si, copies, dev),
                      jnp.zeros((si.shape[0],), jnp.float32),
                      (r_pad, a.n), int(sp[-1]))
            sk_d = to_device(sk_host, copies, dev)
            _, est = kops.merge_estimate_op(sub, sk_d, clip_max=clip_max)
        launches.append(Launch((r0, r1), order, (est,)))
        order += 1
    start_async_host_copies(launches)
    out = np.zeros(a.m, np.float32)
    for it in collect_in_completion_order(launches):
        r0, r1 = it.tag
        out[r0:r1] = to_host(it.arrays[0], copies)[: r1 - r0]
    return out


def contiguous_split_rows(indptr: np.ndarray,
                          n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous nnz-balanced row blocks of a CSR's rows (the standard
    weight for O(nnz) row-partitionable stages)."""
    from .partition import contiguous_split
    nnz_row = (indptr[1:] - indptr[:-1]).astype(np.int64)
    return contiguous_split(nnz_row, n_shards)
