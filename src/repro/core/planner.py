"""Planner/executor split for Ocean SpGEMM (plan caching, paper Fig. 4).

Ocean's analysis, size prediction, and binning depend only on the *sparsity
patterns* of A and B — never on the numeric values. This module makes that
explicit: the planner turns ``(analysis, binning)`` into a reusable
:class:`ExecutionPlan` (bin ladder, per-bin row sets and ELL gather maps,
ESC capacities, bucketed kernel shapes), and the executor runs a plan
against values-only updates. Repeated ``A @ B`` calls with an unchanged
sparsity pattern therefore skip analysis/prediction/binning entirely via an
LRU plan cache keyed by (structure hash, bucketed shapes) — the same way
the binning ladder already buckets kernel shapes to bound recompilation.

Plan lifecycle:

    build_plan(a, b)  ->  ExecutionPlan          (structure-only, cacheable)
    execute_plan(plan, a, b)  ->  (CSR, report)  (values in, values out)

Execution itself lives in ``core.executor`` (one dispatch/collect/merge
pipeline shared by single-device and sharded paths); the ``execute_*``
functions here are thin wrappers kept for API stability.

A plan is invalidated implicitly: the cache key hashes both sparsity
patterns plus every planning knob (config, forced workflow, ablation
flags), so any structural or configuration change misses the cache and
builds a fresh plan. Values-only changes always hit.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.obs import accuracy as obs_accuracy
from repro.obs import trace
from . import esc as esc_mod
from . import tuning as tuning_mod
from .analysis import (SHARD_ROW_FLOOR, AnalysisResult, OceanConfig, analyze,
                       sharded_merge_estimate, sketches_for)
from .binning import BinPlan, plan_bins
from .dispatch import new_copy_bytes, to_device, to_host
from .formats import CSR, csr_from_arrays, flat_gather_index, pow2_at_least


# stage_seconds keys that time part of another stage: the overflow
# fallback runs inside the merge ("overflow" on the serial executor)
NESTED_STAGES = ("fallback",)


@dataclasses.dataclass
class OceanReport:
    workflow: str
    er: float
    sampled_cr: Optional[float]
    nproducts_avg: float
    total_products: int
    m_regs: int
    stage_seconds: Dict[str, float]
    bins: Dict[str, int]
    overflow_rows: int
    nnz_out: int
    plan_cache_hit: bool = False
    # the plan entered binning with exact feed-forward sizes (workflow
    # 'known'): HLL estimation / the symbolic sort were skipped entirely
    feed_forward: bool = False
    n_shards: int = 1
    shard_imbalance: float = 1.0
    executor: str = "serial"
    # host-merge work performed before the final slab was collected, i.e.
    # moved off the post-barrier critical path (overlapped with device
    # work on async backends; pipelined executor only, serial reports 0.0)
    overlap_seconds: float = 0.0
    # device shards the plan's analysis stage ran across, with per-shard
    # host-side seconds (dispatch enqueue + collect/merge per shard — not
    # device execution time; build-time facts of the plan: a cache hit
    # replays the values recorded when the plan was built). stage_seconds
    # ["analysis"] stays the stage total — shard times overlap in wall
    # clock, so they are surfaced separately rather than summed into it.
    analysis_shards: int = 1
    analysis_shard_seconds: Optional[List[float]] = None
    # exact per-row nnz of the raw (pre-mask/pre-prune) product — only
    # tracked when fused merge post-ops ran (None otherwise: the output's
    # own indptr already is the exact raw sizing). Graph chains feed these
    # forward as ``known_sizes`` for the next plan on the same pattern.
    raw_row_nnz: Optional[np.ndarray] = None
    # binning prework the planner ran behind analysis wave 2 (build-time
    # facts of the plan, like analysis_shard_seconds): seconds of host
    # work moved off the serial analysis->binning critical path, and
    # whether wave-2 launches were genuinely still in flight when it ran
    wave2_overlap_seconds: float = 0.0
    wave2_overlapped: bool = False
    # estimate-vs-exact telemetry measured after the numeric pass
    # (repro.obs.accuracy; None when the plan predates pred_row_nnz)
    estimation_accuracy: Optional[object] = None
    # workflow-decision audit record captured at plan-build time: the
    # workflow chosen plus every input to the choice (Table 1 thresholds,
    # ER, sampled CR, forcing) — a build-time fact replayed on cache hits
    decision: Optional[Dict] = None
    # bytes this call moved between host and device, by direction
    # ("d2h", "h2d"): the executor's copies both ways, and the planning
    # stages' read-backs and uploads when this call planned
    # (core.dispatch.to_host / to_device count them)
    copy_bytes: Dict[str, int] = dataclasses.field(
        default_factory=new_copy_bytes)
    # the call's ESC passes, the ESC bin's and the overflow fallback's:
    # products expanded, and the product slots (p_cap) allocated for them
    esc_products: int = 0
    esc_slots: int = 0

    @property
    def total_seconds(self) -> float:
        return sum(v for k, v in self.stage_seconds.items()
                   if k not in NESTED_STAGES)

    @property
    def setup_seconds(self) -> float:
        """Host-side planning time: analysis + prediction + binning (plus
        device partitioning when sharded), plus the plan-cache key
        hash/lookup when a cache was consulted."""
        return sum(self.stage_seconds.get(k, 0.0)
                   for k in ("plan_lookup", "analysis", "prediction",
                             "binning", "partition"))

    @property
    def merge_overlap_frac(self) -> float:
        """Overlapped merge work as a fraction of all merge work — a
        *view* over ``overlap_seconds`` / ``stage_seconds["merge"]`` (one
        measurement, so the fraction can never drift from the seconds it
        summarizes), clamped to [0, 1]."""
        merge_s = self.stage_seconds.get("merge", 0.0)
        if merge_s <= 0.0 or self.overlap_seconds <= 0.0:
            return 0.0
        return min(1.0, self.overlap_seconds / merge_s)

    def audit(self) -> List[str]:
        """Timing-field consistency audit. Returns a list of violation
        descriptions (empty == consistent): non-negative stage/overlap
        times, fractions within [0, 1], and child-span sums never
        exceeding their parent wall time."""
        bad: List[str] = []
        for k, v in self.stage_seconds.items():
            if v < 0.0:
                bad.append(f"stage_seconds[{k!r}] negative: {v}")
        if self.overlap_seconds < 0.0:
            bad.append(f"overlap_seconds negative: {self.overlap_seconds}")
        if self.wave2_overlap_seconds < 0.0:
            bad.append("wave2_overlap_seconds negative: "
                       f"{self.wave2_overlap_seconds}")
        if not 0.0 <= self.merge_overlap_frac <= 1.0:
            bad.append(f"merge_overlap_frac out of [0, 1]: "
                       f"{self.merge_overlap_frac}")
        merge_s = self.stage_seconds.get("merge")
        if merge_s is not None and self.overlap_seconds > merge_s * (
                1.0 + 1e-9):
            bad.append(f"overlap_seconds {self.overlap_seconds} exceeds "
                       f"parent merge time {merge_s}")
        for s in self.analysis_shard_seconds or ():
            if s < 0.0:
                bad.append(f"analysis_shard_seconds entry negative: {s}")
        if self.setup_seconds > self.total_seconds * (1.0 + 1e-9):
            bad.append(f"setup_seconds {self.setup_seconds} exceeds "
                       f"total_seconds {self.total_seconds}")
        return bad


def gather_rows(a: CSR, rows: np.ndarray) -> CSR:
    """Host-side sub-CSR of the selected rows (order preserved)."""
    new_ptr, src = flat_gather_index(a.indptr, rows)
    return csr_from_arrays(new_ptr, np.asarray(a.indices)[src],
                           np.asarray(a.values)[src], (len(rows), a.n))


# ---------------------------------------------------------------------------
# Plan containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DenseBinExec:
    """One dense-accumulator bin with its structure-only kernel inputs."""
    window: int
    col_tiles: int
    cap: int
    rows: np.ndarray
    ell_width: int
    is_longrow: bool
    pos: np.ndarray            # (R, ell) flat gather into A's nnz arrays
    valid: np.ndarray          # (R, ell) bool
    a_rows: jax.Array          # (R, ell) int32 — B-row ids
    a_starts: jax.Array        # (R, ell) int32
    a_lens: jax.Array          # (R, ell) int32
    row_lo: jax.Array          # (R, 1) int32
    cost: np.ndarray           # (R,) int64 per-row estimated product counts
    bin_id: int                # position in the plan's bin ladder (stable
                               # across sharding; shard slices keep it)
    n_valid: int               # real rows; kernel rows beyond this are
                               # inert shape-bucketing padding (a_lens == 0)
    p_cap: int                 # static product capacity. The base plan's
                               # bins carry the bin-level pow2 cover; shard
                               # slices carry the per-rung ladder value
                               # (partition.rung_capacity_cap) — a pure
                               # function of (bin, rung) so same-rung
                               # slices share one jit specialization


@dataclasses.dataclass
class HashBinExec:
    """One hash-accumulator bin with its structure-only kernel inputs.

    ``table``/``spill``/``tile`` are pure functions of the bin
    (``binning.HashBin`` invariant), never of a shard slice, so every
    slice replays the same kernel specialization. ``f_chunk`` (DMA chunk)
    and ``tile`` (rows probed vectorized per grid step) are the autotuned
    Pallas-path knobs (``core.tuning``), frozen at plan-build time so
    cached plans replay their measured choice.
    """
    table: int
    spill: int
    rows: np.ndarray
    ell_width: int
    pos: np.ndarray            # (R, ell) flat gather into A's nnz arrays
    valid: np.ndarray          # (R, ell) bool
    a_rows: jax.Array          # (R, ell) int32 — B-row ids
    a_starts: jax.Array        # (R, ell) int32
    a_lens: jax.Array          # (R, ell) int32
    cost: np.ndarray           # (R,) int64 per-row estimated product counts
    bin_id: int
    n_valid: int               # real rows; kernel rows beyond are inert
    p_cap: int                 # static product capacity for the XLA path
                               # (bin-level pow2 cover; shard slices carry
                               # the per-rung ladder value)
    f_chunk: int = 128
    tile: int = 8


@dataclasses.dataclass
class EscExec:
    """The ESC bin: precomputed sub-CSR structure + capacities.

    Shard slices of the bin are shape-bucketed (``partition._slice_esc``):
    ``sub_indptr``/``sub_indices``/``src`` may carry inert padding past
    the real rows/nnz so slices share jit specializations; ``n_valid``
    (== ``len(rows)``) tells the executor where real rows end.
    """
    rows: np.ndarray
    sub_indptr: np.ndarray     # (padded_rows+1,)
    sub_indices: np.ndarray    # gathered column ids (structure-only)
    src: np.ndarray            # flat gather into A's values
    p_cap: int
    out_cap: int
    cost: np.ndarray           # per-row estimated product counts
    n_valid: int               # real rows; indptr rows beyond are padding


@dataclasses.dataclass
class ExecutionPlan:
    """Everything value-independent about one (A-pattern, B-pattern) pair.

    Reusable across values-only updates; ``execute_plan`` consumes it.
    """
    key: Optional[str]
    shape_a: Tuple[int, int]
    shape_b: Tuple[int, int]
    workflow: str
    assisted: bool
    hybrid: bool
    cfg: OceanConfig
    products: np.ndarray       # (m,) int64 per-row intermediate products
    out_lo: np.ndarray         # (m,) output col-range lower bounds
    dense: List[DenseBinExec]
    esc: Optional[EscExec]
    empty_rows: np.ndarray
    bins_describe: Dict[str, int]
    # analysis summary surfaced into reports
    er: float
    sampled_cr: Optional[float]
    nproducts_avg: float
    total_products: int
    m_regs: int
    b_sketches: Optional[jax.Array]
    hash: List[HashBinExec] = dataclasses.field(default_factory=list)
    build_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    # bytes the build moved between host and device (OceanReport.copy_bytes
    # of the call that built the plan counts them)
    build_copy_bytes: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # how the analysis stage ran when this plan was built (surfaced into
    # OceanReport on every execution of the plan)
    analysis_shards: int = 1
    analysis_shard_seconds: Optional[List[float]] = None
    # built from exact feed-forward sizes (workflow 'known'): estimation
    # and the symbolic pass were skipped when this plan was planned
    feed_forward: bool = False
    # binning prework overlapped with analysis wave 2 at build time (see
    # OceanReport.wave2_overlap_seconds)
    wave2_overlap_seconds: float = 0.0
    wave2_overlapped: bool = False
    # the per-row size prediction binning consumed (float64; HLL estimate,
    # symbolic exact, product upper bound, or clamped feed-forward sizes
    # depending on workflow) — kept so the executor can measure
    # estimate-vs-exact accuracy after the numeric pass
    pred_row_nnz: Optional[np.ndarray] = None
    # workflow-decision audit record (repro.obs.accuracy.record_decision)
    decision: Optional[Dict] = None

    def reuse_b_sketches(self) -> Dict:
        """Seed a sketch cache from this plan for later builds against the
        same B (pass as ``sketch_cache=`` to ``build_plan``/``analyze``)."""
        cache: Dict = {}
        if self.b_sketches is not None:
            cache[(self.m_regs, self.cfg.seed)] = self.b_sketches
        return cache


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def structure_key(a: CSR, b: CSR, cfg: OceanConfig,
                  force_workflow: Optional[str], assisted: bool,
                  hybrid: bool,
                  known_sizes: Optional[np.ndarray] = None) -> str:
    """Cache key: hash of both sparsity patterns + every planning knob.

    O(nnz) hashing — orders of magnitude cheaper than re-running analysis,
    prediction, and binning. Values are deliberately excluded: plans are
    structure-only. ``known_sizes`` (feed-forward exact sizing) is hashed
    in when present: the sizes are a pure function of the structure pair
    when trusted, but a caller-supplied array of unknown provenance must
    not alias the clean key.
    """
    h = hashlib.blake2b(digest_size=16)
    for m in (a, b):
        h.update(np.ascontiguousarray(np.asarray(m.indptr)).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(m.indices)[: m.nnz]).tobytes())
        h.update(repr(m.shape).encode())
    h.update(repr((cfg, force_workflow, assisted, hybrid)).encode())
    if known_sizes is not None:
        h.update(b"|known|")
        h.update(np.ascontiguousarray(
            np.asarray(known_sizes, np.int64)).tobytes())
    return h.hexdigest()


def build_plan(a: CSR, b: CSR, cfg: OceanConfig = OceanConfig(), *,
               force_workflow: Optional[str] = None, assisted: bool = True,
               hybrid: bool = True, analysis: Optional[AnalysisResult] = None,
               sketch_cache: Optional[Dict] = None,
               key: Optional[str] = None,
               analysis_devices=None,
               known_sizes: Optional[np.ndarray] = None) -> ExecutionPlan:
    """Run analysis -> size prediction -> binning and freeze the result.

    ``analysis_devices`` partitions the analysis stage across a device set
    (``core.analysis.AnalysisPipeline``) and, on the estimation workflow,
    the prediction stage's sketch merge too
    (``analysis.sharded_merge_estimate``); both stages' output — and hence
    the plan — is bit-identical to the single-device run, which is why the
    plan-cache key deliberately excludes it.

    ``known_sizes`` (per-row exact output nnz, fed forward from a prior
    numeric pass over the same pattern pair) selects the ``"known"``
    workflow: analysis skips sketching/sampling, the prediction stage is
    free (the sizes *are* the prediction), and binning treats them as
    symbolic-grade exact statistics. A stale feed never corrupts results —
    undersized bins fall back to the exact ESC pass like any other
    overflow.
    """
    stage: Dict[str, float] = {}

    # Binning/prediction prework slotted behind analysis wave 2 — host work
    # decidable from wave-1 products alone, run while the wave-2 launches
    # (output ranges / sketches) are still in flight:
    #   * upper_bound territory: the ESC bin's membership and gather
    #     structure are pure functions of the product counts. The binning
    #     stage below reuses the prework only after verifying the
    #     recomputed ESC row set matches — a mismatch (never expected)
    #     just falls back to recomputing.
    #   * certain-symbolic territory (ER already below threshold, so
    #     Table 1 cannot pick estimation no matter what the sampled CR
    #     says): the whole symbolic prediction runs here via the host
    #     twin of the exact sort (CPU backend only — elsewhere the device
    #     sort is the right tool and overlaps on its own).
    # Per-row A nnz (binning input) is computed here on every path.
    prework: Dict[str, object] = {}

    def _wave2_prework(prod_host: np.ndarray) -> None:
        ptr = np.asarray(a.indptr, np.int64)
        prework["a_row_nnz"] = ptr[1:] - ptr[:-1]
        if known_sizes is not None:
            return
        prods = np.asarray(prod_host, np.int64)
        total = int(prods.sum())
        avg = total / max(a.m, 1)
        if force_workflow in (None, "upper_bound") and hybrid and (
                force_workflow == "upper_bound"
                or avg < cfg.upper_bound_avg_products):
            from .binning import ESC_THRESHOLD
            esc_rows = np.nonzero((prods > 0) & (prods < ESC_THRESHOLD))[0]
            sub_ptr, src = flat_gather_index(a.indptr, esc_rows)
            prework.update(
                esc_rows=esc_rows, sub_ptr=sub_ptr, src=src,
                p_cap=pow2_at_least(int(prods[esc_rows].sum()), floor=64))
            return
        er = total / max(a.nnz, 1)
        certain_symbolic = (force_workflow == "symbolic"
                            or (force_workflow is None
                                and avg >= cfg.upper_bound_avg_products
                                and er < cfg.er_threshold))
        if certain_symbolic and jax.default_backend() == "cpu":
            prework["symbolic_pred"] = np.asarray(
                esc_mod.symbolic_exact_host(
                    a.indptr, a.indices, b.indptr, b.indices,
                    num_rows_a=a.m, n_cols_b=b.n), np.float64)

    # ---------------- analysis ----------------
    copies = new_copy_bytes()
    with trace.span("plan.analysis") as sp:
        t0 = time.perf_counter()
        ov_s, ov_pending = 0.0, False
        if analysis is None:
            analysis = analyze(a, b, cfg, sketch_cache=sketch_cache,
                               devices=analysis_devices,
                               known_sizes=known_sizes,
                               overlap_work=_wave2_prework)
            ov_s = analysis.wave2_overlap_seconds
            ov_pending = analysis.wave2_overlapped
            copies = dict(analysis.copy_bytes)
        if known_sizes is None and analysis.known_sizes is not None:
            known_sizes = analysis.known_sizes
        # exact feed-forward sizes trump both Table-1 selection and
        # ablation forcing: there is nothing left to estimate
        wf = ("known" if known_sizes is not None
              else (force_workflow or analysis.workflow))
        products = np.asarray(analysis.products_row, np.int64)
        total_products = analysis.total_products
        out_lo = np.asarray(analysis.out_lo)
        out_hi = np.asarray(analysis.out_hi)
        a_row_nnz = prework.get("a_row_nnz")
        if a_row_nnz is None:
            ptr = np.asarray(a.indptr, np.int64)
            a_row_nnz = ptr[1:] - ptr[:-1]
        stage["analysis"] = time.perf_counter() - t0
        sp.measured(t0, stage["analysis"]).set(workflow=wf)

    # ---------------- size prediction ----------------
    with trace.span("plan.prediction") as sp:
        t0 = time.perf_counter()
        sketches = analysis.b_sketches
        if wf == "known":
            # feed-forward: the exact sizes are the prediction, at zero
            # cost. A stale/elided feed can report 0 for a row that is
            # provably non-empty (products > 0 implies structural nnz >= 1);
            # clamp to 1 so capacity ladders never size a live row's table
            # from 0 and the overflow fallback stays a correction, not a
            # crutch.
            pred = np.asarray(known_sizes, np.float64)
            pred = np.where(products > 0, np.maximum(pred, 1.0), 0.0)
            pred = np.minimum(pred, products)
        elif wf == "estimation":
            if sketches is None:
                sketches = sketches_for(b, analysis.m_regs, cfg.seed,
                                        sketch_cache)
            # Sentinel concat padded to the pow2 row bucket: rows past b.m
            # are all-zero (the HLL identity / Pallas pad sentinel), so
            # values are untouched while the merge-stage jit specialization
            # stays shared across matrices in the same bucket.
            rb_pad = pow2_at_least(max(b.m, 1), floor=SHARD_ROW_FLOOR)
            sk = jnp.concatenate(
                [sketches, jnp.zeros((rb_pad + 1 - sketches.shape[0],
                                      sketches.shape[1]), jnp.int32)],
                axis=0)
            est = sharded_merge_estimate(a, sk, clip_max=b.n,
                                         devices=analysis_devices,
                                         copies=copies)
            pred = np.maximum(np.asarray(est, np.float64), 1.0)
            pred = np.where(products > 0, pred, 0.0)
            pred = np.minimum(pred, products)  # distinct count <= products
        elif wf == "symbolic":
            pred = prework.get("symbolic_pred")
            if pred is None and jax.default_backend() == "cpu":
                # Device dispatch plus the pow2-padded device sort dominate
                # fresh-plan latency on CPU; the host twin sorts the exact
                # product count and is bit-identical (see
                # symbolic_exact_host).
                pred = np.asarray(esc_mod.symbolic_exact_host(
                    a.indptr, a.indices, b.indptr, b.indices,
                    num_rows_a=a.m, n_cols_b=b.n), np.float64)
            elif pred is None:
                p_cap = pow2_at_least(total_products, floor=64)
                pred = to_host(
                    esc_mod.symbolic_exact(a.indptr, a.indices, b.indptr,
                                           b.indices, p_cap=p_cap,
                                           num_rows_a=a.m),
                    copies).astype(np.float64)
        else:  # upper_bound
            pred = products.astype(np.float64)
        stage["prediction"] = time.perf_counter() - t0
        sp.measured(t0, stage["prediction"])

    # ---------------- binning ----------------
    with trace.span("plan.binning") as sp:
        t0 = time.perf_counter()
        assisted_cr = (analysis.conservative_cr
                       if (assisted and wf == "upper_bound"
                           and analysis.cr_mean) else None)
        # the hash rung rides the hybrid-accumulator switch (V1/V2
        # ablations disable it with ESC) plus its own config knob; the
        # measured load factor steers how binning sizes primary tables
        hash_enabled = hybrid and cfg.hash_rung
        ref_tuned = (tuning_mod.hash_tuning_for(tuning_mod.REFERENCE_RUNG)
                     if hash_enabled else tuning_mod.DEFAULT_TUNING)
        plan = plan_bins(pred, products, out_lo, out_hi, a_row_nnz, b.n,
                         expansion=cfg.expansion_for(analysis.m_regs),
                         workflow=wf, esc_enabled=hybrid,
                         assisted_cr=assisted_cr,
                         hash_enabled=hash_enabled,
                         load_factor=ref_tuned.load_factor,
                         tile_rows=ref_tuned.tile_rows)
        if not hybrid:
            # V1/V2: long rows fall back to the global ESC pass instead of
            # the column-tiled kernel (the paper's 'nonadaptive global
            # kernel').
            longrow_rows = np.concatenate(
                [bn.rows for bn in plan.dense_bins if bn.is_longrow]
                or [np.zeros(0, np.int64)])
            plan = BinPlan(
                dense_bins=[bn for bn in plan.dense_bins
                            if not bn.is_longrow],
                esc_rows=np.concatenate([plan.esc_rows, longrow_rows]),
                esc_caps=np.concatenate(
                    [plan.esc_caps, products[longrow_rows]]),
                empty_rows=plan.empty_rows, hash_bins=plan.hash_bins)

        # Freeze per-bin structure: gather maps + value-independent ELL
        # blocks, the latter committed to the device.
        dense_execs: List[DenseBinExec] = []
        for bin_id, bn in enumerate(plan.dense_bins):
            pos, valid, a_rows, a_starts, a_lens = kops.prep_bin_structure(
                a, b, bn.rows, bn.ell_width)
            lo_arr = (out_lo[bn.rows] if not bn.is_longrow
                      else np.zeros(len(bn.rows)))
            row_lo = to_device(lo_arr.reshape(-1, 1).astype(np.int32),
                               copies)
            bin_products = int(np.asarray(a_lens, np.int64).sum())
            dense_execs.append(DenseBinExec(
                window=bn.window, col_tiles=bn.col_tiles, cap=bn.cap,
                rows=bn.rows, ell_width=bn.ell_width,
                is_longrow=bn.is_longrow, pos=pos, valid=valid,
                a_rows=to_device(a_rows, copies),
                a_starts=to_device(a_starts, copies),
                a_lens=to_device(a_lens, copies),
                row_lo=row_lo, cost=np.asarray(bn.cost, np.int64),
                bin_id=bin_id, n_valid=len(bn.rows),
                p_cap=pow2_at_least(bin_products, floor=64)))

        hash_execs: List[HashBinExec] = []
        for hash_id, hb in enumerate(plan.hash_bins):
            pos, valid, a_rows, a_starts, a_lens = kops.prep_bin_structure(
                a, b, hb.rows, hb.ell_width)
            bin_products = int(np.asarray(a_lens, np.int64).sum())
            tuned = tuning_mod.hash_tuning_for(hb.table)
            hash_execs.append(HashBinExec(
                table=hb.table, spill=hb.spill, rows=hb.rows,
                ell_width=hb.ell_width, pos=pos, valid=valid,
                a_rows=to_device(a_rows, copies),
                a_starts=to_device(a_starts, copies),
                a_lens=to_device(a_lens, copies),
                cost=np.asarray(hb.cost, np.int64),
                bin_id=len(dense_execs) + hash_id, n_valid=len(hb.rows),
                p_cap=pow2_at_least(bin_products, floor=64),
                f_chunk=tuned.f_chunk, tile=tuned.tile_rows))

        esc_exec = None
        if len(plan.esc_rows):
            rows = plan.esc_rows
            if (prework.get("esc_rows") is not None
                    and np.array_equal(prework["esc_rows"], rows)):
                # the wave-2-overlapped prework computed this row set
                sub_ptr, src = prework["sub_ptr"], prework["src"]
                p_cap = prework["p_cap"]
            else:
                sub_ptr, src = flat_gather_index(a.indptr, rows)
                p_cap = pow2_at_least(int(products[rows].sum()), floor=64)
            esc_exec = EscExec(
                rows=rows, sub_indptr=sub_ptr.astype(np.int32),
                sub_indices=np.asarray(a.indices)[src], src=src,
                p_cap=p_cap, out_cap=p_cap,
                cost=np.asarray(plan.esc_costs, np.int64),
                n_valid=len(rows))
        stage["binning"] = time.perf_counter() - t0
        sp.measured(t0, stage["binning"])

    decision = obs_accuracy.record_decision(
        workflow=wf, forced=force_workflow, feed_forward=(wf == "known"),
        er=analysis.er, sampled_cr=analysis.sampled_cr,
        nproducts_avg=analysis.nproducts_avg, cfg=cfg)

    return ExecutionPlan(
        key=key, shape_a=a.shape, shape_b=b.shape, workflow=wf,
        assisted=assisted, hybrid=hybrid, cfg=cfg, products=products,
        out_lo=out_lo, dense=dense_execs, esc=esc_exec, hash=hash_execs,
        empty_rows=plan.empty_rows, bins_describe=plan.describe(),
        er=analysis.er, sampled_cr=analysis.sampled_cr,
        nproducts_avg=analysis.nproducts_avg, total_products=total_products,
        m_regs=analysis.m_regs, b_sketches=sketches
        if wf == "estimation" else analysis.b_sketches,
        build_seconds=stage, build_copy_bytes=copies,
        analysis_shards=analysis.n_shards,
        analysis_shard_seconds=analysis.shard_seconds,
        feed_forward=(wf == "known"),
        wave2_overlap_seconds=ov_s, wave2_overlapped=ov_pending,
        pred_row_nnz=np.asarray(pred, np.float64), decision=decision)


# ---------------------------------------------------------------------------
# Executor entry points (thin wrappers over core.executor)
# ---------------------------------------------------------------------------
#
# The dispatch/collect/merge pipeline lives in ``core.executor``; these
# wrappers exist so the established ``planner.execute_plan`` /
# ``planner.execute_sharded_plan`` call sites keep working. The import is
# function-local because executor imports the plan containers from here.

def execute_plan(plan: ExecutionPlan, a: CSR, b: CSR, *,
                 stage: Optional[Dict[str, float]] = None,
                 cache_hit: bool = False,
                 executor: str = "pipelined",
                 post=None, copy_bytes: Optional[Dict[str, int]] = None,
                 ) -> Tuple[CSR, OceanReport]:
    """Run a frozen plan against (possibly new) values of A and B.

    ``post`` (a :class:`~repro.core.executor.MergePostOps`) fuses
    mask/transform/prune/normalize stages into the executor's merge."""
    from .executor import execute_plan as _execute
    return _execute(plan, a, b, stage=stage, cache_hit=cache_hit,
                    executor=executor, post=post, copy_bytes=copy_bytes)


def execute_sharded_plan(splan, a: CSR, b: CSR, *,
                         stage: Optional[Dict[str, float]] = None,
                         cache_hit: bool = False,
                         executor: str = "pipelined",
                         post=None,
                         copy_bytes: Optional[Dict[str, int]] = None,
                         ) -> Tuple[CSR, OceanReport]:
    """Run a :class:`~repro.core.partition.ShardedPlan` across its devices
    through the unified executor pipeline."""
    from .executor import execute_sharded_plan as _execute
    return _execute(splan, a, b, stage=stage, cache_hit=cache_hit,
                    executor=executor, post=post, copy_bytes=copy_bytes)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

class PlanCache:
    """Thread-safe LRU cache keyed by structure hash.

    Holds :class:`ExecutionPlan` entries and, for device-partitioned
    execution, :class:`~repro.core.partition.ShardedPlan` entries under
    keys extended with the device topology.

    Multi-tenant serving (``repro.serving``) shares one PlanCache across
    tenants through :meth:`namespaced` views: every tenant's keys live
    under a private prefix (identical structures never collide across
    tenants), and inserts are tagged with the owning tenant so eviction
    can be fairness-aware. With ``tenant_quota`` set, a tenant that
    exceeds its quota evicts *its own* least-recently-used entry first;
    only then does the global ``maxsize`` LRU bound apply across all
    tenants. A hot tenant therefore cannot flush the whole cache — it
    recycles its own slots while colder tenants keep theirs warm."""

    def __init__(self, maxsize: int = 32,
                 tenant_quota: Optional[int] = None):
        self.maxsize = maxsize
        self.tenant_quota = tenant_quota
        self._plans: "OrderedDict[str, object]" = OrderedDict()
        self._tenant_of: Dict[str, str] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def lookup(self, key: str):
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return plan

    def peek(self, key: str):
        """Non-counting lookup — internal reuse (e.g. partitioning a
        cached base plan for a new device topology) must not skew the
        request-level hit/miss statistics. Still refreshes LRU recency:
        a base plan hot via sharded derivations must not be evicted as
        cold."""
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
            return plan

    def insert(self, key: str, plan, tenant: Optional[str] = None) -> None:
        with self._lock:
            self._plans[key] = plan
            self._plans.move_to_end(key)
            if tenant is not None:
                self._tenant_of[key] = tenant
            else:
                self._tenant_of.pop(key, None)
            if tenant is not None and self.tenant_quota:
                # fairness first: an over-quota tenant recycles its own
                # LRU slot instead of pushing another tenant's plan out
                mine = [k for k in self._plans
                        if self._tenant_of.get(k) == tenant]
                for k in mine[:max(0, len(mine) - self.tenant_quota)]:
                    del self._plans[k]
                    del self._tenant_of[k]
            while len(self._plans) > self.maxsize:
                k, _ = self._plans.popitem(last=False)
                self._tenant_of.pop(k, None)

    def namespaced(self, tenant: str) -> "TenantPlanCache":
        """A per-tenant view of this cache (see :class:`TenantPlanCache`)."""
        return TenantPlanCache(self, tenant)

    def tenant_sizes(self) -> Dict[str, int]:
        """Live entry count per tenant (untagged entries excluded)."""
        with self._lock:
            out: Dict[str, int] = {}
            for k in self._plans:
                t = self._tenant_of.get(k)
                if t is not None:
                    out[t] = out.get(t, 0) + 1
            return out

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self._tenant_of.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def stats(self) -> Dict[str, int]:
        # snapshot under the lock: unlocked reads next to locked writers
        # could observe a hits/misses/size triple that never existed
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "size": len(self._plans)}


class TenantPlanCache:
    """Per-tenant namespace view over a shared :class:`PlanCache`.

    Prefixes every key with the tenant id — two tenants multiplying the
    *same* structures get separate entries (no cross-tenant plan leakage,
    and one tenant's eviction pressure is attributable to it) — and tags
    inserts with the tenant so the base cache's fairness policy
    (per-tenant quota before global LRU) applies. Exposes the same
    ``lookup``/``peek``/``insert`` surface ``ocean_spgemm`` consumes, so
    a view drops straight in as ``cache=``.
    """

    _SEP = "\x1f"  # never appears in hex structure keys or topology keys

    def __init__(self, base: PlanCache, tenant: str):
        self.base = base
        self.tenant = tenant

    def _k(self, key: str) -> str:
        return f"{self.tenant}{self._SEP}{key}"

    def lookup(self, key: str):
        return self.base.lookup(self._k(key))

    def peek(self, key: str):
        return self.base.peek(self._k(key))

    def insert(self, key: str, plan) -> None:
        self.base.insert(self._k(key), plan, tenant=self.tenant)

    def stats(self) -> Dict[str, int]:
        return self.base.stats()

    def __len__(self) -> int:
        return self.base.tenant_sizes().get(self.tenant, 0)


DEFAULT_PLAN_CACHE = PlanCache()
