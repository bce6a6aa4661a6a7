"""Unified async SpGEMM executor: one dispatch -> collect -> merge pipeline.

Ocean's thesis is that serial setup cost must be driven off the SpGEMM
critical path. After the planner split, the remaining serial tax lived in
the executors: ``core.planner`` carried two near-duplicate functions
(single-device and device-partitioned) that both ran the host merge — slab
pull, overflow scan, CSR compaction — strictly *after* a global barrier on
all device work. This module replaces both with one staged pipeline:

* **dispatch** — enqueue every (shard, bin) kernel launch on its device
  without blocking (jax dispatch is asynchronous) and start async
  device-to-host copies of each result slab;
* **collect** — pull slabs back in *completion order* (per-slab
  ``jax.Array`` readiness, not one global barrier);

The dispatch/collect primitives themselves (``Launch``, async D2H start,
completion-order iteration) live in ``core.dispatch`` — they are the
repo-wide substrate for any sharded stage (``core.analysis`` runs its
device-partitioned analysis stages through the same helpers);
* **merge** — as each slab lands, run its overflow scan and the
  incremental half of compaction on the host while later slabs are still
  being computed/copied. Only the exact-ESC overflow fallback and the
  final scatter wait for the full set.

The merged CSR is bit-identical to the serial path: slabs are row-disjoint,
every kernel's per-row output is independent of which other rows share the
launch, and compaction is order-independent, so neither completion order
nor shard shape can change a byte of the output (property-tested in
``tests/test_executor.py``).

``OceanReport.overlap_seconds`` counts host-merge work performed before
the final slab was collected — exactly the work the serial executor
serializes after its global barrier. On asynchronous backends (real
accelerators) that is merge work overlapped with outstanding device
compute/copies; on a synchronous host it still measures how much of the
merge the pipeline moved off the post-barrier critical path.
``merge_overlap_frac`` is the same as a fraction of all merge work.

The ``"threaded"`` executor goes one step further: a dedicated merge
worker thread runs the overflow scan + incremental compaction
(:class:`_MergeState`) while the collect loop keeps pulling slabs — so
merge/collect overlap happens even when the collect loop is pinned
blocking on a device queue, not only between ``is_ready`` polls. The
worker is the *sole* mutator of the merge state and ``_MergeState`` is
add-order-independent (overflow keyed by dispatch order, kept slabs and
column-sum partials sorted by dispatch order at finalize), so
serial == pipelined == threaded bit for bit, overflow fallback and
``MergePostOps`` included.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.kernels import ops as kops
from repro.obs import accuracy as obs_accuracy
from repro.obs import trace
from . import esc as esc_mod
from .dispatch import (Launch, collect_in_completion_order, device_context,
                       new_copy_bytes, start_async_host_copies, to_device,
                       to_host)
from .esc import EscOverflowError
from .formats import (CSR, PAD_COL, csr_from_arrays, csr_rows_to_ell,
                      flat_gather_index, pow2_at_least)
from .planner import (DenseBinExec, EscExec, ExecutionPlan, HashBinExec,
                      OceanReport)

SERIAL = "serial"
PIPELINED = "pipelined"
THREADED = "threaded"
EXECUTORS = (PIPELINED, THREADED, SERIAL)


class _Slab:
    """Per-row output fragments: row ids + fixed-width (cols, vals, nnz)."""

    def __init__(self, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 nnz: np.ndarray):
        self.rows, self.cols, self.vals, self.nnz = rows, cols, vals, nnz


# ---------------------------------------------------------------------------
# Fused merge post-processing (graph workloads: mask / inflate / prune)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MergePostOps:
    """Post-processing fused into the executor's merge/compaction.

    Applied to each result slab as it lands on the host — in the pipelined
    executor this overlaps still-outstanding device work — replacing
    separate host passes over an assembled CSR (``repro.graph.ops`` builds
    these for masked multiply, boolean semirings, and MCL inflation):

    * ``mask_indptr``/``mask_indices``: keep only entries whose (row, col)
      is present in the mask pattern — ``mask .* (A @ B)`` without ever
      materializing the unmasked product on the host.
    * ``transform``: elementwise value map (Hadamard power for MCL
      inflation, ``sign`` for boolean semirings). Sound per slab because
      each (row, col) entry is fully accumulated within exactly one slab.
    * ``col_normalize``: divide every entry by its column's total of
      post-transform values. Column sums need the whole slab set, so each
      slab contributes a partial as it lands and the partials fold in
      dispatch order at compaction time — completion order can never
      change a byte of the output.
    * ``threshold``: drop entries with ``|value| < threshold`` (applied
      after normalization when ``col_normalize`` is set, else per slab).

    Stage order: mask -> transform -> [colsum partial] -> prune/normalize.
    Overflow scanning always runs on the *unfiltered* per-row counts, so
    fused post-ops never change which rows take the exact-ESC fallback.
    """
    n_cols: int
    mask_indptr: Optional[np.ndarray] = None
    mask_indices: Optional[np.ndarray] = None
    transform: Optional[Callable[[np.ndarray], np.ndarray]] = None
    threshold: float = 0.0
    col_normalize: bool = False

    def __post_init__(self):
        self._mask_keys = None
        if self.mask_indptr is not None:
            ptr = np.asarray(self.mask_indptr, np.int64)
            nnz = int(ptr[-1])
            idx = np.asarray(self.mask_indices, np.int64)[:nnz]
            rows = np.repeat(np.arange(len(ptr) - 1, dtype=np.int64),
                             np.diff(ptr))
            # rows ascend and columns ascend within a CSR row, so the keys
            # arrive sorted; sort defensively for caller-built masks
            self._mask_keys = np.sort(rows * np.int64(self.n_cols) + idx)

def _compact_rows(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                  keep: np.ndarray) -> _Slab:
    """Shift kept entries left into a fresh fixed-width slab (order — and
    hence intra-row column sorting — preserved)."""
    new_nnz = keep.sum(axis=1).astype(np.int64)
    w2 = max(int(new_nnz.max()) if len(new_nnz) else 0, 1)
    out_cols = np.full((keep.shape[0], w2), PAD_COL, np.int32)
    out_vals = np.zeros((keep.shape[0], w2), vals.dtype)
    ri, ci = np.nonzero(keep)
    dest = (np.cumsum(keep, axis=1) - 1)[ri, ci]
    out_cols[ri, dest] = cols[ri, ci]
    out_vals[ri, dest] = vals[ri, ci]
    return _Slab(rows, out_cols, out_vals, new_nnz)


def _filter_slab(slab: _Slab, post: MergePostOps
                 ) -> Tuple[_Slab, Optional[np.ndarray]]:
    """Apply the per-slab half of the post-ops (mask, transform, eager
    prune) and return the filtered slab plus its column-sum partial."""
    r, w = slab.cols.shape
    if r == 0:
        return slab, (np.zeros(post.n_cols, np.float64)
                      if post.col_normalize else None)
    slot = np.arange(w, dtype=np.int64)[None, :]
    keep = (slot < slab.nnz[:, None]) & (slab.cols != PAD_COL)
    vals = slab.vals
    if post._mask_keys is not None:
        keys = (slab.rows[:, None].astype(np.int64) * np.int64(post.n_cols)
                + slab.cols.astype(np.int64))
        pos = np.searchsorted(post._mask_keys, keys)
        member = np.zeros(keys.shape, bool)
        in_rng = pos < len(post._mask_keys)
        member[in_rng] = post._mask_keys[pos[in_rng]] == keys[in_rng]
        keep &= member
    if post.transform is not None:
        # zero out dropped slots first so transforms need not map 0 -> 0
        vals = np.where(keep, post.transform(np.where(keep, vals, 0)), 0)
        vals = vals.astype(slab.vals.dtype, copy=False)
    eager_prune = post.threshold > 0.0 and not post.col_normalize
    if eager_prune:
        keep &= np.abs(vals) >= post.threshold
    colsum = None
    if post.col_normalize:
        colsum = np.zeros(post.n_cols, np.float64)
        np.add.at(colsum, slab.cols[keep].astype(np.int64),
                  vals[keep].astype(np.float64))
    if post._mask_keys is None and not eager_prune:
        # values-only post (bool/inflate transforms): no entry can drop
        # here, so skip the row re-compaction in the merge hot path
        return _Slab(slab.rows, slab.cols, vals, slab.nnz), colsum
    return _compact_rows(slab.rows, slab.cols, vals, keep), colsum


def _esc_to_slab(res, rows: np.ndarray, num_rows: int,
                 out_cap: int, copies: Dict[str, int]) -> Tuple[_Slab, int]:
    """Convert an ESCResult over a row subset, on the device, into a slab
    on the host: the count and the row pointers come down, the rows are
    laid out as ELL on the device, and the ELL blocks come down. The
    ``exec.esc`` span covers all of it, the wait for the pass included."""
    with trace.span("exec.esc"):
        nnz = esc_mod.ensure_esc_capacity(to_host(res.nnz, copies), out_cap,
                                          where="ESC shard")
        # shape-bucketed ESC shards carry inert pad rows past num_rows
        # (zero counts by construction); slice them off before slab assembly
        ptr = to_host(res.indptr, copies).astype(np.int64)
        counts = (ptr[1:] - ptr[:-1])[:num_rows]
        width = int(counts.max()) if len(counts) else 1
        width = max(width, 1)
        ell_i, ell_v = csr_rows_to_ell(res.indptr, res.indices, res.values,
                                       num_rows=num_rows, ell_width=width,
                                       pad_index=int(PAD_COL))
        return _Slab(rows, to_host(ell_i, copies), to_host(ell_v, copies),
                     counts), nnz


def _upload_csr(indptr: np.ndarray, indices: np.ndarray, values: np.ndarray,
                shape: Tuple[int, int], copies: Dict[str, int]) -> CSR:
    """``csr_from_arrays`` of host arrays, counting the bytes that land."""
    c = csr_from_arrays(indptr, indices, values, shape)
    copies["h2d"] += c.indptr.nbytes + c.indices.nbytes + c.values.nbytes
    return c


def _gather_ell_values(exec_, a_values: np.ndarray,
                       copies: Dict[str, int]) -> jax.Array:
    """Value half of ELL bin input prep, shared by the dense and hash bin
    runners: replay the bin's frozen flat-gather map over (possibly new)
    A values and commit the ELL block."""
    return to_device(kops.gather_bin_values(a_values, exec_.pos,
                                            exec_.valid), copies)


def _prep_shard_b(b: CSR, b_cols_host, b_vals_host, shard: "_ShardWork",
                  multi: bool, copies: Dict[str, int]):
    """Per-shard B-side inputs shared by every bin family: the padded
    flat arrays the dense/hash kernels stream (shipped to the shard's
    device when more than one shard participates) plus the raw CSR
    triple the ESC pass consumes (device-committed only when the shard
    actually has an ESC bin — ``None`` means "use host arrays")."""
    if not (multi and shard.device is not None):
        return b_cols_host, b_vals_host, None
    b_cols_pad = to_device(b_cols_host, copies, shard.device)
    b_vals_pad = to_device(b_vals_host, copies, shard.device)
    b_esc = (tuple(to_device(x, copies, shard.device)
                   for x in (b.indptr, b.indices, b.values))
             if shard.esc is not None else None)
    return b_cols_pad, b_vals_pad, b_esc


def _run_dense_bin(be: DenseBinExec, a_values: np.ndarray, b_cols_pad,
                   b_vals_pad, copies: Dict[str, int]):
    """Dispatch one dense bin; returns device arrays (cols, vals, nnz).

    Results are per-row independent, so any row subset of a bin produces
    the same per-row output as the full bin — the property device
    partitioning relies on for bit-identical merges. Shape-bucketed shard
    slices carry inert pad rows (``a_lens == 0``: the kernel does no work
    for them) and a per-rung ``p_cap`` (``partition.rung_capacity_cap``,
    a pure function of (bin, rung)) so every same-rung slice of one bin
    replays a single jit specialization.
    """
    a_vals = _gather_ell_values(be, a_values, copies)
    return kops.dense_bin_op(
        be.a_rows, a_vals, be.a_starts, be.a_lens, be.row_lo,
        b_cols_pad, b_vals_pad, window=be.window,
        col_tiles=be.col_tiles, cap=be.cap, p_cap=be.p_cap)


def _run_hash_bin(hb: HashBinExec, a_values: np.ndarray, b_cols_pad,
                  b_vals_pad, copies: Dict[str, int]):
    """Dispatch one hash bin; returns device arrays (cols, vals, nnz).

    Same per-row-independence contract as dense bins: each row owns its
    tables, table/spill/f_chunk/tile come from the bin (never the shard),
    and shard slices carry inert pad rows plus the per-rung ``p_cap`` for
    the XLA path — so any row subset replays one jit specialization and
    produces the full bin's per-row output bit for bit.
    """
    a_vals = _gather_ell_values(hb, a_values, copies)
    return kops.hash_bin_op(
        hb.a_rows, a_vals, hb.a_starts, hb.a_lens, b_cols_pad, b_vals_pad,
        table=hb.table, spill=hb.spill, p_cap=hb.p_cap,
        f_chunk=hb.f_chunk, tile=hb.tile)


def _run_esc_bin(ex: EscExec, a_values: np.ndarray, b: CSR,
                 copies: Dict[str, int], *,
                 b_arrays: Optional[Tuple] = None):
    """Dispatch the ESC bin; returns the (device-side) ESCResult.

    ``b_arrays`` overrides ``(b.indptr, b.indices, b.values)`` with
    device-committed copies (the sharded path ships B to each shard's
    device once instead of per call). ``num_rows_a`` comes from the
    sub-indptr length, not ``len(ex.rows)``: shape-bucketed shard slices
    pad the sub-CSR with inert rows so slices of one bin replay a single
    jit specialization (see ``partition._slice_esc``)."""
    b_indptr, b_indices, b_values = (
        b_arrays if b_arrays is not None else (b.indptr, b.indices,
                                               b.values))
    return esc_mod.esc_spgemm(
        *(to_device(x, copies) for x in (ex.sub_indptr, ex.sub_indices,
                                         a_values[ex.src])),
        b_indptr, b_indices, b_values, p_cap=ex.p_cap,
        out_cap=ex.out_cap, num_rows_a=ex.sub_indptr.shape[0] - 1)


def _compact_slabs(slabs: List[_Slab], shape: Tuple[int, int],
                   dtype, copies: Dict[str, int]) -> Tuple[CSR, int]:
    """Scatter row-disjoint slabs into one CSR (order-independent) and
    upload it."""
    m = shape[0]
    counts = np.zeros(m, np.int64)
    for s in slabs:
        counts[s.rows] = s.nnz
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    total = int(indptr[-1])
    out_cols = np.full(total, PAD_COL, np.int32)
    out_vals = np.zeros(total, dtype)
    for s in slabs:
        if not len(s.rows):
            continue
        # flat scatter of each slab's valid slots into the output arrays
        capw = s.cols.shape[1]
        slot = np.arange(capw)[None, :]
        valid = slot < s.nnz[:, None]
        pos = indptr[s.rows][:, None] + slot
        out_cols[pos[valid]] = s.cols[valid]
        out_vals[pos[valid]] = s.vals[valid]
    return _upload_csr(indptr, out_cols, out_vals, shape, copies), total


# ---------------------------------------------------------------------------
# Pipeline stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ShardWork:
    """One device's slice of the launch schedule (the whole plan when
    executing unsharded)."""
    device: Optional[object]
    dense: List[DenseBinExec]
    esc: Optional[EscExec]
    hash: List[HashBinExec] = dataclasses.field(default_factory=list)


def _shards_of_plan(plan: ExecutionPlan) -> List[_ShardWork]:
    return [_ShardWork(device=None, dense=plan.dense, esc=plan.esc,
                       hash=plan.hash)]


def _dispatch(shards: List[_ShardWork], a_values: np.ndarray,
              b: CSR, copies: Dict[str, int]) -> List[Launch]:
    """Dispatch stage: enqueue every (shard, bin) launch without blocking.

    B is padded once on the host and shipped to each shard's device when
    more than one shard participates. Async D2H copies are started for
    every result so the collect stage overlaps transfers with compute.
    Each launch is tagged ``(kind, exec)`` so the merge can tell dense
    slabs (overflow-scanned) from ESC slabs (capacities are upper bounds);
    an ESC launch's ``exec`` is ``(EscExec, ESCResult)``.
    """
    items: List[Launch] = []
    order = 0
    multi = len(shards) > 1
    b_cols_host, b_vals_host = kops.pad_b_flat(b)
    for shard in shards:
        if not shard.dense and not shard.hash and shard.esc is None:
            continue
        with device_context(shard.device):
            b_cols_pad, b_vals_pad, b_esc = _prep_shard_b(
                b, b_cols_host, b_vals_host, shard, multi, copies)
            for be in shard.dense:
                arrays = _run_dense_bin(be, a_values, b_cols_pad, b_vals_pad,
                                        copies)
                items.append(Launch(("dense", be), order, tuple(arrays)))
                order += 1
            for hb in shard.hash:
                arrays = _run_hash_bin(hb, a_values, b_cols_pad, b_vals_pad,
                                       copies)
                items.append(Launch(("hash", hb), order, tuple(arrays)))
                order += 1
            if shard.esc is not None:
                res = _run_esc_bin(shard.esc, a_values, b, copies,
                                   b_arrays=b_esc)
                # only the count and the row pointers come down whole:
                # _esc_to_slab lays the rows out on the device first
                items.append(Launch(("esc", (shard.esc, res)), order,
                                    (res.nnz, res.indptr)))
                order += 1
    start_async_host_copies(items)
    return items


def _materialize(it: Launch, copies: Dict[str, int]) -> _Slab:
    """Pull one pending launch to the host (blocks only on this item) and
    shape it as a slab, dropping any shape-bucketing pad rows."""
    kind, exec_ = it.tag
    if kind in ("dense", "hash"):
        be = exec_
        nv = be.n_valid
        cols, vals, nnz = (to_host(x, copies) for x in it.arrays)
        return _Slab(be.rows, cols[:nv], vals[:nv],
                     nnz[:nv].astype(np.int64))
    ex, res = exec_
    slab, _ = _esc_to_slab(res, ex.rows, len(ex.rows), ex.out_cap, copies)
    return slab


# the overflow-fallback slab's position in the deterministic merge order:
# always after every dispatched launch
_FALLBACK_ORDER = 1 << 31


class _MergeState:
    """Incremental host merge: overflow scanning, fused post-ops, and the
    counting half of compaction, fed one slab at a time."""

    def __init__(self, m_rows: int, post: Optional[MergePostOps] = None):
        self.kept: List[Tuple[int, _Slab]] = []
        self.overflow: Dict[int, np.ndarray] = {}
        # overflow-fallback attribution: which bin family's capacity the
        # overflowed rows broke (estimation-accuracy telemetry)
        self.overflow_causes: Dict[str, int] = {}
        self.post = post
        self.colsum_parts: List[Tuple[int, np.ndarray]] = []
        # exact per-row nnz of the *raw* (pre-mask/pre-prune) product —
        # the feed-forward sizes graph chains record (see OceanReport)
        self.raw_counts = (np.zeros(m_rows, np.int64)
                           if post is not None else None)
        # the call's ESC passes, the ESC bin's and the overflow
        # fallback's: products expanded and product slots (p_cap) allocated
        self.esc_products = 0
        self.esc_slots = 0

    def count_esc(self, products: int, slots: int) -> None:
        self.esc_products += products
        self.esc_slots += slots

    def _admit(self, order: int, slab: _Slab) -> None:
        if self.post is not None:
            slab, colsum = _filter_slab(slab, self.post)
            if colsum is not None:
                self.colsum_parts.append((order, colsum))
        self.kept.append((order, slab))

    def add(self, it: Launch, slab: _Slab) -> None:
        if self.raw_counts is not None:
            # dense-bin nnz counts are exact even past the slab capacity
            # (presence comes from the full accumulator window), so raw
            # sizes are right here. Hash-bin counts for *overflowed* rows
            # are occupied+failed-inserts (an overcount of distinct) —
            # but every overflowed row's count is re-written with the
            # exact value when the fallback slab lands, before finalize,
            # so the fed-forward sizes are exact on every path.
            self.raw_counts[slab.rows] = slab.nnz
        kind, exec_ = it.tag
        if kind == "esc":
            # an ESC bin's row costs are its rows' exact product counts
            ex, _ = exec_
            self.count_esc(int(ex.cost.sum()), ex.p_cap)
        if kind in ("dense", "hash"):  # ESC caps are upper bounds
            over = slab.nnz > slab.cols.shape[1]
            if over.any():
                self.overflow[it.order] = slab.rows[over]
                cause = ("hash_spill" if kind == "hash"
                         else "longrow_slab" if exec_.is_longrow
                         else "dense_window")
                self.overflow_causes[cause] = (
                    self.overflow_causes.get(cause, 0) + int(over.sum()))
                keep = ~over
                slab = _Slab(slab.rows[keep], slab.cols[keep],
                             slab.vals[keep], slab.nnz[keep])
        self._admit(it.order, slab)

    def add_fallback(self, slab: _Slab) -> None:
        if self.raw_counts is not None:
            self.raw_counts[slab.rows] = slab.nnz
        self._admit(_FALLBACK_ORDER, slab)

    def fallback_rows(self) -> Optional[np.ndarray]:
        """Overflowed rows in dispatch order — deterministic regardless of
        the completion order slabs were merged in."""
        if not self.overflow:
            return None
        return np.concatenate(
            [self.overflow[k] for k in sorted(self.overflow)])

    def finalize(self) -> List[_Slab]:
        """Deferred half of the post-ops: fold column-sum partials in
        dispatch order and apply normalization (+ post-normalization
        pruning). A no-op without ``col_normalize``."""
        kept = [s for _, s in sorted(self.kept, key=lambda t: t[0])]
        post = self.post
        if post is None or not post.col_normalize:
            return kept
        colsum = np.zeros(post.n_cols, np.float64)
        for _, part in sorted(self.colsum_parts, key=lambda t: t[0]):
            colsum += part
        out: List[_Slab] = []
        for s in kept:
            if not len(s.rows):
                out.append(s)
                continue
            slot = np.arange(s.cols.shape[1], dtype=np.int64)[None, :]
            valid = slot < s.nnz[:, None]
            denom = colsum[np.clip(s.cols, 0, post.n_cols - 1)
                           .astype(np.int64)]
            # a zero column sum implies every value in the column is zero
            vals = s.vals.astype(np.float64) / np.where(denom == 0.0, 1.0,
                                                        denom)
            vals = np.where(valid, vals, 0.0).astype(s.vals.dtype)
            if post.threshold > 0.0:
                out.append(_compact_rows(
                    s.rows, s.cols, vals,
                    valid & (np.abs(vals) >= post.threshold)))
            else:
                out.append(_Slab(s.rows, s.cols, vals, s.nnz))
        return out


def _run_overflow_fallback(state: _MergeState, products: np.ndarray,
                           a: CSR, b: CSR, a_values: np.ndarray,
                           copies: Dict[str, int]) -> Tuple[int, float]:
    """Re-run overflowed rows through the exact ESC pass (paper §3.2).

    One global pass over all overflow rows; per-row results are independent
    of how rows were grouped, so this matches the serial path bit for bit.
    Returns the rows re-run and the pass's seconds (0 without overflow).
    """
    rows = state.fallback_rows()
    if rows is None:
        return 0, 0.0
    with trace.span("exec.overflow_fallback") as sp:
        t0 = time.perf_counter()
        new_ptr, src = flat_gather_index(to_host(a.indptr, copies), rows)
        sub = _upload_csr(new_ptr, to_host(a.indices, copies)[src],
                          a_values[src], (len(rows), a.n), copies)
        n_products = int(products[rows].sum())
        p_cap = pow2_at_least(n_products, floor=64)
        res = esc_mod.esc_spgemm(
            sub.indptr, sub.indices, sub.values, b.indptr, b.indices,
            b.values, p_cap=p_cap, out_cap=p_cap, num_rows_a=sub.m)
        slab, _ = _esc_to_slab(res, rows, sub.m, p_cap, copies)
        state.add_fallback(slab)
        state.count_esc(n_products, p_cap)
        dt = time.perf_counter() - t0
        sp.measured(t0, dt).set(rows=len(rows))
    return len(rows), dt


# ---------------------------------------------------------------------------
# The collect policies
# ---------------------------------------------------------------------------

def _collect_serial(items: List[Launch], plan: ExecutionPlan, a: CSR,
                    b: CSR, a_values: np.ndarray, stage: Dict[str, float],
                    dispatch_s: float, post: Optional[MergePostOps],
                    copies: Dict[str, int]):
    """Reference semantics: one global barrier, then merge. Keeps the
    legacy stage keys (numeric/overflow/postprocess)."""
    state = _MergeState(a.m, post)
    with trace.span("exec.collect") as sp:
        t0 = time.perf_counter()
        slabs = [(it, _materialize(it, copies)) for it in items]
        dt = time.perf_counter() - t0
        sp.measured(t0, dt)
    stage["numeric"] = dispatch_s + dt
    with trace.span("exec.merge") as sp:
        t0 = time.perf_counter()
        for it, slab in slabs:
            state.add(it, slab)
        sp.measured(t0, time.perf_counter() - t0)
    n_overflow, stage["fallback"] = _run_overflow_fallback(
        state, plan.products, a, b, a_values, copies)
    stage["overflow"] = time.perf_counter() - t0
    with trace.span("exec.compact") as sp:
        t0 = time.perf_counter()
        c, total = _compact_slabs(state.finalize(), (a.m, b.n),
                                  a_values.dtype, copies)
        stage["postprocess"] = time.perf_counter() - t0
        sp.measured(t0, stage["postprocess"])
    return c, total, n_overflow, 0.0, state


def _finish_merge(state: _MergeState, plan: ExecutionPlan, a: CSR, b: CSR,
                  a_values: np.ndarray, stage: Dict[str, float],
                  copies: Dict[str, int]) -> Tuple[CSR, int, int, float]:
    """The merge's tail once every slab is in: the overflow fallback
    (``stage["fallback"]``) and the compaction. Returns C, its nnz, the
    rows re-run and the tail's seconds, which belong to the merge."""
    t0 = time.perf_counter()
    n_overflow, stage["fallback"] = _run_overflow_fallback(
        state, plan.products, a, b, a_values, copies)
    with trace.span("exec.compact") as sp:
        t1 = time.perf_counter()
        c, total = _compact_slabs(state.finalize(), (a.m, b.n),
                                  a_values.dtype, copies)
        t2 = time.perf_counter()
        sp.measured(t1, t2 - t1)
    return c, total, n_overflow, t2 - t0


def _collect_pipelined(items: List[Launch], plan: ExecutionPlan, a: CSR,
                       b: CSR, a_values: np.ndarray,
                       stage: Dict[str, float], dispatch_s: float,
                       post: Optional[MergePostOps],
                       copies: Dict[str, int]):
    """Overlapped collect/merge: slabs are pulled in completion order and
    each one's overflow scan + fused post-ops + count accumulation runs
    while later slabs are still being computed or copied back."""
    state = _MergeState(a.m, post)
    collect_s = merge_s = overlap_s = 0.0
    n_left = len(items)
    traced = trace.enabled()   # hot loop: no attr allocation when off
    for it in collect_in_completion_order(items):
        n_left -= 1
        with trace.span("exec.collect") as sp:
            t0 = time.perf_counter()
            slab = _materialize(it, copies)
            dt_c = time.perf_counter() - t0
            sp.measured(t0, dt_c)
            if traced:
                sp.set(order=it.order, kind=it.tag[0])
        collect_s += dt_c
        with trace.span("exec.merge") as sp:
            t0 = time.perf_counter()
            state.add(it, slab)
            dt = time.perf_counter() - t0
            sp.measured(t0, dt)
            if traced:
                sp.set(order=it.order, overlapped=bool(n_left))
        merge_s += dt
        if n_left:
            # merge work done before the last slab was collected — the
            # serial executor runs all of this after its global barrier;
            # on async backends the outstanding items are still computing
            # or copying while this chunk executes
            overlap_s += dt
    c, total, n_overflow, tail_s = _finish_merge(state, plan, a, b,
                                                 a_values, stage, copies)
    merge_s += tail_s
    stage["dispatch"] = dispatch_s
    stage["collect"] = collect_s
    stage["merge"] = merge_s
    return c, total, n_overflow, overlap_s, state


def _collect_threaded(items: List[Launch], plan: ExecutionPlan, a: CSR,
                      b: CSR, a_values: np.ndarray,
                      stage: Dict[str, float], dispatch_s: float,
                      post: Optional[MergePostOps],
                      copies: Dict[str, int]):
    """Collect with a dedicated merge worker thread.

    The main thread runs the collect loop (completion-order pull +
    materialization) and hands each slab to a worker that runs the
    overflow scan, fused post-ops, and the counting half of compaction —
    so merge work proceeds even while the collect loop is *blocked* on a
    device queue (the pipelined policy only merges between ``is_ready``
    polls). Bit-identity holds because the worker is the sole mutator of
    the merge state and ``_MergeState`` is add-order-independent; the
    overflow fallback and final scatter run on the main thread after the
    worker drains. The worker opens its ``exec.merge_worker`` spans on its
    own thread.

    ``overlap_s`` sums the portions of worker merge spans that ran
    before the collect loop finished — merge work a single-threaded
    executor would have serialized behind collection.
    """
    state = _MergeState(a.m, post)
    slabs: "queue.Queue[Optional[Tuple[Launch, _Slab]]]" = queue.Queue()
    spans: List[Tuple[float, float]] = []   # (start, duration) per add
    errors: List[BaseException] = []

    def worker():
        while True:
            item = slabs.get()
            if item is None:
                return
            it, slab = item
            with trace.span("exec.merge_worker") as sp:
                t0 = time.perf_counter()
                try:
                    state.add(it, slab)
                except BaseException as e:  # surfaced on the main thread
                    errors.append(e)
                    return
                dt = time.perf_counter() - t0
                sp.measured(t0, dt)
            spans.append((t0, dt))

    th = threading.Thread(target=worker, name="ocean-merge-worker",
                          daemon=True)
    th.start()
    collect_s = 0.0
    traced = trace.enabled()   # hot loop: no attr allocation when off
    try:
        for it in collect_in_completion_order(items):
            with trace.span("exec.collect") as sp:
                t0 = time.perf_counter()
                slab = _materialize(it, copies)
                dt_c = time.perf_counter() - t0
                sp.measured(t0, dt_c)
                if traced:
                    sp.set(order=it.order, kind=it.tag[0])
            collect_s += dt_c
            slabs.put((it, slab))
    finally:
        collect_end = time.perf_counter()
        slabs.put(None)
        th.join()
    if errors:
        raise errors[0]
    merge_s = sum(dt for _, dt in spans)
    overlap_s = sum(min(max(collect_end - t0, 0.0), dt) for t0, dt in spans)
    c, total, n_overflow, tail_s = _finish_merge(state, plan, a, b,
                                                 a_values, stage, copies)
    merge_s += tail_s
    stage["dispatch"] = dispatch_s
    stage["collect"] = collect_s
    stage["merge"] = merge_s
    return c, total, n_overflow, overlap_s, state


_COLLECT_OF = {PIPELINED: _collect_pipelined, THREADED: _collect_threaded,
               SERIAL: _collect_serial}


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _execute(plan: ExecutionPlan, shards: List[_ShardWork], a: CSR, b: CSR,
             *, stage: Optional[Dict[str, float]], cache_hit: bool,
             mode: str, n_shards: int, shard_imbalance: float,
             post: Optional[MergePostOps] = None,
             copy_bytes: Optional[Dict[str, int]] = None,
             ) -> Tuple[CSR, OceanReport]:
    if mode not in EXECUTORS:
        raise ValueError(f"unknown executor {mode!r}; expected one of "
                         f"{EXECUTORS}")
    if a.shape != plan.shape_a or b.shape != plan.shape_b:
        raise ValueError(
            f"plan built for {plan.shape_a} @ {plan.shape_b}, "
            f"got {a.shape} @ {b.shape}")
    if post is not None and post.n_cols != b.n:
        raise ValueError(f"post-ops built for {post.n_cols} columns, "
                         f"product has {b.n}")
    stage = dict(stage) if stage else {"analysis": 0.0, "prediction": 0.0,
                                       "binning": 0.0}
    # the planning stages' copies, when this call planned, then ours
    copies = dict(copy_bytes) if copy_bytes else new_copy_bytes()

    with trace.span("exec.dispatch") as sp:
        t0 = time.perf_counter()
        a_values = to_host(a.values, copies)
        items = _dispatch(shards, a_values, b, copies)
        dispatch_s = time.perf_counter() - t0
        sp.measured(t0, dispatch_s).set(launches=len(items))

    collect = _COLLECT_OF[mode]
    c, total, n_overflow, overlap_s, state = collect(
        items, plan, a, b, a_values, stage, dispatch_s, post, copies)
    raw_counts, causes = state.raw_counts, state.overflow_causes
    with trace.span("exec.report"):
        # overlap is merge work by definition; clamp so the derived
        # merge_overlap_frac view stays in [0, 1] even under clock jitter
        merge_s = stage.get("merge", 0.0)
        overlap_s = min(max(overlap_s, 0.0), merge_s)

        # estimation-accuracy telemetry: exact per-row nnz of the raw
        # product (the merge state's pre-filter counts when fused post-ops
        # pruned the output, else the output's own indptr diff)
        exact_nnz = (raw_counts if raw_counts is not None else
                     np.diff(to_host(c.indptr, copies).astype(np.int64)))
        if plan.feed_forward and causes:
            # a stale feed-forward size is the likely culprit when the fed
            # plan's bins overflow; qualify the attribution
            causes = {f"{k}+stale_feed": v for k, v in causes.items()}
        accuracy = obs_accuracy.measure_accuracy(plan, exact_nnz, causes)

        report = OceanReport(
            workflow=plan.workflow, er=plan.er, sampled_cr=plan.sampled_cr,
            nproducts_avg=plan.nproducts_avg,
            total_products=plan.total_products, m_regs=plan.m_regs,
            stage_seconds=stage, bins=dict(plan.bins_describe),
            overflow_rows=n_overflow, nnz_out=total,
            plan_cache_hit=cache_hit, feed_forward=plan.feed_forward,
            n_shards=n_shards, shard_imbalance=shard_imbalance,
            executor=mode, overlap_seconds=overlap_s,
            analysis_shards=plan.analysis_shards,
            analysis_shard_seconds=plan.analysis_shard_seconds,
            raw_row_nnz=raw_counts,
            wave2_overlap_seconds=plan.wave2_overlap_seconds,
            wave2_overlapped=plan.wave2_overlapped,
            estimation_accuracy=accuracy, decision=plan.decision,
            copy_bytes=copies, esc_products=state.esc_products,
            esc_slots=state.esc_slots)
    return c, report


def execute_plan(plan: ExecutionPlan, a: CSR, b: CSR, *,
                 stage: Optional[Dict[str, float]] = None,
                 cache_hit: bool = False,
                 executor: str = PIPELINED,
                 post: Optional[MergePostOps] = None,
                 copy_bytes: Optional[Dict[str, int]] = None,
                 ) -> Tuple[CSR, OceanReport]:
    """Run a frozen plan against (possibly new) values of A and B.

    ``post`` fuses mask/transform/prune/normalize stages into the merge
    (see :class:`MergePostOps`); the plan itself is post-independent, so
    one cached plan serves masked and unmasked traffic alike.
    ``stage`` and ``copy_bytes`` carry the seconds and the host<->device
    bytes of the planning this call did, if any, into the report.
    """
    return _execute(plan, _shards_of_plan(plan), a, b, stage=stage,
                    cache_hit=cache_hit, mode=executor, n_shards=1,
                    shard_imbalance=1.0, post=post, copy_bytes=copy_bytes)


def execute_sharded_plan(splan, a: CSR, b: CSR, *,
                         stage: Optional[Dict[str, float]] = None,
                         cache_hit: bool = False,
                         executor: str = PIPELINED,
                         post: Optional[MergePostOps] = None,
                         copy_bytes: Optional[Dict[str, int]] = None,
                         ) -> Tuple[CSR, OceanReport]:
    """Run a :class:`~repro.core.partition.ShardedPlan` across its devices.

    Each shard's bins are dispatched onto that shard's device; slabs are
    merged through the same pipeline as :func:`execute_plan` (including
    any fused ``post`` stages, which run on the host merge and are
    therefore topology-independent). Because every bin's per-row results
    are independent of which other rows share the kernel launch, the
    merged CSR is bit-identical to single-device execution.
    """
    if stage is None:
        stage = {"analysis": 0.0, "prediction": 0.0, "binning": 0.0,
                 "partition": 0.0}
    shards = [_ShardWork(device=sh.device, dense=sh.dense, esc=sh.esc,
                         hash=sh.hash)
              for sh in splan.shards]
    return _execute(splan.plan, shards, a, b, stage=stage,
                    cache_hit=cache_hit, mode=executor,
                    n_shards=len(splan.shards),
                    shard_imbalance=splan.imbalance, post=post,
                    copy_bytes=copy_bytes)
