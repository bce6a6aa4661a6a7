"""jit'd wrappers around the Pallas kernels.

On the CPU backend kernels execute with ``interpret=True``, which runs
the kernel body as traced JAX ops — bit-accurate against the TPU lowering
for these integer/float ops. On TPU backends the same calls compile via
Mosaic.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import (CSR, PAD_COL, csr_rows_to_ell, pad_axis,
                                pow2_at_least)
from . import hll as khll
from . import spgemm_dense as kdense
from . import spgemm_hash as khash

ROW_BLOCK = khll.ROW_BLOCK
ELL_BLOCK = khll.ELL_BLOCK
F_CHUNK = kdense.F_CHUNK


def use_interpret() -> bool:
    """Pallas kernels run interpreted exactly when the backend is the CPU."""
    return jax.default_backend() == "cpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# HLL ops
# ---------------------------------------------------------------------------

def _use_pallas_path() -> bool:
    return (not use_interpret()
            or os.environ.get("REPRO_CPU_NUMERIC") == "pallas")


def build_sketches_op(b: CSR, m_regs: int) -> jax.Array:
    """Per-row sketches of B via the Pallas construction kernel (TPU) or the
    segment-max jnp implementation (CPU executor).

    Returns (b.m + 1, m_regs) — the extra all-zero sentinel row is the merge
    kernel's padding target.
    """
    if not _use_pallas_path():
        from repro.core import hll as chll
        regs = chll.build_sketches(b.indptr, b.indices, m_regs=m_regs,
                                   num_rows=b.m)
        return jnp.concatenate([regs, jnp.zeros((1, m_regs), jnp.int32)],
                               axis=0)
    max_len = int(jnp.max(b.indptr[1:] - b.indptr[:-1]))
    e = max(_round_up(max(max_len, 1), ELL_BLOCK), ELL_BLOCK)
    r = max(_round_up(b.m, ROW_BLOCK), ROW_BLOCK)
    ell, _ = csr_rows_to_ell(b.indptr, b.indices, None, num_rows=b.m,
                             ell_width=e, pad_index=-1)
    ell = pad_axis(ell, r, axis=0, value=-1)
    regs = khll.hll_sketch(ell, m_regs=m_regs, interpret=use_interpret())
    regs = regs[: b.m]
    return jnp.concatenate([regs, jnp.zeros((1, m_regs), jnp.int32)], axis=0)


def merge_estimate_op(a: CSR, sketches_with_sentinel: jax.Array,
                      clip_max: int | None = None):
    """Merged C-row sketches + estimates (Pallas on TPU, jnp on CPU)."""
    if not _use_pallas_path():
        from repro.core import hll as chll
        merged = chll.merge_sketches(a.indptr, a.indices,
                                     sketches_with_sentinel[:-1],
                                     num_rows_a=a.m)
        est = chll.estimate_cardinality(merged, clip_max=clip_max)
        return merged, est
    merged, est = khll.hll_merge(a.indptr, a.indices, sketches_with_sentinel,
                                 interpret=use_interpret())
    if clip_max is not None:
        est = jnp.clip(est, 0.0, float(clip_max))
    return merged, est


# ---------------------------------------------------------------------------
# Dense-accumulator bin op + window -> CSR-slab extraction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cap",))
def extract_window_rows(acc, cnt, row_lo, *, cap: int):
    """Compact dense windows into per-row CSR slabs of width ``cap``.

    Presence comes from the product-count accumulator (cnt > 0), preserving
    structural zeros exactly as the paper's dense bitmap does.
    Returns (cols (R, cap) int32 global indices padded with PAD_COL,
             vals (R, cap), nnz (R,) int32). Rows with nnz > cap overflowed.
    """
    w = acc.shape[1]
    pres = cnt > 0
    big = jnp.int32(2**30)
    local = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    key = jnp.where(pres, local, big)
    key_s, val_s = jax.lax.sort((key, acc), dimension=1, num_keys=1)
    nnz = jnp.sum(pres, axis=1).astype(jnp.int32)
    take = min(cap, w)
    cols = key_s[:, :take]
    vals = val_s[:, :take]
    slot = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    ok = (slot < nnz[:, None]) & (cols < big)
    cols = jnp.where(ok, cols + row_lo, PAD_COL)
    vals = jnp.where(ok, vals, 0)
    if take < cap:
        cols = pad_axis(cols, cap, axis=1, value=int(PAD_COL))
        vals = pad_axis(vals, cap, axis=1, value=0)
    return cols, vals, nnz


@functools.partial(jax.jit, static_argnames=("window", "col_tiles", "p_cap"))
def _dense_bin_xla(a_rows, a_vals, a_starts, a_lens, row_lo, b_cols, b_vals,
                   *, window: int, col_tiles: int, p_cap: int):
    """Vectorized XLA executor for a dense bin — identical semantics to the
    Pallas kernel (same binning/window/capacity), used on CPU where
    interpret-mode grids are too slow for benchmark volume. O(P) expansion
    + scatter-add, the same product enumeration as ``core.esc.expand``."""
    r, e = a_rows.shape
    w = window * col_tiles
    lens_flat = a_lens.reshape(-1).astype(jnp.int32)        # (R*E,)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(lens_flat).astype(jnp.int32)])
    total = offs[-1]
    p = jnp.arange(p_cap, dtype=jnp.int32)
    j = jnp.clip(jnp.searchsorted(offs, p, side="right").astype(jnp.int32)
                 - 1, 0, r * e - 1)
    t = p - offs[j]
    valid = p < total
    row = j // e
    bpos = jnp.clip(a_starts.reshape(-1)[j] + t, 0, b_cols.shape[0] - 1)
    col = b_cols[bpos]
    val = a_vals.reshape(-1)[j] * b_vals[bpos]
    local = col - row_lo[row, 0]
    ok = valid & (local >= 0) & (local < w) & (col >= 0)
    rr = jnp.where(ok, row, r)
    cc = jnp.where(ok, local, 0)
    acc = jnp.zeros((r + 1, w), b_vals.dtype).at[rr, cc].add(
        jnp.where(ok, val, 0))[:r]
    cnt = jnp.zeros((r + 1, w), jnp.float32).at[rr, cc].add(
        jnp.where(ok, 1.0, 0.0))[:r]
    return acc, cnt


def dense_bin_op(a_rows, a_vals, a_starts, a_lens, row_lo, b_cols_pad,
                 b_vals_pad, *, window: int, col_tiles: int = 1,
                 cap: int | None = None, p_cap: int | None = None):
    """Run one bin through the dense-accumulator kernel and compact it.

    Returns (cols (R, cap), vals (R, cap), nnz (R,)). On TPU this is the
    Pallas kernel; on CPU the vectorized XLA executor with identical
    semantics runs instead (``REPRO_CPU_NUMERIC=pallas`` forces the
    interpret-mode kernel, as the per-kernel tests do). ``p_cap`` pins the
    XLA path's static product capacity — shard slices of one bin pass the
    bin-level capacity so they share a single jit specialization instead
    of compiling per shard-local product sum.
    """
    if _use_pallas_path():
        acc, cnt = kdense.spgemm_dense_bin(
            a_rows, a_vals, a_starts, a_lens, row_lo, b_cols_pad, b_vals_pad,
            window=window, col_tiles=col_tiles, interpret=use_interpret())
    else:
        if p_cap is None:
            p_cap = pow2_at_least(int(jnp.sum(a_lens)), floor=64)
        acc, cnt = _dense_bin_xla(
            a_rows, a_vals, a_starts, a_lens, row_lo, b_cols_pad, b_vals_pad,
            window=window, col_tiles=col_tiles, p_cap=p_cap)
    if cap is None:
        cap = window * col_tiles
    return extract_window_rows(acc, cnt, row_lo, cap=cap)


# ---------------------------------------------------------------------------
# Hash-accumulator bin op + table -> CSR-slab extraction
# ---------------------------------------------------------------------------

@jax.jit
def extract_hash_rows(keys, vals, skeys, svals, fail):
    """Compact per-row hash tables (primary + spill) into CSR slabs.

    Concatenates both tables, sorts each row by column (empty slots to a
    big sentinel) and left-packs the occupied entries — the hash analogue
    of ``extract_window_rows``. Slab width is ``table + spill``; per-row
    nnz = occupied slots + failed inserts, so ``nnz > width`` iff the
    row's distinct-column count exceeded both tables (the executor's
    overflow scan condition; failed rows re-run through exact ESC).
    Returns (cols (R, table+spill) int32 padded with PAD_COL,
             vals (R, table+spill), nnz (R,) int32).
    """
    k = jnp.concatenate([keys, skeys], axis=1)
    v = jnp.concatenate([vals, svals], axis=1)
    big = jnp.int32(2**30)
    key = jnp.where(k >= 0, k, big)
    key_s, val_s = jax.lax.sort((key, v), dimension=1, num_keys=1)
    occ = jnp.sum(k >= 0, axis=1).astype(jnp.int32)
    nnz = occ + fail[:, 0]
    slot = jax.lax.broadcasted_iota(jnp.int32, key_s.shape, 1)
    ok = (slot < occ[:, None]) & (key_s < big)
    cols = jnp.where(ok, key_s, PAD_COL)
    out_vals = jnp.where(ok, val_s, 0)
    return cols, out_vals, nnz


@functools.partial(jax.jit,
                   static_argnames=("table", "spill", "p_cap"))
def _hash_bin_xla(a_rows, a_vals, a_starts, a_lens, b_cols, b_vals,
                  *, table: int, spill: int, p_cap: int):
    """Vectorized XLA executor for a hash bin — identical slab semantics to
    the Pallas kernel + ``extract_hash_rows``. Enumerates all products
    (same scheme as ``_dense_bin_xla``), sorts by (row, col) and
    segment-sums duplicates; per-(row, col) accumulation order equals the
    kernel's insertion order (product enumeration order), and the exact
    per-row distinct count crosses ``table + spill`` exactly when the
    kernel's occupied+failed count does, so overflow routing matches."""
    r, e = a_rows.shape
    width = table + spill
    lens_flat = a_lens.reshape(-1).astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(lens_flat).astype(jnp.int32)])
    total = offs[-1]
    p = jnp.arange(p_cap, dtype=jnp.int32)
    j = jnp.clip(jnp.searchsorted(offs, p, side="right").astype(jnp.int32)
                 - 1, 0, r * e - 1)
    t = p - offs[j]
    valid = p < total
    row = j // e
    bpos = jnp.clip(a_starts.reshape(-1)[j] + t, 0, b_cols.shape[0] - 1)
    col = b_cols[bpos]
    val = jnp.where(valid, a_vals.reshape(-1)[j] * b_vals[bpos], 0)
    ok = valid & (col >= 0)
    # sort products by (row, col); stable sort keeps enumeration order
    # within a (row, col) group, so the segment sums accumulate in the
    # same order the hash kernel's sequential inserts do
    from repro.core.esc import sort_by_row_col
    row_d, col_d, val_s, head = sort_by_row_col(jnp.where(ok, row, r), col,
                                                val)
    valid_s = row_d < r
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1
    sums = jax.ops.segment_sum(jnp.where(valid_s, val_s, 0), seg,
                               num_segments=p_cap)
    take = head & valid_s
    rowseg = jnp.where(take, row_d, r)
    counts = jax.ops.segment_sum(take.astype(jnp.int32), rowseg,
                                 num_segments=r + 1)[:r]
    # rank of each distinct entry within its row (sorted keys group rows
    # contiguously, so rank = global distinct index - row's first index)
    dstart = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    rank = seg - dstart[jnp.clip(row_d, 0, r - 1)]
    emit = take & (rank < width)
    rr = jnp.where(emit, row_d, r)
    cc = jnp.clip(jnp.where(emit, rank, 0), 0, width - 1)
    cols_out = jnp.full((r + 1, width), PAD_COL, jnp.int32).at[rr, cc].set(
        jnp.where(emit, col_d, PAD_COL))[:r]
    vals_out = jnp.zeros((r + 1, width), b_vals.dtype).at[rr, cc].set(
        jnp.where(emit, sums[seg], 0))[:r]
    return cols_out, vals_out, counts


def hash_bin_op(a_rows, a_vals, a_starts, a_lens, b_cols_pad, b_vals_pad,
                *, table: int, spill: int,
                p_cap: int | None = None, f_chunk: int = F_CHUNK,
                tile: int = khash.DEFAULT_TILE_ROWS):
    """Run one bin through the hash-accumulator kernel and compact it.

    Returns (cols (R, table+spill), vals (R, table+spill), nnz (R,)). On
    TPU this is the Pallas kernel + ``extract_hash_rows``; on CPU the
    vectorized XLA executor with identical slab semantics runs instead
    (``REPRO_CPU_NUMERIC=pallas`` forces the interpret-mode kernel).
    ``p_cap`` pins the XLA path's static product capacity — shard slices
    of one bin pass the per-rung ladder value so same-rung slices share a
    single jit specialization. ``f_chunk``/``tile`` are the autotuned DMA
    chunk and row-tile for the Pallas path (ignored by the XLA executor,
    whose product enumeration has no analogous knobs); per-row output is
    bit-identical across every (f_chunk, tile) choice.
    """
    if _use_pallas_path():
        out = khash.spgemm_hash_bin(
            a_rows, a_vals, a_starts, a_lens, b_cols_pad, b_vals_pad,
            table=table, spill=spill, f_chunk=f_chunk, tile=tile,
            interpret=use_interpret())
        return extract_hash_rows(*out)
    if p_cap is None:
        p_cap = pow2_at_least(int(jnp.sum(a_lens)), floor=64)
    return _hash_bin_xla(
        a_rows, a_vals, a_starts, a_lens, b_cols_pad, b_vals_pad,
        table=table, spill=spill, p_cap=p_cap)


def prep_bin_structure(a: CSR, b: CSR, rows: np.ndarray, ell_width: int):
    """Host-side, structure-only half of bin preparation (vectorized).

    Returns ``(pos, valid, a_rows, a_starts, a_lens)``: ``pos``/``valid``
    are the (R, ell_width) flat gather positions into A's nnz arrays (the
    value gather each executor call replays), and ``a_rows``/``a_starts``/
    ``a_lens`` are the value-independent ELL blocks — B-row ids and
    pregathered B-row starts/lengths (keeps b_indptr out of kernel SMEM).
    Everything here depends only on the sparsity patterns, so an
    ``ExecutionPlan`` caches it across values-only updates.
    """
    indptr = np.asarray(a.indptr)
    indices = np.asarray(a.indices)
    b_indptr = np.asarray(b.indptr)
    rows = np.asarray(rows, np.int64)
    starts = indptr[rows].astype(np.int64)[:, None]
    lens = (indptr[rows + 1] - indptr[rows]).astype(np.int64)[:, None]
    e = np.arange(ell_width, dtype=np.int64)[None, :]
    valid = e < lens
    pos = np.clip(starts + e, 0, max(indices.shape[0] - 1, 0))
    a_rows = np.where(valid, indices[pos], -1).astype(np.int32)
    k = np.maximum(a_rows, 0)
    a_starts = np.where(a_rows >= 0, b_indptr[k], 0).astype(np.int32)
    a_lens = np.where(a_rows >= 0, b_indptr[k + 1] - b_indptr[k],
                      0).astype(np.int32)
    return pos, valid, a_rows, a_starts, a_lens


def gather_bin_values(values: np.ndarray, pos: np.ndarray,
                      valid: np.ndarray) -> np.ndarray:
    """Value half of bin preparation: ELL-shaped A values for one bin."""
    a_vals = np.zeros(pos.shape, values.dtype)
    a_vals[valid] = values[pos[valid]]
    return a_vals




def pad_b_flat(b: CSR):
    """Flat B arrays padded by F_CHUNK so chunked DMA never over-reads."""
    cols = pad_axis(b.indices, b.capacity + F_CHUNK, axis=0, value=-1)
    vals = pad_axis(b.values, b.capacity + F_CHUNK, axis=0, value=0)
    return cols, vals
