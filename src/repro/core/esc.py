"""ESC (Expand-Sort-Compact) accumulation and the exact symbolic pass.

On TPU, sorting is a first-class XLA primitive, so ESC maps almost verbatim
from the paper (§2.2/§3.3): expansion numbers the products by prefix
scans (each per-A-entry quantity is scattered at its products' first
slot and spread by a ``cumsum``) and reads B with one gather; sorting is
one stable two-key ``lax.sort`` on (row, col) — no packed ``row*n + col``
key, so nothing wraps however wide C is while x64 stays disabled;
compaction is a segmented sum.

The same machinery with indices only implements the *exact symbolic pass*
(the two-pass baseline Ocean replaces), and serves as the overflow-fallback
kernel (paper §3.2) with upper-bound capacity.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import CSR, PAD_COL


class EscOverflowError(ValueError):
    """ESC output exceeded its capacity bound.

    Capacities handed to the ESC pass are *upper bounds* (per-row product
    counts), so overflow here means a sizing bug, not estimation error —
    unlike dense-bin overflow, which the fallback path absorbs by design.
    Subclasses ``ValueError`` so pre-existing ``except ValueError`` callers
    keep working.
    """


class Expanded(NamedTuple):
    rows: jax.Array   # (p_cap,) int32 — output row of each product
    cols: jax.Array   # (p_cap,) int32 — output col of each product
    vals: jax.Array   # (p_cap,) float — a_ik * b_kj
    valid: jax.Array  # (p_cap,) bool
    total: jax.Array  # () int32 — true number of products


def _b_row_nnz(b_indptr):
    return b_indptr[1:] - b_indptr[:-1]


def _spread(per_seg, starts, size: int):
    """``per_seg[s]`` at every position of segment ``s`` of ``size``.

    Segment ``s`` begins at ``starts[s]`` (non-decreasing, ``starts[0]``
    0) and runs to the next start; empty segments share a start with the
    one after them, and starts at or past ``size`` are dropped. Each
    segment's difference from the one before is scattered at its start
    and a prefix sum adds them back up, so position ``p`` reads the last
    segment starting at or before ``p``. Integer sums wrap, so the
    telescoped value is exact for any integer ``per_seg``.
    """
    steps = per_seg - jnp.concatenate([jnp.zeros((1,), per_seg.dtype),
                                       per_seg[:-1]])
    heads = jnp.zeros((size,), per_seg.dtype).at[starts].add(steps,
                                                             mode="drop")
    return jax.lax.cumsum(heads)


@partial(jax.jit, static_argnames=("p_cap", "num_rows_a", "with_values"))
def expand(a_indptr, a_indices, a_values, b_indptr, b_indices, b_values,
           *, p_cap: int, num_rows_a: int, with_values: bool = True) -> Expanded:
    """Enumerate all intermediate products of C = A @ B into flat arrays.

    A-entry ``s`` (a *slot*) owns the contiguous products
    ``offsets[s] .. offsets[s+1]-1``, in row-major order. Per-slot and
    per-row quantities reach their products by :func:`_spread`, an
    O(cap_a) scatter plus one O(p_cap) prefix sum: the product's row, the
    shift ``b_indptr[k] - offsets[s]`` that turns product ``p`` into its
    position in B, and A's value (as its bit pattern, so exactly). The
    only p_cap-wide gathers read B at that position.
    """
    cap_a = a_indices.shape[0]
    nnz_a = a_indptr[-1]
    slot_valid = jnp.arange(cap_a, dtype=jnp.int32) < nnz_a

    b_len = _b_row_nnz(b_indptr)
    k_of_slot = jnp.clip(a_indices, 0, b_len.shape[0] - 1)
    len_of_slot = jnp.where(slot_valid, b_len[k_of_slot], 0)
    offsets = jnp.concatenate([jnp.zeros((1,), len_of_slot.dtype),
                               jnp.cumsum(len_of_slot)]).astype(jnp.int32)
    total = offsets[-1]
    starts = offsets[:-1]

    p = jnp.arange(p_cap, dtype=jnp.int32)
    valid = p < total

    m = a_indptr.shape[0] - 1
    row = _spread(jnp.arange(m, dtype=jnp.int32),
                  offsets[a_indptr[:-1]], p_cap)
    rows = jnp.where(valid, jnp.clip(row, 0, num_rows_a - 1),
                     num_rows_a)  # pads -> sentinel row
    shift = b_indptr[k_of_slot].astype(jnp.int32) - starts
    b_pos = jnp.clip(p + _spread(shift, starts, p_cap), 0,
                     b_indices.shape[0] - 1)
    cols = jnp.where(valid, b_indices[b_pos], PAD_COL)
    if with_values:
        bits = jnp.dtype(f"int{8 * a_values.dtype.itemsize}")
        a_val = jax.lax.bitcast_convert_type(
            _spread(jax.lax.bitcast_convert_type(a_values, bits), starts,
                    p_cap), a_values.dtype)
        vals = jnp.where(valid, a_val * b_values[b_pos], 0)
    else:
        vals = jnp.zeros((p_cap,), jnp.float32)
    return Expanded(rows, cols, vals, valid, total)


def sort_by_row_col(rows, cols, *payload):
    """Stable sort of products by (row, col); ``payload`` arrays ride
    along. Invalid products carry a sentinel row past every real row, so
    they sort last. Returns ``(rows, cols, *payload, head)`` where
    ``head`` marks the first product of every (row, col) group."""
    out = jax.lax.sort((rows, cols) + tuple(payload), num_keys=2)
    rows_s, cols_s = out[0], out[1]
    head = jnp.ones(rows_s.shape, bool).at[1:].set(
        (rows_s[1:] != rows_s[:-1]) | (cols_s[1:] != cols_s[:-1]))
    return tuple(out) + (head,)


class ESCResult(NamedTuple):
    indptr: jax.Array    # (m+1,) int32
    indices: jax.Array   # (out_cap,) int32 (PAD_COL beyond nnz)
    values: jax.Array    # (out_cap,) float
    nnz: jax.Array       # () int32 — true output nnz (may exceed out_cap!)


@partial(jax.jit, static_argnames=("p_cap", "out_cap", "num_rows_a"))
def esc_spgemm(a_indptr, a_indices, a_values, b_indptr, b_indices, b_values,
               *, p_cap: int, out_cap: int, num_rows_a: int) -> ESCResult:
    """Full ESC SpGEMM. Caller checks ``nnz <= out_cap`` (overflow handling)."""
    ex = expand(a_indptr, a_indices, a_values, b_indptr, b_indices, b_values,
                p_cap=p_cap, num_rows_a=num_rows_a)
    rows_s, cols_s, val_s, head = sort_by_row_col(ex.rows, ex.cols, ex.vals)
    valid_s = rows_s < num_rows_a
    head = head & valid_s
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1          # compacted slot id
    nnz = jnp.sum(head.astype(jnp.int32))

    seg_cl = jnp.where(valid_s, jnp.clip(seg, 0, out_cap - 1), out_cap)
    out_vals = jax.ops.segment_sum(val_s, seg_cl, num_segments=out_cap + 1)[:-1]
    # row id and column index of each compacted slot (one head per slot)
    slot_valid = jnp.arange(out_cap) < jnp.minimum(nnz, out_cap)
    first_of = jnp.where(head, seg_cl, out_cap)
    row_of_slot = jnp.full((out_cap + 1,), num_rows_a, jnp.int32).at[
        first_of].set(rows_s)[:-1]
    col_of_slot = jnp.full((out_cap + 1,), PAD_COL, jnp.int32).at[
        first_of].set(cols_s)[:-1]
    row_of_slot = jnp.where(slot_valid, row_of_slot, num_rows_a)
    col_of_slot = jnp.where(slot_valid, col_of_slot, PAD_COL)
    out_vals = jnp.where(slot_valid, out_vals, 0)

    counts = jax.ops.segment_sum(
        jnp.ones((out_cap,), jnp.int32) * slot_valid.astype(jnp.int32),
        row_of_slot, num_segments=num_rows_a + 1)[:-1]
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    return ESCResult(indptr, col_of_slot, out_vals, nnz)


@partial(jax.jit, static_argnames=("p_cap", "num_rows_a"))
def symbolic_exact(a_indptr, a_indices, b_indptr, b_indices,
                   *, p_cap: int, num_rows_a: int) -> jax.Array:
    """Exact per-row output nnz — the classical symbolic pass (indices only).

    This is the step Ocean's HLL estimation replaces; it remains both the
    fallback workflow and the two-pass baseline for benchmarks.
    """
    ex = expand(a_indptr, a_indices, None, b_indptr, b_indices, None,
                p_cap=p_cap, num_rows_a=num_rows_a, with_values=False)
    rows_s, _, head = sort_by_row_col(ex.rows, ex.cols)
    head = head & (rows_s < num_rows_a)
    counts = jax.ops.segment_sum(head.astype(jnp.int32), rows_s,
                                 num_segments=num_rows_a + 1)[:-1]
    return counts


def symbolic_exact_host(a_indptr, a_indices, b_indptr, b_indices,
                        *, num_rows_a: int, n_cols_b: int) -> np.ndarray:
    """Host (numpy) twin of :func:`symbolic_exact` — bit-identical counts.

    Same expand -> (row, col) sort -> unique-head compaction, but over
    int64 numpy arrays with no device round trip or jit specialization.
    On the CPU backend the planner's symbolic prediction takes this path:
    the XLA version pays a device dispatch plus a pow2-padded sort
    (``p_cap``) that dominates fresh-plan latency, while the host sort
    works on the exact product count. Distinct counting is integer-exact
    either way, so the two are interchangeable anywhere
    (``tests/test_planner.py`` asserts equality against the jit path).
    """
    a_ptr = np.asarray(a_indptr, np.int64)
    b_ptr = np.asarray(b_indptr, np.int64)
    m = int(num_rows_a)
    a_idx = np.asarray(a_indices, np.int64)[: int(a_ptr[-1])]
    b_idx = np.asarray(b_indices, np.int64)
    reps = (b_ptr[1:] - b_ptr[:-1])[a_idx]
    total = int(reps.sum())
    if total == 0:
        return np.zeros(m, np.int32)
    a_rows = np.repeat(np.arange(m, dtype=np.int64), a_ptr[1:] - a_ptr[:-1])
    rows = np.repeat(a_rows, reps)
    ends = np.cumsum(reps)
    offs = np.arange(total, dtype=np.int64) - np.repeat(ends - reps, reps)
    cols = b_idx[np.repeat(b_ptr[a_idx], reps) + offs]
    key = rows * int(n_cols_b) + cols
    key.sort()
    head = np.ones(total, bool)
    head[1:] = key[1:] != key[:-1]
    return np.bincount(key[head] // int(n_cols_b),
                       minlength=m).astype(np.int32)


def ensure_esc_capacity(nnz: int, out_cap: int, *, where: str = "ESC") -> int:
    """Single overflow gate for every ESC materialization point.

    ESC capacities are upper bounds (products are exact), so tripping this
    indicates a sizing bug — one raise site keeps the message and the
    trigger condition (strictly greater, capacity == nnz is fine)
    identical between the serial path and the sharded/pipelined merge.
    """
    nnz = int(nnz)
    if nnz > out_cap:
        raise EscOverflowError(
            f"{where} overflow: nnz {nnz} > capacity {out_cap}")
    return nnz


def esc_to_csr(res: ESCResult, shape, out_cap: int) -> CSR:
    """Host-side wrapper: materialize an ESCResult as a CSR (nnz <= out_cap)."""
    nnz = ensure_esc_capacity(res.nnz, out_cap)
    return CSR(res.indptr, res.indices, res.values, tuple(shape), nnz)
