"""Binned dense-accumulator SpGEMM numeric kernel (Pallas TPU).

TPU adaptation of the paper's accumulation kernels (§3.3):

* GPU hash/dense accumulators update scratchpad slots with atomics. TPU has
  no fine-grained atomics, so scatter-add of a chunk of ``F`` intermediate
  products into a width-``W`` dense window is reformulated as a matmul on
  the MXU: ``acc += vals(1,F) @ onehot(F,W)``. Presence (the paper's dense
  bitmap) accumulates the same way from the validity mask, which preserves
  the *structural* nnz semantics the symbolic pass would have produced.
  The matmul runs at ``Precision.HIGHEST``: each output column receives at
  most one product per chunk (a B row holds each column once), so the
  one-hot product reproduces every f32 value exactly and the per-column
  sums accumulate in product-enumeration order, like the XLA twin.

* The enhanced hash accumulator's shared/global split (hot index structure
  on-chip, cold values off-chip) maps to the VMEM/HBM hierarchy: the active
  accumulator window and the B-row chunk live in VMEM; the B nonzero stream
  and the output slab stay in HBM and are moved by explicit async DMA.
  A-side scalars (B-row starts, lengths, A values) sit in SMEM, where the
  scalar core reads them at dynamic indices, in ``ELL_TILE``-slot blocks.

* B's flat nonzero arrays are streamed as lane-dense ``(N, 128)`` blocks
  (:func:`b_blocks`): a DMA moves whole 128-lane block rows, and a chunk
  masks the positions that belong to the B row it serves.

* Long rows (window > VMEM budget) run the same kernel with a column-tile
  grid dimension: each tile re-streams the row's B rows and accumulates only
  columns in its window — trading HBM reads for bounded VMEM, the same
  trade the paper's global-memory fallback makes (its §5.4 ``torso1``
  pathology corresponds exactly to a high re-stream factor here).

Grid: ``(rows / ROW_TILE, col_tiles, ell_width / ELL_TILE)``; col_tiles
== 1 for windowed (binned) rows. Each ``(ROW_TILE, W)`` output block stays
resident across the innermost ELL axis and accumulates its rows one after
another: no cross-program races, which is what the per-row binning
guarantees on GPU too, and each row still takes its A slots in ascending
order. Rows and ELL slots are padded to tile multiples with inert entries
inside the wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# B nonzeros move in lane-dense blocks of F_CHUNK; 128 is the lane width
# and the MXU contraction dimension.
F_CHUNK = 128
# Output rows per grid step: the f32 sublane tile, the smallest row block
# the TPU lowering accepts over an R-row array.
ROW_TILE = 8
# A-side ELL slots per grid step. A bin's ELL width follows its longest A
# row, which nothing bounds; tiling it keeps each (rows, ELL_TILE) SMEM
# block of A scalars small (three arrays, double-buffered, at 16 rows:
# 192 KiB of the v5e's 1 MiB SMEM).
ELL_TILE = 512


def b_blocks(flat: jax.Array, fill) -> jax.Array:
    """Flat B array -> ``(N, F_CHUNK)`` blocks with at least one spare
    block at the end, so a chunk's two-block window never reads past it."""
    n_blk = flat.shape[0] // F_CHUNK + 2
    flat = jnp.pad(flat, (0, n_blk * F_CHUNK - flat.shape[0]),
                   constant_values=fill)
    return flat.reshape(n_blk, F_CHUNK)


def pad_to(x: jax.Array, shape, fill=0) -> jax.Array:
    """Pad each axis of ``x`` at its end up to ``shape`` with ``fill``."""
    if x.shape == tuple(shape):
        return x
    pad = [(0, s - d) for s, d in zip(shape, x.shape)]
    return jnp.pad(x, pad, constant_values=fill)


def _accumulate_kernel(*refs, window: int, with_values: bool):
    """Dense window accumulation of ROW_TILE rows; ``with_values=False``
    is the symbolic (count-only) variant: no value DMA, no value matmul —
    the TPU analogue of the paper's cheaper symbolic accumulation (§2.3:
    'numerical values are discarded')."""
    if with_values:
        (a_vals_ref, a_starts_ref, a_lens_ref, row_lo_ref, b_cols_hbm,
         b_vals_hbm, acc_ref, cnt_ref, bcol_buf, bval_buf, sem) = refs
    else:
        (a_starts_ref, a_lens_ref, row_lo_ref, b_cols_hbm, cnt_ref,
         bcol_buf, sem) = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if with_values:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    t = pl.program_id(1)
    e_total = a_starts_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, F_CHUNK), 1)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (window, F_CHUNK), 0)
    # vals (1,F) . onehot(W,F)^T -> (1,W)
    nt = (((1,), (1,)), ((), ()))
    exact = jax.lax.Precision.HIGHEST

    def row_body(r, _):
        lo = row_lo_ref[r, 0] + t * window

        def e_body(e, _):
            start = a_starts_ref[r, e]
            length = a_lens_ref[r, e]
            first = start // F_CHUNK
            last = (start + jnp.maximum(length, 1) - 1) // F_CHUNK
            n_blk = jnp.where(length > 0, last - first + 1, 0)

            def blk_body(j, _):
                blk = first + j
                cp_c = pltpu.make_async_copy(
                    b_cols_hbm.at[pl.ds(blk, 1), :], bcol_buf, sem.at[0])
                cp_c.start()
                if with_values:
                    cp_v = pltpu.make_async_copy(
                        b_vals_hbm.at[pl.ds(blk, 1), :], bval_buf, sem.at[1])
                    cp_v.start()
                    cp_v.wait()
                cp_c.wait()
                pos = blk * F_CHUNK + lane
                cols_local = bcol_buf[...] - lo
                ok = ((pos >= start) & (pos < start + length)
                      & (cols_local >= 0) & (cols_local < window))
                onehot = (col_iota == jnp.where(ok, cols_local, -1)
                          ).astype(jnp.float32)
                cnt_ref[pl.ds(r, 1), :] += jax.lax.dot_general(
                    ok.astype(jnp.float32), onehot, nt, precision=exact,
                    preferred_element_type=jnp.float32)
                if with_values:
                    vals = jnp.where(ok, a_vals_ref[r, e] * bval_buf[...], 0)
                    acc_ref[pl.ds(r, 1), :] += jax.lax.dot_general(
                        vals, onehot.astype(vals.dtype), nt, precision=exact,
                        preferred_element_type=acc_ref.dtype)
                return 0

            jax.lax.fori_loop(0, n_blk, blk_body, 0)
            return 0

        jax.lax.fori_loop(0, e_total, e_body, 0)
        return 0

    jax.lax.fori_loop(0, ROW_TILE, row_body, 0)


def _bin_call(a_rows, a_vals, a_starts, a_lens, row_lo, b_cols, b_vals, *,
              window: int, col_tiles: int, interpret: bool):
    """Shared pallas_call of the dense and count kernels (``a_vals`` /
    ``b_vals`` None selects the count-only variant)."""
    r, e = a_rows.shape
    r_pad = pl.cdiv(max(r, 1), ROW_TILE) * ROW_TILE
    et = min(e, ELL_TILE)
    e_pad = pl.cdiv(e, et) * et
    out_w = col_tiles * window
    with_values = a_vals is not None
    # inactive ELL slots (a_rows < 0) and pad slots stream nothing
    a_lens = pad_to(jnp.where(a_rows >= 0, a_lens, 0), (r_pad, e_pad))
    a_starts = pad_to(a_starts, (r_pad, e_pad))
    row_lo = pad_to(row_lo, (r_pad, 1))
    smem_block = pl.BlockSpec((ROW_TILE, et), lambda i, t, k: (i, k),
                              memory_space=pltpu.SMEM)
    in_specs = [smem_block, smem_block,
                pl.BlockSpec((ROW_TILE, 1), lambda i, t, k: (i, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [a_starts, a_lens, row_lo, b_blocks(b_cols, -1)]
    out_block = pl.BlockSpec((ROW_TILE, window), lambda i, t, k: (i, t))
    out_specs = [out_block]
    out_shape = [jax.ShapeDtypeStruct((r_pad, out_w), jnp.float32)]
    scratch = [pltpu.VMEM((1, F_CHUNK), jnp.int32)]
    if with_values:
        in_specs = [smem_block] + in_specs + [
            pl.BlockSpec(memory_space=pl.ANY)]
        args = ([pad_to(a_vals, (r_pad, e_pad))] + args
                + [b_blocks(b_vals, 0)])
        out_specs = [out_block, out_block]
        out_shape = [jax.ShapeDtypeStruct((r_pad, out_w), b_vals.dtype)
                     ] + out_shape
        scratch.append(pltpu.VMEM((1, F_CHUNK), b_vals.dtype))
    out = pl.pallas_call(
        functools.partial(_accumulate_kernel, window=window,
                          with_values=with_values),
        grid=(r_pad // ROW_TILE, col_tiles, e_pad // et),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(*args)
    return [x[:r] for x in out]


@functools.partial(jax.jit,
                   static_argnames=("window", "col_tiles", "interpret"))
def spgemm_count_bin(a_rows, a_starts, a_lens, row_lo, b_cols,
                     *, window: int, col_tiles: int = 1,
                     interpret: bool = False):
    """Count-only (symbolic) pass over one bin: returns counts
    (R, col_tiles*window) f32; exact per-row nnz = sum(counts > 0)."""
    (cnt,) = _bin_call(a_rows, None, a_starts, a_lens, row_lo, b_cols, None,
                       window=window, col_tiles=col_tiles,
                       interpret=interpret)
    return cnt


@functools.partial(jax.jit,
                   static_argnames=("window", "col_tiles", "interpret"))
def spgemm_dense_bin(a_rows, a_vals, a_starts, a_lens, row_lo,
                     b_cols, b_vals, *, window: int, col_tiles: int = 1,
                     interpret: bool = False):
    """Run the dense-accumulator kernel over one bin of output rows.

    a_rows:   (R, E) int32 — B-row ids per output row (pad = -1)
    a_vals:   (R, E) float — matching A values
    a_starts: (R, E) int32 — b_indptr[k] pregathered (pad = 0)
    a_lens:   (R, E) int32 — B-row lengths (pad = 0)
    row_lo:   (R, 1) int32 — dense-window base column per row
    b_cols:   (nnzB_pad,) int32 — flat B column indices (HBM)
    b_vals:   (nnzB_pad,) float
    window:   a multiple of 128 (lane-dense output blocks)
    Returns (acc (R, col_tiles*window) float, counts (R, col_tiles*window)
    f32); presence = counts > 0.
    """
    acc, cnt = _bin_call(a_rows, a_vals, a_starts, a_lens, row_lo, b_cols,
                         b_vals, window=window, col_tiles=col_tiles,
                         interpret=interpret)
    return acc, cnt
