"""Binned hash-accumulator SpGEMM numeric kernel (Pallas TPU).

TPU adaptation of the paper's *hybrid hash accumulator* (§3.3/§4.1): each
output row accumulates its partial products into a per-row open-addressing
table sized from the planner's estimated/known row nnz, with a spill slab
for rows whose primary table fills — mirroring the paper's shared/global
memory split:

* The **primary table** (pow2 slots, linear probing, fp accumulate on hit)
  lives in the row's VMEM-resident output block — the analogue of the
  GPU kernel's shared-memory hash table.
* The **spill table** is a second, smaller open-addressing table the
  kernel falls through to when the primary has no free slot — the
  analogue of the paper's global-memory overflow region. Entries never
  migrate back; extraction treats both tables as one pool.
* A **fail counter** records insert attempts that found *both* tables
  full. Lookups scan the full table (vectorized compare over all slots),
  so a present key is always found regardless of load: the counter is
  nonzero iff the row's distinct-column count exceeds
  ``table + spill``, which is exactly the overflow condition the
  executor's merge scan re-routes to the exact ESC fallback.

GPU hash accumulators insert with atomicCAS loops; TPU has no atomics, so
one probe-insert is reformulated as a whole-table vector op: compare every
slot against the key (hit detection), compute each empty slot's probe
distance from the home slot, pick the nearest as the insertion point, and
commit the write through a one-hot mask. Insertion order within a row is
the product enumeration order (A-slot major, B-position minor), matching
the XLA fallback's segment accumulation order bit for bit.

Grid: ``(rows / tile,)`` — each program owns a **tile of T rows** and
probes all T tables per step: the per-element insert is a (T, table)
vector op with per-row key/value/use lanes, so one sequential step
retires T inserts instead of one (the row-split half of the
OpSparse/Yang-Buluç-Owens accumulator design space). Per-row table
contents depend only on that row's own products — rows never interact —
so any tile size produces bit-identical per-row output (``tile=1``
degenerates to the original row-sequential kernel; pinned in
``tests/test_hash.py``). Rows are padded to a tile multiple with inert
rows (no A entries) inside :func:`spgemm_hash_bin`, so callers never see
the tiling. Compiled for a TPU, the tile must be a multiple of 8 (the
sublane tile); other tiles run only in interpret mode.

A-side scalars (A values, B-row starts and lengths) sit in SMEM, in
``ELL_TILE``-slot blocks along a second, innermost grid axis over which
the tile's output blocks stay resident; each step assembles the tile's
(T, 1) lanes from them. B streams as lane-dense ``(N, 128)`` blocks: a
row's chunk lies in two consecutive blocks, and each step picks its
element out of them by position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spgemm_dense import ELL_TILE, F_CHUNK, b_blocks, pad_to

# Knuth's multiplicative (Fibonacci) hash constant: 2**32 / phi.
_FIB_MULT = 2654435769

# Rows probed per grid step. 8 matches the f32 sublane tile, divides every
# pow2 shard-row rung (``partition.bucket_shard_rows`` floor 32), and keeps
# T (table + spill + f_chunk)-sized live blocks comfortably inside VMEM at
# the largest rung (2048 + 1024 + 128 slots * 8 bytes * 8 rows ≈ 200 KB).
DEFAULT_TILE_ROWS = 8


def _probe_insert(keys_ref, vals_ref, col, v, use, size: int):
    """One vectorized linear-probe insert into T (T, size) pow2 tables.

    ``col``/``v``/``use`` are (T, 1) per-row lanes: every row of the tile
    probes its own table with its own key in one whole-table vector op.
    Accumulates ``v`` into the key's slot (existing or first empty slot in
    probe order). Returns a (T, 1) bool: the insert found a slot (always
    true on a hit; false only when the table is full and the key absent)."""
    p = size.bit_length() - 1
    keys = keys_ref[...]                               # (T, size)
    vals = vals_ref[...]
    t = keys.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, size), 1)
    h = (jnp.maximum(col, 0).astype(jnp.uint32) * jnp.uint32(_FIB_MULT)
         >> jnp.uint32(32 - p)).astype(jnp.int32)      # (T, 1)
    # slot holding the key (size when absent); keys are unique per table
    hit = jnp.min(jnp.where(keys == col, iota, size), axis=1, keepdims=True)
    found = hit < size                                 # (T, 1)
    # probe distance of each empty slot from the home slot h (mod size);
    # the nearest one is where linear probing would land
    dist = (iota - h) & (size - 1)
    empty_dist = jnp.where(keys == -1, dist, size)
    first = jnp.min(empty_dist, axis=1, keepdims=True)  # (T, 1)
    target = jnp.where(found, hit, (h + first) & (size - 1))
    has_slot = found | (first < size)                  # (T, 1)
    write = (iota == target) & has_slot & use
    keys_ref[...] = jnp.where(write, col, keys)
    vals_ref[...] = jnp.where(write, vals + v, vals)
    return has_slot


def _hash_kernel(a_vals_ref, a_starts_ref, a_lens_ref, b_cols_hbm,
                 b_vals_hbm, keys_ref, vals_ref, skeys_ref, svals_ref,
                 fail_ref, bcol_buf, bval_buf, sem,
                 *, table: int, spill: int, f_chunk: int, tile: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        keys_ref[...] = jnp.full_like(keys_ref, -1)
        vals_ref[...] = jnp.zeros_like(vals_ref)
        skeys_ref[...] = jnp.full_like(skeys_ref, -1)
        svals_ref[...] = jnp.zeros_like(svals_ref)
        fail_ref[...] = jnp.zeros_like(fail_ref)

    e_total = a_starts_ref.shape[1]
    # last first block of a two-block window inside the padded B arrays
    last_blk = b_cols_hbm.shape[0] - 2
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, F_CHUNK), 1)

    def lanes(ref, e):
        """(T, 1) vector of the tile rows' SMEM scalars at ELL slot e."""
        out = jnp.zeros((tile, 1), ref.dtype)
        for ti in range(tile):
            out = jnp.where(row == ti, ref[ti, e], out)
        return out

    def e_body(e, _):
        # per-row lanes for A slot e: A value, B-row start/len
        avs = lanes(a_vals_ref, e)
        starts = lanes(a_starts_ref, e)
        lens = lanes(a_lens_ref, e)
        # rows stream their B rows in lockstep; rows whose B row ran out
        # are masked by `use` below, so the shared chunk count is the
        # tile's max — per-row insert order is untouched by the batching
        max_len = a_lens_ref[0, e]
        for ti in range(1, tile):
            max_len = jnp.maximum(max_len, a_lens_ref[ti, e])

        def c_body(c, _):
            # each row's chunk [start + c*f_chunk, +f_chunk) lies in the
            # two B blocks from its first element's block; all 4T copies
            # are in flight together before the first wait. A row whose own
            # B row ended (c*f_chunk >= its length) may point past B's
            # end: its window is clamped into the array, and `use` masks
            # everything it reads
            copies = []
            for ti in range(tile):
                blk = jnp.minimum(
                    (a_starts_ref[ti, e] + c * f_chunk) // F_CHUNK, last_blk)
                for half in range(2):
                    src = pl.ds(blk + half, 1)
                    dst = (half, pl.ds(ti, 1), slice(None))
                    copies.append(pltpu.make_async_copy(
                        b_cols_hbm.at[src, :], bcol_buf.at[dst], sem.at[0]))
                    copies.append(pltpu.make_async_copy(
                        b_vals_hbm.at[src, :], bval_buf.at[dst], sem.at[1]))
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()
            first = starts + c * f_chunk                   # (T, 1)
            pos0 = (first // F_CHUNK) * F_CHUNK + lane     # (T, F)
            pos1 = pos0 + F_CHUNK
            cols0, cols1 = bcol_buf[0], bcol_buf[1]
            vals0, vals1 = bval_buf[0], bval_buf[1]
            n_i = jnp.minimum(max_len - c * f_chunk, f_chunk)

            def i_body(i, _):
                # element i of every row's chunk, picked out of its two
                # blocks by position (one lane matches per row)
                target = first + i
                sel0 = pos0 == target
                sel1 = pos1 == target
                col = jnp.sum(jnp.where(sel0, cols0, 0)
                              + jnp.where(sel1, cols1, 0),
                              axis=1, keepdims=True)
                bv = jnp.sum(jnp.where(sel0, vals0, 0)
                             + jnp.where(sel1, vals1, 0),
                             axis=1, keepdims=True)
                use = (target < starts + lens) & (col >= 0)
                v = avs * bv
                ok_t = _probe_insert(keys_ref, vals_ref, col, v, use, table)
                rem = use & ~ok_t
                ok_s = _probe_insert(skeys_ref, svals_ref, col, v, rem,
                                     spill)
                fail_ref[...] += jnp.where(rem & ~ok_s, 1, 0)
                return 0

            jax.lax.fori_loop(0, n_i, i_body, 0)
            return 0

        jax.lax.fori_loop(0, pl.cdiv(max_len, f_chunk), c_body, 0)
        return 0

    jax.lax.fori_loop(0, e_total, e_body, 0)


@functools.partial(jax.jit, static_argnames=("table", "spill", "f_chunk",
                                             "tile", "interpret"))
def spgemm_hash_bin(a_rows, a_vals, a_starts, a_lens, b_cols, b_vals,
                    *, table: int, spill: int, f_chunk: int = F_CHUNK,
                    tile: int = DEFAULT_TILE_ROWS, interpret: bool = False):
    """Run the hash-accumulator kernel over one bin of output rows.

    a_rows:   (R, E) int32 — B-row ids per output row (pad = -1)
    a_vals:   (R, E) float — matching A values
    a_starts: (R, E) int32 — b_indptr[k] pregathered (pad = 0)
    a_lens:   (R, E) int32 — B-row lengths (pad = 0)
    b_cols:   (nnzB_pad,) int32 — flat B column indices (HBM)
    b_vals:   (nnzB_pad,) float
    table/spill: pow2 slot counts for the primary/spill tables.
    tile: rows probed per grid step (vectorized over the tile). R is
          padded to a tile multiple with inert rows internally and the
          outputs sliced back, so per-row results are independent of
          ``tile`` (``tile=1`` is the row-sequential degeneracy). A
          compiled kernel takes multiples of 8 only; others raise
          ValueError.
    Returns (keys (R, table) int32 with -1 empties, vals (R, table),
             skeys (R, spill), svals (R, spill), fail (R, 1) int32).
    ``fail > 0`` iff the row's distinct count exceeds table + spill.
    """
    r, e = a_rows.shape
    dtype = b_vals.dtype
    tile = max(int(tile), 1)
    if not 0 < f_chunk <= F_CHUNK:
        raise ValueError(f"f_chunk must be in (0, {F_CHUNK}], got {f_chunk}")
    if not interpret and tile % 8:
        raise ValueError(f"a compiled tile must be a multiple of 8, got {tile}")
    r_pad = pl.cdiv(max(r, 1), tile) * tile
    et = min(e, ELL_TILE)
    e_pad = pl.cdiv(e, et) * et
    # inactive ELL slots (a_rows < 0) stream nothing; pad rows and pad
    # slots are inert
    a_lens = pad_to(jnp.where(a_rows >= 0, a_lens, 0), (r_pad, e_pad))
    a_starts = pad_to(a_starts, (r_pad, e_pad))
    a_vals = pad_to(a_vals, (r_pad, e_pad))
    kernel = functools.partial(_hash_kernel, table=table, spill=spill,
                               f_chunk=f_chunk, tile=tile)
    smem_block = pl.BlockSpec((tile, et), lambda i, k: (i, k),
                              memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(r_pad // tile, e_pad // et),
        in_specs=[
            smem_block, smem_block, smem_block,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((tile, table), lambda i, k: (i, 0)),
            pl.BlockSpec((tile, table), lambda i, k: (i, 0)),
            pl.BlockSpec((tile, spill), lambda i, k: (i, 0)),
            pl.BlockSpec((tile, spill), lambda i, k: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, table), jnp.int32),
            jax.ShapeDtypeStruct((r_pad, table), dtype),
            jax.ShapeDtypeStruct((r_pad, spill), jnp.int32),
            jax.ShapeDtypeStruct((r_pad, spill), dtype),
            jax.ShapeDtypeStruct((r_pad, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile, F_CHUNK), jnp.int32),
            pltpu.VMEM((2, tile, F_CHUNK), dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(a_vals, a_starts, a_lens, b_blocks(b_cols, -1), b_blocks(b_vals, 0))
    return tuple(x[:r] for x in out)
