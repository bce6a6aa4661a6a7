"""Pallas TPU kernels for HyperLogLog sketch construction and merging.

TPU adaptation of the paper's atomicMax register updates (§3.1): a scatter-max
of ``rho`` values into ``m`` registers becomes a one-hot masked max-reduction
executed on the VPU — `regs = max_e onehot(reg_e) * rho_e` — with the ELL
nonzero stream tiled through VMEM by BlockSpec.

Sketch merging walks A in CSR order: each grid step owns a block of A rows,
streams their B-row ids into SMEM and gathers the matching B-row sketches
from HBM by DMA, reducing them with an elementwise max. The same step fuses
the HLL estimate (harmonic mean + small-range correction), so estimates
leave the kernel without a second pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hll import _alpha

# Block shapes: rows-per-block x ELL-chunk. The (8, 128) granularity matches
# the TPU vector lane/sublane tiling; m registers (<=128) sit in the minor
# dimension so the one-hot reduction stays lane-aligned.
ROW_BLOCK = 8
ELL_BLOCK = 128


def _hash32_u32(x):
    h = x.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _sketch_kernel(cols_ref, out_ref, *, m_regs: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = m_regs.bit_length() - 1
    cols = cols_ref[...]                            # (ROW_BLOCK, ELL_BLOCK)
    valid = cols >= 0
    h = _hash32_u32(jnp.maximum(cols, 0))
    reg = (h & jnp.uint32(m_regs - 1)).astype(jnp.int32)
    w = (h >> p).astype(jnp.int32)
    rho = jax.lax.clz(w) - p + 1
    rho = jnp.where(valid, rho, 0)
    onehot = reg[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, m_regs), 2)
    contrib = jnp.max(jnp.where(onehot, rho[:, :, None], 0), axis=1)
    out_ref[...] = jnp.maximum(out_ref[...], contrib)


@functools.partial(jax.jit, static_argnames=("m_regs", "interpret"))
def hll_sketch(ell_cols: jax.Array, *, m_regs: int,
               interpret: bool = False) -> jax.Array:
    """Build per-row HLL sketches from an ELL index block.

    ell_cols: (R, E) int32, pad = -1; R % ROW_BLOCK == 0, E % ELL_BLOCK == 0.
    Returns (R, m_regs) int32 registers.
    """
    r, e = ell_cols.shape
    assert r % ROW_BLOCK == 0 and e % ELL_BLOCK == 0, (r, e)
    grid = (r // ROW_BLOCK, e // ELL_BLOCK)
    return pl.pallas_call(
        functools.partial(_sketch_kernel, m_regs=m_regs),
        grid=grid,
        in_specs=[pl.BlockSpec((ROW_BLOCK, ELL_BLOCK), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROW_BLOCK, m_regs), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, m_regs), jnp.int32),
        interpret=interpret,
    )(ell_cols)


# Merge-kernel blocking: each grid step merges MERGE_ROWS = 8 x 128 A rows
# (their CSR starts/lengths form one (8, 128) SMEM block) and gathers up to
# 128 B-row sketches per DMA batch. Sketch rows are padded to 128 lanes so
# every gathered row is one lane-dense block row (zero is the max
# identity, and the estimate reads only the first m lanes).
LANES = 128
MERGE_ROWS = 8 * LANES


def _merge_kernel(start_ref, len_ref, idx_hbm, sk_hbm, merged_ref, est_ref,
                  idx_win, gathered, sem, *, m_regs: int):
    sub = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def row_body(r, _):
        start = start_ref[r // LANES, r % LANES]
        length = len_ref[r // LANES, r % LANES]

        def chunk_body(c, acc):
            # this chunk's A indices: [first, first + n) of the flat array,
            # inside the two index blocks from its first element's block
            first = start + c * LANES
            blk = first // LANES
            cp = pltpu.make_async_copy(idx_hbm.at[pl.ds(blk, 2), :], idx_win,
                                       sem.at[0])
            cp.start()
            cp.wait()
            n = jnp.minimum(length - c * LANES, LANES)
            off = first - blk * LANES

            def gather(j, _):
                p = off + j
                k = idx_win[p // LANES, p % LANES]
                pltpu.make_async_copy(sk_hbm.at[pl.ds(k, 1), :],
                                      gathered.at[pl.ds(j, 1), :],
                                      sem.at[1]).start()
                return 0

            def wait(j, _):
                pltpu.make_async_copy(sk_hbm.at[pl.ds(0, 1), :],
                                      gathered.at[pl.ds(j, 1), :],
                                      sem.at[1]).wait()
                return 0

            jax.lax.fori_loop(0, n, gather, 0)
            jax.lax.fori_loop(0, n, wait, 0)
            rows = jnp.where(sub < n, gathered[...], 0)
            return jnp.maximum(acc, jnp.max(rows, axis=0, keepdims=True))

        merged_ref[pl.ds(r, 1), :] = jax.lax.fori_loop(
            0, pl.cdiv(length, LANES), chunk_body,
            jnp.zeros((1, LANES), jnp.int32))
        return 0

    jax.lax.fori_loop(0, MERGE_ROWS, row_body, 0)

    # fused HLL estimate over the block's merged registers
    regs = merged_ref[...].astype(jnp.float32)             # (MERGE_ROWS, 128)
    live = jax.lax.broadcasted_iota(jnp.int32, regs.shape, 1) < m_regs
    inv_sum = jnp.sum(jnp.where(live, jnp.exp2(-regs), 0.0), axis=1,
                      keepdims=True)
    e_raw = _alpha(m_regs) * m_regs * m_regs / inv_sum
    v = jnp.sum(jnp.where(live & (regs == 0), 1.0, 0.0), axis=1,
                keepdims=True)
    e_small = m_regs * jnp.log(
        jnp.where(v > 0, m_regs / jnp.maximum(v, 1e-9), 1.0))
    # lockstep with core.hll.estimate_cardinality: small-range gate on
    # the linear-counting estimate, not e_raw (boundary continuity)
    est_ref[...] = jnp.where((e_small <= 2.5 * m_regs) & (v > 0), e_small,
                             e_raw)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hll_merge(indptr: jax.Array, indices: jax.Array, sketches: jax.Array,
              *, interpret: bool = False):
    """Merge B-row sketches per A row and estimate cardinalities.

    indptr:   (RA+1,) int32 CSR row offsets of A.
    indices:  (cap,) int32 B-row ids; entries outside [0, NB1) read the
              all-zero sentinel sketch row (sketches.shape[0] - 1).
    sketches: (NB1, m) int32, last row all zeros.
    Returns (merged (RA, m) int32, est (RA,) f32).

    Rows merge in blocks of ``MERGE_ROWS``; each row's B-row ids stream
    through SMEM and its sketch rows are gathered from HBM by DMA, so
    the kernel's work and memory follow nnz(A), not rows x max row length.
    """
    ra = indptr.shape[0] - 1
    nb1, m_regs = sketches.shape
    r_pad = pl.cdiv(max(ra, 1), MERGE_ROWS) * MERGE_ROWS
    indptr = indptr.astype(jnp.int32)
    starts = jnp.zeros((r_pad,), jnp.int32).at[:ra].set(indptr[:-1])
    lens = jnp.zeros((r_pad,), jnp.int32).at[:ra].set(
        indptr[1:] - indptr[:-1])
    idx = jnp.where((indices < 0) | (indices >= nb1), nb1 - 1, indices)
    idx_blocks = pl.cdiv(idx.shape[0], LANES) + 2
    idx = jnp.pad(idx, (0, idx_blocks * LANES - idx.shape[0]))
    sk = jnp.pad(sketches, ((0, 0), (0, LANES - m_regs)))
    row_block = pl.BlockSpec((8, LANES), lambda i: (i, 0),
                             memory_space=pltpu.SMEM)
    merged, est = pl.pallas_call(
        functools.partial(_merge_kernel, m_regs=m_regs),
        grid=(r_pad // MERGE_ROWS,),
        in_specs=[row_block, row_block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((MERGE_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((MERGE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, LANES), jnp.int32),
            jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2, LANES), jnp.int32),
            pltpu.VMEM((LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(starts.reshape(-1, LANES), lens.reshape(-1, LANES),
      idx.reshape(-1, LANES), sk)
    return merged[:ra, :m_regs], est[:ra, 0]
