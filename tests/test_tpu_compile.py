"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at the widths ``chip_smoke.py``
produces (2**18-row matrices) for a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses what the chip cannot
run (unaligned blocks, unsupported primitives, too much SMEM or VMEM)
where interpret mode accepts it. The XLA-only ESC passes compile at the
HPCG benchmark cell's shapes, and must come out free of loops. The
topology is described inside a fixture, never at import, and every such
test lives in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import esc
from repro.core.binning import hash_spill_of
from repro.kernels import hll as khll
from repro.kernels import spgemm_dense as kdense
from repro.kernels import spgemm_hash as khash

N = 2**18          # rows of the smoke's matrices
NNZ_B = 2**22 + 256  # padded flat B arrays of the uniform matrix


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe it with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m_regs", [32, 64])
def test_hll_sketch_compiles(one_chip, m_regs):
    _compile(functools.partial(khll.hll_sketch, m_regs=m_regs), one_chip,
             ((N, 128), jnp.int32))


@pytest.mark.parametrize("m_regs,nnz", [(32, 2**22), (64, 2**21)])
def test_hll_merge_compiles(one_chip, m_regs, nnz):
    _compile(khll.hll_merge, one_chip, ((N + 1,), jnp.int32),
             ((nnz,), jnp.int32), ((N + 1, m_regs), jnp.int32))


def _bin_shapes(rows, ell, with_values=True):
    shapes = [((rows, ell), jnp.int32)]
    if with_values:
        shapes.append(((rows, ell), jnp.float32))
    shapes += [((rows, ell), jnp.int32), ((rows, ell), jnp.int32)]
    return shapes


# (rows, ELL width, window, column tiles): the power-law smoke's narrow
# dense bin, the widest window, a column-tiled long-row bin, and hub rows
# far wider than SMEM could hold as one block of A scalars
DENSE_BINS = [(224270, 8, 256, 1), (64, 2048, 4096, 1), (2112, 2048, 2048, 8),
              (16, 65536, 4096, 1)]


@pytest.mark.parametrize("rows,ell,window,tiles", DENSE_BINS)
def test_spgemm_dense_bin_compiles(one_chip, rows, ell, window, tiles):
    fn = functools.partial(kdense.spgemm_dense_bin, window=window,
                           col_tiles=tiles)
    _compile(fn, one_chip, *_bin_shapes(rows, ell), ((rows, 1), jnp.int32),
             ((NNZ_B,), jnp.int32), ((NNZ_B,), jnp.float32))


@pytest.mark.parametrize("rows,ell,window,tiles", DENSE_BINS)
def test_spgemm_count_bin_compiles(one_chip, rows, ell, window, tiles):
    fn = functools.partial(kdense.spgemm_count_bin, window=window,
                           col_tiles=tiles)
    _compile(fn, one_chip, *_bin_shapes(rows, ell, with_values=False),
             ((rows, 1), jnp.int32), ((NNZ_B,), jnp.int32))


# (rows, ELL width, table, tile, f_chunk): the uniform smoke's largest bin,
# the largest table (2048 + 1024 spill), the smallest table at the
# autotuner's other tile and chunk candidates, and hub rows
HASH_BINS = [(209159, 32, 512, 8, 128), (1905, 1024, 2048, 16, 64),
             (25246, 32, 32, 16, 128), (32, 65536, 2048, 16, 128)]


@pytest.mark.parametrize("rows,ell,table,tile,f_chunk", HASH_BINS)
def test_spgemm_hash_bin_compiles(one_chip, rows, ell, table, tile, f_chunk):
    fn = functools.partial(khash.spgemm_hash_bin, table=table,
                           spill=hash_spill_of(table), tile=tile,
                           f_chunk=f_chunk)
    _compile(fn, one_chip, *_bin_shapes(rows, ell), ((NNZ_B,), jnp.int32),
             ((NNZ_B,), jnp.float32))


# The HPCG cell's A (= B): rows, nnz, and the product slots of A·A
HPCG_ROWS, HPCG_NNZ, HPCG_P_CAP = 46656, 1191016, 2**25


@pytest.mark.parametrize("pass_", ["symbolic_exact", "esc_spgemm"])
def test_esc_pass_compiles_without_loops(one_chip, pass_):
    """Products are enumerated by scans: a per-product binary search
    (``searchsorted``) would compile to a ``while`` over the product axis."""
    m, nnz = HPCG_ROWS, HPCG_NNZ
    csr = [((m + 1,), jnp.int32), ((nnz,), jnp.int32),
           ((nnz,), jnp.float32)]
    if pass_ == "symbolic_exact":
        fn = functools.partial(esc.symbolic_exact, p_cap=HPCG_P_CAP,
                               num_rows_a=m)
        shapes = csr[:2] * 2
    else:
        fn = functools.partial(esc.esc_spgemm, p_cap=HPCG_P_CAP,
                               out_cap=HPCG_P_CAP, num_rows_a=m)
        shapes = csr * 2
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "while" not in text
