"""Paper Figure 8 analogue: HLL estimation precision + overflow ratios.

Left panel: mean relative per-row estimation error at m = 32/64/128
registers (paper: 0.13 / 0.10 / 0.07). Right panel: fraction of rows that
overflow their binned allocation (estimate x expansion, rounded up the
capacity ladder; hash-kernel threshold 80%) — paper: 1.2% / 0.3% / <0.1%.
"""
from __future__ import annotations

import numpy as np

from repro.core import hll
from repro.core.analysis import products_per_row
from repro.core.binning import round_up_ladder

from .common import suite


def _true_rows(a, b):
    import jax.numpy as jnp
    from repro.core import esc
    prod = products_per_row(a.indptr, a.indices, b.indptr, num_rows_a=a.m)
    p = int(jnp.sum(prod))
    cap = 64
    while cap < p + 1:
        cap *= 2
    return np.asarray(esc.symbolic_exact(a.indptr, a.indices, b.indptr,
                                         b.indices, p_cap=cap,
                                         num_rows_a=a.m))


def run(rows: list, scale: int = 1):
    mats = [(n, m) for n, m in suite(scale)]
    for m_regs, expansion in [(32, 2.0), (64, 1.5), (128, 1.5)]:
        errs, overflows = [], []
        for name, a in mats:
            true = _true_rows(a, a)
            sk = hll.sketch_rows(a, m_regs)
            est = np.asarray(hll.estimate_row_nnz(a, sk, a.n))
            mask = true > 0
            if not mask.any():
                continue
            rel = np.abs(est[mask] - true[mask]) / true[mask]
            errs.append(rel.mean())
            # binning absorbs estimation error (paper §3.2): overflow when
            # actual > 80% of the rounded-up allocation
            alloc = np.array([round_up_ladder(int(np.ceil(e * expansion)))
                              for e in est[mask]])
            overflows.append(float((true[mask] > 0.8 * alloc).mean()))
        rows.append((f"estimation/hll_m{m_regs}/mean_rel_err", 0.0,
                     f"err={np.mean(errs):.4f} (paper~"
                     f"{ {32: 0.13, 64: 0.10, 128: 0.07}[m_regs] })"))
        rows.append((f"estimation/hll_m{m_regs}/overflow_ratio", 0.0,
                     f"avg={np.mean(overflows):.4f} max="
                     f"{np.max(overflows):.4f} (paper avg~"
                     f"{ {32: 0.012, 64: 0.003, 128: 0.001}[m_regs] })"))
