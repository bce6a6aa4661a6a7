"""The upper-bound workflow on a very sparse operator: hypre's 7-point
Laplacian squared takes ``upper_bound``, every row runs in the ESC bin,
C matches the plain reference, and the ESC passes are counted
(``OceanReport.esc_products`` / ``esc_slots``) and traced (``exec.esc``),
the overflow fallback's pass with the bin's."""
import dataclasses
import json

import numpy as np
import pytest

from bench import cells, reference
from bench.generators import laplace7
from repro.core import formats, planner, workflow
from repro.core.formats import pow2_at_least
from repro.obs import trace

with open(cells.BENCH_DIR / "configs" / "hypre_lap7_n104.json") as f:
    LAP = json.load(f)
with open(cells.BENCH_DIR / "configs" / "kron_g500_s13.json") as f:
    KRON = json.load(f)


def _csr(m):
    return formats.csr_from_arrays(m.indptr, m.indices, m.values, m.shape)


def _laplacian():
    return laplace7.build(LAP["small"]).matrix(np.random.default_rng(0))


def _kron_overflowing_plan():
    """An upper-bound plan of Kronecker's small graph with an ESC bin,
    whose dense bin's slabs are cut to 8 entries a row so that its rows
    overflow into the fallback."""
    g = cells.load_generator(dict(KRON, params=KRON["small"]))
    a = _csr(g.matrix(np.random.default_rng(1)))
    plan = planner.build_plan(a, a, force_workflow="upper_bound")
    assert plan.esc is not None and plan.dense
    cut = dataclasses.replace(plan.dense[0], cap=8)
    return a, dataclasses.replace(plan, dense=[cut] + plan.dense[1:])


def test_laplacian_takes_upper_bound_with_every_row_in_the_esc_bin():
    m = _laplacian()
    c, rep = workflow.ocean_spgemm(_csr(m), _csr(m), cache=False)
    assert rep.workflow == "upper_bound"
    assert rep.bins == {"esc": m.shape[0], "empty": 0}
    assert rep.overflow_rows == 0
    ref = reference.Reference(m.indptr, m.indices, m.shape,
                              m.indptr, m.indices, m.shape)
    comp = ref.compare(*c.to_scipy_like(), *ref.exact(m.values, m.values))
    assert comp.rows_wrong == 0
    assert comp.value_err_over_f32_bound <= \
        LAP["limits"]["value_err_over_f32_bound"]


def test_esc_counters_count_the_esc_bin():
    m = _laplacian()
    a = _csr(m)
    plan = planner.build_plan(a, a)
    _, rep = planner.execute_plan(plan, a, a)
    assert rep.esc_products == int(plan.products[plan.esc.rows].sum())
    assert rep.esc_products == rep.total_products == 41440
    assert rep.esc_slots == plan.esc.p_cap == 65536


@pytest.mark.parametrize("executor", ["serial", "pipelined", "threaded"])
def test_esc_counters_add_the_fallback_pass(executor):
    a, plan = _kron_overflowing_plan()
    c, rep = planner.execute_plan(plan, a, a, executor=executor)
    assert rep.overflow_rows > 0
    bin_products = int(plan.products[plan.esc.rows].sum())
    over = np.diff(np.asarray(c.indptr))[plan.dense[0].rows] > 8
    fallback = int(plan.products[plan.dense[0].rows[over]].sum())
    assert int(over.sum()) == rep.overflow_rows
    assert rep.esc_products == bin_products + fallback
    assert rep.esc_slots == plan.esc.p_cap + pow2_at_least(fallback,
                                                           floor=64)


def test_a_plan_without_esc_passes_counts_none():
    a = formats.random_uniform_csr(3, 64, 64, 8.0)
    _, rep = workflow.ocean_spgemm(a, a, cache=False,
                                   force_workflow="symbolic")
    assert rep.bins.get("esc", 0) == 0 and rep.overflow_rows == 0
    assert (rep.esc_products, rep.esc_slots) == (0, 0)


def _esc_spans(run):
    tr = trace.Tracer()
    with trace.tracing(tr):
        run()
    return [e for e in tr.events() if e["name"] == "exec.esc"]


def test_exec_esc_span_opens_once_per_esc_pass():
    m = _laplacian()
    spans = _esc_spans(lambda: workflow.ocean_spgemm(_csr(m), _csr(m),
                                                     cache=False))
    assert [s["parent"] for s in spans] == ["exec.collect"]
    a, plan = _kron_overflowing_plan()
    spans = _esc_spans(lambda: planner.execute_plan(plan, a, a))
    assert sorted(s["parent"] for s in spans) == ["exec.collect",
                                                  "exec.overflow_fallback"]
