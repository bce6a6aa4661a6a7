"""Paper Table 2 / Figures 6-7 analogue: overall SpGEMM performance.

Compares Ocean's full estimation-based workflow against the baselines the
paper competes with, re-implemented in this repo on the same substrate:

* ``two_pass``    — classic exact symbolic + numeric (spECK-style paradigm;
                    Ocean's V1 baseline: no estimation/assist/hybrid)
* ``upper_bound`` — symbolic-free upper-bound allocation (MOSparse's
                    "upper-bound" method)
* ``esc_global``  — one global expand-sort-compact pass (AC-SpGEMM-style)
* ``ocean``       — full Ocean (analysis -> workflow selection -> hybrid)

Computes AA over the synthetic suite (the paper's square dataset stands in);
GFLOPS uses the paper's 2 x products FLOP convention. Wall times are CPU
(XLA-CPU + interpreted Pallas), so *relative* numbers are the signal.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import planner, tuning, workflow
from repro.kernels import ops as kops

from . import common
from .common import flops_of, geomean, suite, timeit


def run(rows: list, scale: int = 1):
    per_method = {m: [] for m in ("ocean", "ocean_cached", "two_pass",
                                  "upper_bound", "esc_global")}
    setup_fresh, setup_cached = [], []
    ex = common.EXECUTOR
    for name, a in suite(scale):
        fl = flops_of(a, a)
        cache = planner.PlanCache()

        # fresh-path methods plan from scratch on every call (cache=False)
        # so the numbers measure the algorithm, as the seed workflow did
        def ocean():
            workflow.ocean_spgemm(a, a, cache=False, executor=ex)

        def ocean_cached():
            workflow.ocean_spgemm(a, a, cache=cache, executor=ex)

        def two_pass():
            workflow.ocean_spgemm(a, a, force_workflow="symbolic",
                                  assisted=False, hybrid=False, cache=False,
                                  executor=ex)

        def upper_bound():
            workflow.ocean_spgemm(a, a, force_workflow="upper_bound",
                                  assisted=False, hybrid=True, cache=False,
                                  executor=ex)

        def esc_global():
            workflow.spgemm_reference(a, a)

        for mname, fn in [("ocean", ocean), ("ocean_cached", ocean_cached),
                          ("two_pass", two_pass),
                          ("upper_bound", upper_bound),
                          ("esc_global", esc_global)]:
            t = timeit(fn)
            gflops = fl / t / 1e9
            per_method[mname].append(gflops)
            rows.append((f"overall/{name}/{mname}", t * 1e6,
                         f"gflops={gflops:.3f}"))

        # host-side planning cost: fresh build vs plan-cache hit, plus the
        # binning prework the planner ran behind analysis wave 2
        _, rep_fresh = workflow.ocean_spgemm(a, a, cache=False, executor=ex)
        _, rep_hit = workflow.ocean_spgemm(a, a, cache=cache, executor=ex)
        assert rep_hit.plan_cache_hit
        setup_fresh.append(rep_fresh.setup_seconds)
        setup_cached.append(rep_hit.setup_seconds)
        rows.append((f"overall/plan_setup/{name}", rep_fresh.setup_seconds * 1e6,
                     f"cached_us={rep_hit.setup_seconds * 1e6:.1f} "
                     f"wave2_overlap_us="
                     f"{rep_fresh.wave2_overlap_seconds * 1e6:.1f} "
                     f"wave2_overlapped={int(rep_fresh.wave2_overlapped)}"))

        # per-rung accumulator occupancy: how Ocean's hybrid binning split
        # this matrix across the dense-window / hash-table / ESC rungs
        # (hash_rows feeds the CI canary asserting the hash rung engages)
        bins = rep_fresh.bins
        hash_rows = sum(v for k, v in bins.items() if k.startswith("hash_t"))
        occ = " ".join(f"{k}={v}" for k, v in bins.items() if v)
        rows.append((f"overall/{name}/rungs", 0.0,
                     f"{occ} hash_rows={hash_rows}".strip()))

        # estimation-accuracy telemetry: predicted vs exact per-row nnz of
        # the fresh Ocean run (repro.obs.accuracy; feeds the CI
        # observability canary through the summary/trajectory keys)
        acc = rep_fresh.estimation_accuracy
        if acc is not None:
            causes = ";".join(f"{k}:{v}" for k, v in
                              sorted(acc.overflow_causes.items())) or "none"
            rows.append((
                f"overall/{name}/est_accuracy", 0.0,
                f"est_err_p50={acc.est_err_p50:.4g} "
                f"est_err_p95={acc.est_err_p95:.4g} "
                f"rung_mispredict_rate={acc.rung_mispredict_rate:.4g} "
                f"overflow_causes={causes}"))

        # per-rung hash-kernel timing: the multi-row tiled kernel (the
        # bin's autotuned tile) against a base tile, both through the real
        # dispatching backend path (kops.hash_bin_op — compiled Pallas on
        # TPU, interpreted Pallas under REPRO_CPU_NUMERIC=pallas, XLA twin
        # otherwise, where tile is a no-op and the two times tie). The base
        # is the tile=1 row-sequential degeneracy where the kernel is
        # interpreted and the smallest compiled tile (8 rows) on TPU.
        plan_obj = planner.build_plan(a, a)
        if plan_obj.hash:
            b_cols_pad, b_vals_pad = kops.pad_b_flat(a)
            a_vals_np = np.asarray(a.values)
            base = (1 if kops.use_interpret()
                    else min(tuning.TILE_CANDIDATES_PALLAS))
            for hb in plan_obj.hash:
                a_vals = kops.gather_bin_values(a_vals_np, hb.pos, hb.valid)

                def rung_call(tile, hb=hb, a_vals=a_vals):
                    jax.block_until_ready(kops.hash_bin_op(
                        hb.a_rows, a_vals, hb.a_starts, hb.a_lens,
                        b_cols_pad, b_vals_pad, table=hb.table,
                        spill=hb.spill, p_cap=hb.p_cap,
                        f_chunk=hb.f_chunk, tile=tile))

                rung_call(hb.tile)  # compile outside the timed region
                rung_call(base)     # (timeit skips warmup under --smoke)
                t_tiled = timeit(lambda: rung_call(hb.tile))
                t_base = timeit(lambda: rung_call(base))
                rows.append((
                    f"overall/{name}/kernel_rung/hash_t{hb.table}",
                    t_tiled * 1e6,
                    f"tile={hb.tile} rows={hb.n_valid} "
                    f"base_tile={base} base_us={t_base * 1e6:.1f} "
                    f"tile_speedup=x{t_base / max(t_tiled, 1e-12):.2f}"))

        # threaded-executor overlap: merge work the worker thread ran
        # while the collect loop was still pulling slabs (feeds the CI
        # overlap canary; output parity with serial is asserted by the
        # sharding module before its rows are emitted)
        thr_frac = thr_us = 0.0
        for _ in range(3):
            _, rep_thr = workflow.ocean_spgemm(a, a, cache=cache,
                                               executor="threaded")
            thr_frac = max(thr_frac, rep_thr.merge_overlap_frac)
            thr_us = max(thr_us, rep_thr.overlap_seconds * 1e6)
            if thr_frac > 0.0:
                break
        rows.append((f"overall/{name}/threaded",
                     0.0,
                     f"threaded_merge_overlap_frac={thr_frac:.4g} "
                     f"threaded_overlap_us={thr_us:.1f}"))

    for mname, gs in per_method.items():
        rows.append((f"overall/geomean/{mname}", 0.0,
                     f"gflops_geomean={geomean(gs):.3f}"))
    oc = geomean(per_method["ocean"])
    for mname in ("two_pass", "upper_bound", "esc_global"):
        base = geomean(per_method[mname])
        rows.append((f"overall/speedup_vs_{mname}", 0.0,
                     f"x{oc / base:.2f}" if base else "n/a"))
    tot_fresh = sum(setup_fresh)
    tot_cached = sum(setup_cached)
    rows.append(("overall/plan_setup/total", tot_fresh * 1e6,
                 f"cached_us={tot_cached * 1e6:.1f} "
                 f"setup_speedup=x{tot_fresh / max(tot_cached, 1e-12):.0f}"))

    # drain the autotuner's measurement log into the artifact: every
    # candidate the sweep timed (winners *and* losers) plus which
    # descending tile-ladder tails the monotone-regression rule pruned,
    # so losing-candidate timings survive for later hardware comparisons
    for rung, entries in sorted(tuning.measurement_log().items()):
        for e in entries:
            if "pruned_tiles" in e:
                rows.append((
                    f"tuning/rung{rung}/pruned", 0.0,
                    f"load_factor={e['load_factor']} "
                    f"f_chunk={e['f_chunk']} "
                    f"pruned_tiles={'-'.join(map(str, e['pruned_tiles']))}"))
            elif "winner" in e:
                w = e["winner"]
                rows.append((
                    f"tuning/rung{rung}/winner", e["seconds"] * 1e6,
                    f"load_factor={w['load_factor']} "
                    f"f_chunk={w['f_chunk']} tile_rows={w['tile_rows']}"))
            else:
                rows.append((
                    f"tuning/rung{rung}/candidate", e["seconds"] * 1e6,
                    f"load_factor={e['load_factor']} "
                    f"f_chunk={e['f_chunk']} tile_rows={e['tile_rows']}"))
