"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--scale`` grows the matrix suite;
``--only`` runs a single module; ``--json`` additionally writes the rows,
per-module wall times, and a setup-vs-total summary as a JSON record (the
perf-trajectory artifact CI uploads) and appends a compact headline entry
to the append-only ``--trajectory`` file (default ``BENCH_trajectory.json``)
so perf is comparable across commits; ``--devices N`` forces N virtual host
devices (must be set before jax initializes, which this flag guarantees) so
the sharding benchmark exercises real multi-device dispatch.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def check_trajectory_schema(traj: list, entry: dict) -> None:
    """Guard the append-only trajectory record: a new entry must carry
    every key the latest established row has (additive fields are
    tolerated — older rows simply lack them; *dropping* an established
    key fails loudly so CI's canary can't silently lose the field it
    compares against)."""
    if not traj:
        return
    established = set(traj[-1].keys())
    missing = established - set(entry.keys())
    if missing:
        raise SystemExit(
            "trajectory schema violation: new entry drops established "
            f"key(s) {sorted(missing)} — trajectory rows are append-only "
            "and must keep the established key set (new additive fields "
            "are fine)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI dry run: tiny suite, no warmup, core modules")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + timing summary as JSON")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual host devices before jax init")
    ap.add_argument("--executor", default="pipelined",
                    choices=("pipelined", "threaded", "serial"),
                    help="core.executor pipeline the workflow benchmarks "
                         "run through (output is bit-identical in every "
                         "mode)")
    ap.add_argument("--trajectory", default="BENCH_trajectory.json",
                    metavar="PATH",
                    help="append-only perf-trajectory record (one compact "
                         "entry per --json run; pass an empty string to "
                         "skip)")
    ap.add_argument("--analysis-shards", type=int, default=0,
                    help="devices the sharding benchmark partitions the "
                         "analysis stage across (0 = all local devices; "
                         "parity with monolithic analysis is asserted)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    # deferred so --devices takes effect before jax initializes
    from . import (ablation, common, cr_sampling, estimation_precision,
                   estimator_vs_cohen, graph, moe_dispatch, overall,
                   selection_validation, serving, sharding)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    modules = {
        "overall": overall,                       # Table 2 / Fig 6-7
        "estimation_precision": estimation_precision,  # Fig 8
        "estimator_vs_cohen": estimator_vs_cohen,  # §5.3
        "cr_sampling": cr_sampling,                # §5.3 sampling
        "ablation": ablation,                      # Table 3 / Fig 9
        "selection_validation": selection_validation,  # §5.4
        "moe_dispatch": moe_dispatch,              # beyond-paper
        "sharding": sharding,                      # device-partitioned exec
        "graph": graph,                            # chained SpGEMM analytics
        "serving": serving,                        # multi-tenant pool SLOs
    }
    all_modules = modules
    common.EXECUTOR = args.executor
    common.ANALYSIS_SHARDS = args.analysis_shards
    if args.smoke:
        common.SMOKE = True
        modules = {k: modules[k] for k in ("overall", "moe_dispatch",
                                           "sharding", "graph", "serving")}
    if args.only:
        modules = {args.only: all_modules[args.only]}

    rows: list = []
    module_seconds = {}
    for name, mod in modules.items():
        t0 = time.time()
        print(f"# running {name} ...", file=sys.stderr, flush=True)
        mod.run(rows, scale=args.scale)
        module_seconds[name] = round(time.time() - t0, 3)
        print(f"#   {name} done in {module_seconds[name]:.1f}s",
              file=sys.stderr, flush=True)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    # one-line setup-vs-total summary (the plan_setup row is emitted by the
    # overall module; total is the benchmark wall time) — seeds the
    # perf-trajectory record alongside the JSON artifact
    setup_us = cached_us = None
    overlap_fracs = {}
    threaded_fracs = {}
    kernel_us_by_rung = {}
    kernel_tile_speedup = {}
    wave2_us_total = 0.0
    wave2_overlapped_rows = 0
    analysis_rows = {}
    analysis_shards_used = None
    chain_iterations = chain_plan_hits = chain_ff_skips = 0
    chain_rows = {}
    chain_parity_rows = 0
    hash_bin_rows = 0
    hash_rows_by_matrix = {}
    serving = {"p50_us": None, "p95_us": None, "p99_us": None,
               "occupancy": None, "shed_rate": None}
    serving_parity_rows = 0
    plans_warmed = plan_warm_hits = sketch_warm_hits = 0
    tuning_rows = 0
    est_err_p50s, est_err_p95s, mispredict_rates = [], [], []
    overflow_causes: dict = {}
    for name, us, derived in rows:
        if name == "overall/plan_setup/total":
            setup_us = us
        if name.endswith("/analysis_sharded"):
            analysis_rows[name] = us
        is_graph = name.startswith("graph/")
        if is_graph:
            chain_rows[name] = us
            if "parity=ok" in derived:
                chain_parity_rows += 1
        is_serving = name.startswith("serving/")
        if is_serving and "parity=ok" in derived:
            serving_parity_rows += 1
        if "/kernel_rung/" in name:
            kernel_us_by_rung[name] = us
        for part in derived.split():
            if name == "overall/plan_setup/total" and \
                    part.startswith("cached_us="):
                cached_us = float(part.split("=", 1)[1])
            if part.startswith("merge_overlap_frac="):
                overlap_fracs[name] = float(part.split("=", 1)[1])
            if part.startswith("threaded_merge_overlap_frac="):
                threaded_fracs[name] = float(part.split("=", 1)[1])
            if "/kernel_rung/" in name and \
                    part.startswith("tile_speedup=x"):
                kernel_tile_speedup[name] = float(part.split("=x", 1)[1])
            if part.startswith("wave2_overlap_us="):
                wave2_us_total += float(part.split("=", 1)[1])
            if part.startswith("wave2_overlapped="):
                wave2_overlapped_rows += int(part.split("=", 1)[1])
            if name.endswith("/analysis_sharded") and \
                    part.startswith("shards="):
                analysis_shards_used = int(part.split("=", 1)[1])
            if is_graph and part.startswith("iters="):
                chain_iterations += int(part.split("=", 1)[1])
            if is_graph and part.startswith("plan_hits="):
                chain_plan_hits += int(part.split("=", 1)[1])
            if is_graph and part.startswith("ff_skips="):
                chain_ff_skips += int(part.split("=", 1)[1])
            if name.endswith("/est_accuracy"):
                if part.startswith("est_err_p50="):
                    est_err_p50s.append(float(part.split("=", 1)[1]))
                if part.startswith("est_err_p95="):
                    est_err_p95s.append(float(part.split("=", 1)[1]))
                if part.startswith("rung_mispredict_rate="):
                    mispredict_rates.append(float(part.split("=", 1)[1]))
                if part.startswith("overflow_causes=") and \
                        not part.endswith("=none"):
                    for kv in part.split("=", 1)[1].split(";"):
                        ck, cv = kv.split(":")
                        overflow_causes[ck] = (overflow_causes.get(ck, 0)
                                               + int(cv))
            if name.endswith("/rungs") and part.startswith("hash_rows="):
                n_rows = int(part.split("=", 1)[1])
                hash_bin_rows += n_rows
                hash_rows_by_matrix[name] = n_rows
            if is_serving:
                for key in ("p50_us", "p95_us", "p99_us", "occupancy",
                            "shed_rate"):
                    if part.startswith(key + "="):
                        serving[key] = float(part.split("=", 1)[1])
                if part.startswith("plans_warmed="):
                    plans_warmed += int(part.split("=", 1)[1])
                if part.startswith("plan_warm_hits="):
                    plan_warm_hits += int(part.split("=", 1)[1])
                if part.startswith("sketch_warm_hits="):
                    sketch_warm_hits += int(part.split("=", 1)[1])
        if name.startswith("tuning/"):
            tuning_rows += 1
    wall_s = sum(module_seconds.values())
    summary = {"plan_setup_fresh_us": setup_us,
               "plan_setup_cached_us": cached_us,
               "wall_seconds": round(wall_s, 3),
               "module_seconds": module_seconds,
               "executor": args.executor,
               # per-benchmark pipelined-merge overlap + the headline max —
               # the sharding module asserts pipelined == serial output
               # before emitting these, so their presence doubles as the
               # correctness canary. Only published when the run's
               # configured executor is pipelined, so a --executor serial
               # record never carries overlap it did not measure.
               "merge_overlap_frac": (max(overlap_fracs.values())
                                      if overlap_fracs
                                      and args.executor == "pipelined"
                                      else None),
               "merge_overlap_frac_by_row": (overlap_fracs
                                             if args.executor == "pipelined"
                                             else {}),
               # threaded executor: merge work the worker thread ran while
               # the collect loop was still pulling slabs. The sharding
               # module asserts threaded == serial output (monolithic and
               # sharded) before emitting these, so their presence doubles
               # as the threaded-merge correctness canary; measured
               # unconditionally (the overall/sharding modules run the
               # threaded mode explicitly, whatever --executor is)
               "threaded_merge_overlap_frac": (max(threaded_fracs.values())
                                               if threaded_fracs else None),
               "threaded_merge_overlap_frac_by_row": threaded_fracs,
               # per-rung hash-kernel timing: the multi-row tiled kernel
               # vs a base tile (tile=1 interpreted, 8 rows compiled),
               # through the real dispatching backend path (the two tie on
               # the XLA twin, where the tile knob is a no-op)
               "kernel_us_by_rung": kernel_us_by_rung,
               "kernel_tile_speedup_by_rung": kernel_tile_speedup,
               # binning prework overlapped behind analysis wave 2 at
               # plan-build time (planner.build_plan -> analyze
               # overlap_work); *_rows counts plan builds where wave-2
               # launches were genuinely still in flight when it ran
               "wave2_overlap_us": round(wave2_us_total, 1),
               "wave2_overlapped_rows": wave2_overlapped_rows,
               # sharded-analysis stage seconds (the sharding module
               # asserts sharded == monolithic AnalysisResult parity
               # before emitting these rows, so their presence doubles as
               # the sharded-analysis correctness canary)
               "analysis_shards": analysis_shards_used,
               "analysis_sharded_us_by_row": analysis_rows,
               # graph-chain canary: benchmarks/graph.py asserts chain
               # outputs bit-identical across reuse tiers, triangle counts
               # against the spgemm_reference oracle, and MCL against a
               # host loop before emitting rows — the chain_* fields (and
               # their parity=ok rows) are CI's evidence the chained
               # plan-reuse + feed-forward sizing paths work end to end
               "chain_iterations": chain_iterations,
               "chain_plan_hits": chain_plan_hits,
               "chain_feed_forward_skips": chain_ff_skips,
               "chain_parity_rows": chain_parity_rows,
               "chain_us_by_row": chain_rows,
               # hash-rung canary: rows the hybrid binner routed to the
               # hash-accumulator family across the overall suite (CI
               # asserts this is nonzero so the rung cannot silently
               # regress to dense/ESC-only selection)
               "hash_bin_rows": hash_bin_rows,
               "hash_bin_rows_by_matrix": hash_rows_by_matrix,
               # serving-tier SLOs: benchmarks/serving.py asserts every
               # pooled multi-tenant output bit-identical to per-request
               # serial execution before emitting rows (parity=ok), so
               # these fields double as the micro-batching correctness
               # canary. shed_rate > 0 by construction (the module runs a
               # deliberate-overload burst against a bounded queue).
               "serving_p50_us": serving["p50_us"],
               "serving_p95_us": serving["p95_us"],
               "serving_p99_us": serving["p99_us"],
               "serving_batch_occupancy": serving["occupancy"],
               "serving_shed_rate": serving["shed_rate"],
               "serving_parity_rows": serving_parity_rows,
               # plan-warmer canary: benchmarks/serving.py runs a burst
               # where the background warmer builds every queued plan
               # before workers start, asserts the warmed outputs
               # bit-identical to serial references, and emits these
               # counters (CI's plan-setup canary asserts
               # plan_warm_hits >= 1)
               "plans_warmed": plans_warmed,
               "plan_warm_hits": plan_warm_hits,
               "sketch_warm_hits": sketch_warm_hits,
               # estimation-accuracy telemetry (repro.obs.accuracy):
               # worst-case HLL-estimate error percentiles, per-rung
               # misprediction rate, and overflow-fallback attribution
               # across the overall suite's fresh Ocean runs (the CI
               # observability canary asserts these are present and sane)
               "est_err_p50": (max(est_err_p50s) if est_err_p50s
                               else None),
               "est_err_p95": (max(est_err_p95s) if est_err_p95s
                               else None),
               "rung_mispredict_rate": (max(mispredict_rates)
                                        if mispredict_rates else None),
               "overflow_fallback_causes": overflow_causes,
               # autotune sweep evidence: tuning/... rows carry every
               # measured candidate (including losers and pruned tile
               # tails) drained from core.tuning.measurement_log()
               "tuning_measurement_rows": tuning_rows}
    if setup_us is not None:
        print(f"# BENCH summary: setup_us={setup_us:.1f} "
              f"cached_setup_us={cached_us:.1f} wall_s={wall_s:.1f}",
              file=sys.stderr, flush=True)
    else:
        print(f"# BENCH summary: wall_s={wall_s:.1f}", file=sys.stderr,
              flush=True)

    if args.json:
        import jax
        record = {
            "meta": {"smoke": args.smoke, "scale": args.scale,
                     "only": args.only,
                     "devices": [str(d) for d in jax.devices()],
                     "unix_time": time.time()},
            "summary": summary,
            "rows": [{"name": n, "us_per_call": round(us, 1), "derived": d}
                     for n, us, d in rows],
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr, flush=True)

        if args.trajectory:
            # append-only perf trajectory: one compact headline entry per
            # recorded run, so regressions are visible across commits
            # without diffing full artifacts
            entry = {
                "unix_time": record["meta"]["unix_time"],
                "smoke": args.smoke, "scale": args.scale,
                "executor": args.executor,
                "wall_seconds": summary["wall_seconds"],
                "plan_setup_fresh_us": summary["plan_setup_fresh_us"],
                "plan_setup_cached_us": summary["plan_setup_cached_us"],
                "merge_overlap_frac": summary["merge_overlap_frac"],
                "threaded_merge_overlap_frac":
                    summary["threaded_merge_overlap_frac"],
                "kernel_us_by_rung": summary["kernel_us_by_rung"],
                "wave2_overlap_us": summary["wave2_overlap_us"],
                "hash_bin_rows": summary["hash_bin_rows"],
                "serving_p50_us": summary["serving_p50_us"],
                "plans_warmed": summary["plans_warmed"],
                "plan_warm_hits": summary["plan_warm_hits"],
                "est_err_p50": summary["est_err_p50"],
                "est_err_p95": summary["est_err_p95"],
                "rung_mispredict_rate": summary["rung_mispredict_rate"],
                "overflow_fallback_causes":
                    summary["overflow_fallback_causes"],
            }
            try:
                with open(args.trajectory) as f:
                    traj = json.load(f)
                if not isinstance(traj, list):
                    traj = []
            except (OSError, ValueError):
                traj = []
            check_trajectory_schema(traj, entry)
            traj.append(entry)
            with open(args.trajectory, "w") as f:
                json.dump(traj, f, indent=1)
            print(f"# appended to {args.trajectory} "
                  f"({len(traj)} records)", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
