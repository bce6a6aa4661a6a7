"""Chip benchmark of ``ocean_spgemm``: one cell, one run, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(the matrix A, made from the seed) under a traffic mix (what the window
multiplies with ``repro.core.workflow.ocean_spgemm`` and when). The run

1. refuses to run (exit 1, no result) unless JAX finds a TPU with as many
   chips as the cell asks for and the Pallas kernels would be compiled;
2. makes A, the traffic's second operand and its value sets from
   ``--seed`` on the host, puts them on the device, and warms up with the
   traffic's own calls, which compile every shape the window uses; all of
   this, with process start and imports, is ``setup_s``;
3. calls for ``--seconds``: back to back (closed loop), or at the
   traffic's fixed rate (open loop, each call's latency counted from its
   arrival). The window runs from its start to the completion (C on the
   device) of the last call that arrived inside it;
4. compares every call's C with the plain reference (``bench/reference``)
   and prints the numbers compared, each beside its limit;
5. prints one JSON line last: with ``--trace 0`` the end-to-end metrics,
   with ``--trace 1`` the per-layer metrics of a profiled window.

Earlier lines, and ``bench_out/<cell>.<seed>.<trace>.json``, record the
workflow taken, rows per accumulator rung, the hash bins the plan ran
(table, tile, DMA chunk) where the run reads the plan, compilations inside
the window and the device.

Adding to the benchmark takes new files and new entries in
``BENCHMARK.json`` only:

* a cell: an entry of ``workloads`` naming a configuration and a traffic;
* a configuration: ``bench/configs/<name>.json`` (``generator``, its
  ``params``, ``small``: the ``params`` of a size the CPU tests run,
  ``source``, ``reduced``, ``assumed``, ``reckoned`` sizes, and the
  comparison's ``limits``) and, for a new family of matrices, a generator
  module ``bench/generators/<generator>.py`` with ``build(params)``;
* a traffic mix: ``bench/traffic/<name>.json``: ``operands`` (``a_a`` for
  A @ A, ``a_at`` for A @ A^T), ``arrival`` (``{"kind": "closed"}``, or
  ``{"kind": "open", "rate_per_s": r}``), ``plan_cache`` (``off`` or
  ``private``), ``value_sets`` and ``warmup_calls``;
* an end-to-end metric: ``bench/e2e/<name>.py`` with ``read(win)`` over
  the window's calls (``Window``);
* a per-layer metric: ``bench/metrics/<name>.py`` with ``read(ctx)``
  (``Context``), returning a number or ``None`` where the run has nothing
  to read.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / "bench_out"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 1


@dataclasses.dataclass
class Call:
    """One call of the window, on the host's ``perf_counter``."""
    arrival: float           # when the traffic offered it
    start: float
    end: float               # C on the device


@dataclasses.dataclass
class Window:
    """What an end-to-end metric reader sees of one run."""
    calls: List[Call]
    window_s: float
    setup_s: float
    products_per_call: int


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees of one traced run."""
    calls: int
    window_s: float
    reports: List            # OceanReport of every call in the window
    plan: Optional[object]   # the ExecutionPlan the window's calls ran
    products: object         # intermediate products of every row of A @ B
    a_indptr: object
    c_indptr: object         # of the first call's C
    trace: Optional[object]  # trace_reduce.Trace of the window
    busy_s: float
    peaks: object
    memory_peak_bytes: int


class _CompileCounter:
    """Counts lowerings to MLIR (one per new jit specialization)."""

    def __init__(self):
        self.n = 0
        self.on = False

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event == COMPILE_EVENT:
            self.n += 1


def _rows_per_rung(bins: Dict[str, int]) -> Dict[str, int]:
    rungs = {"dense": 0, "hash": 0, "esc": 0, "empty": 0}
    for key, rows in bins.items():
        kind = ("dense" if key.startswith("dense_") else
                "hash" if key.startswith("hash_") else key)
        rungs[kind] = rungs.get(kind, 0) + rows
    return rungs


def _operands(base, kind: str):
    """(B's host pattern, the gather that takes A's values to B's), or
    ``None`` for the gather where B is A itself."""
    if kind == "a_a":
        return base, None
    if kind == "a_at":
        return base.transposed()
    raise ValueError(f"unknown operands {kind!r}")


def _schedule(arrival: Dict) -> Optional[float]:
    """Seconds between arrivals, or ``None`` for a closed loop."""
    if arrival["kind"] == "closed":
        return None
    if arrival["kind"] == "open":
        return 1.0 / float(arrival["rate_per_s"])
    raise ValueError(f"unknown arrival {arrival['kind']!r}")


def _plan_of(a, b, cache, workflow, planner):
    """The plan the window's calls ran: the traffic's own cache entry, or
    one built the same way into a private cache after the window."""
    if not isinstance(cache, planner.PlanCache):
        cache = planner.PlanCache()
    key, _ = workflow.warm_plan(a, b, cache=cache)
    return cache.peek(key)


def _hash_bins(plan) -> List[Dict]:
    """The hash bins a plan runs, each with the tuning it was built with."""
    return [{"table": hb.table, "spill": hb.spill, "tile": hb.tile,
             "f_chunk": hb.f_chunk, "rows": int(hb.n_valid)}
            for hb in plan.hash]


def _pin_hash_tuning(tuning) -> None:
    """Give every hash rung the program's default tuning before the first
    call, so that the autotuner measures nothing. It times kernels that
    run for microseconds, and its pick of the load factor moved 22,722 of
    a FEM stiffness's rows between the hash rung and ESC from one run to
    the next, and a call from 13 s to 194 s (PERF.md); with it every run
    of a cell does the same work."""
    from repro.core.binning import HASH_MAX_TABLE, HASH_MIN_TABLE
    rung = HASH_MIN_TABLE
    rungs = {tuning.REFERENCE_RUNG}
    while rung <= HASH_MAX_TABLE:
        rungs.add(rung)
        rung *= 2
    for rung in sorted(rungs):
        tuning.DEFAULT_TUNING_CACHE.insert(tuning.tuning_key(rung),
                                           tuning.DEFAULT_TUNING)


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, multiply: Optional[Callable] = None,
             device_kind: Optional[str] = None) -> Dict:
    """Set up, measure and check one run of ``cell``; returns the result
    object. ``multiply`` stands in for ``ocean_spgemm`` (tests break the
    timed path with it); ``device_kind`` overrides the peaks' key."""
    import jax
    import numpy as np
    from bench import cells, peaks as peaks_mod, reference, work
    from repro.core import planner, tuning, workflow
    from repro.core.formats import csr_from_arrays
    from repro.obs import trace as obs_trace

    multiply = multiply or workflow.ocean_spgemm
    traffic = cell.traffic
    devices = jax.devices()[: cell.chips]
    dev = devices[0]
    peaks = peaks_mod.peaks_for(device_kind or dev.device_kind)
    gap = _schedule(traffic["arrival"])
    if traffic["plan_cache"] not in ("private", "off"):
        raise ValueError(f"unknown plan_cache {traffic['plan_cache']!r}")

    # ---- set-up: inputs from the seed, on the device; warm-up calls ----
    gen = cells.load_generator(cell.config)
    base = gen.matrix(np.random.default_rng([seed, 0]))
    bpat, take = _operands(base, traffic["operands"])
    a_host = [base.values] + [
        gen.values(np.random.default_rng([seed, 1 + k]))
        for k in range(int(traffic["value_sets"]) - 1)]
    b_host = a_host if take is None else [v[take] for v in a_host]
    a0 = csr_from_arrays(base.indptr, base.indices, base.values, base.shape)
    a_sets = [a0] + [dataclasses.replace(a0, values=jax.device_put(v))
                     for v in a_host[1:]]
    if take is None:
        b_sets = a_sets
    else:
        b0 = csr_from_arrays(bpat.indptr, bpat.indices, bpat.values,
                             bpat.shape)
        b_sets = [b0] + [dataclasses.replace(b0, values=jax.device_put(v))
                         for v in b_host[1:]]
    jax.block_until_ready([m.values for m in a_sets + b_sets])
    _pin_hash_tuning(tuning)
    cache = planner.PlanCache() if traffic["plan_cache"] == "private" \
        else False

    def call(i: int):
        k = i % len(a_sets)
        c, rep = multiply(a_sets[k], b_sets[k], cache=cache)
        jax.block_until_ready((c.indptr, c.indices, c.values))
        return c, rep

    for i in range(int(traffic["warmup_calls"])):
        call(i)
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    setup_s = time.perf_counter() - t_start

    # ---- the window ----
    trace_dir = OUT_DIR / "trace" / f"{cell.name}.{seed}"
    tracer = None
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        tracer = obs_trace.Tracer()
        obs_trace.install(tracer)
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    outputs, reports, calls = [], [], []
    counter.on = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        i = 0
        while True:
            now = time.perf_counter()
            arrival = now if gap is None else t0 + i * gap
            if arrival - t0 >= seconds:
                break
            if arrival > now:
                time.sleep(arrival - now)
            start = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                c, rep = call(i)
            calls.append(Call(arrival, start, time.perf_counter()))
            outputs.append((i % len(a_sets), c))
            reports.append(rep)
            i += 1
    window_s = time.perf_counter() - t0
    counter.on = False
    if traced:
        jax.profiler.stop_trace()
        obs_trace.install(None)
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    n_calls = len(outputs)
    products = work.row_products(base.indptr, base.indices, bpat.indptr)
    total_products = int(products.sum())

    # ---- readings: per-layer in a traced run, else end-to-end ----
    metrics: Dict[str, Dict] = {}
    breakdown = None
    busy_s = 0.0
    plan = None
    if traced or isinstance(cache, planner.PlanCache):
        plan = _plan_of(a_sets[0], b_sets[0], cache, workflow, planner)
        if reports and plan.bins_describe != reports[0].bins:
            print(f"bench: the plan read after the window bins "
                  f"{plan.bins_describe}, the window's calls "
                  f"{reports[0].bins}; per-rung metrics left out",
                  flush=True)
            plan = None
    if traced:
        from bench import trace_reduce
        tr = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)))
        busy_s = trace_reduce.busy_seconds(tr)
        ctx = Context(calls=n_calls, window_s=window_s, reports=reports,
                      plan=plan, products=products, a_indptr=base.indptr,
                      c_indptr=np.asarray(outputs[0][1].indptr),
                      trace=tr, busy_s=busy_s, peaks=peaks,
                      memory_peak_bytes=mem_peak)
        readers = [(m, cells.metric_reader(m["name"]), ctx)
                   for m in cell.per_layer]
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.idle_gaps(
                         tr, _named_spans(tr, tracer))}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        win = Window(calls=calls, window_s=window_s, setup_s=setup_s,
                     products_per_call=total_products)
        readers = [(m, cells.end_to_end_reader(m["name"]), win)
                   for m in cell.end_to_end]
    for m, read, arg in readers:
        v = read(arg)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- the check: every call's C against the reference ----
    t_check = time.perf_counter()
    ref = reference.Reference(base.indptr, base.indices, base.shape,
                              bpat.indptr, bpat.indices, bpat.shape)
    expected = {}
    comp = reference.Comparison(0, 0.0, 0)
    failed = 0
    for vs, c in outputs:
        if vs not in expected:
            expected[vs] = ref.exact(a_host[vs], b_host[vs])
        ip, ii, vv = c.to_scipy_like()
        one = ref.compare(ip, ii, vv, *expected[vs])
        failed += int(not _passes(one, cell.config["limits"]))
        comp = comp.merged(one)
    outputs.clear()
    checks = {
        "rows_wrong": {"value": comp.rows_wrong,
                       "limit": cell.config["limits"]["rows_wrong"]},
        "value_err_over_f32_bound": {
            "value": comp.value_err_over_f32_bound,
            "limit": cell.config["limits"]["value_err_over_f32_bound"]},
    }
    correct = (failed == 0 and n_calls >= 1
               and _passes(comp, cell.config["limits"]))

    rungs = _rows_per_rung(reports[0].bins) if reports else {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if traced:
        device.update(busy_s=busy_s, window_s=window_s)
    info = {
        "workload": cell.name, "seed": seed, "trace": int(traced),
        "workflow": sorted({r.workflow for r in reports}),
        "rows_per_rung": rungs, "bins": reports[0].bins if reports else {},
        "hash_bins": _hash_bins(plan) if plan is not None else None,
        "hash_tunings_measured": len(tuning.measurement_log()),
        "compiles_in_window": counter.n, "calls": n_calls,
        "window_s": window_s, "setup_s": setup_s,
        "total_products": total_products,
        "nnz_c": reports[0].nnz_out if reports else 0,
        "overflow_rows": sorted({r.overflow_rows for r in reports}),
        "call_seconds": [c.end - c.start for c in calls],
        "latency_seconds": [c.end - c.arrival for c in calls],
        "stage_seconds": [r.stage_seconds for r in reports],
        "check_s": time.perf_counter() - t_check, "device": device,
    }
    return {"correct": bool(correct), "attempted": n_calls,
            "failed": failed, "metrics": metrics, "device": device,
            **({"breakdown": breakdown} if breakdown else {}),
            "checks": checks, "_info": info}


def _passes(comp, limits) -> bool:
    return (comp.rows_wrong <= limits["rows_wrong"]
            and comp.value_err_over_f32_bound
            <= limits["value_err_over_f32_bound"])


def _named_spans(tr, tracer):
    """The trace's host spans that the program's tracer or the benchmark
    opened, on the trace's clock: what the host was doing, without the
    runtime's own events."""
    names = {"bench.window", "bench.call", *tracer.names()}
    return [e for e in tr.host_spans if e.name in names]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import cells
    try:
        cell = cells.resolve(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return _fail(f"cannot resolve the cell: {e}")
    try:
        import jax
        from repro.kernels import ops as kops
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return _fail(f"cannot import the system under test: {e}")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return _fail(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if kops.use_interpret():
        return _fail("the Pallas kernels would run interpreted")
    if len(devs) < cell.chips:
        return _fail(f"the cell needs {cell.chips} chips, JAX finds "
                     f"{len(devs)}")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    info = result.pop("_info")
    OUT_DIR.mkdir(exist_ok=True)
    with open(OUT_DIR / f"{cell.name}.{args.seed}.{args.trace}.json",
              "w") as f:
        json.dump(info, f, indent=1, default=str)
    for key in ("workflow", "rows_per_rung", "bins", "hash_bins",
                "hash_tunings_measured", "compiles_in_window", "calls",
                "window_s", "overflow_rows", "check_s", "device"):
        print(f"{key}: {json.dumps(info[key], default=str)}", flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
