"""Readings of the comparison that decides ``correct``, over many seeds in
one process: the program as the window runs it, or the control in its
place.

    python bench/control.py --workload <cell> --seconds <s> --control <0|1> \
        --seeds <n> [<n> ...]

The control is the plain reference computed at bfloat16 (``Reference.
control``) put where the timed path's C would be: each call still runs
``ocean_spgemm`` and is timed as usual, and its C is replaced by the
control's. A sound limit passes every seed of the program and fails every
seed of the control. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_multiply(multiply):
    """``multiply`` with its C replaced by the bfloat16 reference's."""
    import numpy as np
    from bench.reference import Reference
    from repro.core.formats import csr_from_arrays
    refs = {}

    def call(a, b, **kw):
        c, rep = multiply(a, b, **kw)
        key = (a.shape, a.nnz, b.shape, b.nnz)
        if key not in refs:
            refs[key] = Reference(a.indptr, a.indices, a.shape,
                                  b.indptr, b.indices, b.shape)
        ref = refs[key]
        vals = ref.control(np.asarray(a.values)[: a.nnz],
                           np.asarray(b.values)[: b.nnz])
        return csr_from_arrays(ref.indptr, ref.indices,
                               vals.astype(np.float32), c.shape), rep
    return call


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from bench import cells, run
    from repro.core import workflow
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control: JAX finds no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = cells.resolve(args.workload)
    multiply = (control_multiply(workflow.ocean_spgemm) if args.control
                else workflow.ocean_spgemm)
    readings = []
    for seed in args.seeds:
        r = run.run_cell(cell, seed, args.seconds, False,
                         t_start=time.perf_counter(), multiply=multiply)
        row = {"seed": seed, "correct": r["correct"],
               "calls": r["attempted"], "failed": r["failed"],
               **{k: v["value"] for k, v in r["checks"].items()},
               "workflow": r["_info"]["workflow"],
               "rows_per_rung": r["_info"]["rows_per_rung"],
               "gflops": r["metrics"]["gflops"]["value"],
               "setup_s": r["metrics"]["setup_s"]["value"]}
        readings.append(row)
        print(json.dumps(row), flush=True)
    worst = max(r["value_err_over_f32_bound"] for r in readings)
    least = min(r["value_err_over_f32_bound"] for r in readings)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "seeds": len(readings), "value_err_max": worst,
                      "value_err_min": least,
                      "rows_wrong_max": max(r["rows_wrong"]
                                            for r in readings)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
