"""Bring-up smoke of ocean_spgemm on a TPU: the main path at real size.

    python chip_smoke.py [--seed N] [--chips 4]

One chip (the default): computes C = A @ A for two 2**18 x 2**18 matrices
generated from ``--seed`` -- uniform with 16 nnz per row, and power-law
with 8 -- through ``workflow.ocean_spgemm``, twice each (cold: fresh plan
and compiles; warm: plan-cache hit). The uniform multiply takes the
workflow the selector picks; the power-law one is forced to ``estimation``,
so the HLL merge kernel and the dense and hash rungs all receive rows.
Each C is checked against scipy (``workflow.scipy_mismatch``). The
estimation path is checked too: the merge kernel's registers and
estimates for the power-law A against the XLA merge, and the plan's
estimation error (``measure_accuracy``) is printed.

``--chips 4`` runs only the sharded path: the uniform multiply with
``devices=4, analysis_devices=4`` against the same multiply on one device.
The outputs must be bit-identical and the shards' kernel launches must sit
on four distinct devices.

Fails (non-zero exit, no result line) when JAX finds no TPU, when the
Pallas kernels would run interpreted, when a rung that should have run got
no rows, or when a result differs from the reference. The last line of a
passing run is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

N = 2**18


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def _rows_per_rung(bins) -> dict:
    rungs = {"dense": 0, "hash": 0, "esc": 0}
    for key, rows in bins.items():
        if key.startswith("dense_w"):
            rungs["dense"] += rows
        elif key.startswith("hash_t"):
            rungs["hash"] += rows
        elif key == "esc":
            rungs["esc"] += rows
    return rungs


def _timed(fn):
    """Run ``fn() -> (C, report)`` and time it until C is on the device."""
    import jax
    t0 = time.perf_counter()
    c, rep = fn()
    jax.block_until_ready((c.indptr, c.indices, c.values))
    return c, rep, time.perf_counter() - t0


def _multiply_twice(name, a, force, **kw):
    """Cold then warm ``ocean_spgemm(a, a)`` on one fresh plan cache;
    prints both calls and returns (C, report) of the warm one and the
    cache."""
    from repro.core import planner, workflow
    cache = planner.PlanCache()
    out = None
    for call in ("cold", "warm"):
        c, rep, secs = _timed(lambda: workflow.ocean_spgemm(
            a, a, force_workflow=force, cache=cache, **kw))
        dec = rep.decision or {}
        print(f"{name} {call}: workflow={rep.workflow} "
              f"forced={force} plan_cache_hit={rep.plan_cache_hit} "
              f"rows_per_rung={_rows_per_rung(rep.bins)} "
              f"overflow_rows={rep.overflow_rows} nnz_C={c.nnz} "
              f"seconds={secs:.3f} nproducts_avg={dec.get('nproducts_avg')} "
              f"er={dec.get('er')} sampled_cr={dec.get('sampled_cr')} "
              f"shards={rep.n_shards}", flush=True)
        out = (c, rep, cache)
    return out


def _check(name, c, a) -> str | None:
    from repro.core import workflow
    t0 = time.perf_counter()
    bad = workflow.scipy_mismatch(c, a, a)
    print(f"{name}: scipy reference {'ok' if bad is None else 'MISMATCH'} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    return None if bad is None else f"{name}: {bad}"


def _check_merge(a, er) -> str | None:
    """The HLL merge kernel (compiled Pallas) against the XLA merge and
    estimate, on the B-row sketches the estimation workflow builds."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import hll as chll
    from repro.core.analysis import OceanConfig, sketches_for
    from repro.kernels import ops as kops
    cfg = OceanConfig()
    sk = sketches_for(a, cfg.m_regs(er), cfg.seed)
    sentinel = jnp.zeros((1, sk.shape[1]), jnp.int32)
    merged, est = kops.merge_estimate_op(a, jnp.concatenate([sk, sentinel]))
    merged_x = chll.merge_sketches(a.indptr, a.indices, sk, num_rows_a=a.m)
    est_x = np.asarray(chll.estimate_cardinality(merged_x))
    same = np.array_equal(np.asarray(merged), np.asarray(merged_x))
    rel = float(np.max(np.abs(np.asarray(est) - est_x)
                       / np.maximum(est_x, 1.0)))
    print(f"powerlaw hll_merge vs XLA: m_regs={sk.shape[1]} "
          f"registers_identical={same} est_max_rel_diff={rel:.3g}",
          flush=True)
    if not same:
        return "HLL merge kernel registers differ from the XLA merge"
    # the two estimates sum the same exp2 terms in different orders
    if rel > 1e-4:
        return f"HLL merge kernel estimates differ from XLA by {rel:.3g}"
    return None


def one_chip(seed: int) -> int:
    from repro.core import formats
    t0 = time.perf_counter()
    mats = {"uniform": formats.random_uniform_csr(seed, N, N, 16),
            "powerlaw": formats.powerlaw_csr(seed + 1, N, N, 8)}
    for name, a in mats.items():
        print(f"{name}: {a.m}x{a.n} nnz={a.nnz}", flush=True)
    print(f"generated in {time.perf_counter() - t0:.1f}s", flush=True)
    rungs = {"dense": 0, "hash": 0, "esc": 0}
    workflows = []
    for name, force in (("uniform", None), ("powerlaw", "estimation")):
        c, rep, _ = _multiply_twice(name, mats[name], force)
        workflows.append(rep.workflow)
        for k, v in _rows_per_rung(rep.bins).items():
            rungs[k] += v
        bad = _check(name, c, mats[name])
        if bad:
            return _fail(bad)
        if rep.workflow == "estimation":
            print(f"{name} estimation accuracy: "
                  f"{rep.estimation_accuracy.summary()}", flush=True)
            bad = _check_merge(mats[name], rep.decision["er"])
            if bad:
                return _fail(bad)
    if "estimation" not in workflows:
        return _fail(f"no multiply took the estimation workflow: {workflows}")
    for rung in ("dense", "hash"):
        if not rungs[rung]:
            return _fail(f"the {rung} rung received no rows: {rungs}")
    return 0


def four_chips(seed: int) -> int:
    import jax
    import numpy as np
    from repro.core import formats, planner
    from repro.core.analysis import OceanConfig
    from repro.core.partition import resolve_devices, topology_key
    if len(jax.devices()) < 4:
        return _fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    a = formats.random_uniform_csr(seed, N, N, 16)
    print(f"uniform: {a.m}x{a.n} nnz={a.nnz}", flush=True)
    c1, _, _ = _multiply_twice("uniform 1-device", a, None)
    c4, _, cache = _multiply_twice("uniform 4-device", a, None, devices=4,
                                   analysis_devices=4)
    for x, y, part in zip((c1.indptr, c1.indices, c1.values),
                          (c4.indptr, c4.indices, c4.values),
                          ("indptr", "indices", "values")):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return _fail(f"1- and 4-device outputs differ in {part}")
    print("1-device and 4-device outputs bit-identical", flush=True)
    # the launches' committed inputs show where each shard's kernels ran
    key = planner.structure_key(a, a, OceanConfig(), None, True, True)
    splan = cache.peek(key + "|" + topology_key(resolve_devices(4)))
    used = set()
    for sh in splan.shards:
        for be in list(sh.dense) + list(sh.hash):
            (dev,) = be.a_rows.devices()
            if dev != sh.device:
                return _fail(f"shard for {sh.device} launches on {dev}")
            used.add(dev)
    print(f"shard launches on {len(used)} devices: "
          f"{sorted(str(d) for d in used)}", flush=True)
    if len(used) != 4:
        return _fail(f"expected launches on 4 distinct devices, got {used}")
    bad = _check("uniform 1-device", c1, a)
    return _fail(bad) if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import jax
        from repro.kernels import ops as kops
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return _fail(f"cannot import the system: {e}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _fail(f"JAX finds no TPU (platform {dev.platform!r})")
    if kops.use_interpret():
        return _fail("Pallas kernels would run in interpret mode")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    rc = four_chips(args.seed) if args.chips == 4 else one_chip(args.seed)
    if rc:
        return rc
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
