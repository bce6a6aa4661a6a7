"""Ocean's end-to-end SpGEMM workflow (paper Fig. 4).

    analysis -> size prediction (HLL | symbolic | upper-bound)
             -> binning -> numeric accumulation -> overflow fallback
             -> post-processing (CSR compaction)

Workflow/kernel selection happens on the host — exactly where CUDA SpGEMM
does it — and every device stage is a statically-shaped jitted computation
(shapes bucketed by the binning ladder to bound recompilation).

The first three stages are structure-only and live in ``core.planner`` as a
reusable :class:`~repro.core.planner.ExecutionPlan`; ``ocean_spgemm``
consults an LRU plan cache so repeated calls on an unchanged sparsity
pattern skip analysis/prediction/binning entirely (``cache=False`` restores
the always-fresh seed behaviour, e.g. for benchmarking the algorithm).

Ablation knobs mirror the paper's Table 3 versions:
    V1 baseline:  force_workflow='symbolic', assisted=False, hybrid=False
    V2 (+E):      assisted=False, hybrid=False
    V3 (+AS):     assisted=True,  hybrid=False
    V4 (+HA):     assisted=True,  hybrid=True      (full Ocean)
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.obs import trace
from . import esc as esc_mod
from .analysis import AnalysisResult, OceanConfig
from .formats import CSR, pow2_at_least
from .partition import (DeviceSpec, ShardedPlan, partition_plan,
                        resolve_devices, topology_key)
from .planner import (DEFAULT_PLAN_CACHE, ExecutionPlan, OceanReport,
                      PlanCache, build_plan, execute_plan,
                      execute_sharded_plan, gather_rows, structure_key)

__all__ = ["OceanReport", "ocean_spgemm", "ocean_spgemm_many",
           "spgemm_reference", "scipy_mismatch", "gather_rows", "warm_plan"]


def _resolve_cache(cache: Union[bool, PlanCache, None]):
    if cache is True:
        return DEFAULT_PLAN_CACHE
    if cache is False or cache is None:
        return None
    if hasattr(cache, "lookup") and hasattr(cache, "insert"):
        # a PlanCache or any compatible view — e.g. the per-tenant
        # planner.TenantPlanCache namespaces the serving tier hands out
        return cache
    raise TypeError(f"cache must be bool/None or expose lookup/insert, "
                    f"got {type(cache).__name__}")


def ocean_spgemm(a: CSR, b: CSR, cfg: OceanConfig = OceanConfig(), *,
                 force_workflow: Optional[str] = None,
                 assisted: bool = True, hybrid: bool = True,
                 analysis: Optional[AnalysisResult] = None,
                 plan: Union[ExecutionPlan, ShardedPlan, None] = None,
                 cache: Union[bool, PlanCache, None] = True,
                 sketch_cache: Optional[Dict] = None,
                 devices: DeviceSpec = None,
                 analysis_devices: DeviceSpec = None,
                 executor: str = "pipelined",
                 known_sizes=None,
                 post=None,
                 ) -> Tuple[CSR, OceanReport]:
    """Estimation-based SpGEMM, C = A @ B. Returns (C, report).

    ``plan``: execute a prebuilt :class:`ExecutionPlan` (or
    :class:`ShardedPlan`) directly (its structure must match ``a``/``b``).
    ``cache``: ``True`` (default) uses the process-wide LRU plan cache,
    a :class:`PlanCache` instance uses that cache, ``False``/``None``
    always plans from scratch. A caller-supplied ``analysis`` bypasses the
    cache (its provenance is unknown to the keying scheme).
    ``sketch_cache``: dict shared across calls against the same B to reuse
    HLL sketches (see ``ocean_spgemm_many``).
    ``devices``: partition the plan's bins across these devices (int,
    device sequence, or 1-D mesh — see ``core.partition``) and execute the
    shards in parallel; results are bit-identical to single-device
    execution. Sharded plans are cached under the structure key extended
    with the device topology, reusing a cached base plan when present.
    Combined with an explicit ``plan=ExecutionPlan`` this re-partitions
    per call — for repeated calls pass a prebuilt ``ShardedPlan`` instead.
    ``analysis_devices``: partition the *analysis stage* across these
    devices too (``core.analysis.AnalysisPipeline``). Defaults to
    ``devices`` — a multi-device call shards its analysis over the same
    topology unless told otherwise. Analysis output is bit-identical at
    any shard count, so this never changes results or plan-cache keys
    (only where the O(nnz) setup work runs); per-shard timings surface as
    ``OceanReport.analysis_shard_seconds``.
    ``executor``: ``"pipelined"`` (default) overlaps the host merge with
    device work through ``core.executor``; ``"threaded"`` adds a
    dedicated merge-worker thread so merge work also proceeds while the
    collect loop blocks on a device queue; ``"serial"`` keeps the global
    barrier before the merge. Output is bit-identical in all three.
    ``known_sizes``: exact per-row output nnz fed forward from a prior
    numeric pass over the same pattern pair (graph chains —
    ``repro.graph.chain``); planning skips estimation entirely and bins
    with symbolic-grade exact sizes (workflow ``"known"``). Hashed into
    the plan-cache key: feed-forward plans never alias clean ones.
    ``post``: fused merge post-ops (``core.executor.MergePostOps``) — mask
    filter, value transform, prune, column-normalize applied inside the
    executor's merge instead of separate host passes over the output
    (``repro.graph.ops`` builds these). Plans are post-independent, so a
    cached plan serves masked and unmasked traffic alike.
    """
    if plan is not None:
        if isinstance(plan, ShardedPlan):
            if devices is not None:
                topo = topology_key(resolve_devices(devices))
                if topo != plan.topology:
                    raise ValueError(
                        f"plan was partitioned for [{plan.topology}], "
                        f"devices= requests [{topo}]; re-partition the "
                        "base plan with partition_plan(plan.plan, devices)")
            return execute_sharded_plan(plan, a, b, executor=executor,
                                        post=post)
        if devices is not None:
            # convenience path: partitions on every call. For repeated
            # values-only updates partition once (partition_plan) and pass
            # the ShardedPlan; the cost is surfaced as the partition stage.
            stage = {"analysis": 0.0, "prediction": 0.0, "binning": 0.0}
            splan = _partition(plan, devices, stage)
            return execute_sharded_plan(splan, a, b, stage=stage,
                                        executor=executor, post=post)
        return execute_plan(plan, a, b, executor=executor, post=post)

    devs = resolve_devices(devices) if devices is not None else None
    an_devs = (resolve_devices(analysis_devices)
               if analysis_devices is not None else devs)
    cache_obj = _resolve_cache(cache) if analysis is None else None
    if cache_obj is not None:
        with trace.span("plan.lookup") as sp:
            t0 = time.perf_counter()
            key = structure_key(a, b, cfg, force_workflow, assisted, hybrid,
                                known_sizes=known_sizes)
            lkey = key if devs is None else key + "|" + topology_key(devs)
            cached = cache_obj.lookup(lkey)
            lookup_s = time.perf_counter() - t0
            sp.measured(t0, lookup_s).set(hit=bool(cached is not None))
        if cached is not None:
            # the cached path's entire host-side setup cost is the O(nnz)
            # structure hash + LRU lookup
            stage = {"plan_lookup": lookup_s, "analysis": 0.0,
                     "prediction": 0.0, "binning": 0.0}
            if devs is None:
                return execute_plan(cached, a, b, stage=stage,
                                    cache_hit=True, executor=executor,
                                    post=post)
            return execute_sharded_plan(cached, a, b, stage=stage,
                                        cache_hit=True, executor=executor,
                                        post=post)
        # sharded miss: reuse a cached base plan for this structure if one
        # exists (peek — the request-level stats already counted the miss)
        base = cache_obj.peek(key) if devs is not None else None
        copies = None
        if base is not None:
            stage = {"analysis": 0.0, "prediction": 0.0, "binning": 0.0}
        else:
            base = build_plan(a, b, cfg, force_workflow=force_workflow,
                              assisted=assisted, hybrid=hybrid,
                              sketch_cache=sketch_cache, key=key,
                              analysis_devices=an_devs,
                              known_sizes=known_sizes)
            cache_obj.insert(key, base)
            stage = dict(base.build_seconds)
            copies = base.build_copy_bytes
        stage["plan_lookup"] = lookup_s
        if devs is None:
            return execute_plan(base, a, b, stage=stage, executor=executor,
                                post=post, copy_bytes=copies)
        splan = _partition(base, devs, stage)
        cache_obj.insert(lkey, splan)
        return execute_sharded_plan(splan, a, b, stage=stage,
                                    executor=executor, post=post,
                                    copy_bytes=copies)
    fresh = build_plan(a, b, cfg, force_workflow=force_workflow,
                       assisted=assisted, hybrid=hybrid,
                       analysis=analysis, sketch_cache=sketch_cache,
                       analysis_devices=an_devs, known_sizes=known_sizes)
    if devs is not None:
        stage = dict(fresh.build_seconds)
        splan = _partition(fresh, devs, stage)
        return execute_sharded_plan(splan, a, b, stage=stage,
                                    executor=executor, post=post,
                                    copy_bytes=fresh.build_copy_bytes)
    return execute_plan(fresh, a, b, stage=fresh.build_seconds,
                        executor=executor, post=post,
                        copy_bytes=fresh.build_copy_bytes)


def _partition(plan: ExecutionPlan, devices, stage: Dict[str, float]
               ) -> ShardedPlan:
    """``partition_plan`` under a ``plan.partition`` span, its seconds in
    ``stage["partition"]``."""
    with trace.span("plan.partition") as sp:
        t0 = time.perf_counter()
        splan = partition_plan(plan, devices)
        stage["partition"] = time.perf_counter() - t0
        sp.measured(t0, stage["partition"])
    return splan


def warm_plan(a: CSR, b: CSR, cfg: OceanConfig = OceanConfig(), *,
              force_workflow: Optional[str] = None,
              assisted: bool = True, hybrid: bool = True,
              cache: Union[bool, PlanCache, None] = True,
              sketch_cache: Optional[Dict] = None,
              devices: DeviceSpec = None,
              analysis_devices: DeviceSpec = None,
              known_sizes=None) -> Tuple[str, bool]:
    """Build (or verify) the cached plan for ``A @ B`` without executing it.

    The speculative half of ``ocean_spgemm``: identical keying, identical
    ``build_plan``/``partition_plan`` calls, identical cache inserts — so a
    later ``ocean_spgemm`` with the same arguments is a pure cache hit and
    returns bit-identical results to a cold call (plans are deterministic
    functions of structure + config). Used by the serving pool's plan
    warmer to convert queue wait time into plan-setup time.

    Returns ``(cache_key, built)`` where ``built`` says whether any plan
    was constructed (``False`` == already warm). Lookups go through
    ``peek`` so warming never skews request-level hit/miss statistics.
    """
    cache_obj = _resolve_cache(cache)
    if cache_obj is None:
        raise ValueError("warm_plan needs a cache to warm (cache=False/None)")
    devs = resolve_devices(devices) if devices is not None else None
    an_devs = (resolve_devices(analysis_devices)
               if analysis_devices is not None else devs)
    key = structure_key(a, b, cfg, force_workflow, assisted, hybrid,
                        known_sizes=known_sizes)
    lkey = key if devs is None else key + "|" + topology_key(devs)
    if cache_obj.peek(lkey) is not None:
        return lkey, False
    built = False
    base = cache_obj.peek(key) if devs is not None else None
    if base is None:
        base = build_plan(a, b, cfg, force_workflow=force_workflow,
                          assisted=assisted, hybrid=hybrid,
                          sketch_cache=sketch_cache, key=key,
                          analysis_devices=an_devs, known_sizes=known_sizes)
        cache_obj.insert(key, base)
        built = True
    if devs is not None:
        cache_obj.insert(lkey, partition_plan(base, devs))
        built = True
    return lkey, built


def ocean_spgemm_many(a_list: Sequence[CSR], b: CSR,
                      cfg: OceanConfig = OceanConfig(), *,
                      force_workflow: Optional[str] = None,
                      assisted: bool = True, hybrid: bool = True,
                      cache: Union[bool, PlanCache, None, Sequence] = True,
                      sketch_cache: Union[Dict, Sequence, None] = None,
                      devices: DeviceSpec = None,
                      analysis_devices: DeviceSpec = None,
                      executor: str = "pipelined",
                      ) -> List[Tuple[CSR, OceanReport]]:
    """Batched SpGEMM: ``[A_i @ B for A_i in a_list]`` against one B.

    Amortizes B-sketch construction across the stream of left-hand sides
    (the sketches depend only on B); per-call outputs are bit-identical to
    a Python loop of single ``ocean_spgemm`` calls because sketch
    construction is deterministic — including sketches built by the
    sharded analysis pipeline, which interchange with monolithic ones in
    the shared cache. ``devices`` shards every multiply in the stream
    across the same device set (resolved once); ``analysis_devices``
    shards each call's analysis stage (defaults to ``devices``);
    ``executor`` picks the pipelined (overlapped merge), threaded
    (merge-worker thread), or serial execution path.

    ``cache`` and ``sketch_cache`` also accept a *sequence* with one entry
    per left-hand side — the multi-tenant pool (``repro.serving.pool``)
    micro-batches requests from different tenants into one call this way,
    each item hitting its own tenant's plan-cache namespace and per-RHS
    sketch bucket. Outputs are unaffected (plans and sketches are
    deterministic functions of structure + config); only where the cached
    artifacts live changes. When ``sketch_cache`` is ``None`` a fresh dict
    is shared across the batch, preserving the original amortization.
    """
    n = len(a_list)
    caches = (list(cache) if isinstance(cache, (list, tuple))
              else [cache] * n)
    if isinstance(sketch_cache, (list, tuple)):
        sketches = list(sketch_cache)
    else:
        shared: Dict = {} if sketch_cache is None else sketch_cache
        sketches = [shared] * n
    if len(caches) != n or len(sketches) != n:
        raise ValueError(
            f"per-item cache/sketch_cache sequences must match a_list: "
            f"{len(caches)}/{len(sketches)} entries for {n} items")
    devs = resolve_devices(devices) if devices is not None else None
    an_devs = (resolve_devices(analysis_devices)
               if analysis_devices is not None else devs)
    return [ocean_spgemm(a, b, cfg, force_workflow=force_workflow,
                         assisted=assisted, hybrid=hybrid, cache=c,
                         sketch_cache=s, devices=devs,
                         analysis_devices=an_devs, executor=executor)
            for a, c, s in zip(a_list, caches, sketches)]


def scipy_mismatch(c: CSR, a: CSR, b: CSR) -> Optional[str]:
    """Check ``C == A @ B`` against scipy, an implementation independent of
    this package. Returns ``None`` on a match, else what differs.

    The structure must match exactly. scipy drops exact zeros from a value
    product, so the structure comes from the pattern product (all values
    1), which also counts the products each entry sums. Values must lie
    within the forward error bound of f32 summation: ``count * 2**-23 *
    (|A| @ |B|)`` entrywise, with scipy's float64 product as the truth.
    """
    import scipy.sparse as sp

    def mat(x: CSR, data=None) -> "sp.csr_matrix":
        ip, ii, vv = x.to_scipy_like()
        vals = vv.astype(np.float64) if data is None else data(vv)
        return sp.csr_matrix((vals, ii, ip), shape=x.shape)

    ones = lambda v: np.ones(v.shape, np.float64)  # noqa: E731
    count = mat(a, ones) @ mat(b, ones)
    count.sort_indices()
    ip, ii, vv = c.to_scipy_like()
    if c.shape != count.shape:
        return f"shape {c.shape} != {count.shape}"
    if not (np.array_equal(ip, count.indptr)
            and np.array_equal(ii, count.indices)):
        return (f"structure differs: nnz {c.nnz} vs {count.nnz}, "
                f"{int(np.sum(np.diff(ip) != np.diff(count.indptr)))} rows "
                "with a different entry count")
    rows = np.repeat(np.arange(c.m), np.diff(count.indptr))
    exact = np.asarray((mat(a) @ mat(b))[rows, count.indices]).ravel()
    mag = np.asarray((mat(a, np.abs) @ mat(b, np.abs))[rows, count.indices]
                     ).ravel()
    bound = count.data * 2.0**-23 * mag
    err = np.abs(vv.astype(np.float64) - exact)
    bad = err > bound
    if bad.any():
        i = int(np.argmax(err - bound))
        return (f"{int(bad.sum())} values outside the f32 bound; worst at "
                f"({int(rows[i])}, {int(count.indices[i])}): {vv[i]} vs "
                f"{exact[i]} (bound {bound[i]})")
    return None


def spgemm_reference(a: CSR, b: CSR) -> CSR:
    """Exact two-pass reference via the ESC machinery (used as oracle)."""
    from .analysis import products_per_row
    prod = products_per_row(a.indptr, a.indices, b.indptr, num_rows_a=a.m)
    p = int(jnp.sum(prod))
    p_cap = pow2_at_least(p + 1, floor=64)
    res = esc_mod.esc_spgemm(a.indptr, a.indices, a.values, b.indptr,
                             b.indices, b.values, p_cap=p_cap, out_cap=p_cap,
                             num_rows_a=a.m)
    return esc_mod.esc_to_csr(res, (a.m, b.n), p_cap)
