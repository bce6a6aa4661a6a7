"""Chained SpGEMM with plan reuse and exact feed-forward sizing.

Iterative graph workloads multiply against a fixed right-hand side over
and over: ``C_{k+1} = C_k @ A`` (k-hop frontiers, label propagation) or
``C_{k+1} = C_k @ C_k`` (MCL expansion). Two facts make chains cheaper
than independent multiplies:

* **plan reuse** — once the iterate's sparsity pattern stabilizes (k-hop
  closure, MCL convergence), the structure key repeats and the per-chain
  plan cache skips analysis/prediction/binning outright;
* **exact feed-forward sizing** — every numeric pass *measures* the exact
  output row nnz of its pattern pair. :class:`SizeFeed` records them
  (O(m) ints — orders of magnitude lighter than a plan), so when the same
  pattern pair must be re-planned (plan evicted, fresh per-chain cache on
  a warm service, a different topology or tenant), ``build_plan`` enters
  binning with ``known_sizes=`` — symbolic-grade exact statistics at zero
  prediction cost, skipping HLL sketching/merging and the symbolic sort
  entirely (workflow ``"known"``, surfaced as
  ``OceanReport.feed_forward`` / ``ChainStats.feed_forward_skips``).

Between iterations the output CSR handle (device arrays + static
capacity) feeds straight back in as the next left-hand side — no host
CSR canonicalization, no re-sorting, no format roundtrip. Sketches for
the fixed RHS are shared across the whole chain, and fused merge post-ops
(``repro.graph.ops``) ride along each multiply.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.analysis import OceanConfig
from repro.core.executor import MergePostOps
from repro.core.formats import CSR, lru_bucket, structure_hash
from repro.core.partition import (DeviceSpec, partition_plan,
                                  resolve_devices, topology_key)
from repro.core.planner import (OceanReport, PlanCache, build_plan,
                                execute_plan, execute_sharded_plan,
                                structure_key)

__all__ = ["ChainResult", "ChainRunner", "ChainStats", "SizeFeed",
           "spgemm_chain", "structure_hash"]


class SizeFeed:
    """Exact output row nnz measured by past numeric passes, keyed by the
    product's structure key.

    An entry is a device- and value-independent fact of the pattern pair,
    so feeds outlive plan-cache eviction and are shared across chains,
    topologies, and tenants (``SpGEMMService`` keeps one per right-hand
    side). LRU-bounded: an entry costs O(m) int64.
    """

    def __init__(self, maxsize: int = 256):
        self.maxsize = maxsize
        self._sizes: "OrderedDict[str, np.ndarray]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key: str) -> Optional[np.ndarray]:
        sizes = self._sizes.get(key)
        if sizes is None:
            self.misses += 1
            return None
        self._sizes.move_to_end(key)
        self.hits += 1
        return sizes

    def __contains__(self, key: str) -> bool:
        return key in self._sizes

    def record(self, key: str, sizes: np.ndarray) -> None:
        # defensive copy: the caller's array (often the live
        # OceanReport.raw_row_nnz) must not alias a trusted feed entry
        self._sizes[key] = np.array(sizes, np.int64, copy=True)
        self._sizes.move_to_end(key)
        while len(self._sizes) > self.maxsize:
            self._sizes.popitem(last=False)

    def __len__(self) -> int:
        return len(self._sizes)

    def clear(self) -> None:
        self._sizes.clear()
        self.hits = 0
        self.misses = 0


@dataclasses.dataclass
class ChainStats:
    """Chain-level counters (one per :meth:`ChainRunner.run`; the runner
    also accumulates a lifetime copy)."""
    iterations: int = 0
    plan_hits: int = 0                  # structure key repeated, plan reused
    feed_forward_skips: int = 0         # fresh builds sized from a SizeFeed
    estimated_builds: int = 0           # fresh builds that ran full planning
    converged_at: Optional[int] = None  # iteration the pattern fixed (if any)
    nnz_trajectory: List[int] = dataclasses.field(default_factory=list)
    workflows: List[str] = dataclasses.field(default_factory=list)
    total_seconds: float = 0.0
    setup_seconds: float = 0.0

    @property
    def plan_misses(self) -> int:
        return self.feed_forward_skips + self.estimated_builds


@dataclasses.dataclass
class ChainResult:
    final: CSR
    reports: List[OceanReport]
    stats: ChainStats


class ChainRunner:
    """Stateful driver for iterated multiplies against a (usually fixed)
    right-hand side.

    Holds the per-chain plan cache, the RHS sketch caches, and the
    :class:`SizeFeed`; all three are injectable so a serving tier can
    persist the cheap ones (feeds, sketches) beyond any single chain
    while keeping heavyweight plans on a per-chain leash.
    ``devices``/``analysis_devices``/``executor`` mirror
    ``ocean_spgemm``'s knobs and apply to every iteration.
    """

    def __init__(self, rhs: Optional[CSR],
                 cfg: OceanConfig = OceanConfig(), *,
                 plan_cache: Optional[PlanCache] = None,
                 plan_cache_size: int = 32,
                 size_feed: Optional[SizeFeed] = None,
                 devices: DeviceSpec = None,
                 analysis_devices: DeviceSpec = None,
                 executor: str = "pipelined"):
        self.rhs = rhs
        self.cfg = cfg
        self.plan_cache = (plan_cache if plan_cache is not None
                           else PlanCache(maxsize=plan_cache_size))
        self.size_feed = size_feed if size_feed is not None else SizeFeed()
        self.devices = (resolve_devices(devices) if devices is not None
                        else None)
        self.analysis_devices = (resolve_devices(analysis_devices)
                                 if analysis_devices is not None
                                 else self.devices)
        self.executor = executor
        self.stats = ChainStats()           # lifetime accumulation
        self._sketch_caches: "OrderedDict[str, Dict]" = OrderedDict()

    def _sketch_cache_for(self, rhs: CSR) -> Dict:
        return lru_bucket(self._sketch_caches, structure_hash(rhs), dict)

    # ------------------------------------------------------------------

    def step(self, c: CSR, *, rhs: Optional[CSR] = None,
             post: Optional[MergePostOps] = None,
             stats: Optional[ChainStats] = None
             ) -> Tuple[CSR, OceanReport]:
        """One iteration: ``c @ rhs`` (``rhs`` defaults to the chain's).

        Plan resolution order: plan cache -> size feed (feed-forward
        ``known_sizes`` build) -> full estimation-based build. The plan
        cache key is the *clean* structure key — a feed-forward plan for
        a pattern pair is interchangeable with an estimated one (exact
        sizes for that exact structure), so later lookups hit either.
        """
        rhs = self.rhs if rhs is None else rhs
        if rhs is None:
            raise ValueError("no right-hand side: pass rhs= to step() or "
                             "construct the runner with one")
        t0 = time.perf_counter()
        key = structure_key(c, rhs, self.cfg, None, True, True)
        lkey = (key if self.devices is None
                else key + "|" + topology_key(self.devices))
        plan = self.plan_cache.lookup(lkey)
        lookup_s = time.perf_counter() - t0
        # how this iteration's planning resolved, for the stats tiers:
        # "hit" (no planning at all, incl. a base plan that only needed
        # re-partitioning), "known" (fresh build from a size feed),
        # "estimated" (fresh build with full prediction)
        resolved = "hit"
        copies = None   # the plan build's copies, when this call built one
        if plan is None:
            base = (self.plan_cache.peek(key) if self.devices is not None
                    else None)
            if base is None:
                known = self.size_feed.get(key)
                base = build_plan(c, rhs, self.cfg, key=key,
                                  sketch_cache=self._sketch_cache_for(rhs),
                                  analysis_devices=self.analysis_devices,
                                  known_sizes=known)
                self.plan_cache.insert(key, base)
                stage = dict(base.build_seconds)
                copies = base.build_copy_bytes
                resolved = "known" if known is not None else "estimated"
            else:
                stage = {"analysis": 0.0, "prediction": 0.0, "binning": 0.0}
            if self.devices is not None:
                t0 = time.perf_counter()
                plan = partition_plan(base, self.devices)
                stage["partition"] = time.perf_counter() - t0
                self.plan_cache.insert(lkey, plan)
            else:
                plan = base
        else:
            stage = {"analysis": 0.0, "prediction": 0.0, "binning": 0.0}
        hit = resolved == "hit"
        stage["plan_lookup"] = lookup_s

        if self.devices is not None:
            c_out, rep = execute_sharded_plan(plan, c, rhs, stage=stage,
                                              cache_hit=hit,
                                              executor=self.executor,
                                              post=post, copy_bytes=copies)
        else:
            c_out, rep = execute_plan(plan, c, rhs, stage=stage,
                                      cache_hit=hit, executor=self.executor,
                                      post=post, copy_bytes=copies)

        # record the measured exact raw product sizes for this pattern
        # pair — the feed the next plan of the same pair is built from.
        # Plan hits with a resident feed entry skip the O(m) re-record:
        # the measured sizes of an identical pattern pair are identical.
        if resolved != "hit" or key not in self.size_feed:
            raw = (rep.raw_row_nnz if rep.raw_row_nnz is not None
                   else np.diff(np.asarray(c_out.indptr)).astype(np.int64))
            self.size_feed.record(key, raw)

        for st in (self.stats,) if stats is None else (self.stats, stats):
            st.iterations += 1
            st.plan_hits += int(resolved == "hit")
            st.feed_forward_skips += int(resolved == "known")
            st.estimated_builds += int(resolved == "estimated")
            st.nnz_trajectory.append(rep.nnz_out)
            st.workflows.append(rep.workflow)
            st.total_seconds += rep.total_seconds
            st.setup_seconds += rep.setup_seconds
        return c_out, rep

    def run(self, c0: CSR, iterations: int, *,
            rhs: Optional[CSR] = None,
            post: Optional[MergePostOps] = None,
            square: bool = False,
            stop_on_fixed_pattern: bool = False) -> ChainResult:
        """Run ``iterations`` chained multiplies from ``c0``.

        ``square=True`` multiplies the iterate by itself (MCL expansion)
        instead of the chain's RHS. ``stop_on_fixed_pattern`` stops early
        once an iteration leaves the sparsity pattern unchanged (k-hop
        closure; values may still change — callers wanting value
        convergence check the reports). The output handle feeds straight
        back in as the next LHS: no host CSR rebuild between iterations.
        """
        stats = ChainStats()
        reports: List[OceanReport] = []
        c = c0
        prev_hash = structure_hash(c0) if stop_on_fixed_pattern else None
        for it in range(iterations):
            c, rep = self.step(c, rhs=(c if square else rhs), post=post,
                               stats=stats)
            reports.append(rep)
            if stop_on_fixed_pattern:
                cur = structure_hash(c)
                if cur == prev_hash:
                    stats.converged_at = it + 1
                    break
                prev_hash = cur
        return ChainResult(final=c, reports=reports, stats=stats)


def spgemm_chain(c0: CSR, a: CSR, iterations: int,
                 cfg: OceanConfig = OceanConfig(), *,
                 post: Optional[MergePostOps] = None,
                 stop_on_fixed_pattern: bool = False,
                 **runner_kw) -> ChainResult:
    """Convenience one-shot chain: ``C_{k+1} = C_k @ A`` for
    ``iterations`` steps with per-chain plan reuse and feed-forward
    sizing. ``runner_kw`` forwards to :class:`ChainRunner` (``devices=``,
    ``size_feed=``, ``executor=``, ...)."""
    runner = ChainRunner(a, cfg, **runner_kw)
    return runner.run(c0, iterations, post=post,
                      stop_on_fixed_pattern=stop_on_fixed_pattern)
