"""Measured autotuner for the hash-accumulator rung.

The paper tunes its hash kernels per GPU generation (table load factor,
thread-block shapes). The analogue here is measured, not hardcoded: for a
table-size rung the tuner times the hash bin op on a tiny synthetic
workload scaled to that rung, across a small candidate grid of

* primary-table **load factor** (how much slack ``plan_bins`` sizes the
  table with relative to the predicted row nnz),
* DMA **chunk shape** (``f_chunk``, the B-stream chunk the Pallas kernel
  copies per step), and
* row **tile** (``tile_rows``, how many rows one grid step probes
  vectorized — the multi-row dimension of ``kernels.spgemm_hash``).

Measurements run through :func:`repro.kernels.ops.hash_bin_op` — the
*real dispatching backend path*, exactly what the executor calls — so the
timed code is the Pallas kernel (compiled on TPU, interpreted under
``REPRO_CPU_NUMERIC=pallas``) or the XLA twin, whichever this process
will actually execute. On the XLA path the f_chunk/tile candidates are
no-ops, so they tie and the defaults win; the cache key's kernel-path
component keeps those measurements from aliasing Pallas-path ones.

Winners cache in a :class:`TuningCache` — a thread-safe LRU keyed by a
digest of (rung, backend, kernel path), the same keying discipline as
``planner.PlanCache``. A measurement that fails raises, so a kernel that
cannot run on this backend surfaces at plan build instead of being masked
by untuned defaults.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from .binning import HASH_LOAD_FACTOR, HASH_MIN_TABLE, hash_spill_of
from .formats import pow2_at_least

# Candidate grid. Load factors below 0.5 waste VMEM; above ~0.85 linear
# probing degrades. f_chunk=64 only matters on the Pallas path (smaller
# DMA granularity for short B rows), as does the row tile (a multiple of
# the f32 sublane tile 8, the only tiles the TPU lowering accepts). The
# tile ladder descends from the widest candidate: per-step work shrinks
# monotonically down the ladder, so once a step times *worse* than its
# predecessor the rest of the tail can only lose and the sweep prunes it
# (the kernel is bit-identical at every tile, so pruning is timing-only).
LOAD_FACTOR_CANDIDATES = (0.5, HASH_LOAD_FACTOR)
F_CHUNK_CANDIDATES = (128,)
F_CHUNK_CANDIDATES_PALLAS = (128, 64)
TILE_CANDIDATES = (8,)
TILE_CANDIDATES_PALLAS = (16, 8)

# The rung the planner consults for the load factor it hands to binning
# (binning runs before per-bin rungs are known, so one representative
# measurement steers table sizing; per-bin f_chunk is tuned at the bin's
# own rung afterwards).
REFERENCE_RUNG = 256


@dataclasses.dataclass(frozen=True)
class HashTuning:
    """One rung's measured choice."""
    load_factor: float = HASH_LOAD_FACTOR
    f_chunk: int = 128
    tile_rows: int = 8


DEFAULT_TUNING = HashTuning()


class TuningCache:
    """Thread-safe LRU of :class:`HashTuning` entries, keyed like plans
    (hash digest of every input that could change the measurement)."""

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._entries: "OrderedDict[str, HashTuning]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def lookup(self, key: str) -> Optional[HashTuning]:
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return hit

    def insert(self, key: str, tuning: HashTuning) -> None:
        with self._lock:
            self._entries[key] = tuning
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "size": len(self._entries)}


DEFAULT_TUNING_CACHE = TuningCache()

# In-memory log of every autotune measurement — including the losing
# candidates and which tile-ladder tails were pruned. Benchmarks drain it
# into the bench artifact (``tuning/...`` rows in BENCH_smoke.json) so
# losing-candidate timings survive for later hardware runs.
MEASUREMENT_LOG: Dict[int, list] = {}
_LOG_LOCK = threading.Lock()


def _log_measurement(rung: int, entry: Dict) -> None:
    with _LOG_LOCK:
        MEASUREMENT_LOG.setdefault(int(rung), []).append(entry)


def measurement_log() -> Dict[int, list]:
    """Snapshot of all recorded autotune measurements, keyed by rung."""
    with _LOG_LOCK:
        return {r: [dict(e) for e in v] for r, v in MEASUREMENT_LOG.items()}


def clear_measurement_log() -> None:
    with _LOG_LOCK:
        MEASUREMENT_LOG.clear()


def tuning_key(rung: int) -> str:
    """Digest of everything the measurement depends on: the rung, the jax
    backend, and which kernel path (Pallas vs XLA executor) will run."""
    from repro.kernels import ops as kops
    h = hashlib.blake2b(digest_size=16)
    h.update(repr(("hash-rung", int(rung), jax.default_backend(),
                   kops._use_pallas_path())).encode())
    return h.hexdigest()


def _synthetic_workload(rung: int, f_chunk: int) -> Tuple:
    """A tiny bin whose rows hold ~0.6*rung distinct columns — dense
    enough to exercise probing, sparse enough to finish in microseconds."""
    rng = np.random.default_rng(rung)
    r, nb = 4, 4
    nnz_row = max(int(rung * 0.6), 8)
    blen = max(nnz_row // nb, 1)
    b_cols = rng.integers(0, max(2 * rung, 64), size=nb * blen,
                          ).astype(np.int32)
    b_vals = np.ones(nb * blen, np.float32)
    pad = pow2_at_least(nb * blen + f_chunk, floor=f_chunk)
    b_cols = np.concatenate([b_cols, np.full(pad - nb * blen, -1, np.int32)])
    b_vals = np.concatenate([b_vals, np.zeros(pad - nb * blen, np.float32)])
    a_rows = np.tile(np.arange(nb, dtype=np.int32), (r, 1))
    a_vals = np.ones((r, nb), np.float32)
    a_starts = np.tile((np.arange(nb, dtype=np.int32) * blen), (r, 1))
    a_lens = np.full((r, nb), blen, np.int32)
    return a_rows, a_vals, a_starts, a_lens, b_cols, b_vals


def _measure(rung: int) -> HashTuning:
    """Time every (load_factor, f_chunk, tile_rows) candidate through
    ``kops.hash_bin_op`` — the same dispatching entry point the executor
    calls, so the measurement exercises whichever backend path (compiled
    Pallas, interpreted Pallas, or the XLA twin) this process will run."""
    from repro.kernels import ops as kops
    pallas = kops._use_pallas_path()
    f_cands = F_CHUNK_CANDIDATES_PALLAS if pallas else F_CHUNK_CANDIDATES
    t_cands = TILE_CANDIDATES_PALLAS if pallas else TILE_CANDIDATES
    nnz_row = max(int(rung * 0.6), 8)
    best, best_t = DEFAULT_TUNING, float("inf")
    for lf in LOAD_FACTOR_CANDIDATES:
        table = pow2_at_least(int(np.ceil(nnz_row / lf)),
                              floor=HASH_MIN_TABLE)
        for fc in f_cands:
            work = _synthetic_workload(rung, fc)
            p_cap = pow2_at_least(int(work[3].sum()), floor=64)
            prev_dt = None
            for ti, tr in enumerate(t_cands):
                def run():
                    out = kops.hash_bin_op(
                        *work, table=table, spill=hash_spill_of(table),
                        p_cap=p_cap, f_chunk=fc,
                        tile=tr)
                    jax.block_until_ready(out[0])

                run()  # warmup/compile
                t0 = time.perf_counter()
                run()
                run()
                dt = time.perf_counter() - t0
                _log_measurement(rung, {
                    "load_factor": lf, "f_chunk": fc, "tile_rows": tr,
                    "seconds": dt})
                if dt < best_t:
                    best_t, best = dt, HashTuning(load_factor=lf, f_chunk=fc,
                                                  tile_rows=tr)
                if prev_dt is not None and dt > prev_dt:
                    # Monotone regression down the descending tile ladder:
                    # timing the rest of the tail is wasted autotune
                    # budget. Record what was skipped so the artifact
                    # shows the sweep was pruned, not exhaustive.
                    skipped = [int(t) for t in t_cands[ti + 1:]]
                    if skipped:
                        _log_measurement(rung, {
                            "load_factor": lf, "f_chunk": fc,
                            "pruned_tiles": skipped})
                    break
                prev_dt = dt
    _log_measurement(rung, {"winner": dataclasses.asdict(best),
                            "seconds": best_t})
    return best


def hash_tuning_for(rung: int,
                    cache: Optional[TuningCache] = None) -> HashTuning:
    """Measured (load_factor, f_chunk, tile_rows) for a rung, cached.

    A measurement that fails raises: a kernel that cannot run here must
    not hide behind untuned defaults, and nothing is cached for it."""
    cache = DEFAULT_TUNING_CACHE if cache is None else cache
    key = tuning_key(rung)
    hit = cache.lookup(key)
    if hit is not None:
        return hit
    tuned = _measure(int(rung))
    cache.insert(key, tuned)
    return tuned
