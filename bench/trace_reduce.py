"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps
and kernel time.

Loading turns the trace into plain :class:`Event` lists; everything after
that is arithmetic on them, so it can be checked on a hand-made trace.

* Device operations are the events on the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane. Busy time is the length of the union of their
  intervals inside the window; the idle share is one minus busy over the
  window.
* The window is the host annotation :data:`WINDOW_SPAN` that the
  benchmark puts around its measured calls.
* Each operation is named by its HLO instruction (``%spgemm_hash_bin.1``,
  ``%fusion.4``) and carries the jitted module (``XLA Modules`` line) it
  ran in. A kernel's time is the union of the intervals of its own
  operations (``%<kernel>.<n>``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]   # device plane -> its op events
    host_spans: List[Event]              # every host event, any thread


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stat(ev, key: str) -> str:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return ""


def _op_name(name: str) -> str:
    """``%fusion.4 = s32[...] fusion(...)`` -> ``%fusion.4``."""
    return name.split(" = ", 1)[0]


def _module_name(name: str) -> str:
    """``jit_esc_spgemm(9047009911558461880)`` -> ``jit_esc_spgemm``."""
    return name.split("(", 1)[0]


def _with_modules(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Each op with the module whose run on the device contains its start
    (an op's own ``hlo_module`` stat wins where the trace has one)."""
    mods = sorted(modules, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in mods]
    out = []
    for e in ops:
        module = e.module
        if not module and mods:
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k >= 0 and e.start_ns < mods[k].end_ns:
                module = mods[k].name
        out.append(Event(e.name, e.start_ns, e.dur_ns, module))
    return out


def from_profile(pd) -> Trace:
    """Plain events from a ``jax.profiler.ProfileData``."""
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(_op_name(e.name), float(e.start_ns),
                                     float(e.duration_ns),
                                     _module_name(_stat(e, "hlo_module")))
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend(Event(_module_name(e.name),
                                         float(e.start_ns),
                                         float(e.duration_ns))
                                   for e in line.events)
            device_ops[plane.name] = _with_modules(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, float(e.start_ns),
                                  float(e.duration_ns))
                            for e in line.events)
    return Trace(device_ops, host)


def load(path: str) -> Trace:
    import jax
    return from_profile(jax.profiler.ProfileData.from_file(path))


def window_bounds(trace: Trace) -> Optional[Tuple[float, float]]:
    spans = [e for e in trace.host_spans if e.name == WINDOW_SPAN]
    if not spans:
        return None
    return (min(e.start_ns for e in spans), max(e.end_ns for e in spans))


def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return sorted(out)


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    total, cur_s, cur_t = 0.0, None, None
    for s, t in sorted(intervals):
        if cur_t is None or s > cur_t:
            if cur_t is not None:
                total += cur_t - cur_s
            cur_s, cur_t = s, t
        else:
            cur_t = max(cur_t, t)
    if cur_t is not None:
        total += cur_t - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] not covered by any interval."""
    out, cur = [], lo
    for s, t in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def _bounds(trace: Trace) -> Tuple[float, float]:
    b = window_bounds(trace)
    if b is not None:
        return b
    evs = [e for v in trace.device_ops.values() for e in v]
    if not evs:
        return (0.0, 0.0)
    return (min(e.start_ns for e in evs), max(e.end_ns for e in evs))


def busy_seconds(trace: Trace) -> float:
    """Busy seconds inside the window, averaged over the device planes."""
    if not trace.device_ops:
        return 0.0
    lo, hi = _bounds(trace)
    per = [union_ns(_clip(evs, lo, hi)) for evs in trace.device_ops.values()]
    return sum(per) / len(per) * 1e-9


def kernel_seconds(trace: Trace, kernel: str) -> float:
    """Device seconds, summed over planes, of the kernel's own operations
    inside the window: those named ``%<kernel>`` or ``%<kernel>.<n>``,
    as a Pallas call is named after its jitted function."""
    lo, hi = _bounds(trace)
    name = "%" + kernel
    total = 0.0
    for evs in trace.device_ops.values():
        total += union_ns(_clip((e for e in evs if e.name == name
                                 or e.name.startswith(name + ".")), lo, hi))
    return total * 1e-9


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` device operations, by module and name, that took most
    time inside the window (seconds summed over planes)."""
    lo, hi = _bounds(trace)
    tot: Dict[str, float] = {}
    for evs in trace.device_ops.values():
        for e in evs:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                key = f"{e.module}/{e.name}" if e.module else e.name
                tot[key] = tot.get(key, 0.0) + d * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, host_spans: Sequence[Event], n: int = 10
              ) -> List[List]:
    """The ``n`` longest idle gaps of the first device plane inside the
    window, each named by the innermost of ``host_spans`` (on the trace's
    clock) that covers the gap's middle."""
    if not trace.device_ops:
        return []
    lo, hi = _bounds(trace)
    first = sorted(trace.device_ops)[0]
    out = []
    for s, t in gaps(_clip(trace.device_ops[first], lo, hi), lo, hi):
        mid = 0.5 * (s + t)
        cover = [e for e in host_spans if e.start_ns <= mid <= e.end_ns]
        name = (min(cover, key=lambda e: e.dur_ns).name if cover
                else "outside any span")
        out.append([name, (t - s) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:n]
