"""The program's own spans in a traced window: while a ``repro.obs.trace``
tracer is installed, each live span is also a ``jax.profiler``
annotation, so it lies among the trace's host events
(``trace_reduce.Trace.host_spans``) on the device's clock."""
from __future__ import annotations

from typing import Optional

from bench import trace_reduce


def seconds_per_call(ctx, name: str) -> Optional[float]:
    """Seconds of the spans named ``name`` inside the window (each clipped
    to it), over the window's calls; ``None`` where the window holds no
    such span, as in a program whose spans reach no profiler trace."""
    if ctx.trace is None or not ctx.calls:
        return None
    bounds = trace_reduce.window_bounds(ctx.trace)
    if bounds is None:
        return None
    lo, hi = bounds
    spans = [(max(e.start_ns, lo), min(e.end_ns, hi))
             for e in ctx.trace.host_spans if e.name == name]
    spans = [(s, t) for s, t in spans if t > s]
    if not spans:
        return None
    return sum(t - s for s, t in spans) * 1e-9 / ctx.calls
