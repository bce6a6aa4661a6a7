"""Seconds per call in planning: analysis + size prediction + binning,
from ``OceanReport.stage_seconds``, summed over the window's calls, over
the calls. Nothing to read where every call reused a plan. Layer:
planner."""

STAGES = ("analysis", "prediction", "binning")


def read(ctx):
    total = sum(r.stage_seconds.get(s, 0.0) for r in ctx.reports
                for s in STAGES)
    if not ctx.reports or total == 0.0:
        return None
    return total / ctx.calls
