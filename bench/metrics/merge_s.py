"""Seconds per call in the executor's host merge (overflow scan,
compaction, overflow fallback): ``OceanReport.stage_seconds["merge"]``
summed over the window's calls, over the calls. Layer: executor."""


def read(ctx):
    vals = [r.stage_seconds.get("merge") for r in ctx.reports]
    if not vals or None in vals:
        return None
    return sum(vals) / ctx.calls
