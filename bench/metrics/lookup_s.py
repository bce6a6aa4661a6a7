"""Seconds per call in the plan-cache lookup: the program's
``plan.lookup`` spans in the trace (the structure hash of A and B and
the LRU probe), summed over the window, over the calls. On a hit this is
what a values-only call pays before it runs. Nothing to read where no
call consulted a cache. Layer: workflow."""
from bench import spans


def read(ctx):
    return spans.seconds_per_call(ctx, "plan.lookup")
