"""Seconds per call in the planner's size prediction (the symbolic sort,
the HLL merge or the upper bound): the program's ``plan.prediction``
spans in the trace, summed over the window, over the calls. Layer:
planner."""
from bench import spans


def read(ctx):
    return spans.seconds_per_call(ctx, "plan.prediction")
