"""Seconds per call the executor waits to pull result slabs to the host,
in completion order: the program's ``exec.collect`` spans in the trace,
summed over the window, over the calls. Layer: executor."""
from bench import spans


def read(ctx):
    return spans.seconds_per_call(ctx, "exec.collect")
