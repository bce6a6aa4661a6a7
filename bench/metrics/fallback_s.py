"""Seconds per call in the executor's overflow fallback, the exact ESC
pass over the rows whose slabs overflowed: the program's
``exec.overflow_fallback`` spans in the trace, summed over the window,
over the calls. Nothing to read where no row overflowed. Layer:
executor."""
from bench import spans


def read(ctx):
    return spans.seconds_per_call(ctx, "exec.overflow_fallback")
