"""Seconds per call collecting the ESC accumulator's passes, the ESC
bin's and the overflow fallback's: the program's ``exec.esc`` spans in the
trace (the wait for the pass, the read of its count and row pointers, the
ELL layout and its copies), summed over the window, over the calls.
Nothing to read where no ESC pass ran, or in a program without the span.
Layer: executor."""
from bench import spans


def read(ctx):
    return spans.seconds_per_call(ctx, "exec.esc")
