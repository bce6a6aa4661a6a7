"""Share of the traced window in which no operation ran on the device:
1 - busy / window, in %. Busy is the union of the device's operation
intervals in the trace (``bench/trace_reduce``). Layer: device."""


def read(ctx):
    if ctx.trace is None or ctx.busy_s <= 0.0 or ctx.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
