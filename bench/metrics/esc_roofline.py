"""The ESC accumulator's share of its roofline, in %: the least time the
chip needs for the work of the rows the plan puts in the ESC bin
(``bench/work.rows_work``, as for the hash and dense rungs), over the
device time of every operation of the ``jit_esc_spgemm`` module inside
the window, per call. The pass is XLA, not one named kernel, so the
module is matched, not an op name. Nothing to read where the plan has no
ESC bin or the trace shows no such module. Layer: kernels, core.esc."""
from bench import trace_reduce, work

MODULE = "jit_esc_spgemm"


def module_seconds(trace, module: str) -> float:
    """Device seconds, summed over planes, of the union of the operations
    that ran in ``module`` inside the window."""
    bounds = trace_reduce.window_bounds(trace)
    if bounds is None:
        return 0.0
    lo, hi = bounds
    total = 0.0
    for evs in trace.device_ops.values():
        spans = [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in evs
                 if e.module == module]
        total += trace_reduce.union_ns([(s, t) for s, t in spans if t > s])
    return total * 1e-9


def read(ctx):
    if ctx.trace is None or ctx.plan is None or ctx.plan.esc is None \
            or not ctx.calls:
        return None
    seconds = module_seconds(ctx.trace, MODULE)
    if seconds <= 0.0:
        return None
    least, _ = work.rows_work(ctx.plan.esc.rows, ctx.products, ctx.a_indptr,
                              ctx.c_indptr).least_seconds(ctx.peaks)
    return 100.0 * least * ctx.calls / seconds
