"""The HyperLogLog merge kernel's share of its roofline, in %: the least
time the chip needs to merge B's row sketches over every row of A
(``bench/work.hll_merge_work``), over the device time of every
``hll_merge`` launch in the trace, per call. Nothing to read unless every
call of the window took the estimation workflow, whose size prediction
runs the merge over all of A's rows, or where the trace shows no such
kernel. Layer: kernels.hll."""
from bench import work

KERNEL = "hll_merge"


def read(ctx):
    if not ctx.reports or any(r.workflow != "estimation"
                              for r in ctx.reports):
        return None
    return work.kernel_share(ctx, KERNEL, lambda: work.hll_merge_work(
        ctx.a_indptr, ctx.reports[0].m_regs))
