"""The hash accumulator kernel's share of its roofline, in %: the least
time the chip needs for the work of the rows the plan puts on the hash
rung (``bench/work``), over the kernel's device time in the trace, per
call. Nothing to read where the rung got no rows or the trace shows no
such kernel. Layer: kernels."""
from bench import work

KERNEL = "spgemm_hash_bin"


def rows(plan):
    return [b.rows for b in plan.hash]


def read(ctx):
    return work.roofline_share(ctx, KERNEL, rows(ctx.plan)
                               if ctx.plan is not None else [])
