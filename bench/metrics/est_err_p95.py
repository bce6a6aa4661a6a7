"""The 95th percentile of the size estimates' relative error over C's
rows (``OceanReport.estimation_accuracy.est_err_p95``: |predicted -
exact| / exact, measured after the numeric pass), averaged over the
window's calls that estimated. Nothing to read where no call took the
estimation workflow. Layer: planner."""


def read(ctx):
    errs = [r.estimation_accuracy.est_err_p95 for r in ctx.reports
            if r.workflow == "estimation"
            and r.estimation_accuracy is not None]
    if not errs:
        return None
    return sum(errs) / len(errs)
