"""Share of the output slots the plan allocates that C fills, in %:
nnz(C) over the slots of the window's plan, counted per row as the dense
accumulator's width (window x column tiles), the hash tables' slots
(primary + spill), and the ESC bin's output capacity. Layer: planner."""


def slots(plan) -> int:
    n = sum(len(b.rows) * b.window * b.col_tiles for b in plan.dense)
    n += sum(len(b.rows) * (b.table + b.spill) for b in plan.hash)
    if plan.esc is not None:
        n += plan.esc.out_cap
    return n


def read(ctx):
    if ctx.plan is None or not ctx.reports:
        return None
    n = slots(ctx.plan)
    return 100.0 * ctx.reports[0].nnz_out / n if n else None
