"""Share of the ESC passes' product slots that products fill, in %: the
products the window's ESC passes expanded over the slots (``p_cap``) they
allocated, ``OceanReport.esc_products`` and ``esc_slots`` summed over the
calls. Nothing to read where no ESC pass ran, or in a program whose
reports carry no such counters. Layer: executor."""


def read(ctx):
    products = [getattr(r, "esc_products", None) for r in ctx.reports]
    slots = [getattr(r, "esc_slots", None) for r in ctx.reports]
    if not ctx.reports or None in products or None in slots \
            or not sum(slots):
        return None
    return 100.0 * sum(products) / sum(slots)
