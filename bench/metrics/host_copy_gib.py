"""GiB per call moved between host and device, both ways:
``OceanReport.copy_bytes`` ("d2h" + "h2d") summed over the window's
calls, over the calls. Nothing to read where the reports carry no such
count. Layer: executor."""


def read(ctx):
    counts = [getattr(r, "copy_bytes", None) for r in ctx.reports]
    if not counts or None in counts or not ctx.calls:
        return None
    total = sum(c["d2h"] + c["h2d"] for c in counts)
    return total / ctx.calls / 2.0 ** 30
