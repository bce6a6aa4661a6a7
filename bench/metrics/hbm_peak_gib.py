"""Peak device memory in use over the run, in GiB, from
``memory_stats()["peak_bytes_in_use"]`` on the fullest chip, read after
the window. Layer: device."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2.0 ** 30
