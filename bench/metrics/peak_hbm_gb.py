"""Device: peak bytes in use on the chip by the window's end
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
