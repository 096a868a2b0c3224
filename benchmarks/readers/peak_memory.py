"""Peak device memory of the fullest chip after the window, in GiB:
``peak_bytes_in_use`` (live arrays) plus ``peak_bytes_reserved`` (the
scratch of loaded programs, which this backend keeps apart).  A backend
that reports none (the CPU) gives nothing."""


def read(ctx):
    return ctx.peak_bytes / 2 ** 30 if ctx.peak_bytes else None
